"""The benchmark's yardstick: traffic, trace reduction, arithmetic,
reference and comparison. Imports nothing of the program except where a
module says so: the cell drivers under ``bench/kinds`` and the
``program`` module a configuration names (``dense_program.py``)."""
