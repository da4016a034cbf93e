"""On-chip benchmark of the streaming serving and training paths.

Entry point: ``python bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``. Cells, metrics and their bounds are in
``BENCHMARK.json`` at the repository root; each configuration, traffic
mix and per-layer metric is a file of its own under this directory.
"""
