"""Cell drivers, one per traffic ``kind``. These and the ``program``
module each configuration names are what import the program under
test."""
