"""The chip benchmark's yardstick: traffic, loops, trace reduction, work
functions and peaks. Configurations, traffic mixes, drivers, references
and per-layer metrics live in files of their own beside this package and
are found by name (see ``harness.py``)."""
