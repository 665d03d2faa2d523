"""On-chip serving benchmark: open-loop traffic through ``SlotScheduler``.

``run.py`` is the entry point; ``BENCHMARK.json`` at the repository root
names the cells, and each cell, configuration, traffic mix, window driver
and metric sits in a file of its own here, found by name.
"""
