"""The yardstick: loader, statistics, peaks, comparison, trace reduction.

Nothing in this package imports the program under test except
``program.py``, which reads the counters the program already keeps.
"""
