"""The benchmark's yardstick: peaks, analytic counts, traffic, weights,
the plain reference, the trace reduction and the comparison that decides
``correct``.  Nothing here imports the program under test except where a
docstring says so (the drivers and ``harness`` do)."""
