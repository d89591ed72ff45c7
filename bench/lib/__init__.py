"""The benchmark's own library: seeds, traffic, weights, the plain
reference, cost arithmetic, trace reduction and the cell drivers.

Nothing here is imported by the program under test; the program is
imported from ``src/`` only by the cell drivers (``train_cell``,
``serve_cell``), which hand it the weights and inputs made here.
"""
