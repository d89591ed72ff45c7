"""The benchmark's own library: seeds, traffic, weights, the model
modules (``model`` loads the one a configuration names; ``reference`` is
the dense decoder's) over the reference's shared mathematics
(``refmath``), cost arithmetic, trace reduction and the cell drivers.

Nothing here is imported by the program under test; the program is
imported from ``src/`` only by the cell drivers (``train_cell``,
``serve_cell``), which hand it the weights and inputs made here.
"""
