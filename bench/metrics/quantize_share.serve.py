"""Share of ALS-PoTQ outside the MF-MAC kernels in serving: device time of
the leaf operations under the ``als_potq`` name scope (``core/mfmac``'s
quantizers) over busy time, in %."""
from bench.lib import progtrace


def read(ctx):
    return progtrace.scope_share(progtrace.load(), "als_potq")
