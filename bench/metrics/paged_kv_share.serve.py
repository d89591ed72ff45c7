"""Share of the paged K/V path: device time of the leaf operations under
the ``paged_kv`` name scope (the page-table gathers and writes of
``models/transformer``) over busy time, in %."""
from bench.lib import progtrace


def read(ctx):
    return progtrace.scope_share(progtrace.load(), "paged_kv")
