"""Host-bound idle share of serving: % of the traced window in which the
chip runs no leaf operation while the innermost ``serve.*`` span open on
the host (``PoolEngine.run``'s own spans) is anything but
``serve.wait``.  Also prints the idle seconds by span to standard error."""
import sys

from bench.lib import progtrace


def read(ctx):
    prog = progtrace.load()
    report = progtrace.report(prog)
    if report:
        print(f"progtrace: {report}", file=sys.stderr, flush=True)
    return progtrace.idle_host_share(prog)
