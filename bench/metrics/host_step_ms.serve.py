"""Host time of a pooled step: the mean over the window's ``serve.step``
spans of their duration less their ``serve.wait`` children, in ms."""
from bench.lib import progtrace


def read(ctx):
    steps = progtrace.step_host_ms(progtrace.load())
    return sum(steps) / len(steps) if steps else None
