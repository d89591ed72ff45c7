"""A model module for the tests, in a file of its own: the dense
decoder's, each part of the interface counting its calls, so that a test
sees the harness go through the module a configuration names."""
import collections
import functools

from bench.lib import reference

CALLS = collections.Counter()


def _counted(fn):
    @functools.wraps(fn)
    def call(*args, **kwargs):
        CALLS[fn.__name__] += 1
        return fn(*args, **kwargs)

    return call


layout = _counted(reference.layout)
tree = _counted(reference.tree)
loss_fn = _counted(reference.loss_fn)
StepReference = _counted(reference.StepReference)
model_flops_train_per_token = _counted(reference.model_flops_train_per_token)
model_flops_serve_request = _counted(reference.model_flops_serve_request)
