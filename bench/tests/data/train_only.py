"""A model module for the tests that lacks ``StepReference``: it can
drive a training cell and no serving cell."""
from bench.lib.reference import (  # noqa: F401
    layout, loss_fn, model_flops_serve_request, model_flops_train_per_token,
    tree)
