"""A model module for the tests that lacks ``loss_fn``."""
from bench.lib.reference import (  # noqa: F401
    StepReference, layout, model_flops_serve_request,
    model_flops_train_per_token, tree)
