"""The model module a configuration names under its ``reference`` key (a
path from the repository's root, such as ``bench/lib/reference.py``).
Everything in the harness that depends on the architecture comes from
that module; the rest of ``bench/`` knows no model.  So a new
architecture's cell is new files only: its module, its configuration, its
cell, its traffic and its metric readers.

A model module defines:

``layout(cfg) -> {name: (shape, init kind, depth)}``
    Every parameter by its flat name (``layers/wq/w``).  ``shape`` is one
    layer's leaf, or the whole leaf; init kinds are ``weights.one``'s;
    ``depth`` is ``None`` for a single leaf, or the depth of the stack
    the leaf belongs to.  Leaf ``name``'s layer ``l`` is drawn from
    ``fold_in(fold_in(PRNGKey(seed), crc32(name)), l)`` (``weights``).
``tree(cfg, flat) -> program parameter tree``
    The program's parameter tree from the flat dict of whole leaves.
``loss_fn(cfg, params, tokens, labels, mask, num)``
    The training reference's mean loss over the flat parameters, with
    ``refmath``'s MF-MAC linears computed as ``num`` says.
``StepReference(seed, cfg, chunk, span)``
    The serving reference; a serve cell needs it.  ``.logits(seqs, nums)``
    gives, for each ``refmath.Num``, the logits that chose each served
    token of each ``(tokens, prompt length)`` in ``seqs``.
``model_flops_train_per_token(cfg, seq)``
    Useful forward and backward FLOPs a token of a ``seq``-long sequence,
    which ``mfu.train`` counts.
``model_flops_serve_request(cfg, prompt, served)``
    Useful forward FLOPs of one served request, which ``mfu.serve``
    counts.
"""
from __future__ import annotations

import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
INTERFACE = ("layout", "tree", "loss_fn", "model_flops_train_per_token",
             "model_flops_serve_request")
SERVE = ("StepReference",)

_loaded: dict = {}


class ModelModuleError(RuntimeError):
    """A configuration names no model module, or one that lacks part of
    the interface."""


def load(cfg: dict, need: tuple = ()):
    """The model module at ``cfg["reference"]``, loaded once a path.
    ``need`` names parts beyond the interface that the caller uses
    (``SERVE``)."""
    path = cfg.get("reference")
    if not path:
        raise ModelModuleError(f"configuration {cfg.get('name')!r} names "
                               "no model module under 'reference'")
    mod = _loaded.get(path)
    if mod is None:
        full = os.path.join(ROOT, path)
        if not os.path.isfile(full):
            raise ModelModuleError(f"configuration {cfg.get('name')!r}: "
                                   f"no model module at {path}")
        spec = importlib.util.spec_from_file_location(
            "bench_model_" + path.replace("/", "_").replace(".", "_"), full)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    missing = [n for n in INTERFACE + tuple(need) if not hasattr(mod, n)]
    if missing:
        raise ModelModuleError(f"configuration {cfg.get('name')!r}: model "
                               f"module {path} lacks {', '.join(missing)}")
    return mod
