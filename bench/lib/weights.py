"""Weights made from ``--seed``, identically for the program and for the
plain reference.

Every parameter is named (``layers/wq/w``) and drawn from a key mixed
from the seed, the name and, for a leaf of a stack, the layer index.  So
the reference can regenerate any one layer's weights on its own, layer by
layer, without holding the whole model, and the values agree bit for bit
with the stacked leaves handed to the program.  Which leaves a model has,
their shapes, init kinds and stacks, is the ``layout`` of the model module
its configuration names (``bench/lib/model.py``).  Linear weights and the
embedding are untruncated normals of std 0.02, the PRC clipping ratios
0.95, LayerNorm scales 1 and biases 0: the program's own initialisation,
written down here once.
"""
from __future__ import annotations

import zlib

from bench.lib import model

STD = 0.02
GAMMA = 0.95


def vocab_padded(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return -(-cfg["vocab"] // m) * m


def leaf_key(seed, name: str, layer=0):
    """``seed`` is the 32-bit ``seeds.seed_arg``, traced or not."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(key, layer)


def one(key, shape, kind):
    """One layer's value of a leaf, float32, from its key."""
    import jax
    import jax.numpy as jnp

    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * STD
    if kind == "gamma":
        return jnp.full(shape, GAMMA, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(kind)


def leaf(seed, cfg: dict, name: str, layer=None):
    """The whole leaf (stacked over its depth where it belongs to a
    stack), or one layer of it when ``layer`` is given, from the 32-bit
    ``seed``.  Trace under ``jax.jit`` with the seed an argument."""
    import jax
    import jax.numpy as jnp

    shape, kind, depth = model.load(cfg).layout(cfg)[name]
    if depth is None:
        return one(leaf_key(seed, name), shape, kind)
    if layer is not None:
        return one(leaf_key(seed, name, layer), shape, kind)
    keys = jax.vmap(lambda i: leaf_key(seed, name, i))(jnp.arange(depth))
    return jax.vmap(lambda k: one(k, shape, kind))(keys)


def nest(flat: dict) -> dict:
    """``{"layers/wq/w": x}`` -> ``{"layers": {"wq": {"w": x}}}``."""
    out: dict = {}
    for name, x in flat.items():
        node = out
        *head, last = name.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = x
    return out
