"""Weights made from ``--seed``, identically for the program and for the
plain reference.

Every parameter is named (``layers/wq/w``) and drawn from a key mixed
from the seed, the name and, for a per-layer leaf, the layer index.  So
the reference can regenerate any one layer's weights on its own, layer by
layer, without holding the whole model, and the values agree bit for bit
with the stacked leaves handed to the program.  Linear weights and the
embedding are untruncated normals of std 0.02, the PRC clipping ratios
0.95, LayerNorm scales 1 and biases 0: the program's own initialisation,
written down here once.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

STD = 0.02
GAMMA = 0.95


def vocab_padded(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return -(-cfg["vocab"] // m) * m


def layout(cfg: dict) -> Dict[str, Tuple[tuple, str, bool]]:
    """name -> (shape of one layer's leaf or of the whole leaf, init kind,
    per-layer).  Init kinds: ``normal``, ``gamma``, ``ones``, ``zeros``."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    out = {"embed": ((vocab_padded(cfg), d), "normal", False)}

    def linear(name, shape, per_layer=True):
        out[f"{name}/w"] = (shape, "normal", per_layer)
        out[f"{name}/gamma"] = ((), "gamma", per_layer)

    def norm(name, per_layer):
        if cfg["norm"] == "nonparam_ln":
            return
        out[f"{name}/scale"] = ((d,), "ones", per_layer)
        if cfg["norm"] == "ln":
            out[f"{name}/bias"] = ((d,), "zeros", per_layer)

    norm("layers/ln1", True)
    norm("layers/ln2", True)
    linear("layers/wq", (d, cfg["n_heads"] * hd))
    linear("layers/wk", (d, cfg["kv_heads"] * hd))
    linear("layers/wv", (d, cfg["kv_heads"] * hd))
    linear("layers/wo", (cfg["n_heads"] * hd, d))
    f = cfg["d_ff"]
    if cfg["act"] == "swiglu":
        linear("layers/mlp/wi_gate", (d, f))
        linear("layers/mlp/wi_up", (d, f))
    else:
        linear("layers/mlp/wi", (d, f))
    linear("layers/mlp/wo", (f, d))
    norm("final_norm", False)
    if not cfg["tie_embeddings"]:
        linear("lm_head", (d, vocab_padded(cfg)), per_layer=False)
    return out


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
    """The whole leaf (stacked over layers when per-layer), or one layer
    of it when ``layer`` is given, from the 32-bit ``seed``.  Trace under
    ``jax.jit`` with the seed an argument."""
    import jax
    import jax.numpy as jnp

    shape, kind, per_layer = layout(cfg)[name]
    if not per_layer:
        return one(leaf_key(seed, name), shape, kind)
    if layer is not None:
        return one(leaf_key(seed, name, layer), shape, kind)
    keys = jax.vmap(lambda i: leaf_key(seed, name, i))(
        jnp.arange(cfg["n_layers"]))
    return jax.vmap(lambda k: one(k, shape, kind))(keys)


def nest(flat: dict) -> dict:
    """``{"layers/wq/w": x}`` -> ``{"layers": {"wq": {"w": x}}}``; norms
    of a nonparametric LayerNorm become the empty dicts the program's
    parameter tree has."""
    out: dict = {}
    for name, x in flat.items():
        node = out
        *head, last = name.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = x
    return out


def empty_norms(cfg: dict, tree: dict) -> dict:
    if cfg["norm"] == "nonparam_ln":
        tree.setdefault("layers", {}).setdefault("ln1", {})
        tree["layers"].setdefault("ln2", {})
        tree.setdefault("final_norm", {})
    return tree
