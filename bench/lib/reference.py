"""The dense decoder's model module (``bench/lib/model.py`` states the
interface), which the OLMo configurations name under ``reference``: its
parameter layout, its plain reference and its model FLOPs.  The reference
is straightforward ``jax.numpy``, float32 at ``Precision.HIGHEST``,
written from the models' papers, over ``refmath``'s ALS-PoTQ and MF-MAC
linears.  It imports nothing of the program and takes nothing the program
made: its weights come from ``bench/lib/weights.py`` and the seed.

What it computes:

* The decoder: pre-norm blocks (LayerNorm, nonparametric for OLMo),
  rotary positions (rotate-half), causal grouped-query softmax
  attention, SwiGLU or tanh-GELU MLP, a tied or untied LM head, mean
  next-token cross entropy over the unpadded vocabulary.
* Serving: activation scale groups follow the served schedule: one group
  per prompt chunk of ``chunk`` tokens, one per generated token and one
  per LM-head row; keys and values are held at bf16, as the cache is.

Attention's two dots take their operands at the precision the
configuration states under ``attention_operands``: ``bfloat16`` (rounded
operands, float32 accumulation; OLMo trains its matmuls under bfloat16
mixed precision, and a float32 dot at a TPU's default precision is this)
or ``float32``.

``norm``, ``rope``, ``attend`` and ``mlp`` are written for other model
modules to import.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights as W
from bench.lib.refmath import (BF16, F32, quant_a, quant_w, to_bf16,
                               train_linear)


# --- parameters -------------------------------------------------------------

def layout(cfg: dict):
    """name -> (shape of one layer's leaf or of the whole leaf, init kind,
    depth): the stacked leaves under ``layers/`` have ``n_layers``."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    depth = cfg["n_layers"]
    out = {"embed": ((W.vocab_padded(cfg), d), "normal", None)}

    def linear(name, shape, stack=depth):
        out[f"{name}/w"] = (shape, "normal", stack)
        out[f"{name}/gamma"] = ((), "gamma", stack)

    def norm_leaves(name, stack):
        if cfg["norm"] == "nonparam_ln":
            return
        out[f"{name}/scale"] = ((d,), "ones", stack)
        if cfg["norm"] == "ln":
            out[f"{name}/bias"] = ((d,), "zeros", stack)

    norm_leaves("layers/ln1", depth)
    norm_leaves("layers/ln2", depth)
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
    norm_leaves("final_norm", None)
    if not cfg["tie_embeddings"]:
        linear("lm_head", (d, W.vocab_padded(cfg)), stack=None)
    return out


def _layer_names(cfg):
    return [n for n, (_, _, depth) in layout(cfg).items()
            if depth is not None]


def tree(cfg: dict, flat: dict) -> dict:
    """The program's parameter tree; norms of a nonparametric LayerNorm
    are the empty dicts the program's tree has."""
    out = W.nest(flat)
    if cfg["norm"] == "nonparam_ln":
        out.setdefault("layers", {}).setdefault("ln1", {})
        out["layers"].setdefault("ln2", {})
        out.setdefault("final_norm", {})
    return out


# --- model pieces -----------------------------------------------------------

def attn_operand(cfg: dict, x):
    """An operand of attention's dots, at the configuration's precision."""
    if cfg["attention_operands"] == "bfloat16":
        return to_bf16(x)
    if cfg["attention_operands"] != "float32":
        raise ValueError(cfg["attention_operands"])
    return x


def norm(cfg, x, p, num):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = num.r((x - mu) * jax.lax.rsqrt(var + 1e-5))
    if cfg["norm"] == "ln":
        y = num.r(y * p["scale"] + p["bias"])
    return y


def rope(x, pos, theta: float, num):
    """x (..., S, H, hd); pos (S,).  Rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return num.r(jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                 axis=-1))


def attend(cfg, q, k, v, qpos, kpos, kvalid, num):
    """q (Sq, H, hd), k/v (Skv, KV, hd) -> (Sq, H*hd), causal by position."""
    h, kvh, hd = cfg["n_heads"], cfg["kv_heads"], cfg["head_dim"]
    rep = h // kvh
    qg = attn_operand(cfg, q.reshape(q.shape[0], kvh, rep, hd))
    k, v = attn_operand(cfg, k), attn_operand(cfg, v)
    s = jnp.einsum("qgrd,kgd->grqk", qg, k, precision=num.prec,
                   preferred_element_type=F32) * (1.0 / jnp.sqrt(F32(hd)))
    s = num.r(s)
    mask = (kpos[None, :] <= qpos[:, None]) & kvalid[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    p = attn_operand(cfg, num.r(jax.nn.softmax(s, axis=-1)))
    o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=num.prec,
                   preferred_element_type=F32)
    return num.r(o.reshape(q.shape[0], h * hd))


def mlp(cfg, lp, x, lin, num):
    if cfg["act"] == "swiglu":
        g = lin(x, lp["mlp/wi_gate/w"], lp["mlp/wi_gate/gamma"])
        u = lin(x, lp["mlp/wi_up/w"], lp["mlp/wi_up/gamma"])
        hid = num.r(num.r(jax.nn.silu(g)) * u)
    else:
        hid = num.r(jax.nn.gelu(lin(x, lp["mlp/wi/w"], lp["mlp/wi/gamma"]),
                                approximate=True))
    return lin(hid, lp["mlp/wo/w"], lp["mlp/wo/gamma"])


def _norm_params(lp, name):
    return {"scale": lp.get(f"{name}/scale"), "bias": lp.get(f"{name}/bias")}


# --- training -------------------------------------------------------------

def loss_fn(cfg, params, tokens, labels, mask, num):
    b, s = tokens.shape
    lin = functools.partial(train_linear, num=num, bits_g=5)
    x = num.r(params["embed"][tokens])  # (B, S, D)
    pos = jnp.arange(s)

    def block(x, lp):
        h = norm(cfg, x, _norm_params(lp, "ln1"), num)
        q = lin(h, lp["wq/w"], lp["wq/gamma"])
        k = lin(h, lp["wk/w"], lp["wk/gamma"])
        v = lin(h, lp["wv/w"], lp["wv/gamma"])
        hd = cfg["head_dim"]
        q = rope(q.reshape(b, s, -1, hd), pos, cfg["rope_theta"], num)
        k = rope(k.reshape(b, s, -1, hd), pos, cfg["rope_theta"], num)
        v = v.reshape(b, s, -1, hd)
        att = jax.vmap(lambda qq, kk, vv: attend(
            cfg, qq, kk, vv, pos, pos, jnp.ones((s,), bool), num))(q, k, v)
        x = num.r(x + lin(att, lp["wo/w"], lp["wo/gamma"]))
        h2 = norm(cfg, x, _norm_params(lp, "ln2"), num)
        return num.r(x + mlp(cfg, lp, h2, lin, num)), None

    layers = {n[len("layers/"):]: params[n] for n in _layer_names(cfg)}
    x, _ = jax.lax.scan(jax.checkpoint(block), x, layers)
    x = norm(cfg, x, {"scale": params.get("final_norm/scale"),
                      "bias": params.get("final_norm/bias")}, num)
    if cfg["tie_embeddings"]:
        head_w, head_g = params["embed"].T, F32(W.GAMMA)
    else:
        head_w, head_g = params["lm_head/w"], params["lm_head/gamma"]
    logits = train_linear(x, head_w, head_g, num=num, bits_g=6)
    vp = logits.shape[-1]
    logits = jnp.where(jnp.arange(vp) >= cfg["vocab"], -1e30, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --- serving --------------------------------------------------------------

def serve_step(cfg, num, wts, cache, tokens, n_new, pos0):
    """Up to C positions of one request through every layer, as one pooled
    step serves them: ``tokens`` (C,) of which the first ``n_new`` are
    real, from position ``pos0``; the cache holds keys and values at bf16
    over the whole span, ``kpos`` the position of each entry (-1 unset).
    Pad rows are zeroed at each norm's output and after attention, so each
    scale group holds the real rows alone.  Returns the logits of the last
    real row and the cache."""
    c = tokens.shape[0]
    span = cache["kpos"].shape[0]
    hd, kvh = cfg["head_dim"], cfg["kv_heads"]
    rep = cfg["n_heads"] // kvh
    offs = jnp.arange(c)
    valid = (offs < n_new)[None, :, None]
    qpos = jnp.where(offs < n_new, pos0 + offs, -1)
    slot = jnp.where(offs < n_new, pos0 + offs, span)  # pads are dropped
    kpos = cache["kpos"].at[slot].set(qpos, mode="drop")
    mask = ((kpos[None, :] <= qpos[:, None]) & (kpos >= 0)[None, :])

    def lin(a, w, gamma):
        aq = quant_a(a, gamma, lambda v: jnp.max(v, axis=(1, 2),
                                                 keepdims=True))
        return num.mac(aq[0], w.astype(F32))[None]

    def attention(q, k, v):
        qg = jnp.transpose(q.reshape(1, c, kvh, rep, hd), (0, 2, 3, 1, 4))
        kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (k, v))
        s = jax.lax.dot_general(
            attn_operand(cfg, qg), attn_operand(cfg, kt),
            (((4,), (3,)), ((0, 1), (0, 1))), precision=num.prec,
            preferred_element_type=F32) * (1.0 / jnp.sqrt(F32(hd)))
        s = jnp.where(mask[None, None, None], num.r(s), -1e30)
        p = attn_operand(cfg, num.r(jax.nn.softmax(s, axis=-1)))
        o = jax.lax.dot_general(p, attn_operand(cfg, vt),
                                (((4,), (2,)), ((0, 1), (0, 1))),
                                precision=num.prec,
                                preferred_element_type=F32)
        return num.r(jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(1, c, -1))

    def layer(x, xs):
        lp, ck, cv = xs
        h = jnp.where(valid, norm(cfg, x, _norm_params(lp, "ln1"), num), 0.0)
        q = rope(lin(h, lp["wq/w"], lp["wq/gamma"]).reshape(1, c, -1, hd),
                 qpos, cfg["rope_theta"], num)
        k = rope(lin(h, lp["wk/w"], lp["wk/gamma"]).reshape(1, c, -1, hd),
                 qpos, cfg["rope_theta"], num)
        v = lin(h, lp["wv/w"], lp["wv/gamma"]).reshape(1, c, -1, hd)
        ck = ck.at[slot].set(k[0].astype(BF16), mode="drop")
        cv = cv.at[slot].set(v[0].astype(BF16), mode="drop")
        att = attention(q, ck[None].astype(F32), cv[None].astype(F32))
        att = jnp.where(valid, att, 0.0)
        x = num.r(x + lin(att, lp["wo/w"], lp["wo/gamma"]))
        h2 = jnp.where(valid, norm(cfg, x, _norm_params(lp, "ln2"), num),
                       0.0)
        return num.r(x + mlp(cfg, lp, h2, lin, num)), (ck, cv)

    x = num.r(wts["embed"][tokens][None])
    x, (k, v) = jax.lax.scan(layer, x, (wts["layers"], cache["k"],
                                        cache["v"]))
    xe = x[:, jnp.clip(n_new - 1, 0, c - 1)][:, None]  # (1, 1, D)
    xe = norm(cfg, xe, wts["final"], num)
    head_w = (quant_w(wts["embed"].T) if cfg["tie_embeddings"]
              else wts["head"]["w"])
    gamma = (F32(W.GAMMA) if cfg["tie_embeddings"]
             else wts["head"]["gamma"])
    logits = lin(xe, head_w, gamma)
    return logits[0, 0], {"k": k, "v": v, "kpos": kpos}


class StepReference:
    """Teacher-forced serving of each sampled request, one chunk or one
    token at a time, as the pooled step serves a slot: prompt chunks of
    ``chunk`` positions, then one position per served token, against a
    bf16 cache over the whole ``span``.  Linear weights are centred and
    quantized once, per matrix, and held as their bf16-exact values, so a
    whole model fits beside the cache."""

    def __init__(self, seed, cfg: dict, chunk: int, span: int):
        self.cfg, self.chunk, self.span = cfg, chunk, span
        leaves = layout(cfg)

        def make(name):
            depth = leaves[name][2]

            def q(s, l=None):
                x = W.leaf(s, cfg, name, l)
                return quant_w(x).astype(BF16) if name.endswith("/w") else x

            if depth is not None and name.endswith("/w"):
                return jax.jit(lambda s: jax.lax.map(
                    functools.partial(q, s), jnp.arange(depth)))(seed)
            return jax.jit(q)(seed)

        self.wts = {
            "embed": make("embed"),
            "layers": {n[len("layers/"):]: make(n)
                       for n in _layer_names(cfg)},
            "final": {k: (make(f"final_norm/{k}")
                          if f"final_norm/{k}" in leaves else None)
                      for k in ("scale", "bias")},
            "head": ({} if cfg["tie_embeddings"] else
                     {"w": make("lm_head/w"),
                      "gamma": make("lm_head/gamma")}),
        }
        self._step = {}

    def _fn(self, num):
        if num not in self._step:
            self._step[num] = jax.jit(
                functools.partial(serve_step, self.cfg, num),
                donate_argnums=(1,))
        return self._step[num]

    def logits(self, seqs, nums):
        """seqs: list of (tokens (N,), prompt length P).  Returns, for each
        num, a list of float32 (N - P, V) arrays: the logits that chose
        each served token."""
        cfg, c = self.cfg, self.chunk
        shape = (cfg["n_layers"], self.span, cfg["kv_heads"],
                 cfg["head_dim"])
        out = {num: [] for num in nums}
        for toks, plen in seqs:
            toks = np.asarray(toks, np.int32)
            feeds = [(toks[s:min(s + c, plen)], s)
                     for s in range(0, plen, c)]
            feeds += [(toks[p:p + 1], p) for p in range(plen, len(toks) - 1)]
            for num in nums:
                step = self._fn(num)
                cache = {"k": jnp.zeros(shape, BF16),
                         "v": jnp.zeros(shape, BF16),
                         "kpos": jnp.full((self.span,), -1, jnp.int32)}
                rows = []
                for part, pos0 in feeds:
                    buf = np.zeros((c,), np.int32)
                    buf[:len(part)] = part
                    lg, cache = step(self.wts, cache, jnp.asarray(buf),
                                     jnp.int32(len(part)), jnp.int32(pos0))
                    if pos0 + len(part) >= plen:
                        rows.append(lg)
                out[num].append(np.stack([np.asarray(r) for r in rows]))
        return out


# --- model FLOPs ------------------------------------------------------------
# The useful FLOPs of the model, as in ``benchmarks/roofline.py`` (2 per
# weight per token forward, 6 with the backward), written out per matmul
# so the LM head counts the real vocabulary and causal attention its real
# context.  Recomputation is not counted.

def linear_shapes(cfg: dict):
    """(name, K, N) of each linear of one layer."""
    d, hd, f = cfg["d_model"], cfg["head_dim"], cfg["d_ff"]
    h, kv = cfg["n_heads"], cfg["kv_heads"]
    out = [("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
           ("wo", h * hd, d)]
    if cfg["act"] == "swiglu":
        out += [("wi_gate", d, f), ("wi_up", d, f)]
    else:
        out += [("wi", d, f)]
    return out + [("mlp_wo", f, d)]


def layer_flops_per_row(cfg: dict) -> float:
    return sum(2.0 * k * n for _, k, n in linear_shapes(cfg))


def attn_flops(cfg: dict, context: float) -> float:
    """QK^T and PV of one query row against ``context`` keys."""
    return 4.0 * cfg["n_heads"] * cfg["head_dim"] * context


def head_flops_per_row(cfg: dict) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab"]


def model_flops_train_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (3x forward) per token of a causal sequence."""
    fwd = (cfg["n_layers"] * (layer_flops_per_row(cfg)
                              + attn_flops(cfg, (seq + 1) / 2))
           + head_flops_per_row(cfg))
    return 3.0 * fwd


def model_flops_serve_request(cfg: dict, prompt: int, served: int) -> float:
    """Useful forward FLOPs of one request: every prompt row and every fed
    token (the last served token is never fed) through the layers, each
    against its causal context, and the LM head once per served token."""
    rows = prompt + served - 1
    total_ctx = rows * (rows + 1) / 2  # sum of (position + 1)
    return (cfg["n_layers"] * (rows * layer_flops_per_row(cfg)
                               + attn_flops(cfg, 1.0) * total_ctx)
            + served * head_flops_per_row(cfg))
