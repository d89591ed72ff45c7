"""Generic decoder-only transformer LM (dense / GQA / MoE / VLM-prefix).

Covers llama4-scout, grok-1, starcoder2, mistral-nemo, llama3, olmo and the
internvl2 LM backbone.  Layers are homogeneous and stacked along a leading
'layer' axis, executed with ``lax.scan`` (small HLO => fast 512-device
compiles) and per-layer ``jax.checkpoint`` remat.

Every weight matmul goes through MF-MAC (core.mfmac) under the active
QuantPolicy — the paper's Algorithm 1 applied to a modern LM stack.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import compress, mfmac
from repro.core.policy import QuantPolicy
from repro.models import common
from repro.models.spec import ParamSpec
from repro.parallel import actshard


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _linear(shape, axes, std, gamma_init=0.95):
    # PRC gamma: one scalar per layer instance (stacked along 'layer').
    if axes and axes[0] == "layer":
        gshape, gaxes = (shape[0],), ("layer",)
    else:
        gshape, gaxes = (), ()
    return {
        "w": ParamSpec(shape, axes, std=std),
        "gamma": ParamSpec(gshape, gaxes, init="value", value=gamma_init),
    }


def _norm_specs(cfg: ModelConfig, L: Optional[int] = None):
    lead = () if L is None else (L,)
    laxes = () if L is None else ("layer",)
    if cfg.norm == "nonparam_ln":
        return {}
    out = {"scale": ParamSpec(lead + (cfg.d_model,), laxes + (None,), init="ones")}
    if cfg.norm == "ln":
        out["bias"] = ParamSpec(lead + (cfg.d_model,), laxes + (None,), init="zeros")
    return out


def _mlp_specs(cfg: ModelConfig, L: int, std: float):
    d, f = cfg.d_model, cfg.d_ff
    if cfg.act == "swiglu":
        return {
            "wi_gate": _linear((L, d, f), ("layer", "embed", "ffn"), std),
            "wi_up": _linear((L, d, f), ("layer", "embed", "ffn"), std),
            "wo": _linear((L, f, d), ("layer", "ffn", "embed"), std),
        }
    return {
        "wi": _linear((L, d, f), ("layer", "embed", "ffn"), std),
        "wo": _linear((L, f, d), ("layer", "ffn", "embed"), std),
    }


def _moe_specs(cfg: ModelConfig, L: int, std: float):
    m = cfg.moe
    d, f, e = cfg.d_model, cfg.d_ff, m.num_experts
    out = {
        "router": _linear((L, d, e), ("layer", "embed", None), std),
        "gate": _linear((L, e, d, f), ("layer", "expert", "embed", "ffn"), std),
        "up": _linear((L, e, d, f), ("layer", "expert", "embed", "ffn"), std),
        "down": _linear((L, e, f, d), ("layer", "expert", "ffn", "embed"), std),
    }
    if m.shared_expert:
        out["shared"] = _mlp_specs(cfg, L, std)
    return out


def decoder_specs(cfg: ModelConfig):
    L, d = cfg.n_layers, cfg.d_model
    hd = cfg.head_dim
    std = 0.02
    layer = {
        "ln1": _norm_specs(cfg, L),
        "ln2": _norm_specs(cfg, L),
        "wq": _linear((L, d, cfg.n_heads * hd), ("layer", "embed", "heads"), std),
        "wk": _linear((L, d, cfg.kv_heads * hd), ("layer", "embed", "kv"), std),
        "wv": _linear((L, d, cfg.kv_heads * hd), ("layer", "embed", "kv"), std),
        "wo": _linear((L, cfg.n_heads * hd, d), ("layer", "heads", "embed"), std),
    }
    if cfg.moe is not None:
        layer["moe"] = _moe_specs(cfg, L, std)
    else:
        layer["mlp"] = _mlp_specs(cfg, L, std)
    specs = {
        "embed": ParamSpec((cfg.vocab_padded, d), ("vocab", "embed"), std=0.02),
        "layers": layer,
        "final_norm": _norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = _linear((d, cfg.vocab_padded), ("embed", "vocab"), std)
    if cfg.family == "vlm" and cfg.num_patches:
        specs["patch_proj"] = _linear(
            (cfg.patch_dim, d), (None, "embed"), std
        )
    return specs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _mlp_apply(cfg: ModelConfig, policy: QuantPolicy, p, x):
    if cfg.act == "swiglu":
        g = mfmac.mf_linear(x, p["wi_gate"]["w"], p["wi_gate"]["gamma"], policy=policy)
        u = mfmac.mf_linear(x, p["wi_up"]["w"], p["wi_up"]["gamma"], policy=policy)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = common.gelu(
            mfmac.mf_linear(x, p["wi"]["w"], p["wi"]["gamma"], policy=policy)
        )
    return mfmac.mf_linear(h, p["wo"]["w"], p["wo"]["gamma"], policy=policy)


def _moe_apply(cfg: ModelConfig, policy: QuantPolicy, p, x,
               group_size: int = 512, per_slot: bool = False):
    """GShard-style capacity dispatch; experts run via mf_expert_linear.

    x: (B, S, D).  Tokens are flattened and regrouped into groups of
    ``group_size`` so dispatch-einsum FLOPs stay ~O(tokens * group_size)
    instead of O(tokens * seq_len) (DESIGN.md §4).

    ``per_slot`` (decode / serving): every batch row is its own dispatch
    group with its own capacity, so the expert-capacity cumsum never
    crosses rows.  This is what makes MoE decode *batch-invariant* — a
    serving slot's tokens can neither displace nor be displaced by a
    neighbouring slot's (live or retired), which puts MoE inside the
    pool-vs-solo bit-identity guarantee (docs/DESIGN_serving.md §3).  At
    batch 1 a per-slot group and the flat group coincide exactly.
    """
    m = cfg.moe
    b, s, d = x.shape
    n_tok = b * s
    if per_slot:
        g, t = b, s
        xg = x
    else:
        t = min(group_size, n_tok)
        g = n_tok // t
        assert g * t == n_tok, (b, s, group_size)
        xg = x.reshape(g, t, d)

    router_logits = mfmac.mf_linear(
        xg, p["router"]["w"], p["router"]["gamma"], policy=policy
    ).astype(jnp.float32)  # (G, T, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)  # (G, T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    e = m.num_experts
    cap = int(t * m.top_k / e * m.capacity_factor)
    cap = max(4, ((cap + 3) // 4) * 4)

    # Flatten the k slot axis into the token axis (slot-major priority).
    idx_flat = expert_idx.reshape(g, t * m.top_k)
    gate_flat = gate_vals.reshape(g, t * m.top_k)
    onehot = jax.nn.one_hot(idx_flat, e, dtype=jnp.float32)  # (G, T*k, E)
    pos = jnp.cumsum(onehot, axis=1) * onehot - 1.0  # position in expert
    keep = (pos >= 0) & (pos < cap)
    combine = (
        gate_flat[..., None, None]
        * keep[..., None].astype(jnp.float32)
        * jax.nn.one_hot(pos, cap, dtype=jnp.float32)
    )  # (G, T*k, E, C)
    dispatch = (combine > 0).astype(x.dtype)

    # Token slots repeat x along k: (G, T*k, D).
    xk = jnp.repeat(xg, m.top_k, axis=1) if m.top_k > 1 else xg
    # expert_in: (E, G, C, D)
    expert_in = jnp.einsum(
        "gtec,gtd->egcd", dispatch, xk, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    ein = expert_in if per_slot else expert_in.reshape(e, g * cap, d)

    def expert_ffn(name):
        q = p[name]

        def f(h):
            return mfmac.mf_expert_linear(h, q["w"], q["gamma"], policy=policy)

        if per_slot:
            # Per-(expert, slot) activation-scale groups: vmapping over
            # the slot axis G gives every slot's dispatched tokens their
            # own ALS beta / PRC threshold, so expert quantization — like
            # the dispatch cumsum above — never couples pool rows.
            return jax.vmap(f, in_axes=1, out_axes=1)
        return f

    if cfg.act == "swiglu":
        hg = expert_ffn("gate")(ein)
        hu = expert_ffn("up")(ein)
        h = jax.nn.silu(hg.astype(jnp.float32)).astype(x.dtype) * hu
    else:
        h = common.gelu(expert_ffn("gate")(ein))
    eout = expert_ffn("down")(h)
    if not per_slot:
        eout = eout.reshape(e, g, cap, d)

    out = jnp.einsum(
        "egcd,gtec->gtd",
        eout.astype(jnp.float32),
        combine.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    if m.top_k > 1:
        out = out.reshape(g, t, m.top_k, d).sum(axis=2)
    out = out.reshape(b, s, d)
    if m.shared_expert:
        out = out + _mlp_apply(cfg, policy, p["shared"], x)
    return out


def _attn_apply(
    cfg: ModelConfig,
    policy: QuantPolicy,
    p,
    x,
    qpos,
    *,
    cache_kv=None,  # (k, v, kpos) for decode
    window=None,
):
    b, s, d = x.shape
    hd = cfg.head_dim
    q = mfmac.mf_linear(x, p["wq"]["w"], p["wq"]["gamma"], policy=policy)
    k = mfmac.mf_linear(x, p["wk"]["w"], p["wk"]["gamma"], policy=policy)
    v = mfmac.mf_linear(x, p["wv"]["w"], p["wv"]["gamma"], policy=policy)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.kv_heads, hd)
    v = v.reshape(b, s, cfg.kv_heads, hd)
    qp = jnp.broadcast_to(qpos[None, :], (b, s))
    q = common.rope(q, qp, cfg.rope_theta)
    k = common.rope(k, qp, cfg.rope_theta)
    new_kv = (k, v)
    if cache_kv is not None:
        k, v, kpos = cache_kv  # pre-updated by caller; kpos (Skv,)
    else:
        kpos = qpos
    att = _sdpa(cfg, policy, q, k, v, qpos, kpos, window)
    att = att.reshape(b, s, cfg.n_heads * hd)
    out = mfmac.mf_linear(att, p["wo"]["w"], p["wo"]["gamma"], policy=policy)
    return out, new_kv


def _sdpa(cfg, policy, q, k, v, qpos, kpos, window):
    """Grouped-GQA attention: K/V stay at native kv-head width.

    Materializing the GQA-expanded K/V (common._expand_kv) costs
    (H/KV) x cache bytes per layer — 6x for grok-1 — and at decode forces
    full-cache reshard copies when KV doesn't divide the model axis
    (EXPERIMENTS.md §Perf decode iteration).  The grouped einsum keeps
    K/V as (B, S, KV, hd) and folds the head-repeat factor into Q.

    ``qpos``/``kpos`` are either 1-D (positions shared across the batch —
    training forward / lockstep decode) or 2-D ``(B, Sq)``/``(B, Skv)``
    (per-slot offsets: each pool slot decodes at its own position,
    serve/slots.py).  The shared case is broadcast to the batched mask, so
    both paths compute identical bits for identical rows.
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    kv = k.shape[2]
    rep = h // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    # q: (B, KV, rep, Sq, hd); k,v transposed to (B, KV, Skv, hd)
    qg = jnp.transpose(q.reshape(b, sq, kv, rep, hd), (0, 2, 3, 1, 4))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    scores = (
        mfmac.mf_act_dot(
            qg, kt, (((4,), (3,)), ((0, 1), (0, 1))), policy=policy
        ).astype(jnp.float32)
        * scale
    )  # (B, KV, rep, Sq, Skv)
    if qpos.ndim == 1:
        qpos = jnp.broadcast_to(qpos[None, :], (b, sq))
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos[None, :], (b, skv))
    mask = kpos[:, None, :] <= qpos[:, :, None]
    if window is not None:
        mask &= kpos[:, None, :] > qpos[:, :, None] - window
    mask &= (kpos >= 0)[:, None, :]  # ring-cache slots not yet written
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = mfmac.mf_act_dot(
        probs.astype(q.dtype), vt,
        (((4,), (2,)), ((0, 1), (0, 1))), policy=policy,
    )  # (B, KV, rep, Sq, hd)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def _block(cfg, policy, p, x, qpos, cache_kv=None):
    h = common.apply_norm(cfg.norm, x, p["ln1"])
    att, new_kv = _attn_apply(
        cfg, policy, p, h, qpos, cache_kv=cache_kv, window=cfg.window
    )
    # Pin the row-parallel projection output back to the seq-sharded
    # layout BEFORE the residual add: turns the TP partial-sum all-reduce
    # into a reduce-scatter (Megatron-SP style; EXPERIMENTS.md §Perf it.2).
    x = x + actshard.shard_tokens(att)
    h2 = common.apply_norm(cfg.norm, x, p["ln2"])
    if cfg.moe is not None:
        x = x + actshard.shard_tokens(_moe_apply(cfg, policy, p["moe"], h2))
    else:
        x = x + actshard.shard_tokens(_mlp_apply(cfg, policy, p["mlp"], h2))
    return x, new_kv


# ---------------------------------------------------------------------------
# Forward / loss / decode
# ---------------------------------------------------------------------------

def embed_inputs(cfg, policy, params, tokens, patch_embeds=None):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.act_dtype)
    if cfg.family == "vlm" and patch_embeds is not None:
        pp = params["patch_proj"]
        pe = mfmac.mf_linear(
            patch_embeds.astype(jnp.float32), pp["w"], pp["gamma"], policy=policy
        ).astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    return x


def forward(
    cfg: ModelConfig,
    policy: QuantPolicy,
    params,
    tokens: jax.Array,  # (B, S) int32
    *,
    patch_embeds: Optional[jax.Array] = None,
    remat: bool = True,
    return_kv: bool = False,
):
    """Full-sequence forward. Returns logits (B, S_total, V_padded)."""
    x = embed_inputs(cfg, policy, params, tokens, patch_embeds)
    x = actshard.shard_tokens(x)
    s_total = x.shape[1]
    qpos = jax.lax.iota(jnp.int32, s_total)

    def body(carry, lp):
        y, kv = _block(cfg, policy, lp, carry, qpos)
        y = actshard.shard_tokens(y)
        return y, (kv if return_kv else None)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, kvs = jax.lax.scan(body, x, params["layers"])
    x = common.apply_norm(cfg.norm, x, params["final_norm"])
    logits = _lm_head(cfg, policy, params, x)
    if return_kv:
        return logits, kvs
    return logits


def _lm_head(cfg, policy, params, x):
    if cfg.tie_embeddings:
        # Tied head: x @ E^T — quantized with 6-bit last-layer G (App. D).
        # The embedding table is never pre-quantized (lookups use raw
        # values), so force quantize-at-use here.
        if policy.weights_prequantized:
            import dataclasses as _dc

            pol = _dc.replace(policy, weights_prequantized=False)
        else:
            pol = policy
        w = params["embed"].T
        return mfmac.mf_linear(
            x, w, jnp.float32(policy.ratio_clip_init or 1.0),
            policy=pol, is_last=True,
        )
    hp = params["lm_head"]
    return mfmac.mf_linear(
        x, hp["w"], hp["gamma"], policy=policy, is_last=True
    )


def lm_loss(cfg, policy, params, tokens, labels, loss_mask, patch_embeds=None):
    """Mean next-token cross entropy; padded-vocab ids are masked out."""
    logits = forward(cfg, policy, params, tokens, patch_embeds=patch_embeds)
    if patch_embeds is not None:
        logits = logits[:, patch_embeds.shape[1]:, :]
    logits = logits.astype(jnp.float32)
    vpad = cfg.vocab_padded
    if vpad != cfg.vocab:
        invalid = jax.lax.iota(jnp.int32, vpad) >= cfg.vocab
        logits = jnp.where(invalid[None, None, :], -1e30, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    denom = jnp.maximum(jnp.sum(loss_mask), 1.0)
    return jnp.sum(nll * loss_mask) / denom


# --- decode ---------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Ring KV cache. window caps the live span for sliding-window archs."""
    span = min(max_len, cfg.window) if cfg.window else max_len
    L, kv, hd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((L, batch, span, kv, hd), dtype),
        "v": jnp.zeros((L, batch, span, kv, hd), dtype),
        "pos": jnp.full((span,), -1, jnp.int32),  # global pos per slot
        "len": jnp.zeros((), jnp.int32),
    }


def prefill(cfg, policy, params, tokens, cache, patch_embeds=None):
    """Run the prompt through the model, filling the cache; returns logits
    of the last position and the updated cache."""
    logits, kvs = forward(
        cfg, policy, params, tokens, patch_embeds=patch_embeds,
        remat=False, return_kv=True,
    )
    ks, vs = kvs  # (L, B, S, KV, hd)
    s = ks.shape[2]
    span = cache["k"].shape[2]
    take = min(s, span)
    ks_t = ks[:, :, s - take:, :, :].astype(cache["k"].dtype)
    vs_t = vs[:, :, s - take:, :, :].astype(cache["v"].dtype)
    pos = jnp.arange(s - take, s, dtype=jnp.int32)
    cache = dict(cache)
    if take == span:
        # Ring layout: global position p lives in slot p % span.
        shift = s % span
        cache["k"] = jnp.roll(ks_t, shift, axis=2)
        cache["v"] = jnp.roll(vs_t, shift, axis=2)
        cache["pos"] = jnp.roll(pos, shift)
    else:
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], ks_t, (0, 0, 0, 0, 0)
        )
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vs_t, (0, 0, 0, 0, 0)
        )
        cache["pos"] = jax.lax.dynamic_update_slice(cache["pos"], pos, (0,))
    cache["len"] = jnp.asarray(s, jnp.int32)
    return logits[:, -1, :], cache


def _layer_pages(store, layer, pages, *, write):
    """Layer ``layer``'s ``pages`` of a stacked page store (L, P, page,
    ...): ``(view, index)`` such that ``view[index]`` gathers them and
    ``view.at[index + (offset,)]`` writes into them.

    On one device the view is the store as one (L * P, page, ...) array
    of pages, and a page past the layer's last (serve.slots.drop_id)
    indexes past the whole array for a ``write``, which then drops, or
    the layer's null page for a read, as an unstacked gather clamps it.
    Indexed by (layer, page) pairs, the TPU keeps a store whose minor dim
    is under 128 lanes (PoT code pages) in one layout for the scatter and
    another for the gather, and copies the whole stack between them
    every layer.  A pool sharded over its pages (an active actshard plan,
    serve/engine.py) keeps the (layer, page) pairs: merging the sharded
    page dim into the layer dim would gather the whole store onto every
    device."""
    if actshard.active_plan() is not None:
        return store, (layer, pages)
    n_layers, n_pages = store.shape[:2]
    flat = store.reshape((n_layers * n_pages,) + store.shape[2:])
    if write:
        row = jnp.where(pages < n_pages, layer * n_pages + pages,
                        n_layers * n_pages)
    else:
        row = layer * n_pages + jnp.minimum(pages, n_pages - 1)
    return flat, (row,)


def _page_view(leaf, table, span, layer=None):
    """Logical (B, span, ...) row view of a physical page store
    (P, page, ...): gather the slot tables, flatten pages back into a
    span.  Out-of-bounds table entries (serve.slots.drop_id — retired /
    unallocated slots) clamp onto the null page, whose ``pos`` is -1, so
    everything they contribute is masked out of attention.  With
    ``layer`` the store is the stacked (L, P, page, ...) one, and the
    layer's pages come out of it in one gather, without slicing the
    layer's store out first.

    The gather, like every write through a page table, runs under the
    ``paged_kv`` name scope, which a profile reads as the paged K/V
    path's device time (``paged_kv_share`` in PERF.md)."""
    with jax.named_scope("paged_kv"):
        b = table.shape[0]
        if layer is None:
            x = leaf[table]  # (B, n, page, ...)
        else:
            view, idx = _layer_pages(leaf, layer, table, write=False)
            x = view[idx]
        return x.reshape((b, span) + x.shape[3:])


def _kv_check(policy, cache):
    """Is this cache PoT-quantized (serve/slots.py wire format)?  The
    recipe rides the policy (static jit arg); quantization applies iff
    the cache carries the beta scale leaves — a raw cache under a
    kv_quant policy (solo prefill mini caches) stays fp."""
    kvq = isinstance(cache, dict) and "k_beta" in cache
    if kvq and policy.kv_quant is None:
        raise ValueError(
            "cache holds quantized K/V pages but policy.kv_quant is None"
        )
    return kvq


def _kv_scatter(ck, cb, vals, dest, loff, spec, layer=None):
    """Scatter freshly computed K or V vectors (B[, C], KV, hd) into a
    physical page store at (dest, loff) — PoT-encoding them (and their
    per-token betas into ``cb``) when ``spec`` is set.  With ``layer``
    the stores are the stacked ones and the write lands in that layer's
    pages."""
    def put(store, x):
        if layer is None:
            return store.at[dest, loff].set(x, mode="drop")
        view, idx = _layer_pages(store, layer, dest, write=True)
        return view.at[idx + (loff,)].set(x, mode="drop").reshape(store.shape)

    with jax.named_scope("paged_kv"):
        if spec is None:
            return put(ck, vals.astype(ck.dtype)), cb
        codes, beta = compress.kv_page_encode(vals, spec)
        return put(ck, codes), put(cb, beta)


def _kv_page_view(ck, cb, table, span, spec, dtype, layer=None):
    """Gathered logical (B, span, KV, hd) K/V view, dequantized to exact
    PoT float values when ``spec`` is set.  Those values feed the existing
    fixed-order ``_sdpa`` reductions unchanged: exact-PoT operands in the
    highest-precision dot ARE the MF-MAC shift-add datapath (the same
    realization the weight path uses — docs/DESIGN_kernels.md)."""
    with jax.named_scope("paged_kv"):
        view = _page_view(ck, table, span, layer)
        if spec is None:
            return view.astype(dtype)
        bview = _page_view(cb, table, span, layer)
        return compress.kv_page_decode(view, bview, spec).astype(dtype)


#: The stacked (L, ...) K/V store leaves of a decode cache; the beta
#: scale leaves exist only in the PoT-quantized page layout.
_KV_STORES = ("k", "v", "k_beta", "v_beta")


def _kv_stores(ck, cv, ckb=None, cvb=None):
    return {n: a for n, a in zip(_KV_STORES, (ck, cv, ckb, cvb))
            if a is not None}


def _scan_layers(params, cache, x, layer):
    """Run ``layer(y, lp, l, kv) -> (y, kv)`` over the stacked layers,
    ``l`` the layer index and ``kv`` the cache's whole stacked K/V stores
    (``_KV_STORES``).

    The stores ride in the scan's carry, and each layer writes and
    gathers its own pages at index ``l``.  As the scan's xs/ys instead,
    every step would slice each layer's store out and stack it back —
    two copies of the whole cache, whatever the few positions written.
    With the cache donated to the step (serve/engine.py) XLA updates the
    stores in place."""
    kv = {n: cache[n] for n in _KV_STORES if n in cache}
    idx = jax.lax.iota(jnp.int32, cache["k"].shape[0])

    def body(carry, xs):
        y, kv = carry
        lp, l = xs
        return layer(y, lp, l, kv), None

    (x, kv), _ = jax.lax.scan(body, (x, kv), (params["layers"], idx))
    return x, kv


def decode_step(cfg, policy, params, token, cache):
    """One decode step.  token: (B,) int32 -> (logits (B, V), new cache).

    Three cache layouts are accepted (``registry.init_cache`` vs
    ``registry.init_pool_cache``):

    * lockstep — ``len`` scalar, ``pos`` (span,): every row decodes at the
      same position (the pre-pool batched path);
    * slot-pooled — ``len`` (B,), ``pos`` (B, span): each row is a serving
      slot with its own cache offset, so requests admitted mid-flight
      decode next to requests deep into generation (serve/engine.py);
    * paged — slot-pooled plus a ``table`` leaf (serve/slots.py): K/V live
      in fixed-size pages and each slot's row is gathered through its page
      table.  The gathered view contains exactly the same (position,
      value) pairs the contiguous row would, in the same logical order, so
      the attention reduction — and the served bits — are invariant to
      the physical page layout and the page size.

    MoE layers dispatch **per slot** (``_moe_apply(per_slot=True)``): each
    row has its own expert capacity, so neither retired nor live
    neighbours can change a request's expert routing — MoE decode is
    batch-invariant like everything else in this step.
    """
    b = token.shape[0]
    x = jnp.take(params["embed"], token[:, None], axis=0)
    pos = cache["len"]
    per_slot = pos.ndim == 1
    paged = "table" in cache
    kvq = _kv_check(policy, cache)
    spec = policy.kv_quant if kvq else None
    if paged:
        table = cache["table"]  # (B, n)
        page = cache["pos"].shape[1]
        span = table.shape[1] * page
    else:
        span = cache["k"].shape[2]
    slot = pos % span
    rows = jnp.arange(b)
    if paged:
        qpos = pos[:, None].astype(jnp.int32)  # (B, 1)
        with jax.named_scope("paged_kv"):
            # physical write target; drop_id rows (dead slots) scatter-drop
            dest = jnp.take_along_axis(table, (slot // page)[:, None],
                                       1)[:, 0]
            loff = slot % page
            kpos_new = cache["pos"].at[dest, loff].set(pos, mode="drop")
            kpos_view = _page_view(kpos_new, table, span)  # (B, span)
        pq = qpos
    elif per_slot:
        qpos = pos[:, None].astype(jnp.int32)  # (B, 1)
        kpos_new = cache["pos"].at[rows, slot].set(pos)  # (B, span)
        kpos_view = kpos_new
        pq = qpos
    else:
        qpos = pos[None].astype(jnp.int32)  # (1,)
        kpos_new = jax.lax.dynamic_update_slice(
            cache["pos"], pos[None], (slot,)
        )
        kpos_view = kpos_new
        pq = jnp.broadcast_to(qpos[None, :], (b, 1))

    def layer(carry, lp, l, kv):
        ck, cv, ckb, cvb = (kv.get(n) for n in _KV_STORES)
        h = common.apply_norm(cfg.norm, carry, lp["ln1"])
        # project new token
        q = mfmac.mf_linear(h, lp["wq"]["w"], lp["wq"]["gamma"], policy=policy)
        k = mfmac.mf_linear(h, lp["wk"]["w"], lp["wk"]["gamma"], policy=policy)
        v = mfmac.mf_linear(h, lp["wv"]["w"], lp["wv"]["gamma"], policy=policy)
        q = q.reshape(b, 1, cfg.n_heads, cfg.head_dim)
        k = k.reshape(b, 1, cfg.kv_heads, cfg.head_dim)
        v = v.reshape(b, 1, cfg.kv_heads, cfg.head_dim)
        q = common.rope(q, pq, cfg.rope_theta)
        k = common.rope(k, pq, cfg.rope_theta)
        if paged:
            ck, ckb = _kv_scatter(ck, ckb, k[:, 0], dest, loff, spec, l)
            cv, cvb = _kv_scatter(cv, cvb, v[:, 0], dest, loff, spec, l)
            kview = _kv_page_view(ck, ckb, table, span, spec, q.dtype, l)
            vview = _kv_page_view(cv, cvb, table, span, spec, q.dtype, l)
        else:
            if per_slot:
                ck = ck.at[l, rows, slot].set(k[:, 0].astype(ck.dtype))
                cv = cv.at[l, rows, slot].set(v[:, 0].astype(cv.dtype))
            else:
                ck = jax.lax.dynamic_update_slice(
                    ck, k[None].astype(ck.dtype), (l, 0, slot, 0, 0)
                )
                cv = jax.lax.dynamic_update_slice(
                    cv, v[None].astype(cv.dtype), (l, 0, slot, 0, 0)
                )
            kview, vview = ck[l].astype(q.dtype), cv[l].astype(q.dtype)
        att = _sdpa(cfg, policy, q, kview, vview, qpos, kpos_view, cfg.window)
        att = att.reshape(b, 1, cfg.n_heads * cfg.head_dim)
        y = carry + mfmac.mf_linear(
            att, lp["wo"]["w"], lp["wo"]["gamma"], policy=policy
        )
        h2 = common.apply_norm(cfg.norm, y, lp["ln2"])
        if cfg.moe is not None:
            y = y + _moe_apply(cfg, policy, lp["moe"], h2, per_slot=True)
        else:
            y = y + _mlp_apply(cfg, policy, lp["mlp"], h2)
        return y, _kv_stores(ck, cv, ckb, cvb)

    x, kv = _scan_layers(params, cache, x, layer)
    x = common.apply_norm(cfg.norm, x, params["final_norm"])
    logits = _lm_head(cfg, policy, params, x)[:, 0, :]
    new_cache = dict(kv, pos=kpos_new, len=pos + 1)
    if paged:
        new_cache["table"] = table
    return logits, new_cache


def chunk_step(cfg, policy, params, tokens, n_new, cache):
    """One fused pooled step over ``(B, C)`` token positions — the chunked
    piggybacked-prefill step body (serve/engine.py).

    Every slot advances by its own ``n_new[b]`` (0..C) positions in the
    same fixed-shape dispatch: decode slots carry one valid token
    (``tokens[b, 0]``), prefilling slots consume up to C prompt tokens,
    idle slots carry none.  Positions past ``n_new[b]`` are padding: their
    qpos is -1 (they attend to nothing and are never written to the
    cache), their K/V scatters are dropped via out-of-bounds indices, and
    their activations are deterministic per row — so each slot's outputs
    depend only on its own (tokens, n_new) trajectory, never on its pool
    neighbours (the serve bit-identity guarantee, chunked edition).

    Attention layout depends on the window.  Windowed archs attend over
    [ring cache ∪ fresh chunk K/V] so a ring wrap inside the chunk can't
    overwrite keys that earlier chunk positions still need; requires
    C <= span.  Without a window no wrap can occur (every gpos < span),
    so the step scatters first and attends over the post-scatter cache
    view — the *same* reduction ``decode_step`` performs — and pad
    positions are zeroed at each norm output so per-row activation-scale
    groups match decode's.  Together these make a decode row (n_new == 1)
    bit-equal between ``chunk_step`` and ``decode_step``, which is what
    lets the engine's decode fast-path switch step bodies mid-request.

    Returns (logits (B, V) at each slot's last valid position, new pooled
    cache).  Slot-pooled caches only (``len`` (B,), ``pos`` (B, span) —
    or the paged layout with a ``table`` leaf, see serve/slots.py).
    """
    b, c = tokens.shape
    pos0 = cache["len"]
    assert pos0.ndim == 1, "chunk_step requires the slot-pooled cache layout"
    paged = "table" in cache
    kvq = _kv_check(policy, cache)
    spec = policy.kv_quant if kvq else None
    if paged:
        table = cache["table"]  # (B, n)
        page = cache["pos"].shape[1]
        npg = table.shape[1]
        span = npg * page
        drop = cache["pos"].shape[0]  # num_pages + 1 == slots.drop_id
    else:
        span = cache["k"].shape[2]
    assert c <= span, (c, span)
    x = jnp.take(params["embed"], tokens, axis=0)  # (B, C, D)
    rows = jnp.arange(b)
    offs = jax.lax.iota(jnp.int32, c)
    valid = offs[None, :] < n_new[:, None]  # (B, C)
    gpos = pos0[:, None] + offs[None, :]  # (B, C) global positions
    qpos = jnp.where(valid, gpos, -1)
    # ring slot per valid position; invalid positions scatter out of
    # bounds and are dropped (C <= span => no duplicate valid slots)
    lo = gpos % span
    if paged:
        # physical (page, offset) write target per valid position; pads
        # route through an extra all-drop table column
        with jax.named_scope("paged_kv"):
            table_ext = jnp.concatenate(
                [table, jnp.full((b, 1), drop, table.dtype)], axis=1
            )
            lpage = jnp.where(valid, lo // page, npg)
            dest = jnp.take_along_axis(table_ext, lpage, axis=1)  # (B, C)
            loff = lo % page
            kpos_old = _page_view(cache["pos"], table, span)  # (B, span)
            kpos_new = cache["pos"].at[dest, loff].set(qpos, mode="drop")
            kpos_view = _page_view(kpos_new, table, span)
    else:
        sidx = jnp.where(valid, lo, span)
        kpos_old = cache["pos"]  # (B, span), pre-step — all entries < pos0
        kpos_new = kpos_old.at[rows[:, None], sidx].set(qpos, mode="drop")
        kpos_view = kpos_new
    windowed = cfg.window is not None

    def layer(carry, lp, l, kv):
        ck, cv, ckb, cvb = (kv.get(n) for n in _KV_STORES)
        h = common.apply_norm(cfg.norm, carry, lp["ln1"])
        # Zero pad positions BEFORE the projections: each row's
        # activation-scale group is its (C, D) block, so with pads
        # zeroed the group amax equals the single valid row's — the same
        # amax decode_step's (1, D) group sees.  Decode-row bit-equality
        # across step bodies hinges on this.
        h = jnp.where(valid[:, :, None], h, 0.0)
        q = mfmac.mf_linear(h, lp["wq"]["w"], lp["wq"]["gamma"], policy=policy)
        k = mfmac.mf_linear(h, lp["wk"]["w"], lp["wk"]["gamma"], policy=policy)
        v = mfmac.mf_linear(h, lp["wv"]["w"], lp["wv"]["gamma"], policy=policy)
        q = q.reshape(b, c, cfg.n_heads, cfg.head_dim)
        k = k.reshape(b, c, cfg.kv_heads, cfg.head_dim)
        v = v.reshape(b, c, cfg.kv_heads, cfg.head_dim)
        q = common.rope(q, qpos, cfg.rope_theta)
        k = common.rope(k, qpos, cfg.rope_theta)
        if windowed:
            # attend over [old cache ∪ fresh chunk]: old entries hold
            # only positions < pos0, fresh ones >= pos0 (qpos -1 where
            # invalid), so the position mask sees each key exactly once
            # even when the ring wraps mid-chunk.  The old view is
            # gathered before the scatter below overwrites the stores.
            # In the quantized layout the fresh in-chunk K/V is re-read
            # through the wire format (encode-then-decode) so every
            # attended key is the same PoT value later steps will gather
            # — the chunked admission must reproduce the incremental
            # write paths bit for bit.
            if kvq:
                ok = _kv_page_view(ck, ckb, table, span, spec, q.dtype, l)
                ov = _kv_page_view(cv, cvb, table, span, spec, q.dtype, l)
                kc, kb = compress.kv_page_encode(k, spec)
                vc, vb = compress.kv_page_encode(v, spec)
                kf = compress.kv_page_decode(kc, kb, spec).astype(q.dtype)
                vf = compress.kv_page_decode(vc, vb, spec).astype(q.dtype)
            else:
                ok = _page_view(ck, table, span, l) if paged else ck[l]
                ov = _page_view(cv, table, span, l) if paged else cv[l]
                kf, vf = k, v
        if paged:
            nk, nkb = _kv_scatter(ck, ckb, k, dest, loff, spec, l)
            nv, nvb = _kv_scatter(cv, cvb, v, dest, loff, spec, l)
        else:
            nk = ck.at[l, rows[:, None], sidx].set(k.astype(ck.dtype),
                                                   mode="drop")
            nv = cv.at[l, rows[:, None], sidx].set(v.astype(cv.dtype),
                                                   mode="drop")
        if windowed:
            k_all = jnp.concatenate([ok.astype(q.dtype), kf], axis=1)
            v_all = jnp.concatenate([ov.astype(q.dtype), vf], axis=1)
            kpos_all = jnp.concatenate([kpos_old, qpos], axis=1)
            att = _sdpa(
                cfg, policy, q, k_all, v_all, qpos, kpos_all, cfg.window
            )
        else:
            # scatter-then-attend over the post-scatter span view — the
            # identical reduction decode_step performs (decode fast-path
            # bit-equality); no window => no ring wrap => safe
            if kvq:
                kv_k = _kv_page_view(nk, nkb, table, span, spec, q.dtype, l)
                kv_v = _kv_page_view(nv, nvb, table, span, spec, q.dtype, l)
            else:
                kv_k = (_page_view(nk, table, span, l) if paged else nk[l]
                        ).astype(q.dtype)
                kv_v = (_page_view(nv, table, span, l) if paged else nv[l]
                        ).astype(q.dtype)
            att = _sdpa(
                cfg, policy, q, kv_k, kv_v, qpos, kpos_view, None,
            )
        att = att.reshape(b, c, cfg.n_heads * cfg.head_dim)
        # A pad query's mask is all-False => softmax degenerates to a
        # UNIFORM average over every key — including a reused slot's
        # stale K/V, which would leak into the slot's shared (C, D)
        # activation-scale group and break pool-vs-solo bit-identity.
        # Zero it: pad rows then depend only on their own (token, n_new).
        att = jnp.where(valid[:, :, None], att, 0.0)
        y = carry + mfmac.mf_linear(
            att, lp["wo"]["w"], lp["wo"]["gamma"], policy=policy
        )
        h2 = common.apply_norm(cfg.norm, y, lp["ln2"])
        # same group-amax argument as h above, for the MLP/MoE input
        h2 = jnp.where(valid[:, :, None], h2, 0.0)
        if cfg.moe is not None:
            y = y + _moe_apply(cfg, policy, lp["moe"], h2, per_slot=True)
        else:
            y = y + _mlp_apply(cfg, policy, lp["mlp"], h2)
        return y, _kv_stores(nk, nv, nkb, nvb)

    x, kv = _scan_layers(params, cache, x, layer)
    # emit at each slot's last valid position (gather BEFORE the head so
    # its activation-scale group is the (1, D) row, same as decode_step)
    emit = jnp.clip(n_new - 1, 0, c - 1)
    xe = x[rows, emit][:, None, :]  # (B, 1, D)
    xe = common.apply_norm(cfg.norm, xe, params["final_norm"])
    logits = _lm_head(cfg, policy, params, xe)[:, 0, :]
    new_cache = dict(kv, pos=kpos_new, len=pos0 + n_new)
    if paged:
        new_cache["table"] = table
    return logits, new_cache


def verify_step(cfg, policy, params, tokens, n_new, cache):
    """Score ``n_new[b]`` candidate tokens per slot in ONE weight pass,
    bit-identically to ``n_new[b]`` sequential ``decode_step`` calls — the
    speculative-decoding verifier (serve/spec.py).

    ``chunk_step`` cannot be the verifier: it quantizes each slot's chunk
    as one ``(C, D)`` activation-scale group, so a multi-token row shares
    one amax across positions and its logits differ from sequential
    decode's in the last bit.  This step instead streams the weights once
    (the outer layer scan) and replays decode's exact per-position ops in
    an inner Python loop over the C positions: every projection /
    attention / MLP runs on a ``(B, 1, D)`` slice with decode's own
    ``(1, D)`` scale groups, position i's K/V scatter lands before
    position i+1's attention, and the final norm + LM head run per
    position.  The op-for-op dataflow DAG is decode's with the layer and
    position loops interchanged — same values, reduced in the same order,
    so the result is bit-identical by construction on every backend and
    cache layout (including windowed rings, where the sequential
    write-then-attend per position reproduces decode's eviction order
    exactly; the strict ``kpos > qpos - window`` mask means the slot a
    write evicts was already outside its own and every later window).

    ``tokens[b, :n_new[b]]`` is the verify row: the slot's last emitted
    token followed by the draft candidates.  Positions past ``n_new[b]``
    are padding exactly as in ``chunk_step`` (qpos -1, scatters dropped
    out of bounds, activations quarantined in their own scale group).

    Returns (logits ``(B, C, V)`` — position i scores the token *after*
    ``tokens[b, i]`` — and the new cache with ``len = len + n_new``).
    The caller owns acceptance and the rollback of rejected positions
    (serve/slots.py spec_snapshot/spec_restore).
    """
    b, c = tokens.shape
    pos0 = cache["len"]
    assert pos0.ndim == 1, "verify_step requires the slot-pooled cache layout"
    paged = "table" in cache
    kvq = _kv_check(policy, cache)
    spec = policy.kv_quant if kvq else None
    if paged:
        table = cache["table"]  # (B, n)
        page = cache["pos"].shape[1]
        npg = table.shape[1]
        span = npg * page
        drop = cache["pos"].shape[0]  # num_pages + 1 == slots.drop_id
    else:
        span = cache["k"].shape[2]
    assert c <= span, (c, span)
    x = jnp.take(params["embed"], tokens, axis=0)  # (B, C, D)
    rows = jnp.arange(b)
    offs = jax.lax.iota(jnp.int32, c)
    valid = offs[None, :] < n_new[:, None]  # (B, C)
    gpos = pos0[:, None] + offs[None, :]
    qpos = jnp.where(valid, gpos, -1)
    lo = gpos % span
    # Per-position write targets (decode_step's, one column per position;
    # pads scatter out of bounds and drop).  kpos is written position by
    # position so position i's attention sees exactly the pos view
    # sequential decode would.
    kpos_phys = cache["pos"]
    kpos_views, dests, loffs, sidxs = [], [], [], []
    if paged:
        table_ext = jnp.concatenate(
            [table, jnp.full((b, 1), drop, table.dtype)], axis=1
        )
        lpage = jnp.where(valid, lo // page, npg)
        loff_all = lo % page
    else:
        sidx_all = jnp.where(valid, lo, span)
    for i in range(c):
        if paged:
            dest_i = jnp.take_along_axis(
                table_ext, lpage[:, i:i + 1], axis=1
            )[:, 0]
            dests.append(dest_i)
            loffs.append(loff_all[:, i])
            kpos_phys = kpos_phys.at[dest_i, loff_all[:, i]].set(
                qpos[:, i], mode="drop"
            )
            kpos_views.append(_page_view(kpos_phys, table, span))
        else:
            sidxs.append(sidx_all[:, i])
            kpos_phys = kpos_phys.at[rows, sidx_all[:, i]].set(
                qpos[:, i], mode="drop"
            )
            kpos_views.append(kpos_phys)

    def layer(carry, lp, l, kv):
        ck, cv, ckb, cvb = (kv.get(n) for n in _KV_STORES)
        outs = []
        for i in range(c):
            xi = carry[:, i:i + 1, :]  # (B, 1, D) — decode's input shape
            h = common.apply_norm(cfg.norm, xi, lp["ln1"])
            q = mfmac.mf_linear(h, lp["wq"]["w"], lp["wq"]["gamma"],
                                policy=policy)
            k = mfmac.mf_linear(h, lp["wk"]["w"], lp["wk"]["gamma"],
                                policy=policy)
            v = mfmac.mf_linear(h, lp["wv"]["w"], lp["wv"]["gamma"],
                                policy=policy)
            q = q.reshape(b, 1, cfg.n_heads, cfg.head_dim)
            k = k.reshape(b, 1, cfg.kv_heads, cfg.head_dim)
            v = v.reshape(b, 1, cfg.kv_heads, cfg.head_dim)
            pq = qpos[:, i:i + 1]  # (B, 1)
            q = common.rope(q, pq, cfg.rope_theta)
            k = common.rope(k, pq, cfg.rope_theta)
            if paged:
                ck, ckb = _kv_scatter(ck, ckb, k[:, 0], dests[i], loffs[i],
                                      spec, l)
                cv, cvb = _kv_scatter(cv, cvb, v[:, 0], dests[i], loffs[i],
                                      spec, l)
                kview = _kv_page_view(ck, ckb, table, span, spec, q.dtype,
                                      l)
                vview = _kv_page_view(cv, cvb, table, span, spec, q.dtype,
                                      l)
            else:
                ck = ck.at[l, rows, sidxs[i]].set(
                    k[:, 0].astype(ck.dtype), mode="drop"
                )
                cv = cv.at[l, rows, sidxs[i]].set(
                    v[:, 0].astype(cv.dtype), mode="drop"
                )
                kview, vview = ck[l].astype(q.dtype), cv[l].astype(q.dtype)
            att = _sdpa(cfg, policy, q, kview, vview, pq, kpos_views[i],
                        cfg.window)
            att = att.reshape(b, 1, cfg.n_heads * cfg.head_dim)
            y = xi + mfmac.mf_linear(
                att, lp["wo"]["w"], lp["wo"]["gamma"], policy=policy
            )
            h2 = common.apply_norm(cfg.norm, y, lp["ln2"])
            if cfg.moe is not None:
                y = y + _moe_apply(cfg, policy, lp["moe"], h2, per_slot=True)
            else:
                y = y + _mlp_apply(cfg, policy, lp["mlp"], h2)
            outs.append(y)
        return jnp.concatenate(outs, axis=1), _kv_stores(ck, cv, ckb, cvb)

    x, kv = _scan_layers(params, cache, x, layer)
    # per-position head: each (B, 1, D) slice keeps decode's (1, D)
    # activation-scale group through the final norm and LM head
    logits = []
    for i in range(c):
        xe = common.apply_norm(cfg.norm, x[:, i:i + 1, :],
                               params["final_norm"])
        logits.append(_lm_head(cfg, policy, params, xe)[:, 0, :])
    logits = jnp.stack(logits, axis=1)  # (B, C, V)
    new_cache = dict(kv, pos=kpos_phys, len=pos0 + n_new)
    if paged:
        new_cache["table"] = table
    return logits, new_cache
