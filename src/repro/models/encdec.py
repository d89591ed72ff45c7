"""Whisper-style encoder-decoder backbone (arXiv:2212.04356).

The conv/mel frontend is a STUB per the assignment: ``input_specs()``
provides precomputed frame embeddings (B, enc_seq, frame_dim) which a
single (quantized) linear projects into the encoder width.  Everything
else — 32 encoder layers (bidirectional), 32 decoder layers (causal self
attention + cross attention) — is real and MF-MAC quantized.

whisper-large-v3 has 32 encoder AND 32 decoder layers; the assigned "32L"
is interpreted as 32+32 (recorded in DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import mfmac
from repro.models import common
from repro.models.spec import ParamSpec
from repro.parallel import actshard


def _linear(shape, axes, std, stacked=True):
    if axes and axes[0] == "layer":
        gshape, gaxes = (shape[0],), ("layer",)
    else:
        gshape, gaxes = (), ()
    return {
        "w": ParamSpec(shape, axes, std=std),
        "gamma": ParamSpec(gshape, gaxes, init="value", value=0.95),
    }


def _ln(L, d):
    return {
        "scale": ParamSpec((L, d), ("layer", None), init="ones"),
        "bias": ParamSpec((L, d), ("layer", None), init="zeros"),
    }


def encdec_specs(cfg: ModelConfig):
    d, hd, std = cfg.d_model, cfg.head_dim, 0.02
    Le, Ld = cfg.enc_layers, cfg.n_layers
    h, kv, f = cfg.n_heads, cfg.kv_heads, cfg.d_ff
    enc_layer = {
        "ln1": _ln(Le, d),
        "ln2": _ln(Le, d),
        "wq": _linear((Le, d, h * hd), ("layer", "embed", "heads"), std),
        "wk": _linear((Le, d, kv * hd), ("layer", "embed", "kv"), std),
        "wv": _linear((Le, d, kv * hd), ("layer", "embed", "kv"), std),
        "wo": _linear((Le, h * hd, d), ("layer", "heads", "embed"), std),
        "wi": _linear((Le, d, f), ("layer", "embed", "ffn"), std),
        "wo2": _linear((Le, f, d), ("layer", "ffn", "embed"), std),
    }
    dec_layer = {
        "ln1": _ln(Ld, d),
        "ln_cross": _ln(Ld, d),
        "ln2": _ln(Ld, d),
        "wq": _linear((Ld, d, h * hd), ("layer", "embed", "heads"), std),
        "wk": _linear((Ld, d, kv * hd), ("layer", "embed", "kv"), std),
        "wv": _linear((Ld, d, kv * hd), ("layer", "embed", "kv"), std),
        "wo": _linear((Ld, h * hd, d), ("layer", "heads", "embed"), std),
        "cq": _linear((Ld, d, h * hd), ("layer", "embed", "heads"), std),
        "ck": _linear((Ld, d, kv * hd), ("layer", "embed", "kv"), std),
        "cv": _linear((Ld, d, kv * hd), ("layer", "embed", "kv"), std),
        "co": _linear((Ld, h * hd, d), ("layer", "heads", "embed"), std),
        "wi": _linear((Ld, d, f), ("layer", "embed", "ffn"), std),
        "wo2": _linear((Ld, f, d), ("layer", "ffn", "embed"), std),
    }
    return {
        "frame_proj": _linear((cfg.frame_dim, d), (None, "embed"), std),
        "enc_pos": ParamSpec((cfg.enc_seq, d), (None, "embed"), std=0.01),
        "embed": ParamSpec((cfg.vocab_padded, d), ("vocab", "embed"), std=0.02),
        "enc_layers": enc_layer,
        "dec_layers": dec_layer,
        "enc_norm": {
            "scale": ParamSpec((d,), (None,), init="ones"),
            "bias": ParamSpec((d,), (None,), init="zeros"),
        },
        "dec_norm": {
            "scale": ParamSpec((d,), (None,), init="ones"),
            "bias": ParamSpec((d,), (None,), init="zeros"),
        },
    }


def _proj_heads(p, name, x, policy, b, s, nh, hd):
    q = mfmac.mf_linear(x, p[name]["w"], p[name]["gamma"], policy=policy)
    return q.reshape(b, s, nh, hd)


def _mha(cfg, policy, q, k, v, qpos, kpos, causal):
    from repro.models.transformer import _sdpa

    if causal:
        return _sdpa(cfg, policy, q, k, v, qpos, kpos, None)
    # bidirectional: reuse _sdpa with an always-true mask via qpos >= kpos
    # trick is wrong; do it directly here.
    b, sq, h, hd = q.shape
    kf = common._expand_kv(k, h)
    vf = common._expand_kv(v, h)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    scores = (
        mfmac.mf_act_dot(
            jnp.transpose(q, (0, 2, 1, 3)),
            jnp.transpose(kf, (0, 2, 1, 3)),
            (((3,), (3,)), ((0, 1), (0, 1))),
            policy=policy,
        ).astype(jnp.float32)
        * scale
    )
    probs = jax.nn.softmax(scores, axis=-1)
    out = mfmac.mf_act_dot(
        probs.astype(q.dtype),
        jnp.transpose(vf, (0, 2, 1, 3)),
        (((3,), (2,)), ((0, 1), (0, 1))),
        policy=policy,
    )
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def encode(cfg, policy, params, frames, *, remat: bool = True):
    """frames: (B, enc_seq, frame_dim) precomputed embeddings (stub)."""
    fp = params["frame_proj"]
    x = mfmac.mf_linear(
        frames.astype(jnp.float32), fp["w"], fp["gamma"], policy=policy
    )
    x = (x + params["enc_pos"][None]).astype(cfg.act_dtype)
    b, s, d = x.shape
    hd = cfg.head_dim
    pos = jax.lax.iota(jnp.int32, s)

    def body(carry, lp):
        h = common.layer_norm(carry, lp["ln1"]["scale"], lp["ln1"]["bias"])
        q = _proj_heads(lp, "wq", h, policy, b, s, cfg.n_heads, hd)
        k = _proj_heads(lp, "wk", h, policy, b, s, cfg.kv_heads, hd)
        v = _proj_heads(lp, "wv", h, policy, b, s, cfg.kv_heads, hd)
        att = _mha(cfg, policy, q, k, v, pos, pos, causal=False)
        att = att.reshape(b, s, cfg.n_heads * hd)
        y = carry + mfmac.mf_linear(
            att, lp["wo"]["w"], lp["wo"]["gamma"], policy=policy
        )
        h2 = common.layer_norm(y, lp["ln2"]["scale"], lp["ln2"]["bias"])
        m = common.gelu(
            mfmac.mf_linear(h2, lp["wi"]["w"], lp["wi"]["gamma"], policy=policy)
        )
        y = y + mfmac.mf_linear(m, lp["wo2"]["w"], lp["wo2"]["gamma"], policy=policy)
        return actshard.shard_tokens(y), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x = actshard.shard_tokens(x)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return common.layer_norm(
        x, params["enc_norm"]["scale"], params["enc_norm"]["bias"]
    )


def _dec_block(cfg, policy, lp, x, enc_out, qpos, *, cache=None):
    b, s, d = x.shape
    hd = cfg.head_dim
    se = enc_out.shape[1]
    epos = jax.lax.iota(jnp.int32, se)
    h = common.layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
    q = _proj_heads(lp, "wq", h, policy, b, s, cfg.n_heads, hd)
    k = _proj_heads(lp, "wk", h, policy, b, s, cfg.kv_heads, hd)
    v = _proj_heads(lp, "wv", h, policy, b, s, cfg.kv_heads, hd)
    qp = jnp.broadcast_to(qpos[None, :], (b, s))
    q = common.rope(q, qp, cfg.rope_theta)
    k = common.rope(k, qp, cfg.rope_theta)
    new_kv = (k, v)
    if cache is not None:
        ck, cv, kpos, slot = cache
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
        k, v = ck.astype(q.dtype), cv.astype(q.dtype)
        new_kv = (ck, cv)
    else:
        kpos = qpos
    from repro.models.transformer import _sdpa

    att = _sdpa(cfg, policy, q, k, v, qpos, kpos, None)
    x = x + mfmac.mf_linear(
        att.reshape(b, s, cfg.n_heads * hd), lp["wo"]["w"], lp["wo"]["gamma"],
        policy=policy,
    )
    # cross attention
    hc = common.layer_norm(x, lp["ln_cross"]["scale"], lp["ln_cross"]["bias"])
    cq = _proj_heads(lp, "cq", hc, policy, b, s, cfg.n_heads, hd)
    ck_ = _proj_heads(lp, "ck", enc_out, policy, b, se, cfg.kv_heads, hd)
    cv_ = _proj_heads(lp, "cv", enc_out, policy, b, se, cfg.kv_heads, hd)
    catt = _mha(cfg, policy, cq, ck_, cv_, qpos, epos, causal=False)
    x = x + mfmac.mf_linear(
        catt.reshape(b, s, cfg.n_heads * hd), lp["co"]["w"], lp["co"]["gamma"],
        policy=policy,
    )
    h2 = common.layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
    m = common.gelu(
        mfmac.mf_linear(h2, lp["wi"]["w"], lp["wi"]["gamma"], policy=policy)
    )
    x = x + mfmac.mf_linear(m, lp["wo2"]["w"], lp["wo2"]["gamma"], policy=policy)
    return x, new_kv


def forward(cfg, policy, params, tokens, frames, *, remat: bool = True):
    """Returns decoder logits (B, S, V_padded)."""
    enc_out = encode(cfg, policy, params, frames, remat=remat)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.act_dtype)
    s = x.shape[1]
    qpos = jax.lax.iota(jnp.int32, s)

    def body(carry, lp):
        y, _ = _dec_block(cfg, policy, lp, carry, enc_out, qpos)
        return actshard.shard_tokens(y), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x = actshard.shard_tokens(x)
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = common.layer_norm(x, params["dec_norm"]["scale"], params["dec_norm"]["bias"])
    # Whisper ties the output head to the token embedding (embed table is
    # never pre-quantized => force quantize-at-use).
    import dataclasses as _dc

    pol = (_dc.replace(policy, weights_prequantized=False)
           if policy.weights_prequantized else policy)
    w = params["embed"].T
    return mfmac.mf_linear(
        x, w, jnp.float32(policy.ratio_clip_init or 1.0), policy=pol,
        is_last=True,
    )


def lm_loss(cfg, policy, params, tokens, frames, labels, loss_mask):
    logits = forward(cfg, policy, params, tokens, frames).astype(jnp.float32)
    vpad = cfg.vocab_padded
    if vpad != cfg.vocab:
        invalid = jax.lax.iota(jnp.int32, vpad) >= cfg.vocab
        logits = jnp.where(invalid[None, None, :], -1e30, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(loss_mask), 1.0)
    return jnp.sum((logz - gold) * loss_mask) / denom


# --- decode ---------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    L, kv, hd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((L, batch, max_len, kv, hd), dtype),
        "v": jnp.zeros((L, batch, max_len, kv, hd), dtype),
        "pos": jnp.full((max_len,), -1, jnp.int32),
        # cross-attention K/V precomputed once from the encoder output
        "ck": jnp.zeros((L, batch, cfg.enc_seq, kv, hd), dtype),
        "cv": jnp.zeros((L, batch, cfg.enc_seq, kv, hd), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def prefill(cfg, policy, params, tokens, frames, cache):
    enc_out = encode(cfg, policy, params, frames, remat=False)
    b, s = tokens.shape
    hd = cfg.head_dim
    se = enc_out.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0)
    qpos = jax.lax.iota(jnp.int32, s)

    def body(carry, lp):
        y, (k, v) = _dec_block(cfg, policy, lp, carry, enc_out, qpos)
        ck_ = _proj_heads(lp, "ck", enc_out, policy, b, se, cfg.kv_heads, hd)
        cv_ = _proj_heads(lp, "cv", enc_out, policy, b, se, cfg.kv_heads, hd)
        return y, (k, v, ck_, cv_)

    x, (ks, vs, cks, cvs) = jax.lax.scan(body, x, params["dec_layers"])
    x = common.layer_norm(x, params["dec_norm"]["scale"], params["dec_norm"]["bias"])
    import dataclasses as _dc

    _pol = (_dc.replace(policy, weights_prequantized=False)
            if policy.weights_prequantized else policy)
    w = params["embed"].T
    logits = mfmac.mf_linear(
        x[:, -1:, :], w, jnp.float32(policy.ratio_clip_init or 1.0),
        policy=_pol, is_last=True,
    )[:, 0, :]
    span = cache["k"].shape[2]
    pos = jnp.arange(s, dtype=jnp.int32)
    new_cache = dict(cache)
    new_cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0)
    )
    new_cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0)
    )
    new_cache["pos"] = jax.lax.dynamic_update_slice(cache["pos"], pos, (0,))
    new_cache["ck"] = cks.astype(cache["ck"].dtype)
    new_cache["cv"] = cvs.astype(cache["cv"].dtype)
    new_cache["len"] = jnp.asarray(s, jnp.int32)
    return logits, new_cache


def decode_step(cfg, policy, params, token, cache):
    """One decode step.  Like ``transformer.decode_step``, accepts the
    lockstep cache (scalar ``len``, shared ``pos``), the slot-pooled
    cache (``len`` (B,), ``pos`` (B, span)) with per-slot offsets, and
    the paged layout (``table`` leaf; K/V gathered through per-slot page
    tables — serve/slots.py).  Quantized paged pools (``k_beta`` leaves)
    encode/gather self-attention K/V through the PoT wire format; cross
    ``ck``/``cv`` stay raw fp (written once at admission, never shared)."""
    from repro.models.transformer import (
        _kv_check, _kv_page_view, _kv_scatter, _page_view, _sdpa,
    )

    b = token.shape[0]
    hd = cfg.head_dim
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
        dest = jnp.take_along_axis(table, (slot // page)[:, None], 1)[:, 0]
        loff = slot % page
        kpos = cache["pos"].at[dest, loff].set(pos, mode="drop")
        kpos_view = _page_view(kpos, table, span)  # (B, span)
        pq = qpos
    elif per_slot:
        qpos = pos[:, None].astype(jnp.int32)  # (B, 1)
        kpos = cache["pos"].at[rows, slot].set(pos)  # (B, span)
        kpos_view = kpos
        pq = qpos
    else:
        qpos = pos[None].astype(jnp.int32)
        kpos = jax.lax.dynamic_update_slice(cache["pos"], pos[None], (slot,))
        kpos_view = kpos
        pq = jnp.broadcast_to(qpos[None, :], (b, 1))
    se = cache["ck"].shape[2]
    epos = jax.lax.iota(jnp.int32, se)

    def body(carry, lp_kv):
        lp, ck_self, cv_self, ck_x, cv_x, *betas = lp_kv
        ckb, cvb = betas if kvq else (None, None)
        h = common.layer_norm(carry, lp["ln1"]["scale"], lp["ln1"]["bias"])
        q = _proj_heads(lp, "wq", h, policy, b, 1, cfg.n_heads, hd)
        k = _proj_heads(lp, "wk", h, policy, b, 1, cfg.kv_heads, hd)
        v = _proj_heads(lp, "wv", h, policy, b, 1, cfg.kv_heads, hd)
        q = common.rope(q, pq, cfg.rope_theta)
        k = common.rope(k, pq, cfg.rope_theta)
        if paged:
            ck_self, ckb = _kv_scatter(ck_self, ckb, k[:, 0], dest, loff,
                                       spec)
            cv_self, cvb = _kv_scatter(cv_self, cvb, v[:, 0], dest, loff,
                                       spec)
            kview = _kv_page_view(ck_self, ckb, table, span, spec, q.dtype)
            vview = _kv_page_view(cv_self, cvb, table, span, spec, q.dtype)
        elif per_slot:
            ck_self = ck_self.at[rows, slot].set(k[:, 0].astype(ck_self.dtype))
            cv_self = cv_self.at[rows, slot].set(v[:, 0].astype(cv_self.dtype))
            kview = ck_self.astype(q.dtype)
            vview = cv_self.astype(q.dtype)
        else:
            ck_self = jax.lax.dynamic_update_slice(
                ck_self, k.astype(ck_self.dtype), (0, slot, 0, 0)
            )
            cv_self = jax.lax.dynamic_update_slice(
                cv_self, v.astype(cv_self.dtype), (0, slot, 0, 0)
            )
            kview = ck_self.astype(q.dtype)
            vview = cv_self.astype(q.dtype)

        att = _sdpa(cfg, policy, q, kview, vview, qpos, kpos_view, None)
        y = carry + mfmac.mf_linear(
            att.reshape(b, 1, cfg.n_heads * hd), lp["wo"]["w"],
            lp["wo"]["gamma"], policy=policy,
        )
        hc = common.layer_norm(y, lp["ln_cross"]["scale"], lp["ln_cross"]["bias"])
        cq = _proj_heads(lp, "cq", hc, policy, b, 1, cfg.n_heads, hd)
        catt = _mha(
            cfg, policy, cq, ck_x.astype(cq.dtype), cv_x.astype(cq.dtype),
            qpos, epos, causal=False,
        )
        y = y + mfmac.mf_linear(
            catt.reshape(b, 1, cfg.n_heads * hd), lp["co"]["w"],
            lp["co"]["gamma"], policy=policy,
        )
        h2 = common.layer_norm(y, lp["ln2"]["scale"], lp["ln2"]["bias"])
        m = common.gelu(
            mfmac.mf_linear(h2, lp["wi"]["w"], lp["wi"]["gamma"], policy=policy)
        )
        y = y + mfmac.mf_linear(m, lp["wo2"]["w"], lp["wo2"]["gamma"], policy=policy)
        out = (ck_self, cv_self) + ((ckb, cvb) if kvq else ())
        return y, out

    xs = (params["dec_layers"], cache["k"], cache["v"], cache["ck"],
          cache["cv"])
    if kvq:
        xs = xs + (cache["k_beta"], cache["v_beta"])
    x, scanned = jax.lax.scan(body, x, xs)
    nk, nv = scanned[0], scanned[1]
    x = common.layer_norm(x, params["dec_norm"]["scale"], params["dec_norm"]["bias"])
    import dataclasses as _dc

    _pol2 = (_dc.replace(policy, weights_prequantized=False)
             if policy.weights_prequantized else policy)
    w = params["embed"].T
    logits = mfmac.mf_linear(
        x, w, jnp.float32(policy.ratio_clip_init or 1.0), policy=_pol2,
        is_last=True,
    )[:, 0, :]
    new_cache = dict(cache)
    new_cache["k"] = nk
    new_cache["v"] = nv
    if kvq:
        new_cache["k_beta"], new_cache["v_beta"] = scanned[2], scanned[3]
    new_cache["pos"] = kpos
    new_cache["len"] = pos + 1
    return logits, new_cache


def verify_step(cfg, policy, params, tokens, n_new, cache):
    """Speculative-decoding verifier, encdec edition: score ``n_new[b]``
    candidate tokens per slot in one decoder weight pass, bit-identical to
    sequential :func:`decode_step` calls.  Same construction as
    ``transformer.verify_step`` (outer layer scan, inner Python loop over
    the C positions running decode's exact ``(B, 1, D)`` ops — including
    the per-position cross-attention read of the slot's encoder K/V — and
    a per-position final norm + tied LM head).  Slot-pooled and paged
    caches only; encdec is never windowed.  Returns (logits (B, C, V),
    new cache with ``len = len + n_new``)."""
    from repro.models.transformer import (
        _kv_check, _kv_page_view, _kv_scatter, _page_view, _sdpa,
    )

    b, c = tokens.shape
    hd = cfg.head_dim
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
        drop = cache["pos"].shape[0]
    else:
        span = cache["k"].shape[2]
    assert c <= span, (c, span)
    x = jnp.take(params["embed"], tokens, axis=0)  # (B, C, D)
    rows = jnp.arange(b)
    offs = jax.lax.iota(jnp.int32, c)
    valid = offs[None, :] < n_new[:, None]
    gpos = pos0[:, None] + offs[None, :]
    qpos = jnp.where(valid, gpos, -1)
    lo = gpos % span
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
    se = cache["ck"].shape[2]
    epos = jax.lax.iota(jnp.int32, se)

    def body(carry, lp_kv):
        lp, ck_self, cv_self, ck_x, cv_x, *betas = lp_kv
        ckb, cvb = betas if kvq else (None, None)
        outs = []
        for i in range(c):
            xi = carry[:, i:i + 1, :]
            h = common.layer_norm(xi, lp["ln1"]["scale"], lp["ln1"]["bias"])
            q = _proj_heads(lp, "wq", h, policy, b, 1, cfg.n_heads, hd)
            k = _proj_heads(lp, "wk", h, policy, b, 1, cfg.kv_heads, hd)
            v = _proj_heads(lp, "wv", h, policy, b, 1, cfg.kv_heads, hd)
            pq = qpos[:, i:i + 1]  # (B, 1)
            q = common.rope(q, pq, cfg.rope_theta)
            k = common.rope(k, pq, cfg.rope_theta)
            if paged:
                ck_self, ckb = _kv_scatter(ck_self, ckb, k[:, 0], dests[i],
                                           loffs[i], spec)
                cv_self, cvb = _kv_scatter(cv_self, cvb, v[:, 0], dests[i],
                                           loffs[i], spec)
                kview = _kv_page_view(ck_self, ckb, table, span, spec,
                                      q.dtype)
                vview = _kv_page_view(cv_self, cvb, table, span, spec,
                                      q.dtype)
            else:
                ck_self = ck_self.at[rows, sidxs[i]].set(
                    k[:, 0].astype(ck_self.dtype), mode="drop"
                )
                cv_self = cv_self.at[rows, sidxs[i]].set(
                    v[:, 0].astype(cv_self.dtype), mode="drop"
                )
                kview = ck_self.astype(q.dtype)
                vview = cv_self.astype(q.dtype)
            att = _sdpa(cfg, policy, q, kview, vview, pq, kpos_views[i],
                        None)
            y = xi + mfmac.mf_linear(
                att.reshape(b, 1, cfg.n_heads * hd), lp["wo"]["w"],
                lp["wo"]["gamma"], policy=policy,
            )
            hc = common.layer_norm(y, lp["ln_cross"]["scale"],
                                   lp["ln_cross"]["bias"])
            cq = _proj_heads(lp, "cq", hc, policy, b, 1, cfg.n_heads, hd)
            catt = _mha(
                cfg, policy, cq, ck_x.astype(cq.dtype),
                cv_x.astype(cq.dtype), pq, epos, causal=False,
            )
            y = y + mfmac.mf_linear(
                catt.reshape(b, 1, cfg.n_heads * hd), lp["co"]["w"],
                lp["co"]["gamma"], policy=policy,
            )
            h2 = common.layer_norm(y, lp["ln2"]["scale"], lp["ln2"]["bias"])
            m = common.gelu(
                mfmac.mf_linear(h2, lp["wi"]["w"], lp["wi"]["gamma"],
                                policy=policy)
            )
            y = y + mfmac.mf_linear(m, lp["wo2"]["w"], lp["wo2"]["gamma"],
                                    policy=policy)
            outs.append(y)
        out = (ck_self, cv_self) + ((ckb, cvb) if kvq else ())
        return jnp.concatenate(outs, axis=1), out

    xs = (params["dec_layers"], cache["k"], cache["v"], cache["ck"],
          cache["cv"])
    if kvq:
        xs = xs + (cache["k_beta"], cache["v_beta"])
    x, scanned = jax.lax.scan(body, x, xs)
    nk, nv = scanned[0], scanned[1]
    import dataclasses as _dc

    _pol2 = (_dc.replace(policy, weights_prequantized=False)
             if policy.weights_prequantized else policy)
    w = params["embed"].T
    logits = []
    for i in range(c):
        xe = common.layer_norm(
            x[:, i:i + 1, :], params["dec_norm"]["scale"],
            params["dec_norm"]["bias"],
        )
        logits.append(mfmac.mf_linear(
            xe, w, jnp.float32(policy.ratio_clip_init or 1.0), policy=_pol2,
            is_last=True,
        )[:, 0, :])
    logits = jnp.stack(logits, axis=1)  # (B, C, V)
    new_cache = dict(cache)
    new_cache["k"] = nk
    new_cache["v"] = nv
    if kvq:
        new_cache["k_beta"], new_cache["v_beta"] = scanned[2], scanned[3]
    new_cache["pos"] = kpos_phys
    new_cache["len"] = pos0 + n_new
    return logits, new_cache


def encode_cross_kv(cfg, policy, params, frames):
    """Encoder pass + per-decoder-layer cross-attention K/V for chunked
    admission (serve/engine.py): the encoder side of prefill without
    touching the decoder prompt, whose tokens then stream in C at a time
    via :func:`chunk_step`.  Returns (ck, cv), each (L, B, enc_seq, KV, hd).
    """
    enc_out = encode(cfg, policy, params, frames, remat=False)
    b, se = enc_out.shape[0], enc_out.shape[1]
    hd = cfg.head_dim

    def body(carry, lp):
        ck_ = _proj_heads(lp, "ck", enc_out, policy, b, se, cfg.kv_heads, hd)
        cv_ = _proj_heads(lp, "cv", enc_out, policy, b, se, cfg.kv_heads, hd)
        return carry, (ck_, cv_)

    _, (cks, cvs) = jax.lax.scan(body, 0, params["dec_layers"])
    return cks, cvs


def chunk_step(cfg, policy, params, tokens, n_new, cache):
    """Fused decode/prefill-chunk step over ``(B, C)`` positions — the
    encdec mirror of ``transformer.chunk_step`` (same padding discipline:
    qpos -1, dropped scatters, per-row determinism).  Cross-attention
    reads the per-slot ``ck``/``cv`` written at admission by
    :func:`encode_cross_kv`."""
    from repro.models.transformer import (
        _kv_check, _kv_page_view, _kv_scatter, _page_view, _sdpa,
    )

    b, c = tokens.shape
    hd = cfg.head_dim
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
    valid = offs[None, :] < n_new[:, None]
    gpos = pos0[:, None] + offs[None, :]
    qpos = jnp.where(valid, gpos, -1)
    lo = gpos % span
    if paged:
        table_ext = jnp.concatenate(
            [table, jnp.full((b, 1), drop, table.dtype)], axis=1
        )
        lpage = jnp.where(valid, lo // page, npg)
        dest = jnp.take_along_axis(table_ext, lpage, axis=1)  # (B, C)
        loff = lo % page
        kpos_new = cache["pos"].at[dest, loff].set(qpos, mode="drop")
        kpos_view = _page_view(kpos_new, table, span)
    else:
        sidx = jnp.where(valid, lo, span)
        kpos_new = cache["pos"].at[rows[:, None], sidx].set(qpos, mode="drop")
        kpos_view = kpos_new
    se = cache["ck"].shape[2]
    epos = jax.lax.iota(jnp.int32, se)

    def body(carry, lp_kv):
        lp, ck_self, cv_self, ck_x, cv_x, *betas = lp_kv
        ckb, cvb = betas if kvq else (None, None)
        h = common.layer_norm(carry, lp["ln1"]["scale"], lp["ln1"]["bias"])
        # zero pads before the projections: each row's activation-scale
        # group amax must equal decode_step's (1, D) group so decode rows
        # are bit-equal across step bodies (transformer.chunk_step docs)
        h = jnp.where(valid[:, :, None], h, 0.0)
        q = _proj_heads(lp, "wq", h, policy, b, c, cfg.n_heads, hd)
        k = _proj_heads(lp, "wk", h, policy, b, c, cfg.kv_heads, hd)
        v = _proj_heads(lp, "wv", h, policy, b, c, cfg.kv_heads, hd)
        q = common.rope(q, qpos, cfg.rope_theta)
        k = common.rope(k, qpos, cfg.rope_theta)
        if paged:
            nk, nkb = _kv_scatter(ck_self, ckb, k, dest, loff, spec)
            nv, nvb = _kv_scatter(cv_self, cvb, v, dest, loff, spec)
        else:
            nk = ck_self.at[rows[:, None], sidx].set(
                k.astype(ck_self.dtype), mode="drop"
            )
            nv = cv_self.at[rows[:, None], sidx].set(
                v.astype(cv_self.dtype), mode="drop"
            )
        # scatter-then-attend over the post-scatter span view — the same
        # reduction decode_step performs; encdec is never windowed, so no
        # ring wrap can occur
        if kvq:
            kv_k = _kv_page_view(nk, nkb, table, span, spec, q.dtype)
            kv_v = _kv_page_view(nv, nvb, table, span, spec, q.dtype)
        else:
            kv_k = (_page_view(nk, table, span) if paged else nk
                    ).astype(q.dtype)
            kv_v = (_page_view(nv, table, span) if paged else nv
                    ).astype(q.dtype)
        att = _sdpa(
            cfg, policy, q, kv_k, kv_v, qpos, kpos_view, None,
        )
        # Pad queries' all-False mask degenerates softmax to a uniform
        # average over every key — stale K/V from a reused slot included.
        # Zero pad rows so they stay functions of their own tokens only
        # (transformer.chunk_step has the same guard).
        att = jnp.where(
            valid[:, :, None], att.reshape(b, c, cfg.n_heads * hd), 0.0
        )
        y = carry + mfmac.mf_linear(
            att, lp["wo"]["w"], lp["wo"]["gamma"], policy=policy,
        )
        hc = common.layer_norm(y, lp["ln_cross"]["scale"], lp["ln_cross"]["bias"])
        hc = jnp.where(valid[:, :, None], hc, 0.0)  # same amax argument
        cq = _proj_heads(lp, "cq", hc, policy, b, c, cfg.n_heads, hd)
        catt = _mha(
            cfg, policy, cq, ck_x.astype(cq.dtype), cv_x.astype(cq.dtype),
            qpos, epos, causal=False,
        )
        # cross-attention reads only the slot's own per-request ck/cv,
        # but zero pad rows anyway so their downstream values cannot
        # depend on any cache state at all
        catt = jnp.where(
            valid[:, :, None], catt.reshape(b, c, cfg.n_heads * hd), 0.0
        )
        y = y + mfmac.mf_linear(
            catt, lp["co"]["w"], lp["co"]["gamma"], policy=policy,
        )
        h2 = common.layer_norm(y, lp["ln2"]["scale"], lp["ln2"]["bias"])
        h2 = jnp.where(valid[:, :, None], h2, 0.0)  # same amax argument
        m = common.gelu(
            mfmac.mf_linear(h2, lp["wi"]["w"], lp["wi"]["gamma"], policy=policy)
        )
        y = y + mfmac.mf_linear(m, lp["wo2"]["w"], lp["wo2"]["gamma"], policy=policy)
        out = (nk, nv) + ((nkb, nvb) if kvq else ())
        return y, out

    xs = (params["dec_layers"], cache["k"], cache["v"], cache["ck"],
          cache["cv"])
    if kvq:
        xs = xs + (cache["k_beta"], cache["v_beta"])
    x, scanned = jax.lax.scan(body, x, xs)
    nk, nv = scanned[0], scanned[1]
    emit = jnp.clip(n_new - 1, 0, c - 1)
    xe = x[rows, emit][:, None, :]
    xe = common.layer_norm(
        xe, params["dec_norm"]["scale"], params["dec_norm"]["bias"]
    )
    import dataclasses as _dc

    _pol = (_dc.replace(policy, weights_prequantized=False)
            if policy.weights_prequantized else policy)
    w = params["embed"].T
    logits = mfmac.mf_linear(
        xe, w, jnp.float32(policy.ratio_clip_init or 1.0), policy=_pol,
        is_last=True,
    )[:, 0, :]
    new_cache = dict(cache)
    new_cache["k"] = nk
    new_cache["v"] = nv
    if kvq:
        new_cache["k_beta"], new_cache["v_beta"] = scanned[2], scanned[3]
    new_cache["pos"] = kpos_new
    new_cache["len"] = pos0 + n_new
    return logits, new_cache
