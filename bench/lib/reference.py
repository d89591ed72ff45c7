"""The plain reference: the configurations' mathematics in straightforward
``jax.numpy``, float32 at ``Precision.HIGHEST``, written from the paper's
description (arXiv:2302.14458, Algorithm 1) and the models' papers.  It
imports nothing of the program and takes nothing the program made: its
weights come from ``bench/lib/weights.py`` and the seed.

What it computes:

* ALS-PoTQ: b-bit power-of-two values {0, +-2^-emax .. +-2^emax} times a
  power-of-two scale 2^beta, beta = round(log2 max|F|) - emax, rounding
  in the log2 domain to nearest (a value rounds up past 2^e * sqrt 2).
* MF-MAC linear layers (Algorithm 1): weights centred on their mean (WBC)
  and quantized to 5 bits per matrix; activations clipped at gamma times
  their max (PRC) and quantized to 5 bits per scale group; backward: the
  incoming gradient quantized once to 5 bits (6 for the LM head) and
  reused for dA = Gq Wq^T and dW = Aq^T Gq, the clip mask applied to dA,
  dgamma = amax * sum(sign(a) dA) over the clipped entries.
* The decoder: pre-norm blocks (LayerNorm, nonparametric for OLMo),
  rotary positions (rotate-half), causal grouped-query softmax
  attention, SwiGLU or tanh-GELU MLP, a tied or untied LM head, mean
  next-token cross entropy over the unpadded vocabulary.
* Training: global-norm clipping at 1, AdamW (b1 0.9, b2 0.999, eps 1e-8)
  under a linear-warmup cosine schedule.
* Serving: activation scale groups follow the served schedule: one group
  per prompt chunk of ``chunk`` tokens, one per generated token and one
  per LM-head row; keys and values are held at bf16, as the cache is.

Attention's two dots take their operands at the precision the
configuration states under ``attention_operands``: ``bfloat16`` (rounded
operands, float32 accumulation; OLMo trains its matmuls under bfloat16
mixed precision, and a float32 dot at a TPU's default precision is this)
or ``float32``.

``Num`` selects the precision.  ``Num(False)`` is the reference.
``Num(True)`` is the control: the same computation with every activation
rounded to bfloat16 after each operation and dots at default precision.
``Num(no_dgamma=True)`` is the reference with the clipping ratios'
gradient dropped, a planted fault.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights as W

F32 = jnp.float32
BF16 = jnp.bfloat16
SQRT2 = np.float32(np.sqrt(2.0))  # 0x3FB504F3, just below sqrt 2


class Num:
    """How the reference computes.

    ``Num()`` is the reference: float32 everywhere at ``HIGHEST``, except
    attention's operands where the configuration states bfloat16 for them.
    Each MF-MAC product of PoT values (exact in bf16) accumulates in
    float32 over 128-wide chunks of the contraction, left-folded, the
    order the repo's kernels state.  ``Num(bf16=True)`` is the control: every
    activation, and in training the master weights and AdamW's state,
    rounded to bfloat16 after each operation, dots at default precision.
    ``no_dgamma`` drops the clipping ratios' gradient (a planted fault)."""

    def __init__(self, bf16: bool = False, no_dgamma: bool = False):
        self.bf16, self.no_dgamma = bf16, no_dgamma
        self.prec = (jax.lax.Precision.DEFAULT if bf16
                     else jax.lax.Precision.HIGHEST)

    def r(self, x):
        return to_bf16(x) if self.bf16 else x

    def mac(self, x, y):
        """An MF-MAC product of PoT-valued (bf16-exact) operands."""
        return self.dot(x, y) if self.bf16 else fold_dot(x, y)

    def dot(self, x, y):
        return self.r(jnp.dot(x, y, precision=self.prec,
                              preferred_element_type=F32))

    def __hash__(self):
        return hash((self.bf16, self.no_dgamma))

    def __eq__(self, other):
        return (isinstance(other, Num)
                and (other.bf16, other.no_dgamma) == (self.bf16,
                                                      self.no_dgamma))


def to_bf16(x):
    """x rounded to bfloat16, kept in float32.  ``reduce_precision`` is
    never elided, where a float32 -> bfloat16 -> float32 round trip may be
    dropped by a compiler that allows excess precision (XLA on a TPU)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def attn_operand(cfg: dict, x):
    """An operand of attention's dots, at the configuration's precision."""
    if cfg["attention_operands"] == "bfloat16":
        return to_bf16(x)
    if cfg["attention_operands"] != "float32":
        raise ValueError(cfg["attention_operands"])
    return x


CHUNK = 128


def fold_dot(x, y):
    """x (M, K) @ y (K, N): one float32 partial per 128-wide chunk of K
    (zero-padded), summed left to right from zero: the accumulation order
    the repo's kernels state (``ACC_SCHEME``)."""
    pad = -x.shape[1] % CHUNK
    # bf16 operands where the backend multiplies them natively (the values
    # are bf16-exact, so float32 at HIGHEST gives the same products)
    dt = F32 if jax.default_backend() == "cpu" else BF16
    xb = jnp.pad(x, ((0, 0), (0, pad))).astype(dt)
    yb = jnp.pad(y, ((0, pad), (0, 0))).astype(dt)
    out = jnp.zeros((x.shape[0], y.shape[1]), F32)
    for c in range(0, xb.shape[1], CHUNK):
        out = out + jnp.dot(xb[:, c:c + CHUNK], yb[c:c + CHUNK, :],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=F32)
    return out


def pow2(e):
    """2^e exactly, for integer e in [-126, 127], from the exponent bits."""
    bits = (jnp.asarray(e, jnp.int32) + 127).astype(jnp.uint32) << 23
    return jax.lax.bitcast_convert_type(bits, F32)


# --- ALS-PoTQ ------------------------------------------------------------

def round_log2(mag):
    """round(log2 mag) for mag > 0, exactly: mag = m 2^e, m in [0.5, 1)."""
    m, e = jnp.frexp(mag)
    return jnp.where(2 * m > SQRT2, e, e - 1)


def pot(x, bits: int, amax):
    """Quantize-dequantize x to b-bit PoT with scale exponent from amax."""
    emax = 2 ** (bits - 2) - 1
    safe = jnp.where(amax > 0, amax, 1.0)
    beta = jnp.where(amax > 0, round_log2(safe) - emax, 0)
    scale = pow2(beta)
    s = x / scale
    mag = jnp.abs(s)
    e = round_log2(jnp.where(mag > 0, mag, 1.0))
    q = jnp.where((mag > 0) & (e >= -emax), pow2(jnp.clip(e, -emax, emax)),
                  0.0)
    return jnp.sign(s) * q * scale


def quant_w(w, bits: int = 5):
    c = w - jnp.mean(w)
    return pot(c, bits, jnp.max(jnp.abs(c)))


def quant_a(a, gamma, gmax, bits: int = 5):
    """PRC then ALS-PoTQ; ``gmax(x)`` gives each row its group's max."""
    t = gmax(jnp.abs(a)) * gamma
    c = jnp.clip(a, -t, t)
    return pot(c, bits, gmax(jnp.abs(c)))


def _global_max(x):
    return jnp.max(x)


# --- MF-MAC linear with Algorithm 1's backward ----------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def mf_linear(a, w, gamma, bits_g, num):
    return _mf_fwd(a, w, gamma, bits_g, num)[0]


def _mf_fwd(a, w, gamma, bits_g, num):
    aq = quant_a(a, gamma, _global_max)
    wq = quant_w(w)
    return num.mac(aq, wq), (aq, wq, a, gamma)


def _mf_bwd(bits_g, num, res, g):
    aq, wq, a, gamma = res
    gq = pot(g, bits_g, jnp.max(jnp.abs(g)))
    da = num.mac(gq, wq.T)
    dw = num.mac(aq.T, gq)
    amax = jnp.max(jnp.abs(a))
    clipped = jnp.abs(a) > amax * gamma
    dgamma = jnp.sum(jnp.where(clipped, da * jnp.sign(a), 0.0)) * amax
    if num.no_dgamma:
        dgamma = jnp.zeros_like(dgamma)
    return jnp.where(clipped, 0.0, da), dw, dgamma.reshape(jnp.shape(gamma))


mf_linear.defvjp(_mf_fwd, _mf_bwd)


# --- model pieces -----------------------------------------------------------

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

def _layer_names(cfg):
    return [n for n, (_, _, per) in W.layout(cfg).items() if per]


def init_params(seed, cfg: dict):
    """All parameters, float32, as a flat dict; per-layer leaves stacked;
    ``seed`` the 32-bit seed, traced."""
    return {name: W.leaf(seed, cfg, name) for name in W.layout(cfg)}


def loss_fn(cfg, params, tokens, labels, mask, num):
    b, s = tokens.shape
    lin = functools.partial(_train_linear, num=num, bits_g=5)
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
    logits = _train_linear(x, head_w, head_g, num=num, bits_g=6)
    vp = logits.shape[-1]
    logits = jnp.where(jnp.arange(vp) >= cfg["vocab"], -1e30, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _train_linear(a, w, gamma, *, num, bits_g):
    lead = a.shape[:-1]
    out = mf_linear(a.reshape(-1, a.shape[-1]), w, gamma, bits_g, num)
    return out.reshape(*lead, w.shape[-1])


def lr_at(opt: dict, step):
    s = jnp.asarray(step, F32)
    base, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    prog = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    return jnp.where(s < warm, base * s / max(warm, 1),
                     0.5 * base * (1.0 + jnp.cos(jnp.pi * prog)))


def clipped_grad(cfg, opt, num, params, batch):
    """The loss, the gradient's global norm and the gradient clipped to
    it."""
    loss, g = jax.value_and_grad(loss_fn, argnums=1)(
        cfg, params, batch["tokens"], batch["labels"], batch["mask"], num)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gn + 1e-9))
    return loss, gn, {k: x * scale for k, x in g.items()}


def adamw_leaf(opt, num, p, m, v, g, step):
    """AdamW (b1 0.9, b2 0.999, eps 1e-8, no decay) on one leaf."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    t = jnp.asarray(step, F32) + 1.0
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    p = p - lr_at(opt, step) * (m / (1 - b1 ** t)) / (
        jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return num.r(p), num.r(m), num.r(v)


def train_readings(seed, cfg: dict, opt: dict, batches, num: Num,
                   steps: int = 3):
    """Losses and global gradient norms of the first ``steps`` steps,
    per-leaf norms of the first (clipped) gradient and per-leaf norms of
    the change after ``steps``.
    AdamW's moments wait on the host between steps and come to the device
    one leaf at a time, so the device holds the parameters, the gradient
    and the activations."""
    grad_fn = jax.jit(functools.partial(clipped_grad, cfg, opt, num))
    update = jax.jit(functools.partial(adamw_leaf, opt, num),
                     donate_argnums=(0, 1, 2, 3))
    params = jax.jit(lambda s: {k: num.r(x) for k, x in
                                init_params(s, cfg).items()})(seed)
    m = {k: np.zeros(x.shape, np.float32) for k, x in params.items()}
    v = {k: np.zeros(x.shape, np.float32) for k, x in params.items()}
    losses, global_norms, gnorms = [], [], None
    for i in range(steps):
        loss, gn, g = grad_fn(params, batches[i])
        losses.append(float(loss))
        global_norms.append(float(gn))
        if i == 0:
            gnorms = {k: float(jnp.linalg.norm(x)) for k, x in g.items()}
        for k in params:
            p, mk, vk = update(params[k], jnp.asarray(m[k]),
                               jnp.asarray(v[k]), g.pop(k), jnp.int32(i))
            params[k], m[k], v[k] = p, np.asarray(mk), np.asarray(vk)
    del m, v
    p0 = jax.jit(lambda s: init_params(s, cfg))(seed)
    change = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "global_norms": global_norms,
            "grad_norms": gnorms, "change_norms": change}


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
        layout = W.layout(cfg)

        def make(name):
            shape, _, per_layer = layout[name]

            def q(s, l=None):
                x = W.leaf(s, cfg, name, l)
                return quant_w(x).astype(BF16) if name.endswith("/w") else x

            if per_layer and name.endswith("/w"):
                return jax.jit(lambda s: jax.lax.map(
                    functools.partial(q, s), jnp.arange(cfg["n_layers"])))(
                        seed)
            return jax.jit(q)(seed)

        self.wts = {
            "embed": make("embed"),
            "layers": {n[len("layers/"):]: make(n) for n, (_, _, per)
                       in layout.items() if per},
            "final": {k: (make(f"final_norm/{k}")
                          if f"final_norm/{k}" in layout else None)
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
