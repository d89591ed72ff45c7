"""The plain reference's mathematics that every model shares, in
straightforward ``jax.numpy``, float32 at ``Precision.HIGHEST``, written
from the paper's description (arXiv:2302.14458, Algorithm 1).  It imports
nothing of the program and takes nothing the program made: weights come
from ``bench/lib/weights.py`` and the seed, the architecture from the
model module a configuration names (``bench/lib/model.py``).

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
* Training: the model module's loss, global-norm clipping at 1, AdamW
  (b1 0.9, b2 0.999, eps 1e-8) under a linear-warmup cosine schedule.

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


def train_linear(a, w, gamma, *, num, bits_g):
    """``mf_linear`` over every leading axis of ``a``."""
    lead = a.shape[:-1]
    out = mf_linear(a.reshape(-1, a.shape[-1]), w, gamma, bits_g, num)
    return out.reshape(*lead, w.shape[-1])


# --- training -------------------------------------------------------------

def init_params(model, seed, cfg: dict):
    """All parameters of the module's layout, float32, as a flat dict;
    stacked leaves stacked; ``seed`` the 32-bit seed, traced."""
    return {name: W.leaf(seed, cfg, name) for name in model.layout(cfg)}


def lr_at(opt: dict, step):
    s = jnp.asarray(step, F32)
    base, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    prog = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    return jnp.where(s < warm, base * s / max(warm, 1),
                     0.5 * base * (1.0 + jnp.cos(jnp.pi * prog)))


def clipped_grad(loss_fn, cfg, opt, num, params, batch):
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


def train_readings(model, seed, cfg: dict, opt: dict, batches, num: Num,
                   steps: int = 3):
    """Losses and global gradient norms of the first ``steps`` steps of
    the model module's loss, per-leaf norms of the first (clipped)
    gradient and per-leaf norms of the change after ``steps``.
    AdamW's moments wait on the host between steps and come to the device
    one leaf at a time, so the device holds the parameters, the gradient
    and the activations."""
    grad_fn = jax.jit(functools.partial(clipped_grad, model.loss_fn, cfg,
                                        opt, num))
    update = jax.jit(functools.partial(adamw_leaf, opt, num),
                     donate_argnums=(0, 1, 2, 3))
    params = jax.jit(lambda s: {k: num.r(x) for k, x in
                                init_params(model, s, cfg).items()})(seed)
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
    p0 = jax.jit(lambda s: init_params(model, s, cfg))(seed)
    change = {k: float(jnp.linalg.norm(params[k] - p0[k])) for k in params}
    return {"losses": losses, "global_norms": global_norms,
            "grad_norms": gnorms, "change_norms": change}
