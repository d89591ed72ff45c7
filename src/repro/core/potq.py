"""ALS-PoTQ: Adaptive Layer-wise Scaling Power-of-Two Quantization.

Implements §3 + §4 of the paper:

* b-bit PoT numbers take values {0, ±2^emin, ..., ±2^emax} with
  emax = 2^(b-2) - 1 and emin = -emax (1 sign bit, b-1 exponent bits).
* The layer-wise scale alpha = max|F| / 2^emax is rounded to a power of two
  beta = round(log2 alpha), so that scaling F/alpha is an integer addition
  to the FP32 exponent field on the paper's datapath.  Here the numerically
  identical ``F * 2**-beta`` is used (exact: multiplication by a power of
  two only touches the exponent).
* Rounding happens in the log2 domain (round-to-nearest), with underflow to
  zero below emin and saturation at emax — Equations (2)–(3).

Two output forms:
  * :func:`pot_quantize` — dequantized real values alpha*P (exact in bf16;
    these feed the MXU matmul, see DESIGN.md §2).
  * :func:`pot_encode` — the wire format (sign bit, int8 exponent, scalar
    beta), used by the gradient-compression path and by tests that check
    the integer datapath semantics.

Weight Bias Correction (WBC, §4.2) and Parameterized Ratio Clipping
(PRC, §4.3) preprocessing also live here.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


def pot_emax(bits: int) -> int:
    """Largest exponent representable by a ``bits``-bit PoT number."""
    if bits < 3:
        raise ValueError(f"PoT bit-width must be >= 3, got {bits}")
    return 2 ** (bits - 2) - 1


def exp2i(e: jax.Array) -> jax.Array:
    """EXACT 2^e for integer-valued e in [-126, 127].

    ``jnp.exp2`` lowers to exp(x*ln2) on some backends and is off by
    ~1e-6 — which would silently break the paper's core numeric claim
    (PoT values exact in bf16, MXU matmul == integer datapath).  Build
    the float32 directly from its exponent bits instead: this is also
    literally the paper's datapath (beta is ADDED to the FP32 exponent
    field, §5/Figure 5).
    """
    e = jnp.asarray(e)
    ei = e.astype(jnp.int32)
    bits = ((ei + 127).astype(jnp.uint32)) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


#: Smallest 23-bit float32 fraction whose significand 1.f exceeds sqrt(2).
_SQRT2_FRACTION = 0x3504F4


def round_log2(mag: jax.Array) -> jax.Array:
    """round(log2(mag)) for positive float32 ``mag``, exactly, from bits.

    log2(2^e * 1.f) = e + log2(1.f) rounds up exactly when 1.f > sqrt(2),
    so the result is the unbiased exponent plus one where the fraction is
    at least ``_SQRT2_FRACTION`` (no float equals sqrt(2), so there are
    no ties).  A transcendental ``log2`` is not correctly rounded and
    differs between XLA and the TPU kernel compiler, which flipped PoT
    codes next to the sqrt(2) boundaries; this form gives the same bits
    on every backend.  A subnormal gives -127 or -126, below every PoT
    grid's -emax, so it quantizes to zero as its exact exponent would.
    Returns float32.
    """
    bits = jax.lax.bitcast_convert_type(mag.astype(jnp.float32), jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    e = e + ((bits & 0x7FFFFF) >= _SQRT2_FRACTION).astype(jnp.int32)
    return e.astype(jnp.float32)


def compute_beta(f: jax.Array, bits: int, axes=None, *,
                 conservative: bool = False) -> jax.Array:
    """Layer-wise PoT scale exponent beta = round(log2(max|F| / 2^emax)).

    ``axes=None`` reduces over the whole tensor (one scale per layer, the
    paper's setting).  Passing axes yields grouped scales (e.g. per-expert
    for MoE weights: each expert is its own "layer").  Reduced axes are
    kept so the result broadcasts against ``f``.

    ``conservative=True`` uses ceil instead of round so max|F| never
    saturates the grid — required by the unbiased stochastic path
    (gradient compression): saturation clips upward rounding and biases
    the estimate.

    All-zero groups get beta=0 (any finite value works: the quantized
    group is identically zero anyway).
    """
    emax = pot_emax(bits)
    amax = jnp.max(jnp.abs(f), axis=axes, keepdims=axes is not None)
    amax = amax.astype(jnp.float32)
    safe = jnp.where(amax > 0, amax, 1.0)
    e = jnp.ceil(jnp.log2(safe)) if conservative else round_log2(safe)
    beta = e.astype(jnp.int32) - emax
    return jnp.where(amax > 0, beta, 0)


def _log2_round_nearest(mag: jax.Array) -> jax.Array:
    """round(log2(mag)) with mag==0 mapped to a very negative exponent."""
    safe = jnp.where(mag > 0, mag, 1.0)
    return jnp.where(mag > 0, round_log2(safe), -(2.0 ** 20))


def _log2_round_stochastic(mag: jax.Array, key: jax.Array) -> jax.Array:
    """Unbiased-in-linear-domain stochastic log2 rounding (LUQ-style).

    Rounds |x| to 2^floor(log2|x|) or 2^ceil(log2|x|) with probability
    proportional to the position of |x| between the two grid points, so
    E[q] = |x|.  Used by the beyond-paper gradient-compression path.
    """
    safe = jnp.where(mag > 0, mag, 1.0)
    lo = jnp.floor(jnp.log2(safe))
    plo = exp2i(lo)
    # p(round up) = (x - 2^lo) / (2^hi - 2^lo) = x/2^lo - 1  (since hi=lo+1)
    p_up = safe / plo - 1.0
    u = jax.random.uniform(key, mag.shape, dtype=jnp.float32)
    e = lo + (u < p_up).astype(jnp.float32)
    return jnp.where(mag > 0, e, -(2.0 ** 20))


class PotEncoded(NamedTuple):
    """Integer wire format of an ALS-PoTQ tensor.

    value = (-1)^sign * 2^(exp + beta), with exp==EXP_ZERO meaning 0.
    ``exp`` is the *unshifted* PoT exponent in [-emax, emax] stored int8.
    """

    sign: jax.Array  # int8, 0/1
    exp: jax.Array  # int8, EXP_ZERO marks a true zero
    beta: jax.Array  # int32 scalar


EXP_ZERO = -128  # int8 sentinel for exact zero


def pot_quantize(
    f: jax.Array,
    bits: int,
    beta: Optional[jax.Array] = None,
    *,
    stochastic: bool = False,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Quantize-dequantize ``f`` to b-bit PoT with layer-wise PoT scaling.

    Returns real values alpha * P in float32 (every such value is exactly
    representable in bf16).  No gradient is defined here — callers wrap the
    surrounding computation in a custom_vjp (see core/mfmac.py).
    """
    emax = pot_emax(bits)
    f = f.astype(jnp.float32)
    if beta is None:
        beta = compute_beta(f, bits)
    scale = exp2i(beta)  # 2^beta, exact (bit-constructed)
    scaled = f / scale
    mag = jnp.abs(scaled)
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        e = _log2_round_stochastic(mag, key)
    else:
        e = _log2_round_nearest(mag)
    # Eq. (3): representable exponents are [-2^(b-2)+1, 2^(b-2)-1] =
    # [-emax, emax] (symmetric).  e < -emax => underflow to 0; e >= emax
    # saturates.
    underflow = e < -emax
    e_clipped = jnp.clip(e, -emax, emax)
    q = jnp.where(underflow, 0.0, exp2i(e_clipped))
    q = jnp.sign(scaled) * q
    return q * scale


def pot_encode(
    f: jax.Array,
    bits: int,
    beta: Optional[jax.Array] = None,
    *,
    stochastic: bool = False,
    key: Optional[jax.Array] = None,
) -> PotEncoded:
    """Quantize ``f`` to the integer PoT wire format (sign, exp, beta)."""
    emax = pot_emax(bits)
    f = f.astype(jnp.float32)
    if beta is None:
        beta = compute_beta(f, bits)
    scale = exp2i(beta)
    scaled = f / scale
    mag = jnp.abs(scaled)
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        e = _log2_round_stochastic(mag, key)
    else:
        e = _log2_round_nearest(mag)
    underflow = e < -emax
    exp = jnp.clip(e, -emax, emax).astype(jnp.int8)
    exp = jnp.where(underflow, jnp.int8(EXP_ZERO), exp)
    sign = (scaled < 0).astype(jnp.int8)
    return PotEncoded(sign=sign, exp=exp, beta=beta.astype(jnp.int32))


def pot_decode(enc: PotEncoded) -> jax.Array:
    """Inverse of :func:`pot_encode` — exact."""
    e = enc.exp.astype(jnp.float32) + enc.beta.astype(jnp.float32)
    mag = jnp.where(enc.exp == EXP_ZERO, 0.0, exp2i(jnp.where(enc.exp == EXP_ZERO, 0, e)))
    return jnp.where(enc.sign == 1, -mag, mag)


# ---------------------------------------------------------------------------
# Preprocessing: WBC (§4.2) and PRC (§4.3)
# ---------------------------------------------------------------------------

def weight_bias_correction(w: jax.Array) -> jax.Array:
    """WBC: remove the weight mean so W matches the symmetric PoT grid."""
    return w - jnp.mean(w)


def ratio_clip(a: jax.Array, gamma: jax.Array) -> jax.Array:
    """PRC forward: clip activations at +-gamma * max|A|  (Eq. 12).

    max|A| is treated as a constant (stop_gradient), matching PACT.
    """
    t = jax.lax.stop_gradient(jnp.max(jnp.abs(a))) * gamma
    return jnp.clip(a, -t, t)


def ratio_clip_vjp(a: jax.Array, gamma: jax.Array, g: jax.Array):
    """Manual VJP of :func:`ratio_clip` for use inside mf_linear's bwd.

    Returns (da, dgamma): da passes through where unclipped (zero outside,
    PACT-style); dgamma collects sign(a) * max|A| over the clipped region.
    """
    amax = jnp.max(jnp.abs(a))
    t = amax * gamma
    clipped = jnp.abs(a) > t
    da = jnp.where(clipped, 0.0, g)
    dgamma = jnp.sum(jnp.where(clipped, g * jnp.sign(a), 0.0)) * amax
    return da, dgamma.astype(gamma.dtype)
