"""Pure-jnp oracles for the Pallas kernels (no pallas imports here).

These define the repo's *numeric spec*: the Pallas kernel must match them
bit-for-bit (tests/conformance).  In particular the matmul oracle reduces
over K in the same canonical fixed order as the kernel (``CANONICAL_BK``
chunks, left fold), so kernel-vs-oracle equality is exact for every
tiling — not an accumulation-order accident.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import potq
from repro.core.potq import exp2i

# Width of one canonical K chunk of the fixed-order FP32 reduction.  The
# kernel (kernels/potq_matmul.py) imports this — it is the single source
# of truth for the deterministic accumulation contract
# (docs/DESIGN_kernels.md).
CANONICAL_BK = 128


def quantize_tile_ref(x: jax.Array, emax: int) -> jax.Array:
    """Round-to-nearest PoT quantization of an already-scaled tile.

    Input is assumed pre-scaled by 2^-beta; output values are in
    {0, +-2^e : e in [-emax, emax]} — the scaled PoT domain.
    """
    mag = jnp.abs(x)
    safe = jnp.where(mag > 0, mag, 1.0)
    e = potq.round_log2(safe)
    under = (e < -emax) | (mag == 0)
    e = jnp.clip(e, -emax, emax)
    q = jnp.where(under, 0.0, exp2i(jnp.where(under, 0.0, e)))
    return jnp.sign(x) * q


def pot_value_matmul_ref(x: jax.Array, y: jax.Array) -> jax.Array:
    """(M,K)@(K,N) matmul over PoT-valued operands, bf16 MXU semantics.

    FP32 accumulation follows the canonical fixed order: K is zero-padded
    to a multiple of ``CANONICAL_BK``, one bf16 partial dot is taken per
    canonical chunk, and the partials are left-folded in increasing chunk
    order.  Zero padding appends exact-zero partials, so the result is
    independent of the padded length.  This is exactly the reduction the
    Pallas kernel performs for ANY (bm, bn, bk) tiling.
    """
    k = x.shape[1]
    pad = (-k) % CANONICAL_BK
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        y = jnp.pad(y, ((0, pad), (0, 0)))
    xb = x.astype(jnp.bfloat16)
    yb = y.astype(jnp.bfloat16)
    out = jnp.zeros((x.shape[0], y.shape[1]), jnp.float32)
    for c in range(0, k + pad, CANONICAL_BK):
        out = out + jnp.dot(
            xb[:, c:c + CANONICAL_BK],
            yb[c:c + CANONICAL_BK, :],
            preferred_element_type=jnp.float32,
        )
    return out


def chunk_rowsum(x: jax.Array) -> jax.Array:
    """Row sums of a ``(rows, CANONICAL_BK)`` chunk by a halving tree of
    elementwise adds: ``x[:, :64] + x[:, 64:]``, then 32, ..., 1.

    The order is written out because a backend's own lane reduction
    (``sum(axis=1)``) may associate differently in XLA and in the TPU
    kernel compiler; elementwise f32 adds give the same bits on both.
    """
    w = x.shape[1]
    while w > 1:
        w //= 2
        x = x[:, :w] + x[:, w:2 * w]
    return x[:, 0]


def grad_rowsum_ref(x: jax.Array) -> jax.Array:
    """Canonical fixed-order row reduction: sum over the last axis in
    ``CANONICAL_BK``-wide chunks, left-folded in ascending chunk order.

    This is the numeric spec for the dgamma epilogue of the fused backward
    kernel: each 128-wide chunk is reduced by :func:`chunk_rowsum`
    (identical bits for any row-tile height and backend) and the chunk
    partials fold left in global chunk order — so the (M,) result is
    independent of the kernel's (bm, bn, bk) tiling.  Zero padding appends
    exact-zero partials.
    """
    k = x.shape[1]
    pad = (-k) % CANONICAL_BK
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    out = jnp.zeros((x.shape[0],), jnp.float32)
    for c in range(0, k + pad, CANONICAL_BK):
        out = out + chunk_rowsum(x[:, c:c + CANONICAL_BK])
    return out


def potq_grad_ref(
    g: jax.Array,  # (M, N) raw incoming gradient
    aq: jax.Array,  # (M, K) quantized activations (forward residual)
    wq: jax.Array,  # (K, N) quantized weights (forward residual)
    *,
    a: Optional[jax.Array] = None,  # (M, K) raw activations (PRC epilogue)
    clip_t: Optional[jax.Array] = None,  # scalar PRC threshold
    amax: Optional[jax.Array] = None,  # scalar max|a| (dgamma scale)
    bits_g: int = 5,
):
    """Oracle for the fused backward kernels (Algorithm 1, lines 13-15).

    G is ALS-PoTQ quantized ONCE (one beta, real-domain values — exact PoT
    scaling makes this bit-identical to the kernel's scaled-domain
    quantize + 2^beta_g dequant epilogue) and reused for both MACs:

        dA = Gq @ Wq^T, then the PRC clip mask / dgamma reduction
        dW = Aq^T @ Gq

    Both matmuls reduce in the canonical fixed order over their
    contraction axis (N for dA, M for dW).  Returns ``(da, dw, dgamma)``;
    ``dgamma`` is ``None`` when ``a``/``clip_t`` are not given (PRC off).
    """
    g = g.astype(jnp.float32)
    aq = aq.astype(jnp.float32)
    wq = wq.astype(jnp.float32)
    beta_g = potq.compute_beta(g, bits_g)
    gq = quantize_tile_ref(
        g * exp2i(-beta_g), potq.pot_emax(bits_g)
    ) * exp2i(beta_g)
    # transposes are materialized here for clarity — the oracle is the
    # numeric spec, not the datapath; the kernel reads natural layouts
    da_raw = pot_value_matmul_ref(gq, wq.T)
    dw = pot_value_matmul_ref(aq.T, gq)
    if a is None or clip_t is None:
        return da_raw, dw, None
    a = a.astype(jnp.float32)
    clipped = jnp.abs(a) > clip_t
    contrib = jnp.where(clipped, da_raw * jnp.sign(a), 0.0)
    rows = grad_rowsum_ref(contrib)
    if amax is None:
        amax = jnp.max(jnp.abs(a))
    dgamma = jnp.sum(rows) * amax
    da = jnp.where(clipped, 0.0, da_raw)
    return da, dw, dgamma


def potq_matmul_ref(
    a: jax.Array,
    w: jax.Array,
    *,
    bits_a: int = 5,
    bits_w: int = 5,
    w_mean: Optional[jax.Array] = None,
    clip_t: Optional[jax.Array] = None,
) -> jax.Array:
    """Oracle for the fused quantize+matmul kernel.

    a: (M, K) raw activations; w: (K, N) raw weights.
    w_mean: scalar WBC mean to subtract from w (None = no WBC).
    clip_t: scalar PRC threshold for a (None = no clipping).
    """
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if clip_t is not None:
        a = jnp.clip(a, -clip_t, clip_t)
    if w_mean is not None:
        w = w - w_mean
    beta_a = potq.compute_beta(a, bits_a)
    beta_w = potq.compute_beta(w, bits_w)
    sa = exp2i(-beta_a)
    sw = exp2i(-beta_w)
    aq = quantize_tile_ref(a * sa, potq.pot_emax(bits_a))
    wq = quantize_tile_ref(w * sw, potq.pot_emax(bits_w))
    out = pot_value_matmul_ref(aq, wq)
    # Single per-block dequant shift by beta_a + beta_w (paper's INT32 shift).
    return out * exp2i(beta_a + beta_w)
