"""Fused backward-pass MF-MAC Pallas kernels (Algorithm 1, lines 13-15).

The forward kernel (kernels/potq_matmul.py) fuses quantize+matmul for
``out = Aq @ Wq``; these kernels do the same for the two backward MACs

    dA = Gq @ Wq^T     (+ the PRC clip-mask / dgamma epilogue)
    dW = Aq^T @ Gq

with the incoming gradient G quantized *in VMEM* (honoring bits_g /
bits_g_last via ``emax_g`` + the 2^-beta_g pre-scale) and the transposes
expressed purely through BlockSpec index maps: W is streamed in its
natural (K, N) layout for dA, A in its natural (M, K) layout for dW — no
materialized ``.T`` copies and no FP32 quantized intermediates in HBM.

Grids (kk innermost, "arbitrary"/sequential semantics so the FP32 VMEM
scratch carries across contraction steps):

    grad_da: (M/bm, K/bn, N/bk)   g:(bm,bk)@(i,kk)  w:(bn,bk)@(j,kk)
    grad_dw: (K/bm, N/bn, M/bk)   a:(bk,bm)@(kk,i)  g:(bk,bn)@(kk,j)

Both follow the same determinism contract as the forward kernel
(``ACC_SCHEME``): the contraction axis (N for dA, M for dW) is reduced in
canonical ``CANONICAL_BK``-wide chunks, one bf16 partial dot per chunk,
left-folded into the FP32 scratch in increasing global chunk order —
bit-identical output for every (bm, bn, bk) tiling, bit-equal to the
``kernels/ref.py`` backward oracle (``potq_grad_ref``).

PRC epilogue (grad_da only, when enabled): at the last contraction step
the raw ``a`` tile is loaded, ``clipped = |a| > clip_t`` masks dA, and the
dgamma contribution ``where(clipped, dA_raw * sign(a), 0)`` is reduced to
*per-row partials* in canonical 128-wide K chunks (ascending global chunk
order across the j grid dim, left fold) — the O(M*K) reduction work is
fused in-kernel; the final tiling-independent sum over the fixed-shape
(M,) row vector happens in the ops.py wrapper, so dgamma is also
bit-identical across tilings.

G is quantized in the *scaled* domain (operand pre-multiplied by
2^-beta_g, output dequantized by one 2^beta_g exponent-add per tile);
real-domain quantization (core/mfmac.py's jnp path) is bit-identical
because PoT scaling commutes exactly with FP32 rounding in the normal
range (docs/DESIGN_kernels.md conformance matrix).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import CANONICAL_BK, chunk_rowsum
# One accumulation-scheme tag governs forward AND backward kernels: all
# reduce in canonical CANONICAL_BK chunks, left fold.  Any change to the
# backward reduction order or epilogue math must bump it in
# kernels/potq_matmul.py (the autotune cache keys every op tag on it).
# Default block shapes are shared with the forward kernel for the same
# reason the scheme tag is: one source of truth.
from repro.kernels.potq_matmul import (  # noqa: F401
    ACC_SCHEME,
    DEFAULT_BK,
    DEFAULT_BM,
    DEFAULT_BN,
    _quantize_tile,
)


def _grad_da_kernel(
    g_ref,  # (bm, bk) raw-G tile over (M, N)
    w_ref,  # (bn, bk) Wq tile over (K, N) — transposed-operand index map
    *rest,
    emax_g: int,
    prc: bool,
    nk: int,
    nj: int,
    bk: int,
    bn: int,
):
    if prc:
        (a_ref, sg_ref, deq_ref, clip_ref, da_ref, dgr_ref,
         acc_ref, dgrows_ref) = rest
    else:
        sg_ref, deq_ref, clip_ref, da_ref, acc_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if prc:
        # per-row dgamma partials accumulate across the j (K tiles) grid
        # dim — re-zero once per M row-block (j == 0, first kk step)
        @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
        def _init_rows():
            dgrows_ref[...] = jnp.zeros_like(dgrows_ref)

    g = g_ref[...].astype(jnp.float32)
    # quantize G ONCE in VMEM (scaled domain): one exponent-add pre-scale,
    # round-to-nearest-log2 — identical math to the forward kernel's tiles
    gq = _quantize_tile(g * sg_ref[0, 0], emax_g).astype(jnp.bfloat16)
    w = w_ref[...].astype(jnp.bfloat16)
    # Fixed-order reduction over canonical N chunks (left fold, ascending
    # global chunk order — kk is innermost/sequential): contraction is dim
    # 1 of BOTH tiles, i.e. Gq @ Wq^T without materializing Wq^T.
    for c in range(bk // CANONICAL_BK):
        lo = c * CANONICAL_BK
        hi = lo + CANONICAL_BK
        acc_ref[...] += jax.lax.dot_general(
            gq[:, lo:hi],
            w[:, lo:hi],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        da_raw = acc_ref[...] * deq_ref[0, 0]  # exact 2^beta_g dequant
        if prc:
            a = a_ref[...].astype(jnp.float32)
            clipped = jnp.abs(a) > clip_ref[0, 0]
            contrib = jnp.where(clipped, da_raw * jnp.sign(a), 0.0)
            # canonical 128-wide K chunks of the row reduction, ascending
            # global chunk order (j ascends for fixed i), left fold
            for c in range(bn // CANONICAL_BK):
                s = chunk_rowsum(
                    contrib[:, c * CANONICAL_BK:(c + 1) * CANONICAL_BK])
                dgrows_ref[...] += s[:, None]
            da_ref[...] = jnp.where(clipped, 0.0, da_raw)
        else:
            da_ref[...] = da_raw

    if prc:
        # flush the finished per-row partials once per M row-block (the
        # last K tile's last contraction step)
        @pl.when(
            (pl.program_id(1) == nj - 1) & (pl.program_id(2) == nk - 1)
        )
        def _flush_rows():
            dgr_ref[...] = dgrows_ref[...]


def _grad_dw_kernel(
    a_ref,  # (bk, bm) Aq tile over (M, K) — transposed-operand index map
    g_ref,  # (bk, bn) raw-G tile over (M, N)
    sg_ref,
    deq_ref,
    dw_ref,
    acc_ref,
    *,
    emax_g: int,
    nk: int,
    bk: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)
    gq = _quantize_tile(g * sg_ref[0, 0], emax_g).astype(jnp.bfloat16)
    a = a_ref[...].astype(jnp.bfloat16)
    # Aq^T @ Gq: contraction is dim 0 of BOTH tiles (the M axis), reduced
    # in canonical chunks, ascending global order, left fold.
    for c in range(bk // CANONICAL_BK):
        lo = c * CANONICAL_BK
        hi = lo + CANONICAL_BK
        acc_ref[...] += jax.lax.dot_general(
            a[lo:hi, :],
            gq[lo:hi, :],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        dw_ref[...] = acc_ref[...] * deq_ref[0, 0]


@functools.partial(
    jax.jit,
    static_argnames=("emax_g", "prc", "bm", "bn", "bk", "interpret"),
)
def grad_da_padded(
    g: jax.Array,  # (M, N), M % bm == 0, N % bk == 0
    w: jax.Array,  # (K, N), K % bn == 0
    a,  # (M, K) raw activations (any array when prc=False; unused)
    scale_g: jax.Array,  # (1,1) f32: 2^-beta_g
    dequant_g: jax.Array,  # (1,1) f32: 2^beta_g
    clip_t: jax.Array,  # (1,1) f32: PRC threshold
    *,
    emax_g: int = 7,
    prc: bool = True,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
):
    """dA = Gq @ Wq^T with fused in-VMEM G quantization and PRC epilogue.

    Returns ``(da, dgamma_rows)`` with ``dgamma_rows`` of shape (M, 128)
    (every lane carries the same per-row partial; read column 0) when
    ``prc``, else just ``da``.
    """
    m, nn = g.shape
    k, nn2 = w.shape
    assert nn == nn2 and m % bm == 0 and k % bn == 0 and nn % bk == 0, (
        g.shape, w.shape, (bm, bn, bk),
    )
    assert bk % CANONICAL_BK == 0, (
        f"bk={bk} must be a multiple of the canonical chunk ({CANONICAL_BK})"
    )
    nk = nn // bk
    nj = k // bn
    if prc:
        assert a.shape == (m, k), (a.shape, (m, k))
        assert bn % CANONICAL_BK == 0, (
            f"bn={bn} must be a multiple of {CANONICAL_BK} for the canonical "
            f"dgamma row reduction"
        )
    grid = (m // bm, nj, nk)
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0))
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),  # g over (M, N)
        pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),  # w over (K, N)
    ]
    operands = [g, w]
    if prc:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(a)
    in_specs += [scalar_spec, scalar_spec, scalar_spec]
    operands += [scale_g, dequant_g, clip_t]

    out_specs = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    out_shape = jax.ShapeDtypeStruct((m, k), jnp.float32)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if prc:
        out_specs = [out_specs,
                     pl.BlockSpec((bm, 128), lambda i, j, kk: (i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((m, 128), jnp.float32)]
        scratch.append(pltpu.VMEM((bm, 128), jnp.float32))

    return pl.pallas_call(
        functools.partial(
            _grad_da_kernel,
            emax_g=emax_g, prc=prc, nk=nk, nj=nj, bk=bk, bn=bn,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("emax_g", "bm", "bn", "bk", "interpret"),
)
def grad_dw_padded(
    a: jax.Array,  # (M, K) Aq residual, M % bk == 0, K % bm == 0
    g: jax.Array,  # (M, N) raw gradient, N % bn == 0
    scale_g: jax.Array,  # (1,1) f32: 2^-beta_g
    dequant_g: jax.Array,  # (1,1) f32: 2^beta_g
    *,
    emax_g: int = 7,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """dW = Aq^T @ Gq with fused in-VMEM G quantization; returns (K, N)."""
    mm, k = a.shape
    mm2, n = g.shape
    assert mm == mm2 and k % bm == 0 and n % bn == 0 and mm % bk == 0, (
        a.shape, g.shape, (bm, bn, bk),
    )
    assert bk % CANONICAL_BK == 0, (
        f"bk={bk} must be a multiple of the canonical chunk ({CANONICAL_BK})"
    )
    nk = mm // bk
    grid = (k // bm, n // bn, nk)
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0))
    return pl.pallas_call(
        functools.partial(_grad_dw_kernel, emax_g=emax_g, nk=nk, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i)),  # a over (M, K)
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),  # g over (M, N)
            scalar_spec,
            scalar_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, g, scale_g, dequant_g)
