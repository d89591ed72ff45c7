"""Fused ALS-PoTQ quantize + matmul Pallas TPU kernel.

TPU-native adaptation of the paper's MF-MAC (DESIGN.md §2): operands are
streamed HBM->VMEM once, PRC-clipped / WBC-shifted / PoT-quantized *inside
VMEM*, multiplied on the MXU in bf16 (exact for PoT values), accumulated in
an FP32 VMEM scratch across the K grid, and dequantized by a single scalar
2^(beta_a+beta_w) multiply per output tile (the paper's one INT32 shift per
block).  No FP32 quantized intermediates ever touch HBM.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics) so the
accumulator scratch carries across K steps.  Block shapes must be
MXU-aligned multiples of 128 (bm >= 8) and are tunable; the ops.py wrapper
pads ragged shapes and consults kernels/autotune.py for block choices.

Determinism contract (docs/DESIGN_kernels.md): the FP32 accumulation is a
fixed-order reduction over *canonical* K chunks of width ``CANONICAL_BK``,
independent of the grid's bk.  A bk-wide tile is reduced as bk/CANONICAL_BK
sequential partial dots, each over exactly CANONICAL_BK columns, added into
the scratch in increasing global chunk order.  Every tiling therefore
performs the *same* FP32 additions in the *same* order — the left fold
acc = ((p_0 + p_1) + p_2) + ... over global chunk index — which is the
unique bk-independent schedule that needs O(1) scratch (any balanced tree
would key its combine structure to tile boundaries, i.e. to bk).  Output
is bit-identical across all (bm, bn, bk) tilings; zero K padding appends
exact-zero partials and preserves bits (x + 0.0 == x; -0.0 folds to +0.0,
equal under ==).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import potq

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256

# Width of one canonical K chunk of the fixed-order reduction.  128 is the
# MXU systolic dimension and the minimum lane-aligned tile, so every legal
# bk is a multiple of it.  Defined in kernels/ref.py (the pallas-free
# numeric spec) so oracle and kernel cannot drift apart.
from repro.kernels.ref import CANONICAL_BK  # noqa: E402

# Accumulation-scheme tag.  Bump on ANY change to the reduction order or
# the in-kernel quantizer math — the autotune cache (kernels/autotune.py)
# keys on it, so stale tuned entries (and any golden outputs derived from
# the old order) are invalidated automatically.
ACC_SCHEME = "canonical-k128-leftfold-v2"


def _quantize_tile(x, emax: int):
    """Round-to-nearest PoT quantization of a pre-scaled VMEM tile."""
    mag = jnp.abs(x)
    safe = jnp.where(mag > 0, mag, 1.0)
    e = potq.round_log2(safe)
    under = (e < -emax) | (mag == 0)
    e = jnp.clip(e, float(-emax), float(emax))
    # exact 2^e via exponent-bit construction (jnp.exp2 is inexact on
    # exp(x*ln2) backends; see core.potq.exp2i)
    ebits = ((e.astype(jnp.int32) + 127).astype(jnp.uint32)) << 23
    p2 = jax.lax.bitcast_convert_type(ebits, jnp.float32)
    q = jnp.where(under, 0.0, p2)
    return jnp.sign(x) * q


def _potq_matmul_kernel(
    a_ref,
    w_ref,
    sa_ref,  # (1,1) f32: 2^-beta_a
    sw_ref,  # (1,1) f32: 2^-beta_w
    deq_ref,  # (1,1) f32: 2^(beta_a+beta_w)
    wmean_ref,  # (1,1) f32: WBC mean (0 if disabled)
    clip_ref,  # (1,1) f32: PRC threshold (+inf if disabled)
    o_ref,
    acc_ref,
    *,
    emax_a: int,
    emax_w: int,
    quantize: bool,
    nk: int,
    bk: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    if quantize:
        t = clip_ref[0, 0]
        a = jnp.clip(a, -t, t)  # PRC, fused
        w = w - wmean_ref[0, 0]  # WBC, fused
        # exponent-add scaling (exact multiply by a power of two)
        aq = _quantize_tile(a * sa_ref[0, 0], emax_a)
        wq = _quantize_tile(w * sw_ref[0, 0], emax_w)
    else:
        aq, wq = a, w
    ab = aq.astype(jnp.bfloat16)
    wb = wq.astype(jnp.bfloat16)
    # Fixed-order reduction: one partial dot per canonical K chunk, added
    # into the FP32 scratch sequentially.  The grid's K dim is "arbitrary"
    # (sequential, innermost), so across the whole K axis the additions
    # happen in increasing global chunk order for EVERY bk — the output is
    # bit-identical across tilings (see module docstring).
    for c in range(bk // CANONICAL_BK):
        lo = c * CANONICAL_BK
        hi = lo + CANONICAL_BK
        acc_ref[...] += jnp.dot(
            ab[:, lo:hi],
            wb[lo:hi, :],
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...] * deq_ref[0, 0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "emax_a",
        "emax_w",
        "quantize",
        "bm",
        "bn",
        "bk",
        "interpret",
    ),
)
def potq_matmul_padded(
    a: jax.Array,  # (M, K), M % bm == 0, K % bk == 0
    w: jax.Array,  # (K, N), N % bn == 0
    scale_a: jax.Array,  # (1,1) f32
    scale_w: jax.Array,  # (1,1) f32
    dequant: jax.Array,  # (1,1) f32
    w_mean: jax.Array,  # (1,1) f32
    clip_t: jax.Array,  # (1,1) f32
    *,
    emax_a: int = 7,
    emax_w: int = 7,
    quantize: bool = True,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    m, k = a.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (
        a.shape,
        w.shape,
        (bm, bn, bk),
    )
    assert bk % CANONICAL_BK == 0, (
        f"bk={bk} must be a multiple of the canonical K chunk "
        f"({CANONICAL_BK}) for the fixed-order reduction"
    )
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0))
    return pl.pallas_call(
        functools.partial(
            _potq_matmul_kernel,
            emax_a=emax_a,
            emax_w=emax_w,
            quantize=quantize,
            nk=nk,
            bk=bk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            scalar_spec,
            scalar_spec,
            scalar_spec,
            scalar_spec,
            scalar_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, w, scale_a, scale_w, dequant, w_mean, clip_t)
