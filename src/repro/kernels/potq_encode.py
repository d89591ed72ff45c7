"""Standalone ALS-PoTQ encode Pallas kernel: FP32 -> int8 PoT codes.

The elementwise producer of the paper's wire format (sign + exponent
packed into one int8 code per element, core/compress.py layout), used by
gradient compression and offline weight packing.  On TPU this is a pure
VPU kernel: one HBM read (f32) + one HBM write (int8) per element, 8-wide
sublane tiles; VMEM block shape is the tuning knob.

ops.py exposes :func:`potq_encode` (jit'd, padded) and tests validate
against core.potq in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import potq

DEFAULT_BM = 256
DEFAULT_BN = 512


def _encode_kernel(x_ref, scale_ref, o_ref, *, emax: int):
    x = x_ref[...].astype(jnp.float32) * scale_ref[0, 0]  # 2^-beta scaling
    mag = jnp.abs(x)
    safe = jnp.where(mag > 0, mag, 1.0)
    e = potq.round_log2(safe)
    under = (e < -emax) | (mag == 0)
    e = jnp.clip(e, float(-emax), float(emax))
    code = (e.astype(jnp.int32) + (emax + 1))  # magnitude code in [1, 2e+1]
    code = jnp.where(under, 0, code)
    code = jnp.where(x < 0, -code, code)
    o_ref[...] = code.astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("emax", "bm", "bn", "interpret")
)
def potq_encode_padded(
    x: jax.Array,  # (M, N), M % bm == 0, N % bn == 0
    scale: jax.Array,  # (1,1) f32: 2^-beta
    *,
    emax: int = 7,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jax.Array:
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, (x.shape, (bm, bn))
    return pl.pallas_call(
        functools.partial(_encode_kernel, emax=emax),
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=interpret,
    )(x, scale)
