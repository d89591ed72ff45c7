"""jit'd public wrappers around the Pallas kernels.

* :func:`potq_matmul`     — fused PRC-clip + WBC + ALS-PoTQ + matmul.
* :func:`pot_value_matmul`— tiled matmul over already-PoT-valued operands
  (what core/mfmac.py dispatches to when policy.use_pallas=True).
* :func:`potq_grad_matmuls` — fused backward: quantize the incoming
  gradient once, compute dA = Gq @ Wq^T and dW = Aq^T @ Gq via
  transposed-operand BlockSpecs, PRC clip-mask + dgamma epilogue fused
  (what core/mfmac.py's backward dispatches to under use_pallas).
  :func:`grad_da_matmul` / :func:`grad_dw_matmul` expose the two halves
  individually (autotuner, benchmarks).

Off the TPU the kernels run in interpret mode (the Pallas body executes
in Python); on the TPU they compile through Mosaic (``interpret=None``
resolves by backend).  Ragged shapes are zero-padded to block multiples —
zero padding is exact for both the quantizer (0 -> 0) and the matmul.
Under an ambient multi-device mesh each kernel runs whole on every
device (:func:`_whole`).

Block shapes default to ``None`` = consult ``kernels/autotune.py`` (tuned
cache -> heuristic); the fixed-order reduction makes every tiling
bit-identical, so tuned and explicit blocks compute the same bits.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import potq
from repro.kernels import autotune
from repro.kernels import potq_encode as _ke
from repro.kernels import potq_grad as _kg
from repro.kernels import potq_matmul as _k


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _whole(kernel, **static):
    """``kernel`` with its static arguments bound, run whole on every
    device of the ambient abstract mesh (set by ``jax.set_mesh`` or
    ``actshard.use_plan``) when it has several.

    The SPMD partitioner cannot split a Mosaic kernel and refuses to
    lower one under a multi-device jit.  Inside a ``shard_map`` whose
    specs are all replicated each device receives the full operands (the
    partitioner gathers sharded ones) and computes the full result, so
    the output has the bits of a one-device run."""
    fn = functools.partial(kernel, **static)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _pad_to(x: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def potq_matmul(
    a: jax.Array,
    w: jax.Array,
    *,
    bits_a: int = 5,
    bits_w: int = 5,
    w_mean: Optional[jax.Array] = None,
    clip_t: Optional[jax.Array] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused ALS-PoTQ quantize + matmul: a(M,K) @ w(K,N) -> (M,N) f32.

    Layer-wise betas are derived from global amax reductions (one cheap
    pass, as in the paper); everything else is fused in-kernel.
    """
    if interpret is None:
        interpret = _default_interpret()
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    m, k = a.shape
    _, n = w.shape

    if clip_t is None:
        clip_t = jnp.float32(jnp.inf)
    a_eff_max = jnp.minimum(jnp.max(jnp.abs(a)), clip_t)
    if w_mean is None:
        w_mean = jnp.float32(0.0)
    w_eff = jnp.max(jnp.abs(w - w_mean))

    emax_a = potq.pot_emax(bits_a)
    emax_w = potq.pot_emax(bits_w)

    def beta_of(amax, emax):
        safe = jnp.where(amax > 0, amax, 1.0)
        b = potq.round_log2(safe).astype(jnp.int32) - emax
        return jnp.where(amax > 0, b, 0)

    beta_a = beta_of(a_eff_max, emax_a)
    beta_w = beta_of(w_eff, emax_w)

    one = lambda v: jnp.full((1, 1), v, jnp.float32)
    sa = one(potq.exp2i(-beta_a))
    sw = one(potq.exp2i(-beta_w))
    deq = one(potq.exp2i(beta_a + beta_w))

    bm_, bn_, bk_ = autotune.resolve(
        m, k, n, bm, bn, bk, emax_a=emax_a, emax_w=emax_w, quantize=True
    )
    ap = _pad_to(_pad_to(a, 8, 128), bm_, bk_)
    wp = _pad_to(_pad_to(w, 128, 128), bk_, bn_)
    out = _whole(
        _k.potq_matmul_padded, emax_a=emax_a, emax_w=emax_w, quantize=True,
        bm=bm_, bn=bn_, bk=bk_, interpret=interpret,
    )(ap, wp, sa, sw, deq, one(w_mean), one(clip_t))
    return out[:m, :n]


def pot_value_matmul(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(M,K)@(K,N) matmul over already-quantized (PoT-valued) operands."""
    if interpret is None:
        interpret = _default_interpret()
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    m, k = x.shape
    _, n = y.shape
    one = lambda v: jnp.full((1, 1), v, jnp.float32)
    bm_, bn_, bk_ = autotune.resolve(m, k, n, bm, bn, bk, quantize=False)
    xp = _pad_to(_pad_to(x, 8, 128), bm_, bk_)
    yp = _pad_to(_pad_to(y, 128, 128), bk_, bn_)
    out = _whole(
        _k.potq_matmul_padded, quantize=False, bm=bm_, bn=bn_, bk=bk_,
        interpret=interpret,
    )(xp, yp, one(1.0), one(1.0), one(1.0), one(0.0), one(jnp.inf))
    return out[:m, :n]


def _g_scales(g: jax.Array, bits_g: int, beta_g: Optional[jax.Array]):
    """(scale, dequant, emax) for the in-kernel gradient quantizer."""
    if beta_g is None:
        beta_g = potq.compute_beta(g, bits_g)
    one = lambda v: jnp.full((1, 1), v, jnp.float32)
    return one(potq.exp2i(-beta_g)), one(potq.exp2i(beta_g)), potq.pot_emax(bits_g)


def grad_da_matmul(
    g: jax.Array,  # (M, N) raw incoming gradient
    wq: jax.Array,  # (K, N) quantized weights (forward residual)
    *,
    a: Optional[jax.Array] = None,  # (M, K) raw activations (PRC epilogue)
    clip_t: Optional[jax.Array] = None,  # scalar PRC threshold
    bits_g: int = 5,
    beta_g: Optional[jax.Array] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Fused dA = Gq @ Wq^T: G quantized in VMEM, Wq streamed in natural
    (K, N) layout (transposed-operand index map, no ``.T`` copy).

    With ``a``/``clip_t`` the PRC epilogue runs in-kernel: dA is
    clip-masked and the dgamma contribution is reduced to per-row partials
    in canonical order.  Returns ``(da, dgamma_rows)`` where
    ``dgamma_rows`` is the (M,) canonical row-sum vector (``None`` when
    the epilogue is off); ``sum(dgamma_rows) * max|a|`` is dgamma.
    """
    if interpret is None:
        interpret = _default_interpret()
    prc = a is not None
    if prc and clip_t is None:
        raise ValueError("PRC epilogue needs both a and clip_t")
    g = g.astype(jnp.float32)
    wq = wq.astype(jnp.float32)
    m, nn = g.shape
    k = wq.shape[0]
    sg, deq, emax_g = _g_scales(g, bits_g, beta_g)
    # matmul problem: rows=M, contraction=N, cols=K; the PRC epilogue
    # changes the VMEM footprint, so PRC-off tunes under its own tag
    bm_, bn_, bk_ = autotune.resolve(
        m, nn, k, bm, bn, bk, emax_a=emax_g,
        op="grad_da" if prc else "grad_da_raw",
    )
    gp = _pad_to(_pad_to(g, 8, 128), bm_, bk_)
    wp = _pad_to(_pad_to(wq, 128, 128), bn_, bk_)
    if prc:
        a = a.astype(jnp.float32)
        ap = _pad_to(a, bm_, bn_)
        assert ap.shape == (gp.shape[0], wp.shape[0])
        out, rows = _whole(
            _kg.grad_da_padded, emax_g=emax_g, prc=True, bm=bm_, bn=bn_,
            bk=bk_, interpret=interpret,
        )(gp, wp, ap, sg, deq, jnp.full((1, 1), clip_t, jnp.float32))
        # every lane of a row carries the same partial; the final
        # tiling-independent reduction over the fixed-shape (M,) vector
        # belongs to the caller (potq_grad_matmuls / tests)
        return out[:m, :k], rows[:m, 0]
    out = _whole(
        _kg.grad_da_padded, emax_g=emax_g, prc=False, bm=bm_, bn=bn_,
        bk=bk_, interpret=interpret,
    )(gp, wp, gp, sg, deq, jnp.full((1, 1), jnp.inf, jnp.float32))
    return out[:m, :k], None


def grad_dw_matmul(
    g: jax.Array,  # (M, N) raw incoming gradient
    aq: jax.Array,  # (M, K) quantized activations (forward residual)
    *,
    bits_g: int = 5,
    beta_g: Optional[jax.Array] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused dW = Aq^T @ Gq: G quantized in VMEM, Aq streamed in natural
    (M, K) layout (transposed-operand index map, no ``.T`` copy)."""
    if interpret is None:
        interpret = _default_interpret()
    g = g.astype(jnp.float32)
    aq = aq.astype(jnp.float32)
    m, nn = g.shape
    k = aq.shape[1]
    sg, deq, emax_g = _g_scales(g, bits_g, beta_g)
    # matmul problem: rows=K, contraction=M, cols=N
    bm_, bn_, bk_ = autotune.resolve(
        k, m, nn, bm, bn, bk, emax_a=emax_g, op="grad_dw"
    )
    ap = _pad_to(_pad_to(aq, 128, 128), bk_, bm_)
    gp = _pad_to(_pad_to(g, 128, 128), bk_, bn_)
    out = _whole(
        _kg.grad_dw_padded, emax_g=emax_g, bm=bm_, bn=bn_, bk=bk_,
        interpret=interpret,
    )(ap, gp, sg, deq)
    return out[:k, :nn]


def potq_grad_matmuls(
    g: jax.Array,  # (M, N) raw incoming gradient
    aq: jax.Array,  # (M, K) quantized activations (forward residual)
    wq: jax.Array,  # (K, N) quantized weights (forward residual)
    *,
    a: Optional[jax.Array] = None,  # (M, K) raw activations (PRC epilogue)
    clip_t: Optional[jax.Array] = None,  # scalar PRC threshold
    amax: Optional[jax.Array] = None,  # scalar max|a| (dgamma scale)
    bits_g: int = 5,
    interpret: Optional[bool] = None,
):
    """Fused backward MACs (Algorithm 1, lines 13-15): the incoming
    gradient is quantized ONCE — a single beta_g derivation, one
    deterministic in-VMEM quantization shared by both products, no FP32
    quantized intermediate in HBM — then

        dA = Gq @ Wq^T   (PRC clip mask + dgamma epilogue fused)
        dW = Aq^T @ Gq

    Returns ``(da, dw, dgamma)``; ``dgamma`` is ``None`` when ``a`` /
    ``clip_t`` are not given (PRC disabled).  Bit-identical across all
    block tilings and bit-equal to ``kernels/ref.py::potq_grad_ref``
    (tests/conformance/test_grad_paths.py).
    """
    g = g.astype(jnp.float32)
    beta_g = potq.compute_beta(g, bits_g)  # quantized once: one shared beta
    da, rows = grad_da_matmul(
        g, wq, a=a, clip_t=clip_t, bits_g=bits_g, beta_g=beta_g,
        interpret=interpret,
    )
    dw = grad_dw_matmul(
        g, aq, bits_g=bits_g, beta_g=beta_g, interpret=interpret
    )
    if rows is None:
        return da, dw, None
    if amax is None:
        amax = jnp.max(jnp.abs(a.astype(jnp.float32)))
    # fixed-shape (M,) reduction: independent of both kernels' tilings
    dgamma = jnp.sum(rows) * amax
    return da, dw, dgamma


def potq_encode(
    x: jax.Array,
    *,
    bits: int = 5,
    bm: int = _ke.DEFAULT_BM,
    bn: int = _ke.DEFAULT_BN,
    interpret: Optional[bool] = None,
) -> tuple:
    """Encode a tensor to int8 PoT codes + scalar beta (wire format).

    Matches core.compress layout: code 0 => zero; otherwise
    |code| = exp + emax + 1, sign(code) = sign(value).
    """
    if interpret is None:
        interpret = _default_interpret()
    orig_shape = x.shape
    x2 = x.astype(jnp.float32).reshape(-1, orig_shape[-1]) if x.ndim > 1 else (
        x.astype(jnp.float32).reshape(1, -1)
    )
    emax = potq.pot_emax(bits)
    beta = potq.compute_beta(x2, bits)
    scale = jnp.full((1, 1), potq.exp2i(-beta), jnp.float32)
    m, n = x2.shape
    xp = _pad_to(x2, 8, 128)
    bm_ = min(bm, xp.shape[0])
    bn_ = min(bn, max(128, xp.shape[1]))
    xp = _pad_to(xp, bm_, bn_)
    codes = _whole(
        _ke.potq_encode_padded, emax=emax, bm=bm_, bn=bn_,
        interpret=interpret,
    )(xp, scale)[:m, :n]
    return codes.reshape(orig_shape), beta
