"""MF-MAC: multiplication-free linear layers with custom VJP (Algorithm 1).

The public entry points are

* :func:`mf_linear`       — a[..., K] @ w[K, N]   (dense projections)
* :func:`mf_expert_linear`— a[E, T, K] @ w[E, K, N] (MoE experts, per-expert
  layer-wise scales: each expert is its own "layer"; serving's per-slot
  dispatch vmaps this over the slot axis so the scale groups become
  per-(expert, slot) and decode stays batch-invariant —
  models/transformer.py `_moe_apply(per_slot=True)`)
* :func:`mf_act_dot`      — activation x activation dot_general (attention
  QK^T / PV), beyond-paper opt-in (policy.quantize_attention)

Forward (paper Algorithm 1, lines 4–8):
    Wq = ALS-PoTQ(W - mean(W))          # WBC then quantize
    Aq = ALS-PoTQ(clip(A, gamma*max|A|))  # PRC then quantize
    out = MF_MAC(Aq, Wq)

Backward (lines 13–15): the incoming gradient G is itself ALS-PoTQ
quantized **once** and reused:
    dA = MF_MAC(Gq, Wq^T)   — then PRC's clip mask / gamma VJP is applied
    dW = MF_MAC(Aq^T, Gq)   — paper uses the raw MF-MAC output (no WBC
                               Jacobian correction), which we follow.

The MF-MAC itself is computed as a bf16 MXU matmul over the *dequantized*
PoT values — bit-identical to the paper's INT4-add + XOR datapath because
every 5-bit PoT value is exact in bf16 (DESIGN.md §2).  Accumulation is
FP32 (MXU) vs the paper's INT32; tests bound the deviation.

``policy.use_pallas`` routes the forward MACs through the fused Pallas
TPU kernel (repro.kernels.ops) instead of jnp — same math, fused quantize
— and the backward through ``ops.potq_grad_matmuls``: G quantized once in
VMEM, transposed operands expressed as BlockSpec index maps (no ``.T``
copies), PRC clip-mask + dgamma reduction fused as the kernel epilogue.

Every ALS-PoTQ pass outside the kernels (``_quantize_w``, ``_quantize_a``,
``_quantize_g``, ``_qact``) runs under the ``als_potq`` name scope, which
the profiler records in each device operation's name, so a trace tells
the quantizers' time from the MF-MAC kernels' (``quantize_share`` in
PERF.md).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import potq
from repro.core.policy import QuantPolicy

_BF16 = jnp.bfloat16


def _maybe_pallas_matmul(x: jax.Array, y: jax.Array, policy: QuantPolicy) -> jax.Array:
    """(M,K)@(K,N) over already-quantized (PoT-valued) f32 operands."""
    if policy.use_pallas:
        from repro.kernels import ops  # lazy: keeps CPU-only paths light

        return ops.pot_value_matmul(x, y)
    return jnp.dot(
        x.astype(_BF16), y.astype(_BF16), preferred_element_type=jnp.float32
    )


def _quantize_w(w: jax.Array, policy: QuantPolicy, axes=None) -> jax.Array:
    with jax.named_scope("als_potq"):
        if policy.weights_prequantized:
            return w.astype(_BF16)  # already exact PoT values (serving path)
        w = w.astype(jnp.float32)
        if policy.weight_bias_correction:
            if axes is None:
                w = w - jnp.mean(w)
            else:
                w = w - jnp.mean(w, axis=axes, keepdims=True)
        beta = potq.compute_beta(w, policy.bits_w, axes)
        # bf16 is EXACT for PoT values (DESIGN.md §2); materializing
        # quantized operands at 2 bytes halves FSDP gather traffic and remat
        # residuals.
        return potq.pot_quantize(w, policy.bits_w, beta).astype(_BF16)


def _sample_axes(policy: QuantPolicy, x: jax.Array, axes):
    """Scale-group axes for a forward activation: per-sample (all dims but
    the leading batch dim) under ``policy.per_sample_act_scales``, so slot-
    pooled decode is batch-invariant (serve/engine.py).  Explicit ``axes``
    (e.g. per-expert groups) always win."""
    if axes is None and policy.per_sample_act_scales and x.ndim >= 2:
        return tuple(range(1, x.ndim))
    return axes


def _quantize_a(a: jax.Array, gamma: jax.Array, policy: QuantPolicy, axes=None):
    """Returns (a_clipped_for_vjp_inputs_unchanged, aq)."""
    with jax.named_scope("als_potq"):
        axes = _sample_axes(policy, a, axes)
        a32 = a.astype(jnp.float32)
        if policy.prc_enabled:
            if axes is None:
                t = jax.lax.stop_gradient(jnp.max(jnp.abs(a32))) * gamma
            else:
                t = jax.lax.stop_gradient(
                    jnp.max(jnp.abs(a32), axis=axes, keepdims=True)
                ) * gamma
            a_c = jnp.clip(a32, -t, t)
        else:
            a_c = a32
        beta = potq.compute_beta(a_c, policy.bits_a, axes)
        return potq.pot_quantize(a_c, policy.bits_a, beta).astype(_BF16)


def _quantize_g(g: jax.Array, policy: QuantPolicy, is_last: bool, axes=None):
    with jax.named_scope("als_potq"):
        g32 = g.astype(jnp.float32)
        bits = policy.bits_g_last if is_last else policy.bits_g
        beta = potq.compute_beta(g32, bits, axes)
        return potq.pot_quantize(g32, bits, beta).astype(_BF16)


# ---------------------------------------------------------------------------
# mf_linear: a[..., K] @ w[K, N]
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mf_linear(policy: QuantPolicy, is_last: bool, a, w, gamma):
    out, _ = _mf_linear_fwd(policy, is_last, a, w, gamma)
    return out


def _mf_linear_fwd(policy, is_last, a, w, gamma):
    aq = _quantize_a(a, gamma, policy)
    wq = _quantize_w(w, policy)
    lead = a.shape[:-1]
    k = a.shape[-1]
    out = _maybe_pallas_matmul(aq.reshape(-1, k), wq, policy)
    out = out.reshape(*lead, w.shape[-1]).astype(a.dtype)
    # Residuals: quantized operands (paper reuses Wq/Aq in backward) plus
    # what the PRC VJP needs (raw a, gamma).
    return out, (aq, wq, a, gamma)


def _mf_linear_bwd(policy, is_last, res, g):
    aq, wq, a, gamma = res
    k, n = wq.shape
    if policy.use_pallas:
        # Fused backward kernels: G quantized once IN VMEM, transposed
        # operands via BlockSpec index maps (no materialized .T copies),
        # PRC clip-mask + dgamma reduction fused as the output epilogue.
        from repro.kernels import ops

        bits = policy.bits_g_last if is_last else policy.bits_g
        g2 = g.astype(jnp.float32).reshape(-1, n)
        aq2 = aq.reshape(-1, k)
        if policy.prc_enabled:
            a32 = a.astype(jnp.float32)
            amax = jnp.max(jnp.abs(a32))
            da, dw, dgamma = ops.potq_grad_matmuls(
                g2, aq2, wq, a=a32.reshape(-1, k),
                clip_t=amax * gamma, amax=amax, bits_g=bits,
            )
            dgamma = dgamma.reshape(gamma.shape).astype(gamma.dtype)
        else:
            da, dw, _ = ops.potq_grad_matmuls(g2, aq2, wq, bits_g=bits)
            dgamma = jnp.zeros_like(gamma)
        return (da.reshape(a.shape).astype(a.dtype),
                dw.astype(jnp.float32), dgamma)
    gq = _quantize_g(g, policy, is_last)  # quantized ONCE, reused (line 13)
    g2 = gq.reshape(-1, n)
    # dA = Gq @ Wq^T   (line 14)
    da = _maybe_pallas_matmul(g2, wq.T, policy).reshape(a.shape)
    # dW = Aq^T @ Gq   (line 15) — raw MF-MAC output, per the paper.
    dw = _maybe_pallas_matmul(aq.reshape(-1, k).T, g2, policy)
    # PRC VJP: mask dA outside the clip threshold, collect dgamma (PACT).
    if policy.prc_enabled:
        a32 = a.astype(jnp.float32)
        amax = jnp.max(jnp.abs(a32))
        clipped = jnp.abs(a32) > amax * gamma
        dgamma = (jnp.sum(jnp.where(clipped, da * jnp.sign(a32), 0.0)) * amax)
        da = jnp.where(clipped, 0.0, da)
        dgamma = dgamma.reshape(gamma.shape).astype(gamma.dtype)
    else:
        dgamma = jnp.zeros_like(gamma)
    return da.astype(a.dtype), dw.astype(jnp.float32), dgamma


_mf_linear.defvjp(_mf_linear_fwd, _mf_linear_bwd)


def mf_linear(
    a: jax.Array,
    w: jax.Array,
    gamma: Optional[jax.Array] = None,
    *,
    policy: QuantPolicy,
    is_last: bool = False,
) -> jax.Array:
    """Quantized (or plain, if policy.enabled=False) linear projection."""
    if not policy.enabled:
        w_ = w.astype(a.dtype)
        if a.ndim == 3 and a.shape[1] == 1:
            # Decode-shaped (B, 1, D) rows: XLA's matmul strategy is
            # M-dependent, so a plain dot's last-ulp reduction order
            # changes with the batch size — breaking the serving stack's
            # batch-invariance on the raw-FP32 path.  A per-row map runs
            # the SAME (1, D) @ (D, N) program for every batch size,
            # making the reduction row-independent by construction (the
            # quantized path gets this from the tiling-invariant
            # kernels).  Decode batches are pool-sized, so the map adds
            # no meaningful cost; training shapes (S > 1) keep the fast
            # fused dot.
            return jax.lax.map(
                lambda r: jnp.dot(r, w_,
                                  precision=jax.lax.Precision.HIGHEST), a
            )
        return jnp.dot(a, w_, precision=jax.lax.Precision.HIGHEST)
    if gamma is None:
        gamma = jnp.float32(policy.ratio_clip_init or 1.0)
    return _mf_linear(policy, is_last, a, w, gamma)


# ---------------------------------------------------------------------------
# mf_expert_linear: a[E, T, K] @ w[E, K, N], per-expert scales
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mf_expert_linear(policy: QuantPolicy, a, w, gamma):
    out, _ = _mf_expert_fwd(policy, a, w, gamma)
    return out


def _expert_bmm(x, y, policy):
    """Batched (E,M,K)@(E,K,N) over PoT-valued operands."""
    if policy.use_pallas:
        from repro.kernels import ops

        return jax.vmap(ops.pot_value_matmul)(x, y)
    return jax.lax.dot_general(
        x.astype(_BF16),
        y.astype(_BF16),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _mf_expert_fwd(policy, a, w, gamma):
    aq = _quantize_a(a, gamma, policy, axes=(1, 2))
    wq = _quantize_w(w, policy, axes=(1, 2))
    out = _expert_bmm(aq, wq, policy).astype(a.dtype)
    return out, (aq, wq, a, gamma)


def _mf_expert_bwd(policy, res, g):
    aq, wq, a, gamma = res
    if policy.use_pallas:
        # vmap the fused backward over experts: per-expert beta_g / clip
        # thresholds / dgamma partials, each expert its own "layer".
        from repro.kernels import ops

        g32 = g.astype(jnp.float32)
        a32 = a.astype(jnp.float32)
        if policy.prc_enabled:
            amax = jnp.max(jnp.abs(a32), axis=(1, 2))  # (E,)

            def one(ge, aqe, wqe, ae, ame):
                return ops.potq_grad_matmuls(
                    ge, aqe, wqe, a=ae, clip_t=ame * gamma, amax=ame,
                    bits_g=policy.bits_g,
                )

            da, dw, dg = jax.vmap(one)(g32, aq, wq, a32, amax)
            dgamma = jnp.sum(dg).reshape(gamma.shape).astype(gamma.dtype)
        else:
            def one(ge, aqe, wqe):
                return ops.potq_grad_matmuls(
                    ge, aqe, wqe, bits_g=policy.bits_g
                )

            da, dw, _ = jax.vmap(one)(g32, aq, wq)
            dgamma = jnp.zeros_like(gamma)
        return da.astype(a.dtype), dw.astype(jnp.float32), dgamma
    gq = _quantize_g(g, policy, False, axes=(1, 2))
    # dA[e] = Gq[e] @ Wq[e]^T
    da = _expert_bmm(gq, jnp.swapaxes(wq, 1, 2), policy)
    # dW[e] = Aq[e]^T @ Gq[e]
    dw = _expert_bmm(jnp.swapaxes(aq, 1, 2), gq, policy)
    if policy.prc_enabled:
        a32 = a.astype(jnp.float32)
        amax = jnp.max(jnp.abs(a32), axis=(1, 2), keepdims=True)
        clipped = jnp.abs(a32) > amax * gamma
        dgamma = jnp.sum(jnp.where(clipped, da * jnp.sign(a32), 0.0) * amax)
        da = jnp.where(clipped, 0.0, da)
        dgamma = dgamma.reshape(gamma.shape).astype(gamma.dtype)
    else:
        dgamma = jnp.zeros_like(gamma)
    return da.astype(a.dtype), dw.astype(jnp.float32), dgamma


_mf_expert_linear.defvjp(_mf_expert_fwd, _mf_expert_bwd)


def mf_expert_linear(
    a: jax.Array,
    w: jax.Array,
    gamma: Optional[jax.Array] = None,
    *,
    policy: QuantPolicy,
) -> jax.Array:
    if not policy.enabled:
        return jax.lax.dot_general(
            a, w.astype(a.dtype), (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,  # batch-invariant FP32
        )
    if gamma is None:
        gamma = jnp.float32(policy.ratio_clip_init or 1.0)
    return _mf_expert_linear(policy, a, w, gamma)


# ---------------------------------------------------------------------------
# mf_act_dot: activation x activation einsum (attention), opt-in extension
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mf_act_dot(policy: QuantPolicy, dn, x, y):
    out, _ = _mf_act_dot_fwd(policy, dn, x, y)
    return out


def _qact(x, bits, axes=None):
    with jax.named_scope("als_potq"):
        x32 = x.astype(jnp.float32)
        return potq.pot_quantize(
            x32, bits, potq.compute_beta(x32, bits, axes)
        ).astype(_BF16)


def _mf_act_dot_fwd(policy, dn, x, y):
    xq = _qact(x, policy.bits_a, _sample_axes(policy, x, None))
    yq = _qact(y, policy.bits_a, _sample_axes(policy, y, None))
    out = jax.lax.dot_general(
        xq.astype(_BF16), yq.astype(_BF16), dn, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    return out, (xq, yq)


def _mf_act_dot_bwd(policy, dn, res, g):
    xq, yq = res
    gq = _qact(g, policy.bits_g)
    # Fall back to autodiff transposition of dot_general on quantized
    # residuals: build the linear fn and transpose it.
    fx = lambda xx: jax.lax.dot_general(xx, yq, dn, preferred_element_type=jnp.float32)
    fy = lambda yy: jax.lax.dot_general(xq, yy, dn, preferred_element_type=jnp.float32)
    dx = jax.linear_transpose(fx, xq)(gq.astype(jnp.float32))[0]
    dy = jax.linear_transpose(fy, yq)(gq.astype(jnp.float32))[0]
    return dx.astype(xq.dtype), dy.astype(yq.dtype)


_mf_act_dot.defvjp(_mf_act_dot_fwd, _mf_act_dot_bwd)


def mf_act_dot(x: jax.Array, y: jax.Array, dn, *, policy: QuantPolicy) -> jax.Array:
    """Quantized activation-by-activation dot_general (attention scores/PV)."""
    if not policy.enabled:
        # Fully-disabled raw-FP32 baseline only (NOT merely
        # quantize_attention=False: the enabled policies get their
        # batch-invariance from bf16-snapped operands and must keep the
        # fused dot — a per-row map would serialize the batch on real
        # hardware and blow up the dryrun cost model at scale).
        (cx, cy), (bx, by) = dn
        if (x.ndim >= 3 and x.shape[-2] == 1 and bx and by
                and bx[0] == 0 and by[0] == 0):
            # Decode-shaped attention (one query row per batch element,
            # both operands batched over axis 0): XLA fuses these dots
            # into the surrounding softmax/mask graph with
            # batch-size-dependent reduction splits, so the last ulps of
            # a row change with the pool size.  Mapping over the batch
            # runs the SAME per-sample program for every batch size —
            # row-independent by construction, like the quantized
            # kernels.  Training/prefill shapes keep the fused dot.
            dn1 = (
                (tuple(c - 1 for c in cx), tuple(c - 1 for c in cy)),
                (tuple(b - 1 for b in bx[1:]), tuple(b - 1 for b in by[1:])),
            )
            out = jax.lax.map(
                lambda xy: jax.lax.dot_general(
                    xy[0], xy[1], dn1, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                ),
                (x, y),
            )
            return out.astype(x.dtype)
        return jax.lax.dot_general(
            x, y, dn, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,  # batch-invariant FP32
        ).astype(x.dtype)
    if not policy.quantize_attention:
        # enabled policy, unquantized attention — seed-exact fused dot
        return jax.lax.dot_general(
            x, y, dn, preferred_element_type=jnp.float32
        ).astype(x.dtype)
    return _mf_act_dot(policy, dn, x, y)


# ---------------------------------------------------------------------------
# mf_conv2d: convolution as im2col + MF-MAC (the paper's CNN linear layers)
# ---------------------------------------------------------------------------


def mf_conv2d(
    x: jax.Array,  # (B, H, W, Cin) NHWC
    w: jax.Array,  # (KH, KW, Cin, Cout)
    gamma: Optional[jax.Array] = None,
    *,
    policy: QuantPolicy,
    stride: int = 1,
    padding: str = "SAME",
    is_last: bool = False,
) -> jax.Array:
    """2D convolution through the quantized MAC path.

    Convolution IS a linear layer in the paper's sense (its Table 2 counts
    conv MACs); im2col turns it into the exact (patches x filters) matmul
    that MF-MAC consumes, with one layer-wise scale for W and one for A —
    identical semantics to quantizing the conv directly.
    """
    kh, kw, cin, cout = w.shape
    patches = jax.lax.conv_general_dilated_patches(
        x,
        (kh, kw),
        (stride, stride),
        padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (B, Ho, Wo, Cin*KH*KW) — patch features are Cin-major
    wm = jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout)
    out = mf_linear(patches, wm, gamma, policy=policy, is_last=is_last)
    return out
