"""Operations and bytes, from shapes alone.

* ``matmul_cost``: one (M, K) x (K, N) product.  Operations are 2MKN.
  ``kernel_call_cost`` applies it to one traced MF-MAC kernel call, from
  the shapes the call was made with, and ``kernel_roofline`` sums a
  family's calls against their traced time.
  Bytes count the same work whatever implements it: PoT operands at their
  code width (5 bits for weights, activations and gradients, 6 for the LM
  head's gradient), float operands at their own width, outputs at bf16.
  So no implementation can read above its roofline, one that streams int8
  or nibble codes included.  (The arithmetic of ``benchmarks/kernelbench.py``
  counted operations and intensity the same way, at 2 bytes an operand.)
* ``model_flops_*``: the useful FLOPs of the model, as in
  ``benchmarks/roofline.py`` (2 per weight per token forward, 6 with the
  backward), written out per matmul so the LM head counts the real
  vocabulary and causal attention its real context.  Recomputation is not
  counted.
"""
from __future__ import annotations

from typing import Tuple


def matmul_cost(m: int, k: int, n: int, bits_a: float, bits_b: float,
                out_bytes: float = 2.0) -> Tuple[float, float]:
    ops = 2.0 * m * k * n
    nbytes = m * k * bits_a / 8 + k * n * bits_b / 8 + m * n * out_bytes
    return ops, nbytes


def linear_shapes(cfg: dict):
    """(name, K, N) of each linear of one layer."""
    d, hd, f = cfg["d_model"], cfg["head_dim"], cfg["d_ff"]
    h, kv = cfg["n_heads"], cfg["kv_heads"]
    out = [("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
           ("wo", h * hd, d)]
    if cfg["act"] == "swiglu":
        out += [("wi_gate", d, f), ("wi_up", d, f)]
    else:
        out += [("wi", d, f)]
    return out + [("mlp_wo", f, d)]


def layer_flops_per_row(cfg: dict) -> float:
    return sum(2.0 * k * n for _, k, n in linear_shapes(cfg))


def attn_flops(cfg: dict, context: float) -> float:
    """QK^T and PV of one query row against ``context`` keys."""
    return 4.0 * cfg["n_heads"] * cfg["head_dim"] * context


def head_flops_per_row(cfg: dict) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab"]


def model_flops_train_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (3x forward) per token of a causal sequence."""
    fwd = (cfg["n_layers"] * (layer_flops_per_row(cfg)
                              + attn_flops(cfg, (seq + 1) / 2))
           + head_flops_per_row(cfg))
    return 3.0 * fwd


def model_flops_serve_request(cfg: dict, prompt: int, served: int) -> float:
    """Useful forward FLOPs of one request: every prompt row and every fed
    token (the last served token is never fed) through the layers, each
    against its causal context, and the LM head once per served token."""
    rows = prompt + served - 1
    total_ctx = rows * (rows + 1) / 2  # sum of (position + 1)
    return (cfg["n_layers"] * (rows * layer_flops_per_row(cfg)
                               + attn_flops(cfg, 1.0) * total_ctx)
            + served * head_flops_per_row(cfg))


def kernel_call_cost(kind: str, m: int, k: int, n: int,
                     vocab_padded: int) -> Tuple[float, float]:
    """Operations and bytes of one MF-MAC kernel call of shape (M, K, N):
    ``fwd`` (M, K) x (K, N); ``da`` dA = Gq Wq^T (M, K) from G (M, N),
    with the PRC clip mask at 1 bit an input; ``dw`` dW = Aq^T Gq (K, N).
    A gradient whose N is the padded vocabulary is the LM head's, at 6
    bits."""
    bits_g = 6 if n == vocab_padded else 5
    if kind == "fwd":
        return matmul_cost(m, k, n, 5, 5)
    if kind == "da":
        ops, nbytes = matmul_cost(m, n, k, bits_g, 5)
        return ops, nbytes + m * k / 8
    if kind == "dw":
        return matmul_cost(k, m, n, 5, bits_g)
    raise ValueError(kind)


def kernel_roofline(calls, vocab_padded: int, peak: dict):
    """Share (%) of a kernel family's traced time that its calls would take
    at the roofline, and the bound that sets most of it; None if the trace
    holds no call.  ``calls``: [(kind, M, K, N, seconds)]."""
    least = {"compute": 0.0, "memory": 0.0}
    seconds = 0.0
    for kind, m, k, n, dur in calls:
        ops, nbytes = kernel_call_cost(kind, m, k, n, vocab_padded)
        t_c, t_m = ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
        least["compute" if t_c >= t_m else "memory"] += max(t_c, t_m)
        seconds += dur
    if not calls or seconds <= 0:
        return None
    return (100.0 * sum(least.values()) / seconds,
            max(least, key=least.get))
