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

A model's useful FLOPs depend on its architecture and are its model
module's (``bench/lib/model.py``).
"""
from __future__ import annotations

from typing import Tuple


def matmul_cost(m: int, k: int, n: int, bits_a: float, bits_b: float,
                out_bytes: float = 2.0) -> Tuple[float, float]:
    ops = 2.0 * m * k * n
    nbytes = m * k * bits_a / 8 + k * n * bits_b / 8 + m * n * out_bytes
    return ops, nbytes


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
