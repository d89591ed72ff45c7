"""Roofline share of the fused backward MF-MAC kernels in training (the Pallas
``grad_da_padded`` and ``grad_dw_padded`` calls): the least
time of every traced call (the larger of its operations over the bf16
peak and its bytes over HBM bandwidth, from its shapes) over their traced
time, in %."""


def read(ctx):
    share = ctx["flops"].kernel_roofline(
        ctx["trace"]["kernels"].get("potq_grad", []), ctx["vocab_padded"],
        ctx["peak"])
    return None if share is None else share[0]
