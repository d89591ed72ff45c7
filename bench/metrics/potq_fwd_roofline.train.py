"""Roofline share of the forward MF-MAC kernel in training (the Pallas
``potq_matmul_padded`` calls behind ``ops.pot_value_matmul``): the least
time of every traced call (the larger of its operations over the bf16
peak and its bytes over HBM bandwidth, from its shapes) over their traced
time, in %."""


def read(ctx):
    share = ctx["flops"].kernel_roofline(
        ctx["trace"]["kernels"].get("potq_fwd", []), ctx["vocab_padded"],
        ctx["peak"])
    return None if share is None else share[0]
