"""Model FLOP utilization of serving: the useful forward FLOPs of the
window's requests (prompt rows and fed tokens against their causal
context, the LM head once per served token; pad rows of the pooled step
not counted), over the window's seconds and the chip's bf16 peak, in %."""


def read(ctx):
    rec, model = ctx["record"], ctx["model"]
    total = sum(model.model_flops_serve_request(ctx["cfg"], p, s)
                for p, s in rec["requests"])
    return 100.0 * total / rec["window_s"] / ctx["peak"]["bf16_flops"]
