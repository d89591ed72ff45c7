"""Model FLOP utilization of training: useful forward and backward FLOPs
per token (recomputation not counted) times tokens per second of the
window, over the chip's bf16 peak, in %."""


def read(ctx):
    rec, model = ctx["record"], ctx["model"]
    per_token = model.model_flops_train_per_token(ctx["cfg"],
                                                  ctx["traffic"]["seq"])
    return (100.0 * rec["train_tokens_per_s"] * per_token
            / ctx["peak"]["bf16_flops"])
