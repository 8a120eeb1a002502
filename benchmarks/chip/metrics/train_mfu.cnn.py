"""train_mfu.cnn: the operations a CNN ticket's retrain step
requires (the live weights of every convolution as im2col products
and the head, forward and backward), times the
steps of the traced window, over the window and the chip's bf16 peak.
The bound is FLOPs."""


def read(ctx):
    flops = ctx.work.get("flops")
    if not flops or ctx.window_s <= 0 or ctx.steps <= 0:
        return None
    return 100.0 * flops * ctx.steps / (ctx.window_s
                                        * ctx.peaks["flops_bf16"])
