"""train_mfu.lm: the operations a decoder ticket's retrain step
requires (live tiles of every routed projection, the dense head and
causal attention, forward and backward, no recomputation), times the
steps of the traced window, over the window and the chip's bf16 peak.
The bound is FLOPs."""


def read(ctx):
    flops = ctx.work.get("flops")
    if not flops or ctx.window_s <= 0 or ctx.steps <= 0:
        return None
    return 100.0 * flops * ctx.steps / (ctx.window_s
                                        * ctx.peaks["flops_bf16"])
