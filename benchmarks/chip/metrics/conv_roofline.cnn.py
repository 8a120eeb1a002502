"""conv_roofline.cnn: the convolutions' share of their roofline over a
CNN retrain step (forward, input and weight gradients).

Convolution time is the device time of the window's output fusions
(HLO ``kind=kOutput``): the form XLA gives a convolution with its fused
epilogue.  The step's only other product, the 512 x 10 head, is one of
them and is counted in the work too.  The least time is each product's
larger of required operations (the ticket's live weights) over the bf16
peak and required bytes (its input and output feature maps once each,
and its live weights) over HBM bandwidth."""
from chipbench import work


def is_conv(event):
    return "kind=kOutput" in event.name


def read(ctx):
    calls = ctx.work.get("conv")
    secs = ctx.op_seconds(is_conv)
    if not calls or secs <= 0:
        return None
    least, bound = work.least_seconds(calls, ctx.peaks["flops_bf16"],
                                      ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.steps / secs, bound
