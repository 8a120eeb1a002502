"""bsmm_roofline.train: the block-sparse matmul kernels' share of their
roofline over a retrain step's forward, dx and dw products.

Kernel time is the device time of the window's Pallas kernels: ops whose
HLO is a ``tpu_custom_call``.  The program launches them without a name
(their ``kernel_metadata`` is empty), and in the retrain step every
Pallas kernel is a ``bsmm`` launch: forward (twice, with the
rematerialised forward), dx and dw for each routed projection.  The
least time is each product's larger of required operations over the
bf16 peak and required bytes over HBM bandwidth (``chipbench.work``),
over the ticket's own live tiles."""
from chipbench import work


def is_kernel(event):
    return 'custom_call_target="tpu_custom_call"' in event.name


def read(ctx):
    calls = ctx.work.get("bsmm")
    secs = ctx.op_seconds(is_kernel)
    if not calls or secs <= 0:
        return None
    least, bound = work.least_seconds(calls, ctx.peaks["flops_bf16"],
                                      ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.steps / secs, bound
