"""bsmm_dw_roofline.train: the block-sparse weight-gradient kernels' share of
their roofline over a retrain step's dw products.

Kernel time is the device time of the window's launches named
``bsmm_dw``.  The least time is each dw product's larger of required
operations over the bf16 peak and required bytes over HBM bandwidth
(``chipbench.work``), over the ticket's own live tiles."""
from chipbench import names


def read(ctx):
    return names.pass_roofline(ctx, "dw")
