"""bsmm_dx_roofline.train: the block-sparse input-gradient kernels' share of
their roofline over a retrain step's dx products.

Kernel time is the device time of the window's launches named
``bsmm_dx``.  The least time is each dx product's larger of required
operations over the bf16 peak and required bytes over HBM bandwidth
(``chipbench.work``), over the ticket's own live tiles."""
from chipbench import names


def read(ctx):
    return names.pass_roofline(ctx, "dx")
