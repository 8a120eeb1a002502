"""bsmm_fwd_roofline.train: the block-sparse forward kernels' share of
their roofline over a retrain step's forward products.

Kernel time is the device time of the window's launches named
``bsmm_fwd`` or ``bsmm_fwd_epilogue``.  The rematerialised forward's
launches are among them: their time counts, their work does not, as in
``bsmm_roofline.train``.  The least time is each forward product's
larger of required operations over the bf16 peak and required bytes
over HBM bandwidth (``chipbench.work``), over the ticket's own live
tiles."""
from chipbench import names


def read(ctx):
    return names.pass_roofline(ctx, "fwd")
