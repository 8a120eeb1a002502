"""Readings from the kernel names the program writes into its own trace.

Each Pallas launch carries its ``KernelSpec`` name (``bsmm_fwd``,
``bsmm_fwd_epilogue``, ``bsmm_dx``, ``bsmm_dw``, ...), and these names
are part of the benchmark's contract.  JAX prefixes the transformations
it traced the launch under (``jvp_bsmm_fwd_``,
``transpose_jvp_bsmm_dw__``), so a kind is a whole ``_``-separated word
of the op's name.

A reading is ``None`` where the window holds none of its names, as in a
trace of a program that does not write them: the metric is then left
out, not read as 0.
"""
from __future__ import annotations

import re
from typing import Optional

from chipbench import work, xplane

# the bsmm KernelSpecs' names, kept here so that a program without them
# reads nothing
BSMM_PASS_OF = {"bsmm_fwd": "fwd", "bsmm_fwd_epilogue": "fwd",
                "bsmm_dx": "dx", "bsmm_dw": "dw"}
# ``work.lm_train_step`` lists the products of each projection and layer
# as ``work.product`` gives them: forward, dx, dw
PASS_ORDER = ("fwd", "dx", "dw")

_KIND = re.compile(r"(?:^|_)(%s)(?:_|$)" % "|".join(
    sorted(BSMM_PASS_OF, key=len, reverse=True)))


def bsmm_pass(event) -> Optional[str]:
    """The pass (``fwd``, ``dx``, ``dw``) whose kernel ``event`` is, if
    it is a named ``bsmm`` launch."""
    m = _KIND.search(xplane.op_name(event.name))
    return BSMM_PASS_OF[m.group(1)] if m else None


def pass_roofline(ctx, which: str):
    """The ``which`` pass's share of its roofline: the least time of its
    products (the ticket's live tiles) over the device time of its named
    launches, with the bound that holds for most of it.  The forward's
    time includes the rematerialised forward; its work does not."""
    calls = ctx.work.get("bsmm")
    secs = ctx.op_seconds(lambda e: bsmm_pass(e) == which)
    if not calls or secs <= 0:
        return None
    own = calls[PASS_ORDER.index(which)::len(PASS_ORDER)]
    least, bound = work.least_seconds(own, ctx.peaks["flops_bf16"],
                                      ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.steps / secs, bound
