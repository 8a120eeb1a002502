"""Declarative Pallas kernel specs — the single source the kernels
build their ``pallas_call`` from AND the static auditor verifies.

Every kernel in this package describes its launch as a ``KernelSpec``:
the grid, the dimension semantics, the scalar-prefetch operands, one
``BlockMap`` per input/output (block shape + the *actual* index-map
callable + the full operand shape/dtype), the VMEM scratch, and a host
mirror of the ``pl.when`` work gate.  The kernel then constructs its
real ``pl.BlockSpec``/scratch list *from the spec* (``pallas_in_specs``
etc.), so the object ``analysis.kernel_audit`` enumerates is byte-for-
byte the object the accelerator executes — there is no second copy of
the index maps to drift.

Index maps are ordinary lambdas over ``(grid ids..., scalar
operands...)``.  Pallas calls them with scalar *refs* during tracing;
the auditor calls them with the concrete numpy scalar operands stored
in ``spec.scalars`` — same code path, two evaluation modes.

This module is deliberately numpy-only at import time (jax/pallas are
imported lazily inside the builder methods) so the analysis layer can
reason about specs without touching device state.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple

import numpy as np

#: scratch roles the auditor knows; accumulator-like roles must be f32
ACCUMULATOR_ROLES = ("accumulator", "softmax_state")


@dataclass(frozen=True)
class BlockMap:
    """One operand's blocking: ``index_map(*grid_ids, *scalars)`` returns
    the block-unit coordinates of the block a grid cell touches."""
    name: str
    block: Tuple[int, ...]          # block shape (elements)
    index_map: Callable[..., Tuple[Any, ...]]
    shape: Tuple[int, ...]          # full operand shape
    dtype: Any                      # anything np.dtype() accepts
    gather: bool = False            # index map reads scalar-prefetch data

    @property
    def itemsize(self) -> int:
        return int(np.dtype(self.dtype).itemsize)

    @property
    def block_bytes(self) -> int:
        return int(np.prod(self.block)) * self.itemsize

    def tile_grid(self) -> Tuple[int, ...]:
        """Operand extent in block units (requires even tiling)."""
        return tuple(s // b for s, b in zip(self.shape, self.block))


@dataclass(frozen=True)
class ScratchSpec:
    """One VMEM scratch buffer and its audit role."""
    shape: Tuple[int, ...]
    dtype: Any
    role: str = "accumulator"       # accumulator | softmax_state | other

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * int(np.dtype(self.dtype).itemsize)


@dataclass(frozen=True)
class KernelSpec:
    """The full launch geometry of one Pallas kernel."""
    name: str
    grid: Tuple[int, ...]
    dims: Tuple[str, ...]           # dimension_semantics per grid axis
    inputs: Tuple[BlockMap, ...]
    outputs: Tuple[BlockMap, ...]
    scratch: Tuple[ScratchSpec, ...] = ()
    # values the kernel body materialises in VMEM beyond its blocks and
    # scratch (an epilogue's f32 intermediates): counted, never allocated
    temporaries: Tuple[ScratchSpec, ...] = ()
    # concrete scalar-prefetch operands, in kernel argument order
    scalars: Tuple[np.ndarray, ...] = ()
    # host mirror of the pl.when work gate: guard(*ids, *scalars) -> bool;
    # None means every grid cell does work
    guard: Optional[Callable[..., bool]] = None
    # MXU flops one unguarded grid cell issues (0 = not modelled)
    cell_flops: float = 0.0
    notes: str = field(default="", compare=False)

    # -- builders: the kernels construct their pallas_call from these ----
    def pallas_in_specs(self):
        from jax.experimental import pallas as pl
        return [pl.BlockSpec(bm.block, bm.index_map) for bm in self.inputs]

    def pallas_out_specs(self):
        from jax.experimental import pallas as pl
        return [pl.BlockSpec(bm.block, bm.index_map) for bm in self.outputs]

    def pallas_scratch(self):
        from jax.experimental.pallas import tpu as pltpu
        return [pltpu.VMEM(s.shape, s.dtype) for s in self.scratch]

    @property
    def num_scalar_prefetch(self) -> int:
        return len(self.scalars)

    # -- audit-facing geometry ------------------------------------------
    def parallel_axes(self) -> Tuple[int, ...]:
        return tuple(d for d, s in enumerate(self.dims) if s == "parallel")

    def vmem_breakdown(self) -> dict:
        """Estimated VMEM residency at the planned block shapes.

        Block operands are double-buffered (Pallas pipelines the next
        block's DMA behind the current compute), scratch and the body's
        temporaries are single:
        ``2·Σ in + 2·Σ out + Σ scratch + Σ temporaries`` bytes.
        """
        ins = sum(bm.block_bytes for bm in self.inputs)
        outs = sum(bm.block_bytes for bm in self.outputs)
        scr = sum(s.nbytes for s in self.scratch)
        tmp = sum(s.nbytes for s in self.temporaries)
        return {"inputs": 2 * ins, "outputs": 2 * outs, "scratch": scr,
                "temporaries": tmp,
                "total": 2 * ins + 2 * outs + scr + tmp}

    def vmem_bytes(self) -> int:
        return self.vmem_breakdown()["total"]


# registry of spec builders, filled by the kernel modules at import time
# (name -> zero-arg callable returning a representative KernelSpec is NOT
# what we store — audit cases need concrete shapes, so kernel_audit owns
# the canonical cases; this registry just names the audited kernels)
AUDITED_KERNELS = (
    "bsmm_fwd", "bsmm_fwd_epilogue", "bsmm_dx", "bsmm_dw",
    "paged_attention_gqa", "paged_attention_mla",
    "flash_attention", "masked_matmul", "tile_stats",
)
