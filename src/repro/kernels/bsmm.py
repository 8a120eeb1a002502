"""Block-sparse matmul Pallas TPU kernel — the paper's "turned-off
crossbar" realised on the MXU.

A crossbar whose rows/cols are all zero can be power-gated (paper
Fig. 2); the TPU analogue is a 128×128 weight tile that is never DMA'd
HBM→VMEM and never issued to the MXU.  The kernel gets, per output tile
column j, a *compacted* list of live K-tile indices (scalar-prefetched,
so index maps can steer the DMA engine):

    grid = (M/bm, N/bn, KMAX)            KMAX = max_j nnz_k(j)
    x block   (bm, bk) at (i, idx[j,k])  ← skips dead K tiles entirely
    w block   (bk, bn) at (idx[j,k], j)
    out block (bm, bn) at (i, j), f32 VMEM accumulator

Slots beyond a column's live count are masked with ``pl.when`` and
repeat the column's last live index, so their block index does not
change and the pipeline fetches nothing for them.  Compute and
bandwidth both scale with the *live tile count* — the paper's hardware
savings, as FLOP/byte savings.

The row block ``bm`` is as tall as VMEM allows (``row_block``): a
retrain batch of a few thousand rows is one row block, so each grid
step issues a (bm, bk) × (bk, bn) product rather than one 128³ tile,
and the fixed cost of a grid step is paid once per live tile.

The mask is static at compile time (pruning is a one-time offline step,
paper §V.C), so the compacted indices are baked in as constants.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import MXU_TILE, vmem_budget
from repro.kernels.spec import BlockMap, KernelSpec, ScratchSpec
from repro.models import hooks


class GeometryError(ValueError):
    """A mask/weight shape disagrees with the tile/crossbar geometry.

    Raised where the disagreement is detected (plan construction, plan
    application) instead of surfacing later as an opaque index error
    deep inside a Pallas grid.  Carries the offending ``shape``, the
    ``tile`` edge, and a ``where`` location so lint findings and
    tracebacks can name the exact projection.
    """

    def __init__(self, reason: str, *, shape=None, tile=None, where=""):
        self.reason = reason
        self.shape = None if shape is None else tuple(shape)
        self.tile = tile
        self.where = where
        parts = [reason]
        if shape is not None:
            parts.append(f"shape={self.shape}")
        if tile is not None:
            parts.append(f"tile={tile}")
        if where:
            parts.append(f"at {where}")
        super().__init__(" | ".join(parts))


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit bool wins;
    ``None`` emulates the Pallas kernels everywhere except on a real TPU
    backend (interpret mode is a correctness path, not a fast path).
    Every kernel entry point and plan builder resolves through here, so
    a caller that passes nothing runs the compiled kernel on the chip."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def launch(kernel, *args):
    """Call a Pallas kernel.  Under an installed multi-device mesh
    (``distributed.sharding.install``) the call runs inside a
    ``shard_map`` that replicates every operand: the TPU compiler
    cannot partition a Mosaic kernel, so each device runs the whole
    launch on gathered operands and GSPMD inserts the gathers."""
    mesh = hooks.kernel_mesh()
    if mesh is None:
        return kernel(*args)
    rep = jax.sharding.PartitionSpec()
    return jax.shard_map(kernel, mesh=mesh, in_specs=(rep,) * len(args),
                         out_specs=rep, check_vma=False)(*args)


def tile_bitmap(mask: np.ndarray, bk: int = MXU_TILE,
                bn: int = MXU_TILE) -> np.ndarray:
    """Elementwise {0,1} mask (K, N) → tile liveness (⌈K/bk⌉, ⌈N/bn⌉)."""
    m = np.asarray(mask) != 0
    K, N = m.shape
    pk, pn = (-K) % bk, (-N) % bn
    if pk or pn:
        m = np.pad(m, ((0, pk), (0, pn)))
    return m.reshape(m.shape[0] // bk, bk, m.shape[1] // bn, bn) \
            .any(axis=(1, 3)).astype(np.int32)


def compact_tile_indices(tile_mask: np.ndarray) -> Tuple[np.ndarray,
                                                         np.ndarray, int]:
    """Per column j of the (Kt, Nt) tile mask: live k indices + counts.

    Returns (idx (Nt, KMAX) int32, count (Nt,) int32, KMAX).
    Dead slots (masked in-kernel) repeat the column's last live index,
    or tile 0 for an empty column: a valid DMA target whose block index
    equals the previous step's, so the pipeline skips the copy.
    """
    tm = np.asarray(tile_mask) != 0
    Kt, Nt = tm.shape
    counts = tm.sum(axis=0).astype(np.int32)
    kmax = max(int(counts.max()) if Nt else 0, 1)
    idx = np.zeros((Nt, kmax), np.int32)
    for j in range(Nt):
        live = np.nonzero(tm[:, j])[0]
        if len(live):
            idx[j, : len(live)] = live
            idx[j, len(live):] = live[-1]
    return idx, counts, kmax


# Epilogue activations the flush can apply in-register (f32 accumulator
# → act → output dtype, one pass over the output instead of two)
_EPILOGUE_ACTS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _epilogue(z, act: Optional[str]):
    if act is None:
        return z
    if act not in _EPILOGUE_ACTS:
        raise ValueError(f"unsupported epilogue act {act!r}; "
                         f"known: {sorted(_EPILOGUE_ACTS)}")
    return _EPILOGUE_ACTS[act](z)


def _bsmm_kernel(count_ref, idx_ref, x_ref, w_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < count_ref[j])
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _bsmm_epilogue_kernel(count_ref, idx_ref, x_ref, w_ref, b_ref, o_ref,
                          acc_ref, *, act: Optional[str]):
    """``_bsmm_kernel`` with the bias+activation epilogue fused into the
    flush: the f32 accumulator gets ``+ b`` and the activation while it
    is still in VMEM, saving the extra HBM round-trip a separate
    bias/act pass would cost."""
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < count_ref[j])
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        z = acc_ref[...] + b_ref[...].astype(jnp.float32)
        o_ref[...] = _epilogue(z, act).astype(o_ref.dtype)


def bsmm_pallas(x, w, tile_mask: np.ndarray, *, bm: int = MXU_TILE,
                bk: int = MXU_TILE, bn: int = MXU_TILE,
                interpret: Optional[bool] = None):
    """x: (M, K) @ block-sparse w: (K, N) → (M, N).

    ``tile_mask``: host numpy (⌈K/bk⌉, ⌈N/bn⌉) — static sparsity.
    ``interpret``: see ``default_interpret``.
    """
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise GeometryError("x/w contraction dims disagree",
                            shape=(K, K2), where="bsmm_pallas")
    if M % bm or K % bk or N % bn:
        raise GeometryError(f"shapes must tile {(bm, bk, bn)}",
                            shape=(M, K, N), where="bsmm_pallas")
    idx, counts, kmax = compact_tile_indices(tile_mask)
    assert idx.shape[0] == N // bn and tile_mask.shape[0] == K // bk
    return _bsmm_compact(x, w, idx, counts, kmax, bm=bm, bk=bk, bn=bn,
                         interpret=default_interpret(interpret))


def bsmm_fwd_spec(idx, counts, kmax: int, *, M: int, K: int, N: int,
                  bm: int, bk: int, bn: int, dtype=jnp.float32,
                  fused: bool = False) -> KernelSpec:
    """Launch geometry of the forward bsmm (optionally with the fused
    bias epilogue).  The returned spec's index maps ARE the ones the
    ``pallas_call`` executes — ``_bsmm_compact`` builds from it."""
    idx = np.asarray(idx, np.int32)
    counts = np.asarray(counts, np.int32)
    inputs = [
        BlockMap("x", (bm, bk),
                 lambda i, j, k, cnt, idx: (i, idx[j, k]),
                 (M, K), dtype, gather=True),
        BlockMap("w", (bk, bn),
                 lambda i, j, k, cnt, idx: (idx[j, k], j),
                 (K, N), dtype, gather=True),
    ]
    if fused:
        inputs.append(BlockMap("bias", (1, bn),
                               lambda i, j, k, cnt, idx: (0, j),
                               (1, N), dtype))
    return KernelSpec(
        name="bsmm_fwd_epilogue" if fused else "bsmm_fwd",
        grid=(M // bm, N // bn, kmax),
        dims=("parallel", "parallel", "arbitrary"),
        inputs=tuple(inputs),
        outputs=(BlockMap("out", (bm, bn),
                          lambda i, j, k, cnt, idx: (i, j),
                          (M, N), dtype),),
        scratch=(ScratchSpec((bm, bn), jnp.float32, "accumulator"),),
        # the flush's f32 ``acc + b`` and the activation's intermediate
        temporaries=(ScratchSpec((2, bm, bn), jnp.float32, "other"),)
        if fused else (),
        scalars=(counts, idx),
        guard=lambda i, j, k, cnt, idx: bool(k < cnt[j]),
        cell_flops=2.0 * bm * bk * bn,
        notes="live K-tile accumulation per output column",
    )


def _bsmm_compact(x, w, idx, counts, kmax: int, *, bm: int, bk: int,
                  bn: int, interpret: bool, bias=None,
                  act: Optional[str] = None):
    M, K = x.shape
    N = w.shape[1]
    fused = bias is not None or act is not None
    spec = bsmm_fwd_spec(idx, counts, kmax, M=M, K=K, N=N, bm=bm, bk=bk,
                         bn=bn, dtype=x.dtype, fused=fused)
    body = functools.partial(_bsmm_epilogue_kernel, act=act) if fused \
        else _bsmm_kernel
    kernel = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=spec.num_scalar_prefetch,
            grid=spec.grid,
            in_specs=spec.pallas_in_specs(),
            out_specs=spec.pallas_out_specs()[0],
            scratch_shapes=spec.pallas_scratch(),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=spec.dims),
        interpret=interpret,
        name=spec.name,
    )
    if fused:
        b = jnp.zeros((1, N), x.dtype) if bias is None \
            else jnp.asarray(bias).reshape(1, N)
        return launch(kernel, jnp.asarray(counts), jnp.asarray(idx), x, w,
                      b)
    return launch(kernel, jnp.asarray(counts), jnp.asarray(idx), x, w)


# ---------------------------------------------------------------------------
# Tile plans: precompiled sparsity metadata for serving-time matmuls
# ---------------------------------------------------------------------------
class TilePlan(NamedTuple):
    """Static bsmm dispatch data for one pruned (K, N) weight.

    Built once offline from the pruning masks (``make_tile_plan``);
    closed over by the jitted decode/train step so the compacted indices
    are compile-time constants, exactly like the crossbar bitstream the
    paper bakes into the ReRAM controller.

    The forward plan (``idx``/``counts``/``kmax``) steers ``out = x @ w``
    skipping dead K tiles.  The *transposed* plan (``idx_t``/``counts_t``
    /``nmax``) steers the backward ``dx = g @ wᵀ`` the same way along N,
    and the flat live-tile coordinates (``kk``/``nn``) let the ``dw``
    kernel materialise only live (bk, bn) tiles — dead-tile weight grads
    are identically zero because the mask is static.
    """
    idx: np.ndarray         # (Nt, KMAX) int32 — live K-tile ids per column
    counts: np.ndarray      # (Nt,) int32
    kmax: int
    tile: int               # square tile edge (the MXU/crossbar 128)
    live_tiles: int
    total_tiles: int
    interpret: bool = False  # resolved by make_tile_plan
    idx_t: Optional[np.ndarray] = None    # (Kt, NMAX) live N-tile ids per row
    counts_t: Optional[np.ndarray] = None  # (Kt,)
    nmax: int = 1
    kk: Optional[np.ndarray] = None       # (L,) K-tile id of each live tile
    nn: Optional[np.ndarray] = None       # (L,) N-tile id of each live tile


def make_tile_plan(mask: np.ndarray, *, tile: int = MXU_TILE,
                   interpret: Optional[bool] = None,
                   strict: bool = False,
                   where: str = "make_tile_plan") -> Optional[TilePlan]:
    """Elementwise {0,1} mask (K, N) → ``TilePlan``.

    A shape that does not tile evenly returns ``None`` (the caller's
    dense fallback) — or, with ``strict=True``, raises a structured
    ``GeometryError`` naming the shape/tile/location, for callers that
    expect the geometry to hold (lint, tests, TPU launches).  An
    invalid ``tile`` always raises.  ``interpret`` is resolved here
    (``default_interpret``) and fixed in the plan.
    """
    if tile <= 0:
        raise GeometryError(f"tile edge must be positive, got {tile}",
                            tile=tile, where=where)
    m = np.asarray(mask)
    if m.ndim != 2:
        if strict:
            raise GeometryError("mask must be 2-D to tile",
                                shape=m.shape, tile=tile, where=where)
        return None
    K, N = m.shape
    if K == 0 or N == 0 or K % tile or N % tile:
        if strict:
            raise GeometryError("mask shape does not tile evenly",
                                shape=m.shape, tile=tile, where=where)
        return None
    bitmap = tile_bitmap(m, tile, tile)
    idx, counts, kmax = compact_tile_indices(bitmap)
    idx_t, counts_t, nmax = compact_tile_indices(bitmap.T)
    kk, nn = np.nonzero(bitmap)
    return TilePlan(idx=idx, counts=counts, kmax=kmax, tile=tile,
                    live_tiles=int(bitmap.sum()),
                    total_tiles=int(bitmap.size),
                    interpret=default_interpret(interpret),
                    idx_t=idx_t, counts_t=counts_t, nmax=nmax,
                    kk=kk.astype(np.int32), nn=nn.astype(np.int32))


# ---------------------------------------------------------------------------
# Backward kernels: dx via the transposed plan, dw over live tiles only
# ---------------------------------------------------------------------------
def _bsmm_dx_kernel(count_ref, idx_ref, g_ref, w_ref, o_ref, acc_ref):
    """dx[i, k] = Σ_n g[i, n] @ w[k, n]ᵀ over live N tiles of K-row k."""
    k = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < count_ref[k])
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            g_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bsmm_dx_spec(idx_t, counts_t, nmax: int, *, M: int, K: int, N: int,
                 bm: int, tile: int, dtype=jnp.float32) -> KernelSpec:
    """Launch geometry of the dx backward: the transposed plan steers
    ``g @ wᵀ`` over live N tiles of each K-row."""
    idx_t = np.asarray(idx_t, np.int32)
    counts_t = np.asarray(counts_t, np.int32)
    bk = bn = tile
    return KernelSpec(
        name="bsmm_dx",
        grid=(M // bm, K // bk, nmax),
        dims=("parallel", "parallel", "arbitrary"),
        inputs=(
            BlockMap("g", (bm, bn),
                     lambda i, k, t, cnt, idx: (i, idx[k, t]),
                     (M, N), dtype, gather=True),
            BlockMap("w", (bk, bn),
                     lambda i, k, t, cnt, idx: (k, idx[k, t]),
                     (K, N), dtype, gather=True),
        ),
        outputs=(BlockMap("dx", (bm, bk),
                          lambda i, k, t, cnt, idx: (i, k),
                          (M, K), dtype),),
        scratch=(ScratchSpec((bm, bk), jnp.float32, "accumulator"),),
        scalars=(counts_t, idx_t),
        guard=lambda i, k, t, cnt, idx: bool(t < cnt[k]),
        cell_flops=2.0 * bm * bk * bn,
        notes="transposed plan: live N-tile accumulation per K-row",
    )


def _bsmm_dx(g, w, plan: TilePlan, *, bm: int):
    """g (M, N) @ (w ⊙ bitmap)ᵀ → (M, K), skipping dead N tiles.

    The grid's last dimension is ``nmax`` = max live N-tiles per K-row
    (the transposed analogue of the forward ``kmax``), so backward
    input-grad compute scales with live tiles exactly like the forward.
    """
    M, N = g.shape
    K = w.shape[0]
    spec = bsmm_dx_spec(plan.idx_t, plan.counts_t, plan.nmax, M=M, K=K,
                        N=N, bm=bm, tile=plan.tile, dtype=g.dtype)
    kernel = pl.pallas_call(
        _bsmm_dx_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=spec.num_scalar_prefetch,
            grid=spec.grid,
            in_specs=spec.pallas_in_specs(),
            out_specs=spec.pallas_out_specs()[0],
            scratch_shapes=spec.pallas_scratch(),
        ),
        out_shape=jax.ShapeDtypeStruct((M, K), g.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=spec.dims),
        interpret=plan.interpret,
        name=spec.name,
    )
    return launch(kernel, jnp.asarray(plan.counts_t),
                  jnp.asarray(plan.idx_t), g, w)


def _bsmm_dw_kernel(kk_ref, nn_ref, x_ref, g_ref, o_ref, acc_ref):
    """dw tile l = Σ_m x[m, kk[l]]ᵀ @ g[m, nn[l]] — live tiles only."""
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(m == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)[None]


def bsmm_dw_spec(kk, nn, *, M: int, K: int, N: int, bm: int, tile: int,
                 dtype=jnp.float32) -> KernelSpec:
    """Launch geometry of the dw backward: grid (L, M/bm) over the flat
    live-tile coordinates — no guard, every cell is live by
    construction (dead tiles are never in ``kk``/``nn``)."""
    kk = np.asarray(kk, np.int32)
    nn = np.asarray(nn, np.int32)
    bk = bn = tile
    L = int(kk.shape[0])
    return KernelSpec(
        name="bsmm_dw",
        grid=(L, M // bm),
        dims=("parallel", "arbitrary"),
        inputs=(
            BlockMap("x", (bm, bk),
                     lambda l, m, kk, nn: (m, kk[l]),
                     (M, K), dtype, gather=True),
            BlockMap("g", (bm, bn),
                     lambda l, m, kk, nn: (m, nn[l]),
                     (M, N), dtype, gather=True),
        ),
        outputs=(BlockMap("dw_tiles", (1, bk, bn),
                          lambda l, m, kk, nn: (l, 0, 0),
                          (L, bk, bn), dtype),),
        scratch=(ScratchSpec((bk, bn), jnp.float32, "accumulator"),),
        scalars=(kk, nn),
        guard=None,
        cell_flops=2.0 * bm * bk * bn,
        notes="live (bk, bn) grad tiles only; scattered to dense after",
    )


def _bsmm_dw(x2, g, plan: TilePlan, *, bm: int, out_dtype):
    """xᵀ (K, M) @ g (M, N) → (K, N), materialising ONLY live tiles.

    The grid is (L, M/bm) with L = live-tile count: dead tiles are never
    DMA'd and never issued to the MXU (their grads are identically zero
    under a static mask).  The compacted (L, bk, bn) tile stack is then
    scattered into the dense (K, N) grad — live-tile bandwidth only.
    """
    M, K = x2.shape
    N = g.shape[1]
    bk = bn = plan.tile
    Kt, Nt = K // bk, N // bn
    L = int(plan.kk.shape[0])
    if L == 0:
        return jnp.zeros((K, N), out_dtype)
    spec = bsmm_dw_spec(plan.kk, plan.nn, M=M, K=K, N=N, bm=bm,
                        tile=plan.tile, dtype=out_dtype)
    kernel = pl.pallas_call(
        _bsmm_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=spec.num_scalar_prefetch,
            grid=spec.grid,
            in_specs=spec.pallas_in_specs(),
            out_specs=spec.pallas_out_specs()[0],
            scratch_shapes=spec.pallas_scratch(),
        ),
        out_shape=jax.ShapeDtypeStruct((L, bk, bn), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=spec.dims),
        interpret=plan.interpret,
        name=spec.name,
    )
    tiles = launch(kernel, jnp.asarray(plan.kk), jnp.asarray(plan.nn), x2, g)
    dw = jnp.zeros((Kt, Nt, bk, bn), out_dtype)
    dw = dw.at[jnp.asarray(plan.kk), jnp.asarray(plan.nn)].set(tiles)
    return dw.transpose(0, 2, 1, 3).reshape(K, N)


def bsmm_apply(x2, w, plan: TilePlan, *, bm: int, bias=None,
               act: Optional[str] = None):
    """Differentiable ``x2 (M, K) @ (w ⊙ tile-bitmap) (K, N)``.

    Forward AND both backward matmuls run through block-sparse Pallas
    kernels, so a retrain step's cost scales with the live-tile count in
    every pass — the paper's "pruning makes training faster" claim on
    the MXU.  The VJP is exact for the tile-masked product: ``dw`` is
    zero on dead tiles (never computed); callers that also carry an
    elementwise mask (``ops.sparse_dense``) recover the elementwise
    gradient through the chain rule of ``w * mask``.

    ``bias``/``act`` fuse a ``+ b`` / activation epilogue into the
    kernel flush (one pass over the output instead of two).  The
    backward recomputes the pre-activation block-sparsely — nothing
    dense sneaks in — and returns ``db = dz.sum(0)`` alongside dx/dw.
    """
    if plan.idx_t is None or plan.kk is None:
        raise ValueError("TilePlan lacks backward metadata — rebuild it "
                         "with make_tile_plan()")

    if bias is None and act is None:
        @jax.custom_vjp
        def f(x2, w):
            return _bsmm_compact(x2, w, plan.idx, plan.counts, plan.kmax,
                                 bm=bm, bk=plan.tile, bn=plan.tile,
                                 interpret=plan.interpret)

        def f_fwd(x2, w):
            return f(x2, w), (x2, w)

        def f_bwd(res, g):
            x2, w = res
            dx = _bsmm_dx(g, w, plan, bm=bm).astype(x2.dtype)
            dw = _bsmm_dw(x2, g, plan, bm=bm, out_dtype=w.dtype)
            return dx, dw

        f.defvjp(f_fwd, f_bwd)
        return f(x2, w)

    if act is not None and act not in _EPILOGUE_ACTS:
        raise ValueError(f"unsupported epilogue act {act!r}; "
                         f"known: {sorted(_EPILOGUE_ACTS)}")
    N = plan.counts.shape[0] * plan.tile
    b = jnp.zeros((N,), x2.dtype) if bias is None \
        else jnp.asarray(bias).reshape(N)

    def _compact(x2, w, b, a):
        return _bsmm_compact(x2, w, plan.idx, plan.counts, plan.kmax,
                             bm=bm, bk=plan.tile, bn=plan.tile,
                             interpret=plan.interpret, bias=b, act=a)

    @jax.custom_vjp
    def f(x2, w, b):
        return _compact(x2, w, b, act)

    def f_fwd(x2, w, b):
        return f(x2, w, b), (x2, w, b)

    def f_bwd(res, g):
        x2, w, b = res
        if act is None:
            dz = g
        else:
            # recompute the pre-activation block-sparsely, then pull the
            # cotangent through the activation alone
            z = _compact(x2, w, b, None)
            dz = jax.vjp(_EPILOGUE_ACTS[act], z)[1](g)[0]
        dx = _bsmm_dx(dz, w, plan, bm=bm).astype(x2.dtype)
        dw = _bsmm_dw(x2, dz, plan, bm=bm, out_dtype=w.dtype)
        db = dz.sum(0).astype(b.dtype)
        return dx, dw, db

    f.defvjp(f_fwd, f_bwd)
    return f(x2, w, b)


def row_block_fits(bm: int, dtype, tile: int = MXU_TILE) -> bool:
    """Whether the forward, fused forward, dx and dw launches at row
    block ``bm`` each fit ``vmem_budget("tpu")`` by their own specs'
    ``vmem_breakdown`` (blocks, accumulator, the epilogue's f32
    temporaries)."""
    one, idx = np.ones(1, np.int32), np.zeros((1, 1), np.int32)
    geo = dict(M=bm, K=tile, N=tile, bm=bm, dtype=dtype)
    specs = (
        bsmm_fwd_spec(idx, one, 1, bk=tile, bn=tile, **geo),
        bsmm_fwd_spec(idx, one, 1, bk=tile, bn=tile, fused=True, **geo),
        bsmm_dx_spec(idx, one, 1, tile=tile, **geo),
        bsmm_dw_spec(idx[0], idx[0], tile=tile, **geo),
    )
    return all(s.vmem_bytes() <= vmem_budget("tpu") for s in specs)


@functools.lru_cache(maxsize=None)
def row_block(M: int, dtype, tile: int = MXU_TILE) -> Tuple[int, int]:
    """Rows ``M`` of a routed matmul → (padded rows Mp, row block bm).

    M pads to a sublane multiple (8); below one tile that is the whole
    block, so a decode batch of a few slots stays one small block.  From
    one tile up Mp pads to a tile multiple, and bm is the largest tile
    multiple that divides Mp and ``row_block_fits``.  A grid step then
    issues a (bm, tile) × (tile, tile) product, and the fixed cost of a
    step is paid once per live tile rather than once per live tile and
    128-row block.
    """
    Mp = M + (-M % 8)
    if Mp < tile:
        return Mp, Mp
    Mp += -Mp % tile
    n = Mp // tile
    for d in range(n, 0, -1):
        if n % d == 0 and row_block_fits(d * tile, dtype, tile):
            return Mp, d * tile
    return Mp, tile


def plan_matmul(x, w, plan: Optional[TilePlan], bias=None,
                act: Optional[str] = None):
    """x (..., K) @ w (K, N) routed through the block-sparse kernel.

    ``plan=None`` is the dense path.  Rows are zero-padded and blocked
    by ``row_block``: a decode batch of a few slots is one sublane-
    padded block; a retrain or prefill batch pads to a tile multiple
    and runs in as few row blocks as VMEM allows (one, for a few
    thousand rows), so compute/bandwidth still scales with the live-tile
    count along K — the dimension pruning actually thins.
    Differentiable: gradients flow through the custom-VJP block-sparse
    backward kernels (``bsmm_apply``).

    ``bias``/``act`` fuse the bias-add and activation into the kernel's
    flush (``bsmm_apply`` epilogue); the dense fallback applies them
    unfused for bit-compatible semantics.
    """
    if plan is None:
        out = x @ w
        if bias is not None:
            out = out + bias
        return _epilogue(out, act)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    # a stale or mis-routed plan would otherwise fail far downstream as
    # an opaque Pallas grid/index error — name the disagreement here
    planK = plan.counts_t.shape[0] * plan.tile \
        if plan.counts_t is not None else None
    planN = plan.counts.shape[0] * plan.tile
    if w.shape[-2] != K:
        raise GeometryError("x/w contraction dims disagree",
                            shape=(K, w.shape[-2]), where="plan_matmul")
    if N != planN or (planK is not None and K != planK):
        raise GeometryError(
            f"TilePlan covers ({planK}, {planN}) but the weight is "
            f"({K}, {N}) — plan built from different masks?",
            shape=(K, N), tile=plan.tile, where="plan_matmul")
    M = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(M, K)
    Mp, bm = row_block(M, jnp.promote_types(x.dtype, w.dtype), plan.tile)
    mp = Mp - M
    if mp:
        x2 = jnp.pad(x2, ((0, mp), (0, 0)))
    # padded rows come out as act(bias) garbage; they are sliced off below
    out = bsmm_apply(x2, w, plan, bm=bm, bias=bias, act=act)
    if mp:
        out = out[:M]
    return out.reshape(*lead, N)


def _masked_kernel(x_ref, w_ref, m_ref, o_ref, acc_ref):
    """Dense-grid variant: every tile DMA'd, dead tiles skip the MXU.

    This models LTP's crossbar-UNAWARE sparsity on TPU: bytes still
    move (no bandwidth saved) even when compute is skipped — the
    kernel-level version of the paper's Fig. 2 argument.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.any(m_ref[...] != 0))
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[...],
                                w_ref[...] * m_ref[...].astype(w_ref.dtype),
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def masked_matmul_spec(*, M: int, K: int, N: int, bm: int, bk: int,
                       bn: int, dtype=jnp.float32) -> KernelSpec:
    """Launch geometry of the dense-grid masked matmul.  The MXU skip
    is data-dependent (``jnp.any(mask block)``) so the spec carries no
    host guard — every block is DMA'd, which is exactly the LTP
    crossbar-unaware point this kernel exists to demonstrate."""
    return KernelSpec(
        name="masked_matmul",
        grid=(M // bm, N // bn, K // bk),
        dims=("parallel", "parallel", "arbitrary"),
        inputs=(
            BlockMap("x", (bm, bk), lambda i, j, k: (i, k),
                     (M, K), dtype),
            BlockMap("w", (bk, bn), lambda i, j, k: (k, j),
                     (K, N), dtype),
            BlockMap("mask", (bk, bn), lambda i, j, k: (k, j),
                     (K, N), dtype),
        ),
        outputs=(BlockMap("out", (bm, bn), lambda i, j, k: (i, j),
                          (M, N), dtype),),
        scratch=(ScratchSpec((bm, bn), jnp.float32, "accumulator"),),
        guard=None,
        cell_flops=2.0 * bm * bk * bn,
        notes="dense grid; MXU skip is data-dependent, DMA never skips",
    )


def masked_matmul_pallas(x, w, mask, *, bm: int = MXU_TILE,
                         bk: int = MXU_TILE, bn: int = MXU_TILE,
                         interpret: Optional[bool] = None):
    """Elementwise-masked matmul with per-tile MXU skip (no DMA skip)."""
    M, K = x.shape
    _, N = w.shape
    if M % bm or K % bk or N % bn:
        raise GeometryError(f"shapes must tile {(bm, bk, bn)}",
                            shape=(M, K, N), where="masked_matmul_pallas")
    spec = masked_matmul_spec(M=M, K=K, N=N, bm=bm, bk=bk, bn=bn,
                              dtype=x.dtype)
    kernel = pl.pallas_call(
        _masked_kernel,
        grid=spec.grid,
        in_specs=spec.pallas_in_specs(),
        out_specs=spec.pallas_out_specs()[0],
        scratch_shapes=spec.pallas_scratch(),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=spec.dims),
        interpret=default_interpret(interpret),
        name=spec.name,
    )
    return launch(kernel, x, w, mask)
