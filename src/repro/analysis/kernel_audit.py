"""Static Pallas kernel verifier (rules K300–K306).

Every kernel in ``repro.kernels`` describes its launch as a declarative
``KernelSpec`` — grid, dimension semantics, the *actual* BlockSpec
index-map callables, scalar-prefetch operands, scratch, and a host
mirror of its ``pl.when`` work gate.  Because the kernels construct
their ``pallas_call`` *from* those specs, auditing the spec audits the
executed launch geometry, with no source re-parsing and no second copy
of the index maps to drift.

``audit_kernel_spec`` evaluates the spec exhaustively over its concrete
grid (audit cases are a handful of grid cells; the checks are O(grid ×
operands) host numpy):

  K300  spec malformed — grid/dims/blocks/shapes inconsistent, or an
        index map that does not evaluate.
  K301  output coverage exact — the output index map is constant along
        'arbitrary' axes (revolving accumulator) and a bijection from
        the parallel axes onto the output tile grid: every tile written
        exactly once, none skipped on a ragged edge.
  K302  all index maps in bounds over ALL grid cells — including
        guarded ones, whose DMA still happens (this is why dead block-
        table entries must point at the scratch block, not past the
        pool).
  K303  guard/liveness agreement — per parallel class, the multiset of
        blocks the *unguarded* cells gather equals the live set derived
        independently from the truth source (tile bitmap, block table +
        lengths, causal structure).
  K304  accumulator/softmax scratch is f32 and the accumulator shape
        matches the output block it flushes into.
  K305  VMEM footprint (double-buffered blocks + scratch + the body's
        counted temporaries) within the
        per-backend budget declared in ``configs.base``.
  K306  passes/FLOPs/bytes enumerated from the spec equal
        ``core.perf_model``'s analytic ``KernelCost`` prediction from
        plan metadata (the no-elision, guarded-skip traffic model) —
        the perf model and the kernels cannot silently diverge.

``default_cases()`` is the canonical registry of small concrete cases
covering every registered kernel (bsmm fwd plain + fused epilogue, dx,
dw, each also with row blocks taller than a tile and dead plan slots;
paged attention GQA + fused-V MLA, flash attention, masked matmul,
tile stats); ``audit_kernels()`` runs them all and is what ``lint
--kernels`` invokes — the static gate before a chip run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.findings import Finding, error
from repro.configs.base import MXU_TILE, vmem_budget
from repro.kernels.spec import ACCUMULATOR_ROLES, BlockMap, KernelSpec

Coord = Tuple[int, ...]
#: truth for K303: input name -> parallel class -> live block coords
ExpectedGathers = Dict[str, Dict[Coord, List[Coord]]]

_DIM_SEMANTICS = ("parallel", "arbitrary")
_MAX_EXAMPLES = 3       # coords quoted per finding before eliding


@dataclass(frozen=True)
class AuditCase:
    """One concrete kernel launch plus its independent liveness truth
    and (optionally) the perf model's cost prediction to cross-check."""
    name: str
    spec: KernelSpec
    expected_gathers: Optional[ExpectedGathers] = None
    cost: Optional[object] = None           # core.perf_model.KernelCost


def _eval_map(bm: BlockMap, ids: Coord, scalars) -> Coord:
    out = bm.index_map(*ids, *scalars)
    if not isinstance(out, tuple):
        out = (out,)
    return tuple(int(c) for c in out)


def _squeeze(shape: Sequence[int]) -> Tuple[int, ...]:
    return tuple(int(d) for d in shape if int(d) != 1)


def _check_structure(spec: KernelSpec, where: str) -> List[Finding]:
    bad: List[Finding] = []
    if len(spec.grid) != len(spec.dims):
        bad.append(error("K300", where,
                         f"grid rank {len(spec.grid)} != "
                         f"dimension_semantics rank {len(spec.dims)}"))
    for d in spec.dims:
        if d not in _DIM_SEMANTICS:
            bad.append(error("K300", where,
                             f"unknown dimension semantic {d!r}"))
    if any(g <= 0 for g in spec.grid):
        bad.append(error("K300", where,
                         f"non-positive grid extent {spec.grid}"))
    for bm in (*spec.inputs, *spec.outputs):
        if len(bm.block) != len(bm.shape):
            bad.append(error(
                "K300", where,
                f"{bm.name}: block rank {len(bm.block)} != operand "
                f"rank {len(bm.shape)}"))
            continue
        if any(b <= 0 for b in bm.block) or \
                any(s % b for s, b in zip(bm.shape, bm.block)):
            bad.append(error(
                "K300", where,
                f"{bm.name}: block {bm.block} does not tile shape "
                f"{bm.shape} evenly"))
    if bad:
        return bad
    origin = tuple(0 for _ in spec.grid)
    for bm in (*spec.inputs, *spec.outputs):
        try:
            coord = _eval_map(bm, origin, spec.scalars)
        except Exception as e:   # noqa: BLE001 — any failure is the finding
            bad.append(error("K300", where,
                             f"{bm.name}: index map failed at grid "
                             f"origin: {type(e).__name__}: {e}"))
            continue
        if len(coord) != len(bm.block):
            bad.append(error(
                "K300", where,
                f"{bm.name}: index map returns {len(coord)} coords for "
                f"a rank-{len(bm.block)} block"))
    if spec.guard is not None:
        try:
            spec.guard(*origin, *spec.scalars)
        except Exception as e:   # noqa: BLE001
            bad.append(error("K300", where,
                             f"guard failed at grid origin: "
                             f"{type(e).__name__}: {e}"))
    return bad


def _fmt_cells(cells: List) -> str:
    shown = ", ".join(map(str, cells[:_MAX_EXAMPLES]))
    more = len(cells) - _MAX_EXAMPLES
    return shown + (f", … +{more} more" if more > 0 else "")


def audit_kernel_spec(spec: KernelSpec, *, backend: str = "tpu",
                      expected_gathers: Optional[ExpectedGathers] = None,
                      cost=None, where: str = "") -> List[Finding]:
    """Run K300–K306 against one concrete ``KernelSpec``.

    ``expected_gathers`` supplies the independent liveness truth for
    K303; ``cost`` a ``core.perf_model.KernelCost`` for K306.  Either
    may be None to skip that rule (e.g. data-dependent guards).
    """
    where = where or f"kernels/{spec.name}"
    findings = _check_structure(spec, where)
    if findings:
        return findings      # geometry unusable; later rules would lie

    par = spec.parallel_axes()
    cells = list(np.ndindex(*spec.grid))
    unguarded = [c for c in cells
                 if spec.guard is None or spec.guard(*c, *spec.scalars)]

    # one evaluation sweep shared by K301/K302/K303/K306
    coords: Dict[str, Dict[Coord, Coord]] = {}     # map name -> cell -> coord
    for bm in (*spec.inputs, *spec.outputs):
        coords[bm.name] = {c: _eval_map(bm, c, spec.scalars)
                           for c in cells}

    # -- K302: every cell's DMA target in bounds (guarded cells too) ----
    for bm in (*spec.inputs, *spec.outputs):
        tgrid = bm.tile_grid()
        bad = [(c, coords[bm.name][c]) for c in cells
               if any(not 0 <= x < t
                      for x, t in zip(coords[bm.name][c], tgrid))]
        if bad:
            findings.append(error(
                "K302", where,
                f"{bm.name}: index map leaves the {tgrid} tile grid at "
                f"{len(bad)} of {len(cells)} grid cells "
                f"(cell -> block): {_fmt_cells(bad)}"))

    # -- K301: output coverage exact --------------------------------------
    for bm in spec.outputs:
        per_class: Dict[Coord, Coord] = {}
        moved = []
        for c in cells:
            cls = tuple(c[d] for d in par)
            coord = coords[bm.name][c]
            prev = per_class.setdefault(cls, coord)
            if prev != coord:
                moved.append((cls, prev, coord))
        if moved:
            findings.append(error(
                "K301", where,
                f"{bm.name}: output block moves along an 'arbitrary' "
                f"grid axis — the revolving accumulator would flush to "
                f"different tiles (class, first, later): "
                f"{_fmt_cells(moved)}"))
            continue
        written = list(per_class.values())
        wset = set(written)
        expected = set(np.ndindex(*bm.tile_grid()))
        missing = sorted(expected - wset)
        dup = sorted({w for w in wset if written.count(w) > 1})
        if missing or dup:
            parts = []
            if missing:
                parts.append(f"{len(missing)} of {len(expected)} output "
                             f"tiles never written: {_fmt_cells(missing)}")
            if dup:
                parts.append(f"tiles written by multiple parallel "
                             f"classes: {_fmt_cells(dup)}")
            findings.append(error(
                "K301", where, f"{bm.name}: " + "; ".join(parts)))

    # -- K303: unguarded gathers == independent liveness truth ----------
    if expected_gathers:
        by_name = {bm.name: bm for bm in spec.inputs}
        for name, truth in expected_gathers.items():
            if name not in by_name:
                findings.append(error(
                    "K303", where,
                    f"liveness truth names unknown input {name!r}"))
                continue
            got: Dict[Coord, List[Coord]] = {}
            for c in unguarded:
                cls = tuple(c[d] for d in par)
                got.setdefault(cls, []).append(coords[name][c])
            classes = set(truth) | set(got)
            bad_cls = []
            for cls in sorted(classes):
                want = sorted(tuple(map(int, w)) for w in
                              truth.get(cls, []))
                have = sorted(got.get(cls, []))
                if want != have:
                    bad_cls.append((cls, want, have))
            if bad_cls:
                cls, want, have = bad_cls[0]
                findings.append(error(
                    "K303", where,
                    f"{name}: unguarded gathers disagree with the live "
                    f"set for {len(bad_cls)} parallel class(es); e.g. "
                    f"class {cls}: live={want} gathered={have} — a "
                    f"loose guard streams dead/scratch blocks, a tight "
                    f"one drops live work"))

    # -- K304: accumulator dtype/shape ----------------------------------
    for i, s in enumerate(spec.scratch):
        if s.role in ACCUMULATOR_ROLES and \
                np.dtype(s.dtype) != np.dtype(np.float32):
            findings.append(error(
                "K304", where,
                f"scratch[{i}] ({s.role}) is {np.dtype(s.dtype).name}, "
                f"must be float32 — low-precision accumulation breaks "
                f"the kernels' exactness contract"))
    accs = [s for s in spec.scratch if s.role == "accumulator"]
    if accs and spec.outputs:
        acc, out = accs[0], spec.outputs[0]
        if _squeeze(acc.shape) != _squeeze(out.block):
            findings.append(error(
                "K304", where,
                f"accumulator shape {tuple(acc.shape)} does not match "
                f"the output block {tuple(out.block)} it flushes into"))

    # -- K305: VMEM footprint vs backend budget -------------------------
    bd = spec.vmem_breakdown()
    budget = vmem_budget(backend)
    if bd["total"] > budget:
        findings.append(error(
            "K305", where,
            f"estimated VMEM {bd['total']} B (2×in {bd['inputs']} + "
            f"2×out {bd['outputs']} + scratch {bd['scratch']} + "
            f"temporaries {bd['temporaries']}) exceeds "
            f"the {budget} B {backend} budget "
            f"(configs.base.VMEM_BUDGET_BYTES)"))

    # -- K306: spec-enumerated cost == perf-model prediction ------------
    if cost is not None:
        passes = len(unguarded)
        flops = passes * float(spec.cell_flops)
        in_bytes = passes * sum(bm.block_bytes for bm in spec.inputs)
        out_bytes = sum(
            len({coords[bm.name][c] for c in cells}) * bm.block_bytes
            for bm in spec.outputs)
        got = (passes, flops, float(in_bytes + out_bytes))
        want = (int(cost.passes), float(cost.flops),
                float(cost.hbm_bytes))
        if got != want:
            findings.append(error(
                "K306", where,
                f"spec enumeration (passes={got[0]}, flops={got[1]:.0f}, "
                f"bytes={got[2]:.0f}) disagrees with the perf model "
                f"(passes={want[0]}, flops={want[1]:.0f}, "
                f"bytes={want[2]:.0f}) — kernels and core.perf_model "
                f"have diverged"))
    return findings


def audit_case(case: AuditCase, *, backend: str = "tpu",
               where: str = "") -> List[Finding]:
    return audit_kernel_spec(case.spec, backend=backend,
                             expected_gathers=case.expected_gathers,
                             cost=case.cost,
                             where=where or f"kernels/{case.name}")


# ---------------------------------------------------------------------------
# Canonical audit cases: one small concrete launch per registered
# kernel, with liveness truth derived from first principles (the
# bitmap / the block lists the tables were built from / causal math),
# NOT from the plan arrays the index maps read.
# ---------------------------------------------------------------------------

#: (Kt, Nt) tile bitmap with dead tiles in both directions
_BITMAP = np.array([[1, 0],
                    [0, 1],
                    [1, 1]], np.int32)

#: a bitmap whose plans have dead slots: forward columns of 2, 0 and 3
#: live tiles (kmax 3), transposed rows of 2, 1 and 2 (nmax 2)
_BITMAP_SLOTS = np.array([[1, 0, 1],
                          [0, 0, 1],
                          [1, 0, 1]], np.int32)


def _bsmm_cases(tile: int, bitmap: np.ndarray = _BITMAP, rows: int = 1,
                suffix: str = "") -> List[AuditCase]:
    """fwd, fused fwd, dx and dw over two row blocks of ``rows`` tiles
    each; ``suffix`` tells a second geometry's case names from the
    kernels' own."""
    from repro.core.perf_model import (bsmm_dw_cost, bsmm_dx_cost,
                                       bsmm_fwd_cost)
    from repro.kernels.bsmm import (bsmm_dw_spec, bsmm_dx_spec,
                                    bsmm_fwd_spec, make_tile_plan)

    Kt, Nt = bitmap.shape
    K, N = Kt * tile, Nt * tile
    bm, Mt = rows * tile, 2
    M = Mt * bm
    mask = np.repeat(np.repeat(bitmap, tile, 0), tile, 1)
    plan = make_tile_plan(mask, tile=tile, strict=True)

    live_k = {j: np.nonzero(bitmap[:, j])[0] for j in range(Nt)}
    live_n = {k: np.nonzero(bitmap[k, :])[0] for k in range(Kt)}
    fwd_truth = {
        "x": {(i, j): [(i, int(kt)) for kt in live_k[j]]
              for i in range(Mt) for j in range(Nt)},
        "w": {(i, j): [(int(kt), j) for kt in live_k[j]]
              for i in range(Mt) for j in range(Nt)},
    }
    dx_truth = {
        "g": {(i, k): [(i, int(nt)) for nt in live_n[k]]
              for i in range(Mt) for k in range(Kt)},
        "w": {(i, k): [(k, int(nt)) for nt in live_n[k]]
              for i in range(Mt) for k in range(Kt)},
    }
    kk, nn = np.nonzero(bitmap)              # row-major, == plan order
    dw_truth = {
        "x": {(l,): [(m, int(kk[l])) for m in range(Mt)]
              for l in range(len(kk))},
        "g": {(l,): [(m, int(nn[l])) for m in range(Mt)]
              for l in range(len(kk))},
    }
    cases = [
        AuditCase(
            "bsmm_fwd" + suffix,
            bsmm_fwd_spec(plan.idx, plan.counts, plan.kmax, M=M, K=K,
                          N=N, bm=bm, bk=tile, bn=tile),
            fwd_truth, bsmm_fwd_cost(plan, M, bm=bm)),
        AuditCase(
            "bsmm_fwd_epilogue" + suffix,
            bsmm_fwd_spec(plan.idx, plan.counts, plan.kmax, M=M, K=K,
                          N=N, bm=bm, bk=tile, bn=tile, fused=True),
            fwd_truth, bsmm_fwd_cost(plan, M, bm=bm, fused=True)),
        AuditCase(
            "bsmm_dx" + suffix,
            bsmm_dx_spec(plan.idx_t, plan.counts_t, plan.nmax, M=M,
                         K=K, N=N, bm=bm, tile=tile),
            dx_truth, bsmm_dx_cost(plan, M, bm=bm)),
        AuditCase(
            "bsmm_dw" + suffix,
            bsmm_dw_spec(plan.kk, plan.nn, M=M, K=K, N=N, bm=bm,
                         tile=tile),
            dw_truth, bsmm_dw_cost(plan, M, bm=bm)),
    ]
    return cases


def _paged_cases() -> List[AuditCase]:
    from repro.core.perf_model import paged_decode_cost
    from repro.kernels.paged_attention import (BLOCK_TOKENS,
                                               PagedGeometry,
                                               paged_attention_spec)

    T = BLOCK_TOKENS
    B, Hq, Hkv, hd, P, NB = 2, 4, 2, 8, 5, 3
    # the truth source: per-sequence physical block lists + lengths the
    # tables are BUILT from (dead entries -> the pool's scratch block 0)
    blocks = [[1, 2], [3]]
    lengths = [T + 2, 7]                    # seq0 spans 2 blocks, seq1 1
    tables = np.zeros((B, NB), np.int32)
    for b, blks in enumerate(blocks):
        tables[b, :len(blks)] = blks
    lengths_a = np.asarray(lengths, np.int32)

    def truth(dv: int, fused: bool) -> ExpectedGathers:
        t: ExpectedGathers = {
            "k_pool": {(b,): [(blk, 0, 0, 0) for blk in blocks[b]]
                       for b in range(B)}}
        if not fused:
            t["v_pool"] = {(b,): [(blk, 0, 0, 0) for blk in blocks[b]]
                           for b in range(B)}
        return t

    cases = []
    for fused, dv, name in ((False, hd, "paged_attention_gqa"),
                            (True, hd // 2, "paged_attention_mla")):
        geo = PagedGeometry(B=B, Hq=Hq, hd=hd, Hkv=Hkv, T=T, NB=NB,
                            P=P, dv=dv)
        cases.append(AuditCase(
            name,
            paged_attention_spec(geo, tables, lengths_a, fused_v=fused),
            truth(dv, fused),
            paged_decode_cost(lengths, nb=NB, block_tokens=T,
                              n_q_heads=Hq, n_kv_heads=Hkv, head_dim=hd,
                              v_dim=dv, fused_v=fused)))
    return cases


def _flash_case(tile: int) -> AuditCase:
    from repro.core.perf_model import flash_cost
    from repro.kernels.flash_attention import flash_attention_spec

    B, Hq, Hkv, hd = 1, 2, 1, 16
    S, bq, bk = 2 * tile, tile, tile
    G = Hq // Hkv
    spec = flash_attention_spec(B=B, S=S, Hq=Hq, Hkv=Hkv, hd=hd, bq=bq,
                                bk=bk, causal=True)
    # causal truth from first principles: with square blocks, q block i
    # attends k blocks 0..i
    truth: ExpectedGathers = {
        "k": {(b, h, i): [(b, h // G, j, 0) for j in range(i + 1)]
              for b in range(B) for h in range(Hq)
              for i in range(S // bq)}}
    return AuditCase(
        "flash_attention", spec, truth,
        flash_cost(batch=B, n_q_heads=Hq, seq=S, head_dim=hd, bq=bq,
                   bk=bk, causal=True))


def default_cases(tile: int = MXU_TILE) -> List[AuditCase]:
    """The canonical small concrete launches, one per registered
    kernel under its own name, and the bsmm kernels once more at a
    second geometry (``.rows``).  ``masked_matmul``/``tile_stats``
    carry no liveness truth or cost (their work gates are
    data-dependent / VPU-only), so K303 and K306 are skipped for them
    by construction."""
    from repro.kernels.bsmm import masked_matmul_spec
    from repro.kernels.tile_stats import tile_stats_spec

    cases = _bsmm_cases(tile)
    # row blocks taller than a tile (``bsmm.row_block``) over plans
    # whose dead slots repeat a column's last live index
    cases.extend(_bsmm_cases(tile, _BITMAP_SLOTS, rows=2, suffix=".rows"))
    cases.extend(_paged_cases())
    cases.append(_flash_case(tile))
    cases.append(AuditCase(
        "masked_matmul",
        masked_matmul_spec(M=2 * tile, K=3 * tile, N=2 * tile, bm=tile,
                           bk=tile, bn=tile)))
    cases.append(AuditCase(
        "tile_stats", tile_stats_spec(K=2 * tile, N=2 * tile, bk=tile,
                                      bn=tile)))
    return cases


def audit_kernels(*, backend: str = "tpu",
                  cases: Optional[Sequence[AuditCase]] = None
                  ) -> List[Finding]:
    """K300–K306 over every registered kernel's canonical audit case —
    the ``lint --kernels`` entry point and the first TPU bring-up gate."""
    out: List[Finding] = []
    for case in (cases if cases is not None else default_cases()):
        out.extend(audit_case(case, backend=backend))
    return out
