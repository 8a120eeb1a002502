"""Jaxpr auditor: trace jitted hot paths abstractly, audit the trace.

``jax.make_jaxpr`` runs the closure with abstract values — no FLOPs, no
compile — and hands back the full equation graph, including the bodies
of every nested ``jit``/``scan``/``cond``.  The auditor walks that
graph looking for the failure modes that do not crash but silently
forfeit the sparsity the plan paid for:

* a dense ``dot_general`` whose weight operand has exactly the (K, N)
  shape some ``TilePlan`` covers (J201) — the kernel router fell back
  to dense for a projection it was supposed to skip tiles on;
* no ``pallas_call`` anywhere in a trace whose plan routes at least one
  projection (J205) — the whole path lost its routing (e.g. a stale
  ``use_bsmm=False`` default);
* f64 values (J202), host callbacks (J203), and unjitted closures
  (J204) — each a per-step tax invisible in unit tests.

``pallas_call`` bodies are NOT descended into: the block-sparse kernel
legitimately contains a dense per-tile ``dot`` — that is the point.

The compiled-artifact cross-check (``audit_compiled``) reuses
``launch.hlo_analysis`` to confirm at the HLO level what the trace
promised (J206, J207).

Rule codes J201–J208; see ``analysis.findings.RULES``.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from repro.analysis.findings import Finding, error, info, warning

# primitives whose params hold sub-jaxprs we must NOT descend into:
# the block-sparse kernel body is dense per tile by design
_OPAQUE_PRIMS = ("pallas_call",)
# primitives that round-trip to the host (J203); ``jax.debug.print``
# traces to ``debug_print`` since JAX 0.7, not to a "*callback" name
_HOST_CALLBACK_PRIMS = frozenset({"debug_print", "debug_callback",
                                  "pure_callback", "io_callback"})


def collect_covered(plan_tree) -> Dict[Tuple[int, int], str]:
    """{(K, N) weight shape: plan path} for every TilePlan in a tree.

    A plan built by ``make_tile_plan`` covers a (K, N) weight where
    K = len(counts_t)·tile and N = len(counts)·tile; any dense
    ``dot_general`` against that exact shape in a hot path is a routing
    miss.  Later duplicates keep the first label (the shape is the key —
    shared-shape projections are indistinguishable in the trace anyway).
    """
    from repro.analysis.invariants import _walk_plan_leaves
    covered: Dict[Tuple[int, int], str] = {}
    for path, plan in _walk_plan_leaves(plan_tree):
        if plan.counts_t is None:
            continue
        K = int(plan.counts_t.shape[0]) * plan.tile
        N = int(plan.counts.shape[0]) * plan.tile
        covered.setdefault((K, N), path)
    return covered


def unambiguous_covered(plan_tree, params) -> Dict[Tuple[int, int], str]:
    """``collect_covered`` minus shapes that non-routed weights share.

    A dense ``dot_general`` is identified by its weight operand's
    (K, N) alone — the trace has no param paths — so a shape is a
    reliable routing-miss signature only when EVERY weight of that
    shape is plan-covered.  Tiny-scale configs collide constantly
    (every square projection is (128, 128), including RG-LRU gates and
    patch projections that legitimately run dense), so the lint driver
    filters through the param tree: if more ≥2-D param leaves carry a
    covered (…, K, N) shape than the plan routes, that shape is
    ambiguous and is not audited.  Stacked leaves (scan segments, MoE
    experts) count once — they share one traced matmul, exactly like
    their union-reduced plan.
    """
    import jax

    from repro.analysis.invariants import _walk_plan_leaves
    covered: Dict[Tuple[int, int], str] = {}
    plan_counts: Dict[Tuple[int, int], int] = {}
    for path, plan in _walk_plan_leaves(plan_tree):
        if plan.counts_t is None:
            continue
        s = (int(plan.counts_t.shape[0]) * plan.tile,
             int(plan.counts.shape[0]) * plan.tile)
        covered.setdefault(s, path)
        plan_counts[s] = plan_counts.get(s, 0) + 1
    leaf_counts: Dict[Tuple[int, int], int] = {}
    for leaf in jax.tree.leaves(params):
        if getattr(leaf, "ndim", 0) >= 2:
            s = tuple(int(d) for d in leaf.shape[-2:])
            leaf_counts[s] = leaf_counts.get(s, 0) + 1
    return {s: label for s, label in covered.items()
            if leaf_counts.get(s, 0) <= plan_counts[s]}


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Yield every equation of a (Closed)Jaxpr, recursing through
    call/control-flow sub-jaxprs but treating ``_OPAQUE_PRIMS`` bodies
    as leaves."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr → Jaxpr
    for eqn in jx.eqns:
        yield eqn
        if eqn.primitive.name in _OPAQUE_PRIMS:
            continue
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    for v in params.values():
        for item in (v if isinstance(v, (list, tuple)) else (v,)):
            if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                yield item


def _is_jitted(fn) -> bool:
    import jax
    return isinstance(fn, (jax.stages.Wrapped,)) or \
        type(fn).__name__ in ("PjitFunction", "CompiledFunction")


def audit_closure(fn, args: Iterable[Any], *,
                  covered: Optional[Dict[Tuple[int, int], str]] = None,
                  where: str = "closure",
                  expect_jitted: bool = True,
                  kwargs: Optional[dict] = None) -> List[Finding]:
    """Trace ``fn(*args)`` abstractly and audit the jaxpr.

    ``args`` may be ``ShapeDtypeStruct``s or concrete arrays — nothing
    executes.  ``covered`` maps plan-covered weight shapes to labels
    (``collect_covered``); None skips the routing rules (J201/J205).
    """
    import jax
    import numpy as np

    findings: List[Finding] = []
    if expect_jitted and not _is_jitted(fn):
        findings.append(warning(
            "J204", where,
            f"closure is {type(fn).__name__}, not a jitted function — "
            f"every call retraces and dispatches op-by-op"))
    try:
        jaxpr = jax.make_jaxpr(fn)(*args, **(kwargs or {}))
    except Exception as e:  # trace failure is itself a finding
        findings.append(error(
            "J204", where,
            f"could not trace the closure abstractly: "
            f"{type(e).__name__}: {e}"))
        return findings

    n_pallas = 0
    f64_seen: set = set()
    cb_seen: set = set()
    dense_hits: Dict[Tuple[int, int], int] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _OPAQUE_PRIMS:
            n_pallas += 1
            continue
        if name in _HOST_CALLBACK_PRIMS and name not in cb_seen:
            cb_seen.add(name)
            findings.append(warning(
                "J203", where,
                f"host callback primitive {name!r} in the trace — every "
                f"step round-trips to Python (debug print/jax.debug "
                f"left in a hot path?)"))
        if covered and name == "dot_general":
            # weight operand is the rhs; covered shapes are (K, N)
            rhs = eqn.invars[-1].aval
            shape = tuple(int(d) for d in getattr(rhs, "shape", ()))
            if len(shape) >= 2 and shape[-2:] in covered:
                dense_hits[shape[-2:]] = dense_hits.get(shape[-2:], 0) + 1
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and np.dtype(dt) == np.float64 and \
                    "f64" not in f64_seen:
                f64_seen.add("f64")
                findings.append(warning(
                    "J202", where,
                    f"float64 value produced by {name!r} — accidental "
                    f"x64 promotion doubles bytes moved on the hot "
                    f"path (check jax_enable_x64 / python-float "
                    f"constants)"))
    for shape, n in sorted(dense_hits.items()):
        findings.append(error(
            "J201", where,
            f"dense dot_general on weight shape {shape} ({n}x) — a "
            f"TilePlan covers this projection "
            f"({covered[shape]}); the block-sparse route was bypassed"))
    if covered and n_pallas == 0:
        findings.append(error(
            "J205", where,
            f"plan covers {len(covered)} projection shape(s) but the "
            f"trace contains no pallas_call — block-sparse routing is "
            f"disabled for this whole path"))
    return findings


def audit_engine_sharding(engine, *, where: str = "engine") -> List[Finding]:
    """J208: a ``ServeEngine`` on a >1-device mesh whose hot-path
    params never got a ``NamedSharding`` placement.

    The jitted prefill/decode closures pick their GSPMD partitioning up
    from their operands — params that were never ``device_put`` with
    the rules' NamedShardings leave every device running the full dense
    computation (correct outputs, none of the mesh's speedup, N× the
    memory).  No NamedSharding at all is an error; NamedShardings that
    are all fully replicated (no mesh axis appears in any spec) is a
    warning — legal for degenerate configs, almost certainly a
    divisibility bug at real scale.
    """
    import jax

    from jax.sharding import NamedSharding

    findings: List[Finding] = []
    mesh = getattr(engine, "mesh", None)
    if mesh is None or mesh.size <= 1:
        return findings
    for g in engine.generations:
        gwhere = f"{where}/gen{g.gid}"
        leaves = [l for l in jax.tree.leaves(g.params)
                  if hasattr(l, "sharding")]
        named = [l for l in leaves
                 if isinstance(l.sharding, NamedSharding)]
        if not named:
            findings.append(error(
                "J208", gwhere,
                f"engine mesh has {mesh.size} devices but none of the "
                f"{len(leaves)} param leaves carries a NamedSharding — "
                f"the jitted hot paths run fully replicated"))
            continue
        partitioned = [l for l in named
                       if any(s is not None for s in l.sharding.spec)]
        if not partitioned:
            findings.append(warning(
                "J208", gwhere,
                f"all {len(named)} NamedSharding'd param leaves are "
                f"fully replicated on a {mesh.size}-device mesh — no "
                f"dimension divided (shape/mesh mismatch?)"))
    return findings


def audit_compiled(fn, args: Iterable[Any], *,
                   where: str = "compiled",
                   kwargs: Optional[dict] = None) -> List[Finding]:
    """Lower+compile ``fn`` and cross-check the optimized HLO text.

    Slower than the abstract trace (XLA actually compiles), so the lint
    driver only runs it when asked (``--hlo``).  Reuses
    ``launch.hlo_analysis`` parsing: an f64 tensor surviving into the
    optimized module is J206; collective traffic is surfaced as J207
    info (single-host lint traces should have none).
    """
    import jax

    findings: List[Finding] = []
    try:
        jitted = fn if _is_jitted(fn) else jax.jit(fn)
        text = jitted.lower(*args, **(kwargs or {})).compile().as_text()
    except Exception as e:
        findings.append(error(
            "J204", where,
            f"could not compile the closure: {type(e).__name__}: {e}"))
        return findings
    findings.extend(audit_hlo_text(text, where=where))
    return findings


def audit_hlo_text(text: str, *, where: str = "hlo") -> List[Finding]:
    """The J206/J207 checks on an optimized HLO module text."""
    from repro.launch.hlo_analysis import collective_bytes, hlo_dtype_census

    findings: List[Finding] = []
    census = hlo_dtype_census(text)
    if census.get("f64"):
        findings.append(warning(
            "J206", where,
            f"optimized HLO contains {census['f64']} f64 shape(s) — an "
            f"x64 promotion survived compilation"))
    coll = collective_bytes(text)
    if coll.total_bytes:
        findings.append(info(
            "J207", where,
            f"compiled module moves {coll.total_bytes} collective "
            f"bytes: " +
            ", ".join(f"{k}×{coll.count_by_kind[k]}"
                      for k in sorted(coll.bytes_by_kind))))
    return findings
