"""Sparsity lint: every rule code proven by a seeded defect.

Each test plants one specific defect — a bad recipe program, a
corrupted TilePlan, a closure that bypasses the block-sparse route —
and asserts the analyzer reports exactly that rule code.  A final
coverage check asserts the suite exercises every registered code, so a
new rule cannot land without its defect test.
"""
import copy
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (RULES, Finding, Report, audit_closure,
                            collect_covered, lint_arch, lint_recipe,
                            verify_decode_plan, verify_engine,
                            verify_mask_accounting, verify_tile_plan,
                            verify_xbar_stats)
from repro.analysis.jaxpr_audit import audit_hlo_text, unambiguous_covered
from repro.api.recipes import Recipe, prune_stage, quantize_stage
from repro.core.crossbar import xbar_stats
from repro.kernels.bsmm import make_tile_plan
from repro.models.plans import PlanStats, build_decode_plan

# codes asserted by the tests below; the coverage test at the bottom
# demands this set equals the registry
TESTED = set()


def codes_of(findings):
    return {f.code for f in findings}


def assert_code(findings, code, severity=None):
    TESTED.add(code)
    got = codes_of(findings)
    assert code in got, f"expected {code} in {got}: {findings}"
    if severity:
        assert any(f.severity == severity for f in findings
                   if f.code == code)


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mask():
    rng = np.random.default_rng(0)
    m = (rng.random((256, 384)) < 0.4).astype(np.float32)
    m[:128, :128] = 0          # one dead tile
    m[128:, 256:] = 0          # another
    return m


@pytest.fixture(scope="module")
def plan(mask):
    return make_tile_plan(mask, tile=128, interpret=True)


@pytest.fixture(scope="module")
def lm_masks(mask):
    rng = np.random.default_rng(1)
    m2 = (rng.random((384, 256)) < 0.5).astype(np.float32)
    m2[:128, :] = 0
    return {"segments": [[{"mlp": {"up": mask, "down": m2}}]]}


# ---------------------------------------------------------------------------
# recipe linter: R001-R009
# ---------------------------------------------------------------------------
GRANS = ("filter", "channel", "index")


def test_r001_unresolvable_recipe():
    assert_code(lint_recipe("no-such-recipe-xyz"), "R001", "error")


def test_r002_unknown_granularity():
    r = Recipe(name="r", stages=(prune_stage("expert", rate=0.2),))
    assert_code(lint_recipe(r, allowed_granularities=GRANS, family="cnn"),
                "R002", "error")


def test_r003_non_monotonic_target():
    r = Recipe(name="r", stages=(
        prune_stage("filter", rate=0.3, target_sparsity=0.9),
        prune_stage("index", rate=0.3, target_sparsity=0.5),
    ))
    assert_code(lint_recipe(r), "R003", "error")


def test_r004_zero_retrain_budget():
    r = Recipe(name="r", stages=(
        prune_stage("filter", rate=0.3, retrain_steps=0),))
    assert_code(lint_recipe(r), "R004", "error")


def test_r005_quantize_before_prune():
    r = Recipe(name="r", stages=(
        quantize_stage(8), prune_stage("filter", rate=0.3)))
    assert_code(lint_recipe(r), "R005", "warning")


def test_r006_prune_after_quantize():
    r = Recipe(name="r", stages=(
        prune_stage("filter", rate=0.3), quantize_stage(8),
        prune_stage("index", rate=0.3)))
    assert_code(lint_recipe(r), "R006", "warning")


def test_r007_unreachable_target():
    # 2 rounds at 10% reach at most 19% — 0.99 is fiction
    r = Recipe(name="r", stages=(
        prune_stage("filter", rate=0.1, max_rounds=2,
                    target_sparsity=0.99),))
    assert_code(lint_recipe(r), "R007", "warning")


def test_r008_duplicate_stage_names():
    r = Recipe(name="r", stages=(
        prune_stage("filter", rate=0.3), prune_stage("filter", rate=0.2)))
    assert_code(lint_recipe(r), "R008", "warning")


def test_r009_no_prune_stage():
    r = Recipe(name="r", stages=(quantize_stage(8),))
    assert_code(lint_recipe(r), "R009", "warning")


def test_shipped_recipes_clean_of_errors():
    for name in ("cnn-full", "dense-full", "moe-full"):
        findings = lint_recipe(
            name, allowed_granularities=GRANS + ("expert",))
        assert not [f for f in findings if f.severity == "error"], findings


# ---------------------------------------------------------------------------
# invariant verifier: P101-P112
# ---------------------------------------------------------------------------
def test_healthy_plan_verifies_clean(plan, mask):
    assert verify_tile_plan(plan, mask) == []
    assert verify_tile_plan(plan) == []      # structure-only mode


def test_p101_out_of_bounds_index(plan, mask):
    bad = plan._replace(idx=np.full_like(np.asarray(plan.idx), 99))
    assert_code(verify_tile_plan(bad, mask), "P101", "error")


def test_p102_counts_disagree(plan, mask):
    counts = np.asarray(plan.counts).copy()
    counts[0] = max(0, counts[0] - 1)
    assert_code(verify_tile_plan(plan._replace(counts=counts), mask),
                "P102", "error")


def test_p103_live_set_disagrees(plan, mask):
    idx = np.asarray(plan.idx).copy()
    # swap a live row index for a dead one in the column with slack
    j = int(np.argmin(np.asarray(plan.counts)))
    c = int(np.asarray(plan.counts)[j])
    assert 0 < c < idx.shape[1] or c > 0
    dead = (set(range(idx.shape[1])) -
            set(int(v) for v in idx[j, :c]))
    idx[j, 0] = sorted(dead)[0]
    assert_code(verify_tile_plan(plan._replace(idx=idx), mask),
                "P103", "error")


def test_p104_cap_below_densest_column(plan, mask):
    cap = int(np.asarray(plan.counts).max()) - 1
    bad = plan._replace(idx=np.asarray(plan.idx)[:, :cap], kmax=cap)
    assert_code(verify_tile_plan(bad, mask), "P104", "error")


def test_p105_transpose_mismatch(plan, mask):
    counts_t = np.asarray(plan.counts_t).copy()
    counts_t[0] += 1
    assert_code(verify_tile_plan(plan._replace(counts_t=counts_t), mask),
                "P105", "error")


def test_p106_flat_coords_disagree(plan, mask):
    kk = np.asarray(plan.kk).copy()
    nn = np.asarray(plan.nn).copy()
    kk[0], nn[0] = 0, 0          # (0,0) is a dead tile in the fixture
    assert_code(verify_tile_plan(plan._replace(kk=kk, nn=nn), mask),
                "P106", "error")


def test_p107_tile_accounting(plan, mask):
    assert_code(verify_tile_plan(
        plan._replace(live_tiles=plan.live_tiles + 1), mask),
        "P107", "error")


def test_p108_geometry_mismatch(plan):
    wrong = np.ones((128, 384), np.float32)
    assert_code(verify_tile_plan(plan, wrong), "P108", "error")


def test_p109_decode_plan_drift(lm_masks):
    plan, stats = build_decode_plan(lm_masks, interpret=True)
    assert verify_decode_plan(lm_masks, plan, stats) == []
    # missing entry: the projection silently runs dense
    missing = copy.deepcopy(plan)
    del missing[0][0]["mlp"]["up"]
    assert_code(verify_decode_plan(lm_masks, missing), "P109", "error")
    # stale entry: plan leaf from different masks
    stale = copy.deepcopy(plan)
    stale[0][0]["mlp"]["up"] = stale[0][0]["mlp"]["down"]
    assert_code(verify_decode_plan(lm_masks, stale), "P109", "error")


def test_p110_planstats_totals(lm_masks):
    plan, stats = build_decode_plan(lm_masks, interpret=True)
    bad = PlanStats(routed=stats.routed,
                    live_tiles=stats.live_tiles + 1,
                    total_tiles=stats.total_tiles)
    assert_code(verify_decode_plan(lm_masks, plan, bad), "P110", "error")


def test_p111_xbar_stats(mask):
    st = xbar_stats(mask != 0, 128, 128)
    assert verify_xbar_stats(st, mask) == []
    st.nonzero_cells += 3
    assert_code(verify_xbar_stats(st, mask), "P111", "error")


def test_mask_accounting_walks_pytree(mask):
    rng = np.random.default_rng(2)
    masks = {"convs": [{"w": (rng.random((3, 3, 8, 16)) < 0.5)
                        .astype(np.float32)}],
             "fc": {"w": mask}, "b": None}
    out = verify_mask_accounting(masks, lambda p: p.startswith("convs"),
                                 rows=128, cols=128)
    assert out == []


def test_p112_engine_consistency(lm_masks):
    plan, stats = build_decode_plan(lm_masks, interpret=True)
    g0 = SimpleNamespace(gid=0, masks=None, plan=None, plan_stats=None)
    dup = SimpleNamespace(gid=0, masks=None, plan=None, plan_stats=None)
    eng = SimpleNamespace(generations=(g0, dup), report=None)
    assert_code(verify_engine(eng), "P112", "error")
    # plan without masks
    orphan = SimpleNamespace(gid=1, masks=None, plan=plan,
                             plan_stats=stats)
    eng2 = SimpleNamespace(
        generations=(g0, orphan),
        report=SimpleNamespace(
            skipped_tile_fraction=stats.skipped_tile_fraction))
    assert_code(verify_engine(eng2), "P112", "error")
    # stale plan inside a generation surfaces as P112 too
    stale = copy.deepcopy(plan)
    stale[0][0]["mlp"]["up"] = stale[0][0]["mlp"]["down"]
    bad_gen = SimpleNamespace(gid=2, masks=lm_masks, plan=stale,
                              plan_stats=stats)
    eng3 = SimpleNamespace(
        generations=(bad_gen,),
        report=SimpleNamespace(
            skipped_tile_fraction=stats.skipped_tile_fraction))
    assert_code(verify_engine(eng3), "P112", "error")


# ---------------------------------------------------------------------------
# paged KV invariants: P113-P115
# ---------------------------------------------------------------------------
def test_p115_block_pool_accounting():
    from repro.analysis import verify_block_pool
    from repro.serve import BlockPool
    pool = BlockPool(6)
    pool.reserve(1, 2)
    pool.alloc(1)
    assert verify_block_pool(pool) == []
    # seeded defect: a block tracked as both free and owned
    pool._owned[1].append(pool._free[-1])
    assert_code(verify_block_pool(pool), "P115", "error")
    # seeded defect: a block leaks out of the accounting entirely
    pool2 = BlockPool(6)
    pool2._free.pop()
    assert_code(verify_block_pool(pool2), "P115", "error")


def test_p113_block_table_consistency():
    from repro.analysis import verify_block_tables
    from repro.serve import BlockPool
    T = 128
    pool = BlockPool(8)
    pool.reserve(7, 3)
    b0, b1 = pool.alloc(7), pool.alloc(7)
    tables = np.zeros((2, 4), np.int32)
    tables[0, :2] = [b0, b1]
    lens = np.array([T + 5, 0], np.int32)
    nbs = np.array([2, 0], np.int64)
    uids = [7, None]
    kw = dict(block_tokens=T)
    assert verify_block_tables(pool, tables, lens, nbs, uids, **kw) == []
    # logical order broken vs pool ownership
    bad = tables.copy()
    bad[0, :2] = [b1, b0]
    assert_code(verify_block_tables(pool, bad, lens, nbs, uids, **kw),
                "P113", "error")
    # block count disagrees with the token count
    short = lens.copy()
    short[0] = 5                      # 5 tokens need 1 block, slot holds 2
    assert_code(verify_block_tables(pool, tables, short, nbs, uids, **kw),
                "P113", "error")
    # inactive slot with leftover state
    stale = lens.copy()
    stale[1] = 4
    assert_code(verify_block_tables(pool, tables, stale, nbs, uids, **kw),
                "P113", "error")
    # dead tail entry off the scratch block
    tail = tables.copy()
    tail[0, 3] = 5
    assert_code(verify_block_tables(pool, tail, lens, nbs, uids, **kw),
                "P113", "error")


def test_p114_paged_reconstruction():
    from repro.analysis import verify_paged_reconstruction
    from repro.models import attention as attn
    rng = np.random.default_rng(3)
    T = attn.BLOCK_TOKENS
    H, d, S = 2, 4, T + 3
    k = jnp.asarray(rng.random((1, S, H, d)), jnp.float32)
    v = jnp.asarray(rng.random((1, S, H, d)), jnp.float32)
    dense = [[attn.KVCache(k, v, jnp.asarray(S, jnp.int32))]]
    empty = attn.PagedKVCache(jnp.zeros((4, T, H, d), jnp.float32),
                              jnp.zeros((4, T, H, d), jnp.float32))
    blocks = jnp.asarray([1, 2], jnp.int32)
    adopted = [[attn.gqa_paged_adopt(empty, dense[0][0], blocks)]]
    assert verify_paged_reconstruction(adopted, dense, blocks, S) == []
    # seeded defect: gathering in the wrong logical order
    assert_code(verify_paged_reconstruction(adopted, dense, [2, 1], S),
                "P114", "error")


def test_p116_fleet_accounting():
    from repro.analysis import verify_fleet
    from repro.serve import FleetRecord, FleetReport

    def rec(uid, toks, status="done"):
        r = FleetRecord(uid=uid, prompt=np.zeros(2, np.int32),
                        max_new_tokens=4, seq=uid)
        r.tokens = list(toks)
        r.status = status
        return r

    def router(finished, records, per, tokens):
        return SimpleNamespace(
            finished=finished, records=records, rejected=[], idle=True,
            live=set(), frontends=[],
            report=FleetReport(engines=len(per), live_engines=len(per),
                               requests=len(finished),
                               tokens_generated=tokens, per_engine=per))

    a, b = rec(0, [1, 2]), rec(1, [3])
    per = [SimpleNamespace(tokens_generated=2, requests=1),
           SimpleNamespace(tokens_generated=1, requests=1)]
    healthy = router([a, b], {0: a, 1: b}, per, 3)
    assert verify_fleet(healthy) == []
    # seeded defect: one uid finished twice across engines
    assert_code(verify_fleet(router([a, a, b], {0: a, 1: b}, per, 3)),
                "P116", "error")
    # seeded defect: a submitted request vanished (idle but never done)
    lost = rec(2, [], status="running")
    assert_code(
        verify_fleet(router([a, b], {0: a, 1: b, 2: lost}, per, 3)),
        "P116", "error")
    # seeded defect: merged token total disagrees with per-engine sums
    inflated = [SimpleNamespace(tokens_generated=2, requests=1),
                SimpleNamespace(tokens_generated=2, requests=1)]
    assert_code(verify_fleet(router([a, b], {0: a, 1: b}, inflated, 3)),
                "P116", "error")


def test_p116_live_fleet_clean():
    """A real two-engine fleet drained to idle verifies clean."""
    from repro.analysis import verify_fleet
    from repro.api.registry import make_adapter
    from repro.serve import FleetRouter, ServeEngine

    ad = make_adapter("llama3.2-3b", scale="tiny")
    params = ad.init_params(jax.random.PRNGKey(0))
    prefill_fn, decode_fn = ad.serve_fns()

    def eng():
        return ServeEngine(params=params, cfg=ad.cfg,
                           prefill_fn=prefill_fn, decode_fn=decode_fn,
                           batch_slots=2, capacity=48)

    router = FleetRouter([eng(), eng()])
    rng = np.random.RandomState(0)
    for i in range(4):
        router.submit(rng.randint(1, ad.cfg.vocab_size, 5)
                      .astype(np.int32), uid=i, max_new_tokens=4)
    router.drain()
    assert verify_fleet(router) == []
    TESTED.add("P116")


# ---------------------------------------------------------------------------
# jaxpr auditor: J201-J208
# ---------------------------------------------------------------------------
def test_j201_dense_dot_on_covered_shape(plan, mask):
    covered = collect_covered({"mlp": {"up": plan}})
    assert (256, 384) in covered
    w = jnp.asarray(mask)

    @jax.jit
    def dense_fn(x):
        return x @ w             # plan covers (256, 384): routing miss

    x = jax.ShapeDtypeStruct((4, 256), jnp.float32)
    findings = audit_closure(dense_fn, [x], covered=covered)
    assert_code(findings, "J201", "error")


def test_routed_closure_is_clean(plan, mask):
    from repro.kernels.bsmm import plan_matmul
    covered = collect_covered({"mlp": {"up": plan}})
    w = jnp.asarray(mask)

    @jax.jit
    def routed(x):
        return plan_matmul(x, w, plan)

    x = jax.ShapeDtypeStruct((4, 256), jnp.float32)
    assert audit_closure(routed, [x], covered=covered) == []


def test_j202_f64_promotion():
    with jax.enable_x64(True):
        @jax.jit
        def f(x):
            return x.astype(jnp.float64) * 2.0
        findings = audit_closure(
            f, [jax.ShapeDtypeStruct((4,), jnp.float32)])
    assert_code(findings, "J202", "warning")


def test_j203_host_callback():
    @jax.jit
    def f(x):
        jax.debug.print("v={v}", v=x.sum())
        return x
    findings = audit_closure(f, [jax.ShapeDtypeStruct((4,), jnp.float32)])
    assert_code(findings, "J203", "warning")


def test_j204_unjitted_closure():
    findings = audit_closure(
        lambda x: x * 2, [jax.ShapeDtypeStruct((4,), jnp.float32)])
    assert_code(findings, "J204", "warning")


def test_j205_no_pallas_call_at_all(plan):
    covered = collect_covered({"up": plan})

    @jax.jit
    def elementwise(x):
        return x * 2 + 1         # no matmul, no pallas: routing is off

    findings = audit_closure(
        elementwise, [jax.ShapeDtypeStruct((4, 256), jnp.float32)],
        covered=covered)
    assert_code(findings, "J205", "error")
    assert "J201" not in codes_of(findings)


def test_j206_j207_hlo_cross_check():
    text = ("%ag = bf16[16,4096]{1,0} all-gather(%x), replica_groups={}\n"
            "%p = f64[8]{0} add(f64[8]{0} %a, f64[8]{0} %b)\n")
    findings = audit_hlo_text(text)
    assert_code(findings, "J206", "warning")
    assert_code(findings, "J207", "info")


def test_audit_compiled_clean():
    from repro.analysis import audit_compiled
    out = audit_compiled(lambda x: x * 2, [jnp.ones((4,), jnp.float32)])
    assert out == []


def test_j208_sharding_placement():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.analysis import audit_engine_sharding

    w = jnp.zeros((4, 4), jnp.float32)
    # >1-device mesh, params without any NamedSharding: error
    eng = SimpleNamespace(
        mesh=SimpleNamespace(size=2),
        generations=[SimpleNamespace(gid=0, params={"w": w})])
    assert_code(audit_engine_sharding(eng), "J208", "error")
    # NamedShardings present but all fully replicated: warning
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("model",))
    wr = jax.device_put(w, NamedSharding(mesh1, P()))
    eng2 = SimpleNamespace(
        mesh=SimpleNamespace(size=2),
        generations=[SimpleNamespace(gid=1, params={"w": wr})])
    assert_code(audit_engine_sharding(eng2), "J208", "warning")
    # 1-device mesh (or no mesh): nothing to place, silent
    eng3 = SimpleNamespace(mesh=mesh1, generations=eng.generations)
    assert audit_engine_sharding(eng3) == []
    assert audit_engine_sharding(SimpleNamespace(mesh=None)) == []


def test_unambiguous_covered_drops_shape_collisions(plan):
    plan_tree = {"up": plan}
    routed_only = {"w": jnp.zeros((256, 384), jnp.float32)}
    assert (256, 384) in unambiguous_covered(plan_tree, routed_only)
    # a second, non-routed weight of the same shape makes it ambiguous
    collided = {"w": jnp.zeros((256, 384), jnp.float32),
                "other": jnp.zeros((256, 384), jnp.float32)}
    assert unambiguous_covered(plan_tree, collided) == {}


# ---------------------------------------------------------------------------
# findings model + driver + CLI
# ---------------------------------------------------------------------------
def test_finding_rejects_unregistered_code():
    with pytest.raises(ValueError):
        Finding("error", "X999", "here", "nope")
    with pytest.raises(ValueError):
        Finding("fatal", "P101", "here", "nope")


def test_report_accounting():
    r = Report()
    r.add(Finding("error", "P101", "a", "m"))
    r.add(Finding("warning", "R005", "b", "m"))
    assert not r.ok and len(r.errors) == 1 and len(r.warnings) == 1
    assert r.by_code("P101")[0].where == "a"
    loaded = json.loads(r.to_json())
    assert loaded["summary"]["error"] == 1
    assert loaded["findings"][0]["code"] == "P101"


def test_lint_arch_cnn_smoke():
    rep = lint_arch("vgg11")
    assert rep.ok, rep.findings


@pytest.mark.slow
def test_lint_arch_serving_smoke():
    # full pipeline incl. ServeEngine hot-swap + P112 verification
    rep = lint_arch("llama3.2-3b")
    assert rep.ok, rep.findings


def test_cli_lint(capsys):
    from repro.api.cli import main
    assert main(["lint", "--arch", "vgg11", "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["arch"] == "vgg11" and out["summary"]["ok"]


def test_cli_lint_fails_on_error_findings(monkeypatch):
    from repro.api import cli as cli_mod
    import repro.analysis as analysis_mod

    def bad_lint(name, **kw):
        r = Report()
        r.add(Finding("error", "P101", f"{name}/x", "seeded"))
        return r

    monkeypatch.setattr(analysis_mod, "lint_arch", bad_lint)
    assert cli_mod.main(["lint", "--arch", "vgg11", "--json"]) == 1


# keep last: every registered R/P/J rule code must have a defect test
# above (K3xx codes are exercised by tests/test_kernel_audit.py, whose
# own coverage test closes the other half; tests/test_rules_meta.py
# asserts the two halves tile the registry exactly)
def test_every_rule_code_is_exercised():
    expected = {c for c in RULES if not c.startswith("K")}
    assert TESTED == expected, \
        f"untested rule codes: {sorted(expected - TESTED)}"
