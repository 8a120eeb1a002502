"""Kernel auditor (K300–K306): seeded-defect tests.

Mirrors tests/test_analysis.py's convention: every K rule code must be
demonstrated by planting the defect it exists to catch and asserting
the auditor reports it; the coverage test at the bottom closes the K
half of the registry (test_analysis.py closes R/P/J, and
test_rules_meta.py asserts the two halves tile the whole registry).
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.analysis import (RULES, audit_kernel_spec, audit_kernels,
                            default_cases, explain, rules_markdown)
from repro.analysis.kernel_audit import audit_case
from repro.configs.base import MXU_TILE
from repro.kernels import AUDITED_KERNELS, ScratchSpec

TESTED = set()


def codes_of(findings):
    return {f.code for f in findings}


def assert_code(findings, code):
    TESTED.add(code)
    got = codes_of(findings)
    assert code in got, f"expected {code} in {got}: {findings}"


def assert_only(findings, code):
    assert_code(findings, code)
    assert codes_of(findings) == {code}, findings


@pytest.fixture(scope="module")
def cases():
    return {c.name: c for c in default_cases()}


# ---------------------------------------------------------------------------
# the clean path: every registered kernel's canonical case audits green
# ---------------------------------------------------------------------------
def test_registered_kernels_all_audited(cases):
    # every registered kernel has a case under its own name; further
    # cases (the bsmm ``.rows`` geometry) audit registered kernels only
    assert set(AUDITED_KERNELS) <= set(cases)
    assert {c.spec.name for c in cases.values()} == set(AUDITED_KERNELS)
    assert {n for n in cases if n not in AUDITED_KERNELS} == {
        "bsmm_fwd.rows", "bsmm_fwd_epilogue.rows", "bsmm_dx.rows",
        "bsmm_dw.rows"}


@pytest.mark.parametrize("name", ["bsmm_fwd.rows", "bsmm_fwd_epilogue.rows",
                                  "bsmm_dx.rows"])
def test_bsmm_guarded_slots_repeat_the_previous_block(cases, name):
    """Row blocks taller than a tile, and every guarded slot after a
    column's first gathers the blocks of the step before it: an
    unchanged block index, so the pipeline fetches nothing for it."""
    spec = cases[name].spec
    assert spec.inputs[0].block[0] > MXU_TILE
    cells = list(np.ndindex(*spec.grid))
    repeats = 0
    for prev, c in zip(cells, cells[1:]):
        if c[-1] == 0 or spec.guard(*c, *spec.scalars):
            continue
        repeats += 1
        for bm in spec.inputs:
            assert tuple(bm.index_map(*c, *spec.scalars)) == \
                tuple(bm.index_map(*prev, *spec.scalars)), (bm.name, c)
    assert repeats


def test_default_cases_audit_clean():
    findings = audit_kernels()
    assert findings == [], findings


def test_audit_is_pure_host_numpy(cases):
    # the audited specs' index maps and guards must evaluate on plain
    # ints/numpy — no tracing, which is what makes the lint gate cheap
    for case in cases.values():
        for f in audit_case(case):
            raise AssertionError(f)


# ---------------------------------------------------------------------------
# K300 — malformed specs are reported, not crashed on
# ---------------------------------------------------------------------------
def test_k300_block_rank_mismatch(cases):
    s = cases["bsmm_fwd"].spec
    x = s.inputs[0]
    bad = dataclasses.replace(
        s, inputs=(dataclasses.replace(x, block=(128,)),) + s.inputs[1:])
    assert_only(audit_kernel_spec(bad), "K300")


def test_k300_uneven_tiling(cases):
    s = cases["bsmm_fwd"].spec
    x = s.inputs[0]
    bad = dataclasses.replace(
        s, inputs=(dataclasses.replace(x, block=(100, 128)),)
        + s.inputs[1:])
    assert_only(audit_kernel_spec(bad), "K300")


def test_k300_raising_index_map(cases):
    s = cases["bsmm_fwd"].spec
    x = s.inputs[0]

    def boom(*a):
        raise RuntimeError("no")

    bad = dataclasses.replace(
        s, inputs=(dataclasses.replace(x, index_map=boom),)
        + s.inputs[1:])
    assert_only(audit_kernel_spec(bad), "K300")


# ---------------------------------------------------------------------------
# K301 — output coverage
# ---------------------------------------------------------------------------
def test_k301_output_map_collapses_tiles(cases):
    # every parallel class writes row 0: rows 1+ never written, row 0
    # written by multiple classes
    s = cases["bsmm_fwd"].spec
    o = s.outputs[0]
    bad = dataclasses.replace(
        s, outputs=(dataclasses.replace(
            o, index_map=lambda i, j, k, cnt, idx: (0, j)),))
    assert_code(audit_kernel_spec(bad), "K301")


def test_k301_output_moves_along_arbitrary_axis(cases):
    # revolving accumulator would flush to a different tile per k step
    s = cases["bsmm_fwd"].spec
    o = s.outputs[0]
    bad = dataclasses.replace(
        s, outputs=(dataclasses.replace(
            o, index_map=lambda i, j, k, cnt, idx: (i, (j + k) % 2)),))
    assert_code(audit_kernel_spec(bad), "K301")


# ---------------------------------------------------------------------------
# K302 — bounds, including guarded cells (their DMA still happens)
# ---------------------------------------------------------------------------
def test_k302_index_map_off_ragged_edge(cases):
    s = cases["bsmm_fwd"].spec
    x = s.inputs[0]
    bad = dataclasses.replace(
        s, inputs=(dataclasses.replace(
            x, index_map=lambda i, j, k, cnt, idx: (i + 1, idx[j, k])),)
        + s.inputs[1:])
    assert_only(audit_kernel_spec(bad), "K302")


def test_k302_block_table_entry_past_pool(cases):
    # a DEAD table slot pointing past the pool: the guarded cell's DMA
    # still prefetches the block, so this must be an error even though
    # pl.when masks the compute
    case = cases["paged_attention_gqa"]
    from repro.kernels.paged_attention import (BLOCK_TOKENS, PagedGeometry,
                                               paged_attention_spec)
    B, Hq, Hkv, hd, P, NB = 2, 4, 2, 8, 5, 3
    tables = np.array([[1, 2, P], [3, 0, 0]], np.int32)   # P == pool size
    lengths = np.array([BLOCK_TOKENS + 2, 7], np.int32)
    geo = PagedGeometry(B=B, Hq=Hq, hd=hd, Hkv=Hkv, T=BLOCK_TOKENS,
                        NB=NB, P=P, dv=hd)
    spec = paged_attention_spec(geo, tables, lengths, fused_v=False)
    findings = audit_kernel_spec(spec,
                                 expected_gathers=case.expected_gathers)
    assert_only(findings, "K302")


# ---------------------------------------------------------------------------
# K303 — guard vs liveness truth, both directions
# ---------------------------------------------------------------------------
def test_k303_loose_guard_streams_dead_blocks(cases):
    # bsmm_dx has dead slots (rows with 1 live tile, nmax 2); widen the
    # guard by one so dead slots' scratch gathers join the accumulation
    case = cases["bsmm_dx"]
    s = case.spec
    hi = s.grid[2]
    bad = dataclasses.replace(
        s, guard=lambda i, k, t, cnt, idx: bool(t <= cnt[k]) and t < hi)
    assert_only(
        audit_kernel_spec(bad, expected_gathers=case.expected_gathers),
        "K303")


def test_k303_tight_guard_drops_live_work(cases):
    case = cases["bsmm_dx"]
    s = case.spec
    bad = dataclasses.replace(
        s, guard=lambda i, k, t, cnt, idx: bool(t + 1 < cnt[k]))
    assert_only(
        audit_kernel_spec(bad, expected_gathers=case.expected_gathers),
        "K303")


# ---------------------------------------------------------------------------
# K304 — accumulator dtype/shape
# ---------------------------------------------------------------------------
def test_k304_f16_accumulator(cases):
    s = cases["bsmm_fwd"].spec
    bad = dataclasses.replace(
        s, scratch=(ScratchSpec(s.scratch[0].shape, np.float16,
                                "accumulator"),))
    assert_only(audit_kernel_spec(bad), "K304")


def test_k304_accumulator_shape_mismatch(cases):
    s = cases["flash_attention"].spec
    acc = s.scratch[0]
    assert acc.role == "accumulator"
    bad = dataclasses.replace(
        s, scratch=(ScratchSpec((acc.shape[0], acc.shape[1] // 2),
                                np.float32, "accumulator"),)
        + s.scratch[1:])
    assert_only(audit_kernel_spec(bad), "K304")


def test_k304_f16_softmax_state(cases):
    s = cases["paged_attention_gqa"].spec
    sm = next(x for x in s.scratch if x.role == "softmax_state")
    scratch = tuple(
        ScratchSpec(x.shape, np.float16, x.role) if x is sm else x
        for x in s.scratch)
    bad = dataclasses.replace(s, scratch=scratch)
    assert_only(audit_kernel_spec(bad), "K304")


# ---------------------------------------------------------------------------
# K305 — VMEM budget
# ---------------------------------------------------------------------------
def test_k305_oversized_block_exceeds_budget(cases):
    # a (2048, 2048) f32 block double-buffers to 32 MiB > the 16 MiB
    # budget; shape stretched so the index maps stay in bounds and the
    # finding is K305 alone
    s = cases["bsmm_fwd"].spec
    x = s.inputs[0]
    bad = dataclasses.replace(
        s, inputs=(dataclasses.replace(x, block=(2048, 2048),
                                       shape=(4096, 6144)),)
        + s.inputs[1:])
    assert_only(audit_kernel_spec(bad), "K305")


def test_k305_respects_backend_budget(cases, monkeypatch):
    from repro.configs import base as base_mod
    monkeypatch.setitem(base_mod.VMEM_BUDGET_BYTES, "tiny_backend", 1024)
    findings = audit_kernel_spec(cases["bsmm_fwd"].spec,
                                 backend="tiny_backend")
    assert_code(findings, "K305")


# ---------------------------------------------------------------------------
# K306 — perf-model agreement
# ---------------------------------------------------------------------------
def test_k306_tampered_cost_detected(cases):
    case = cases["bsmm_fwd"]
    for field in ("passes", "flops", "hbm_bytes"):
        bad = dataclasses.replace(
            case.cost, **{field: getattr(case.cost, field) + 1})
        findings = audit_kernel_spec(
            case.spec, expected_gathers=case.expected_gathers, cost=bad)
        assert_only(findings, "K306")


def test_k306_stale_plan_cost_detected(cases):
    # the signature drift: perf model predicting from a DIFFERENT plan
    # than the kernel launches (e.g. cost computed pre-hot-swap)
    from repro.core.perf_model import bsmm_fwd_cost
    from repro.kernels.bsmm import make_tile_plan
    case = cases["bsmm_fwd"]
    denser = np.ones((3 * 128, 2 * 128), np.float32)
    stale = bsmm_fwd_cost(make_tile_plan(denser, tile=128), 256, bm=128)
    findings = audit_kernel_spec(case.spec, cost=stale)
    assert_only(findings, "K306")


# ---------------------------------------------------------------------------
# registry + CLI surface
# ---------------------------------------------------------------------------
def test_k_rules_registered_and_documented():
    kcodes = {c for c in RULES if c.startswith("K")}
    assert kcodes == {"K300", "K301", "K302", "K303", "K304", "K305",
                      "K306"}
    md = rules_markdown()
    for code in sorted(kcodes):
        assert code in md
        text = explain(code)
        assert RULES[code].title in text and RULES[code].doc in text


def test_explain_unknown_code_raises():
    with pytest.raises(KeyError):
        explain("K999")


def test_cli_lint_kernels_json(capsys):
    from repro.api.cli import main
    assert main(["lint", "--kernels", "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["arch"] == "kernels" and out["summary"]["ok"]


def test_cli_lint_kernels_fails_on_defect(monkeypatch, capsys):
    from repro.analysis import Report, error
    from repro.api import cli as cli_mod

    # cmd_lint imports lint_kernels from the package namespace
    monkeypatch.setattr(
        "repro.analysis.lint_kernels",
        lambda backend="tpu": Report(
            findings=[error("K301", "kernels/bsmm_fwd", "seeded")]))
    assert cli_mod.main(["lint", "--kernels", "--json"]) == 1
    out = json.loads(capsys.readouterr().out.strip())
    assert out["findings"][0]["code"] == "K301"


def test_cli_lint_explain(capsys):
    from repro.api.cli import main
    assert main(["lint", "--explain", "k301", "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["code"] == "K301" and out["family"] == "kernel auditor"
    assert main(["lint", "--explain", "K999"]) == 2


def test_cli_lint_requires_a_target(capsys):
    from repro.api.cli import main
    assert main(["lint"]) == 2


# keep last: the K half of the registry must be fully exercised above
def test_every_k_rule_code_is_exercised():
    expected = {c for c in RULES if c.startswith("K")}
    assert TESTED == expected, \
        f"untested K rules: {sorted(expected - TESTED)}"
