"""Readings from the program's own names in a trace (benchmarks/chip/
chipbench/names.py and the readers that use it): the named bsmm
launches, on hand-built events and on trimmed traces recorded on a TPU
v5e; and the gaps that Trainer.run's spans name."""
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                     "chip")
sys.path.insert(0, BENCH)

from chipbench import cells, harness, names, work, xplane  # noqa: E402
from chipbench.xplane import Event  # noqa: E402

DEV0, HOST = "/device:TPU:0", "/host:CPU"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["bsmm_fwd_roofline.train", "bsmm_dx_roofline.train",
       "bsmm_dw_roofline.train"]


def kernel(name, start, dur):
    return Event(DEV0, xplane.OPS_LINE,
                 f"%{name}.3 = bf16[8,128]{{1,0}} custom-call(bf16[8,128] "
                 f'%x), custom_call_target="tpu_custom_call"',
                 float(start), float(dur))


def op(name, start, dur):
    return Event(DEV0, xplane.OPS_LINE, name, float(start), float(dur))


def span(name, start, dur):
    return Event(HOST, "python3", name, float(start), float(dur))


def reader(name):
    """``read`` of the metric file ``metrics/<name>.py``."""
    return cells.metric_reader(name)


def _is_kernel():
    """What ``bsmm_roofline.train`` counts as kernel time."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bsmm_roofline_train", os.path.join(BENCH, "metrics",
                                            "bsmm_roofline.train.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.is_kernel


is_kernel = _is_kernel()


def context(events, work_counts=None, steps=1):
    return harness.MetricContext(
        events=events, window=xplane.window_of(events),
        work=work_counts or {}, peaks=PEAKS, steps=steps)


@pytest.mark.parametrize("raw,which", [
    ("bsmm_fwd", "fwd"), ("jvp_bsmm_fwd_", "fwd"),
    ("bsmm_fwd_epilogue", "fwd"), ("jvp_bsmm_fwd_epilogue_", "fwd"),
    ("bsmm_dx", "dx"), ("transpose_jvp_bsmm_dx__", "dx"),
    ("bsmm_dw", "dw"), ("transpose_jvp_bsmm_dw__", "dw"),
    ("closed_call", None), ("checkpoint", None), ("masked_matmul", None),
    ("xbsmm_dxy", None),
])
def test_a_launch_is_matched_by_its_kernel_name_as_a_word(raw, which):
    assert names.bsmm_pass(kernel(raw, 0, 1)) == which


def test_work_lists_each_products_forward_dx_and_dw_in_turn():
    """The per-pass readers slice ``work.lm_train_step``'s products
    ``[0::3]``, ``[1::3]``, ``[2::3]``: pin that order."""
    shape = {"n_layers": 2, "d_model": 256, "n_heads": 2, "n_kv_heads": 1,
             "head_dim": 128, "d_ff": 384, "vocab_rows": 1024}
    live = {"attn/wq": [1, 2], "mlp/up": [3, 1]}
    calls = work.lm_train_step(shape, live, batch=2, seq=4)["bsmm"]
    dims = work.projection_dims(shape)
    expect = [work.product(8, *dims[k], n * work.TILE ** 2, 2)
              for k, per_layer in live.items() for n in per_layer]
    n = len(names.PASS_ORDER)
    for i, p in enumerate(names.PASS_ORDER):
        assert calls[i::n] == [e[p] for e in expect]


def test_pass_rooflines_read_their_own_launches_and_products():
    fl = 197e12 * 1e-6                        # 1 us at the bf16 peak
    calls = [(fl, 0.0), (2 * fl, 0.0), (4 * fl, 0.0)] * 2
    events = [kernel("bsmm_fwd", 0, 1000), kernel("jvp_bsmm_fwd_", 1000,
                                                  3000),
              kernel("transpose_jvp_bsmm_dx__", 4000, 8000),
              kernel("bsmm_dw", 12000, 16000),
              kernel("closed_call", 28000, 500),
              span("bench.window", 0, 40000)]
    ctx = context(events, {"bsmm": calls})
    # fwd: 2 us over 4 us; dx: 4 us over 8 us; dw: 8 us over 16 us
    for p in ("fwd", "dx", "dw"):
        share, bound = reader(f"bsmm_{p}_roofline.train")(ctx)
        assert share == pytest.approx(50.0)
        assert bound == "flops"
    # the unnamed launch counts in bsmm_roofline.train and in no pass
    per_pass = sum(ctx.op_seconds(lambda e, p=p: names.bsmm_pass(e) == p)
                   for p in names.PASS_ORDER)
    assert per_pass == pytest.approx(28e-6)


def test_a_pass_without_launches_or_work_reads_nothing():
    events = [kernel("bsmm_fwd", 0, 10), span("bench.window", 0, 100)]
    assert reader("bsmm_dx_roofline.train")(
        context(events, {"bsmm": [(1.0, 1.0)] * 3})) is None
    assert reader("bsmm_fwd_roofline.train")(context(events)) is None


def test_idle_gaps_are_named_by_the_programs_train_spans():
    # the harness's bench.step holds Trainer.run's train.step, which
    # holds its phases: in events that keep them (the harness's loader
    # keeps only bench.* host spans), a gap is named by the innermost
    events = [op("a", 0, 10), op("b", 40, 20), op("c", 70, 30),
              span("bench.window", 0, 100), span("bench.step", 0, 69),
              span("train.step", 2, 66), span("train.data", 5, 10),
              span("train.dispatch", 20, 25), span("train.wait", 45, 17),
              span("bench.step", 69, 31), span("train.step", 70, 30)]
    gaps = xplane.idle_gaps(events, 0, 100, n=2)
    assert gaps[0] == ("train.dispatch", pytest.approx(30e-9))
    assert gaps[1] == ("train.step", pytest.approx(10e-9))


# -- the trimmed traces recorded on a TPU v5e.  The first two hold one step
# of a program that wrote none of these names: every new reader reads
# nothing there
DATA = os.path.join(os.path.dirname(__file__), "data")
UNNAMED = ["yi6b-s8.retrain.step.json.gz",
           "vgg16-cifar10.retrain.step.json.gz"]


@pytest.mark.parametrize("trace", UNNAMED)
@pytest.mark.parametrize("metric", NEW)
def test_new_readers_read_nothing_where_the_program_named_nothing(
        trace, metric):
    events = xplane.read(os.path.join(DATA, trace))
    ctx = context(events, work_counts=yi_work(), steps=1)
    assert reader(metric)(ctx) is None


def yi_work():
    """The required work of the yi6b-s8 retrain step at the recorded
    ticket (10% of each projection's tiles, per layer)."""
    shape = {"n_layers": 4, "d_model": 4096, "n_heads": 32, "n_kv_heads": 4,
             "head_dim": 128, "d_ff": 11008, "vocab_rows": 8192}
    tiles = {"attn/wq": [102] * 4, "attn/wk": [13] * 4, "attn/wv": [13] * 4,
             "attn/wo": [102] * 4, "mlp/up": [275] * 4,
             "mlp/gate": [275] * 4, "mlp/down": [275] * 4}
    return work.lm_train_step(shape, tiles, 4, 512)


# -- and from a program that names them: one step of each cell, with
# the device's XLA Ops, the bench.* spans and Trainer.run's train.*
# spans of the full trace (the harness's loader keeps only bench.* host
# spans; what a loader that also kept train.* would name is shown here)
NAMED_YI = "yi6b-s8.retrain.named-step.json.gz"
NAMED_VGG = "vgg16-cifar10.retrain.named-step.json.gz"


def test_recorded_yi_step_names_every_launch_by_its_pass():
    """7 projections x 4 layers: forward twice (the rematerialised
    forward), dx and dw once; the passes' seconds are the kernels'."""
    from collections import Counter
    events = xplane.read(os.path.join(DATA, NAMED_YI))
    kernels = [e for e in xplane.device_ops(events) if is_kernel(e)]
    assert Counter(names.bsmm_pass(e) for e in kernels) == {
        "fwd": 56, "dx": 28, "dw": 28}
    ctx = context(events, yi_work())
    per_pass = {p: ctx.op_seconds(lambda e, p=p: names.bsmm_pass(e) == p)
                for p in names.PASS_ORDER}
    assert sum(per_pass.values()) == pytest.approx(
        ctx.op_seconds(is_kernel), rel=1e-12)
    calls = yi_work()["bsmm"]
    for i, p in enumerate(names.PASS_ORDER):
        least, bound = work.least_seconds(calls[i::3], 197e12, 819e9)
        share, which = reader(f"bsmm_{p}_roofline.train")(ctx)
        assert share == pytest.approx(100 * least / per_pass[p])
        assert which == bound == "flops"
    # over the whole traced window these read 0.93, 1.81 and 1.84% on a
    # v5e: each pass at 0.5-2.5% of its roofline
    assert 0.5 < reader("bsmm_fwd_roofline.train")(ctx)[0] < 1.2
    for p in ("dx", "dw"):
        assert 1.2 < reader(f"bsmm_{p}_roofline.train")(ctx)[0] < 2.5


def test_recorded_vgg_step_idles_in_train_dispatch():
    """VGG's device waits for the host: the step's longest gap lies in
    ``train.dispatch``; no launch is a Pallas kernel."""
    events = xplane.read(os.path.join(DATA, NAMED_VGG))
    ctx = context(events)
    gaps = xplane.idle_gaps(events, *ctx.window, n=2)
    assert gaps[0][0] == "train.dispatch"
    assert gaps[0][1] > 0.5 * ctx.window_s
    assert {g[0] for g in gaps} <= {"train.data", "train.dispatch",
                                    "train.wait", "train.step"}
    step, = [e for e in events if e.name == "train.step"]
    phases = sorted((e for e in events if e.name in (
        "train.data", "train.dispatch", "train.wait")),
        key=lambda e: e.start_ns)
    assert [e.name for e in phases] == ["train.data", "train.dispatch",
                                        "train.wait"]
    assert step.start_ns <= phases[0].start_ns
    assert phases[-1].end_ns <= step.end_ns
    # the host's own time in the step, 22.2 ms on a v5e traced
    assert 15e6 < step.dur_ns - phases[-1].dur_ns < 30e6
    for metric in NEW:
        assert reader(metric)(context(events, yi_work())) is None
