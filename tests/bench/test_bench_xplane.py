"""Trace reduction of the on-chip benchmark (benchmarks/chip/chipbench/
xplane.py): busy time, idle gaps, op time by name and roofline share,
on hand-built events and on a trimmed trace recorded on a TPU v5e."""
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                     "chip")
sys.path.insert(0, BENCH)

from chipbench import xplane  # noqa: E402
from chipbench.xplane import Event  # noqa: E402

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS = xplane.OPS_LINE


def op(name, start, dur, plane=DEV0, cat=""):
    return Event(plane, OPS, name, float(start), float(dur), cat)


def span(name, start, dur):
    return Event(HOST, "python", name, float(start), float(dur))


def test_union_merges_overlaps_and_touching_intervals():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [
        (0, 4), (5, 7), (9, 10)]


def test_busy_counts_overlapping_ops_once_and_clips_to_the_window():
    events = [op("a", 0, 40), op("b", 20, 40), op("c", 90, 30),
              span("bench.window", 10, 100)]
    # [10, 60] and [90, 110] lie in the window [10, 110]
    assert xplane.busy_seconds(events, 10, 110) == pytest.approx(70e-9)


def test_busy_is_averaged_over_the_device_planes():
    events = [op("a", 0, 100), op("a", 0, 50, plane=DEV1)]
    assert xplane.busy_seconds(events, 0, 100) == pytest.approx(75e-9)


def test_host_events_are_not_device_time():
    events = [span("bench.step", 0, 100), op("a", 0, 10)]
    assert xplane.busy_seconds(events, 0, 100) == pytest.approx(10e-9)
    assert xplane.device_ops(events) == [events[1]]


@pytest.mark.parametrize("raw,name", [
    ("%fusion.12 = f32[8,128]{1,0} fusion(f32[8,128] %p), kind=kLoop",
     "fusion"),
    ("%while.3", "while"), ("bsmm_dx", "bsmm_dx"), ("copy-done", "copy-done"),
])
def test_op_name_drops_hlo_text_and_suffixes(raw, name):
    assert xplane.op_name(raw) == name


def test_op_seconds_by_name_drops_compiler_suffixes():
    events = [op("%bsmm_dx.3", 0, 10), op("bsmm_dx", 20, 5),
              op("bsmm_dw.1", 30, 7), op("fusion.2", 40, 100)]
    got = xplane.op_seconds(events,
                            lambda e: xplane.op_name(e.name) == "bsmm_dx")
    assert got == pytest.approx(15e-9)
    top = xplane.top_ops(events, 2)
    assert [n for n, _ in top] == ["fusion", "bsmm_dx"]
    assert top[1][1] == pytest.approx(15e-9)


def test_top_ops_count_each_op_without_the_ops_nested_in_it():
    events = [op("%while.1", 0, 100), op("fusion.1", 10, 30),
              op("%bsmm_fwd", 50, 40), op("copy", 60, 10),
              op("fusion.2", 120, 5)]
    got = dict(xplane.self_seconds(events))
    assert got["while"] == pytest.approx(30e-9)
    assert got["fusion"] == pytest.approx(35e-9)
    assert got["bsmm_fwd"] == pytest.approx(30e-9)
    assert got["copy"] == pytest.approx(10e-9)
    assert sum(got.values()) == pytest.approx(
        xplane.busy_seconds(events, 0, 200))


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    events = [op("a", 0, 10), op("b", 50, 10), op("c", 65, 35),
              span("bench.window", 0, 100), span("bench.step", 0, 60),
              span("bench.data", 12, 30), span("bench.step", 58, 10)]
    gaps = xplane.idle_gaps(events, 0, 100, n=2)
    assert gaps[0] == ("bench.data", pytest.approx(40e-9))
    assert gaps[1] == ("bench.step", pytest.approx(5e-9))


def test_window_is_the_bench_window_span():
    events = [span("bench.step", 5, 1), span("bench.window", 3, 90)]
    assert xplane.window_of(events) == (3.0, 93.0)
    assert xplane.window_of([op("a", 0, 1)]) is None


def reader(name):
    """``read`` of the metric file ``metrics/<name>.py``."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


KERNEL = ('%custom-call.7 = bf16[8,128]{1,0} custom-call(bf16[8,128] %x), '
          'custom_call_target="tpu_custom_call"')


def context(events, calls, steps=1):
    from chipbench import harness
    return harness.MetricContext(
        events=events, window=xplane.window_of(events), work={"bsmm": calls},
        peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}, steps=steps)


@pytest.mark.parametrize("flops,nbytes,share,bound", [
    (197e12 * 2e-6, 0.0, 50.0, "flops"),      # 2 us of a 4 us kernel
    (1.0, 819e9 * 1e-6, 25.0, "bytes"),
    (197e12 * 4e-6, 819e9 * 1e-6, 100.0, "flops"),
])
def test_roofline_share_and_its_bound(flops, nbytes, share, bound):
    events = [op(KERNEL, 1000, 4000), op("fusion.1", 5000, 100),
              span("bench.window", 0, 10000)]
    got, which = reader("bsmm_roofline.train").read(
        context(events, [(flops, nbytes)]))
    assert got == pytest.approx(share)
    assert which == bound


def test_roofline_without_kernel_time_gives_no_share():
    events = [op("fusion.1", 0, 10), span("bench.window", 0, 100)]
    assert reader("bsmm_roofline.train").read(
        context(events, [(1.0, 1.0)])) is None


def test_events_round_trip_through_the_trimmed_format(tmp_path):
    events = [op("a", 0, 10, cat="convolution"), span("bench.window", 0, 20)]
    path = str(tmp_path / "t.json.gz")
    xplane.save(events, path)
    assert xplane.read(path) == events


# -- a trimmed trace recorded on a TPU v5e: one yi6b-s8 retrain step and
# one VGG-16 retrain step (the device's XLA Ops and the bench.* spans)
DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = ["yi6b-s8.retrain.step.json.gz", "vgg16-cifar10.retrain.step.json.gz"]


def brute_busy_ns(events, t0, t1):
    """Busy time by a sweep over every op boundary (no interval union)."""
    ops = [(max(e.start_ns, t0), min(e.end_ns, t1))
           for e in xplane.device_ops(events) if e.end_ns > t0
           and e.start_ns < t1]
    cuts = sorted({x for iv in ops for x in iv})
    return sum(b - a for a, b in zip(cuts, cuts[1:])
               if any(s <= a and b <= f for s, f in ops))


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_step_busy_idle_and_self_time(name):
    events = xplane.read(os.path.join(DATA, name))
    t0, t1 = xplane.window_of(events)
    busy = xplane.busy_seconds(events, t0, t1)
    assert busy * 1e9 == pytest.approx(brute_busy_ns(events, t0, t1))
    assert 0 < busy <= (t1 - t0) / 1e9
    inside = [e for e in events if t0 <= e.start_ns <= t1]
    # each op's own time, nested ops taken out, adds up to the busy time
    assert sum(xplane.self_seconds(inside).values()) == pytest.approx(
        busy, rel=1e-3)
    gaps = xplane.idle_gaps(events, t0, t1, n=1000)
    assert sum(g for _, g in gaps) == pytest.approx((t1 - t0) / 1e9 - busy,
                                                    rel=1e-6, abs=1e-9)


def test_recorded_retrain_step_kernel_time_and_roofline():
    """In the yi retrain step every Pallas kernel is a bsmm launch: 7
    projections x 4 layers x (forward, rematerialised forward, dx, dw)."""
    from chipbench import harness, work
    events = xplane.read(os.path.join(DATA, RECORDED[0]))
    bsmm = reader("bsmm_roofline.train")
    kernels = [e for e in xplane.device_ops(events) if bsmm.is_kernel(e)]
    assert len(kernels) == 7 * 4 * 4
    shape = {"n_layers": 4, "d_model": 4096, "n_heads": 32, "n_kv_heads": 4,
             "head_dim": 128, "d_ff": 11008, "vocab_rows": 8192}
    tiles = {"attn/wq": [102] * 4, "attn/wk": [13] * 4, "attn/wv": [13] * 4,
             "attn/wo": [102] * 4, "mlp/up": [275] * 4,
             "mlp/gate": [275] * 4, "mlp/down": [275] * 4}
    ctx = harness.MetricContext(
        events=events, window=xplane.window_of(events),
        work=work.lm_train_step(shape, tiles, 4, 512),
        peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}, steps=1)
    secs = sum(e.dur_ns for e in kernels) / 1e9
    least, bound = work.least_seconds(ctx.work["bsmm"], 197e12, 819e9)
    share, which = bsmm.read(ctx)
    assert share == pytest.approx(100 * least / secs)
    assert which == bound == "flops"
    assert 0 < share < 100
