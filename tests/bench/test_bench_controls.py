"""The on-chip benchmark's control, kept at a size a test run holds.

The control is the computation one precision below the configuration's,
in the program's place: for the bfloat16 yi cell the reference with
float8 e4m3 operands in every projection and the head, for the float32
VGG cell the reference at ``high`` (three bfloat16 passes).  On the chip
each is read at its cell's own size (``benchmarks/chip/calibrate.py``).
Here, at a small size on the CPU, each control must read above every
sound reading of the program by at least twice on the number that
separates them on the chip; the yi control, and the half-batch fault of
both cells, must come out not correct under the cell's own limits
(``compare.judge``), where the program comes out correct.  (The VGG
control fails its limits only at the cell's depth, where BatchNorm over
13 layers amplifies its rounding.)"""
import functools
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmarks", "chip"))

import jax  # noqa: E402

from chipbench import cells, compare, harness  # noqa: E402

SEEDS = (1, 2)
# per cell: a size a test run holds, and the number on which the
# control separates from the program on the chip
SMALL = {
    "yi6b-s8.retrain.t10": (
        {"n_layers": 2, "d_model": 512, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 128, "d_ff": 768, "vocab_size": 2000},
        {"batch": 2, "seq_len": 128, "density": 0.25}, "loss_gap"),
    "vgg16-cifar10.retrain.t10": (
        {"convs": [{"out_channels": 32}, {"out_channels": 64, "pool": True}],
         "image_size": 8},
        {"batch": 16, "images": 64}, "grad_median_gap"),
}


@functools.lru_cache(maxsize=None)
def readings(name):
    """(limits, program's numbers, control's and fault's numbers) per
    seed, at the small size."""
    model, traffic, _ = SMALL[name]
    cell = cells.load_cell(name)
    (cell.config.get("arch") or cell.config["cnn"]).update(model)
    cell.traffic.update(traffic)
    job = cells.job_module(cell)
    runs = [harness.Run(cell, s, 0.0, False, jax.devices(),
                        time.perf_counter()) for s in SEEDS]
    return (cell.traffic["limits"], job.calibrate(runs, "program"),
            job.calibrate(runs, "control"))


def failed(numbers, limits):
    return {c.name for c in compare.judge(
        {k: (v, "") for k, v in numbers.items()}, limits) if not c.ok}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_the_control_reads_above_the_program(name):
    num = SMALL[name][2]
    _, prog, ctl = readings(name)
    lower = max(prog[s]["program"][num] for s in SEEDS)
    upper = min(ctl[s]["control"][num] for s in SEEDS)
    assert upper >= 2 * lower, (lower, upper)


@pytest.mark.parametrize("name,tag", [
    ("yi6b-s8.retrain.t10", "control"),
    ("yi6b-s8.retrain.t10", "half_batch"),
    ("vgg16-cifar10.retrain.t10", "half_batch"),
])
def test_the_control_and_the_fault_are_not_correct(name, tag):
    limits, prog, ctl = readings(name)
    for s in SEEDS:
        assert not failed(prog[s]["program"], limits), prog[s]
        assert failed(ctl[s][tag], limits), ctl[s][tag]
