"""The on-chip benchmark's check catches a broken training step.

Each test drives a whole run of a retrain cell at a small size on the
CPU (the chip look skipped), with the program's train step broken
underneath, and sees ``correct`` come out false: a step that returns
its state unchanged, and a step that leaves out half of the batch and
takes the mean over the rest.  A sound step comes out correct."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmarks", "chip"))

import jax  # noqa: E402

from chipbench import cells, harness  # noqa: E402

SMALL = {
    "yi6b-s8.retrain.t10": (
        {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 64, "d_ff": 384, "vocab_size": 1000},
        {"batch": 4, "seq_len": 32, "density": 0.25}),
    "vgg16-cifar10.retrain.t10": (
        {"convs": [{"out_channels": 32}, {"out_channels": 64, "pool": True}],
         "image_size": 8},
        {"batch": 16, "images": 64}),
}


def small_cell(name):
    cell = cells.load_cell(name)
    model, traffic = SMALL[name]
    (cell.config.get("arch") or cell.config["cnn"]).update(model)
    cell.traffic.update(traffic)
    return cell


def broken_steps(fault):
    """``make_train_step`` whose step has ``fault``."""
    from repro.train import loop
    real = loop.make_train_step

    def make(*args, **kw):
        kw["donate"] = False
        step = real(*args, **kw)

        def half(batch):
            return jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)

        def unchanged(*xs):
            out = step(*xs)
            return (*xs[:-1], out[-1])

        def half_batch(*xs):
            return step(*xs[:-1], half(xs[-1]))

        return {"unchanged": unchanged, "half_batch": half_batch,
                None: step}[fault]
    return make


def run_cell(name, fault, monkeypatch):
    from repro.train import loop
    monkeypatch.setattr(loop, "make_train_step", broken_steps(fault))
    cell = small_cell(name)
    run = harness.Run(cell, 2**31 + 11, 0.5, False, jax.devices(),
                      time.perf_counter())
    out = cells.job_module(cell).run(run)
    return out, {c.name: c for c in out.checks}


@pytest.mark.parametrize("name", sorted(SMALL))
@pytest.mark.parametrize("fault,caught_by", [
    (None, ()),
    ("unchanged", ("grad", "change")),
    ("half_batch", ("grad",)),
])
def test_a_broken_step_is_not_correct(name, fault, caught_by, monkeypatch):
    out, checks = run_cell(name, fault, monkeypatch)
    assert out.attempted > 0 and out.failed == 0
    failed = {k for k, c in checks.items() if not c.ok}
    # each cell compares its own gradient and change numbers
    assert all(any(k.startswith(f) for k in failed) for f in caught_by), \
        checks
    assert bool(failed) == (fault is not None), checks
