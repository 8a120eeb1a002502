"""Job ``lm_retrain``: retrain a decoder's ticket through the program's
block-sparse step, back to back for the whole window.

Set-up builds one ``Trainer`` through ``LMAdapter.make_trainer`` (the
documented handoff point) with the benchmark's token source as
``data=``, and drives it through its first ``check_steps`` steps with
``Trainer.run`` on rows that all differ, reading the losses, the first
gradient (from AdamW's first moment after step 1) and the change of
the weights.  The window keeps calling ``Trainer.run(1)`` on the same
object.  After it, the program's state is freed and the plain reference
retrains the same ticket from the same seed on the same rows.

Traffic keys: ``batch``, ``seq_len``, ``density``, ``ticket_seed``,
``learning_rate``, ``check_steps`` and ``limits`` (one per check).
"""
from __future__ import annotations

import functools
import gc
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench import compare, data, weights, work
from chipbench.harness import Outcome, Run
from chipbench.reference import llama

ADAM_B1 = 0.9       # AdamW's first-moment decay in the program and here


def arch(config: Dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(**config["arch"])


def shape_of(cfg) -> Dict:
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim_, "d_ff": cfg.d_ff,
            "rope_theta": cfg.rope_theta, "vocab_rows": cfg.padded_vocab}


def layout(cfg):
    from repro.models import transformer
    return jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def stacked_pred(cfg):
    return lambda p: p.startswith("segments/") and cfg.n_layers > 1


def live_tiles(masks, cfg) -> Dict[str, list]:
    """Live 128x128 tiles per projection and layer (keys of
    ``work.projection_dims``)."""
    fm = compare.flat(masks)
    out = {}
    for key, path in llama.layer_paths().items():
        if key not in work.projection_dims(shape_of(cfg)):
            continue
        m = fm[path].reshape(-1, *fm[path].shape[-2:])
        L, K, N = m.shape
        t = m.reshape(L, K // work.TILE, work.TILE, N // work.TILE,
                      work.TILE).max(axis=(2, 4)).sum(axis=(1, 2))
        out[key] = [int(x) for x in jax.device_get(t)]
    return out


def print_ticket(tiles: Dict[str, list], cfg, plan_stats, log) -> None:
    dims = work.projection_dims(shape_of(cfg))
    for key, per in tiles.items():
        k, n = dims[key]
        total = (k // work.TILE) * (n // work.TILE)
        log(f"ticket {key}: live tiles per layer "
            f"{'/'.join(map(str, per))} of {total}")
    log(f"plan (union over the layers of a segment): routed "
        f"{plan_stats.routed}, live tiles {plan_stats.live_tiles} of "
        f"{plan_stats.total_tiles} (union fraction "
        f"{plan_stats.live_tiles / max(plan_stats.total_tiles, 1):.4f})")


def first_moment(opt_state):
    """AdamW's first moment: after step 1 it is (1 - b1) times the
    gradient the optimizer got."""
    return opt_state.get("_opt", opt_state)["m"]


def reference(cfg, shapes, seed, masks, batches, lr, *,
              half_batch=False, operands=llama.exact) -> Dict:
    """The reference's readings of the same steps."""
    w0 = compare.flat(weights.initial(shapes, seed, masks))
    losses, grad, r = llama.retrain(shape_of(cfg), w0, compare.flat(masks),
                                    batches, lr=lr, half_batch=half_batch,
                                    operands=operands)
    del w0
    change = weights.change_norms(shapes, seed, masks, r.w,
                                  stacked_pred(cfg))
    del r
    gc.collect()        # Retrain's jitted methods hold it in a cycle
    return {"losses": losses, "grad": grad, "change": change}


def build(run: Run):
    """(adapter, trainer, masks, layout, cfg) for the run's seed."""
    from repro.api.adapters import LMAdapter
    from repro.core.masks import lm_prunable
    tr = run.cell.traffic
    cfg = arch(run.cell.config)
    shapes = layout(cfg)
    params, masks = weights.draw(
        shapes, run.seed, prunable=lm_prunable, conv=lambda p: False,
        density=tr["density"], ticket_seed=tr["ticket_seed"])
    jax.block_until_ready(masks)
    run.mark("weights and ticket drawn")
    src = data.TokenSource(run.seed, cfg.vocab_size, tr["seq_len"])
    adapter = LMAdapter(cfg, data=src, batch_size=tr["batch"],
                        seq_len=tr["seq_len"])
    trainer = adapter.make_trainer(params, masks,
                                   learning_rate=tr["learning_rate"])
    return adapter, trainer, masks, shapes, cfg


def restart(trainer, adapter, run: Run, cfg, shapes, masks) -> None:
    """Point ``trainer`` at the run's seed: fresh weights, zero optimizer
    state, step 0 of the seed's data.  Its compiled step is kept: the
    ticket, and so every constant the step closes over, is the same for
    every seed."""
    from repro.data import DataPipeline
    from repro.train.loop import TrainState
    tr = run.cell.traffic
    zeros = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         trainer.state.opt_state)
    trainer.state = None
    gc.collect()
    params, _ = weights.draw(
        shapes, run.seed, prunable=lambda p, x: False, conv=lambda p: False,
        density=1.0, ticket_seed=tr["ticket_seed"])
    params = jax.tree.map(lambda p, m: p if m is None else p * m.astype(
        p.dtype), params, masks, is_leaf=lambda x: x is None)
    adapter.data = data.TokenSource(run.seed, cfg.vocab_size, tr["seq_len"])
    trainer.state = TrainState(params, jax.tree.map(
        lambda z: jnp.zeros(z.shape, z.dtype), zeros), 0)
    trainer.data_iter = DataPipeline(adapter._batch, prefetch=0)


def calibrate(runs, kind: str) -> Dict[int, Dict[str, Dict]]:
    """Every number the check can compare, per seed of ``runs`` (one Run
    per seed): for the program (``kind`` "program"), or for the control,
    the reference with float8 e4m3 operands in every projection and the
    head, and the half-batch fault planted in the reference (``kind``
    "control").  As in a run, the program goes first and is freed
    before the references: one trainer serves every seed, restarted, so
    its step compiles once."""
    from repro.core.masks import lm_prunable
    if kind not in ("program", "control"):
        raise ValueError(f"no {kind!r} readings for a bfloat16 cell")
    tr = runs[0].cell.traffic
    cfg = arch(runs[0].cell.config)
    shapes = layout(cfg)
    read: Dict[int, Dict[str, Dict]] = {r.seed: {} for r in runs}
    if kind == "program":
        trainer = adapter = None
        for run in runs:
            if trainer is None:
                adapter, trainer, masks, shapes, cfg = build(run)
            else:
                restart(trainer, adapter, run, cfg, shapes, masks)
            read[run.seed]["program"] = compare.readings(
                trainer, first_moment, 1.0 / (1.0 - ADAM_B1), shapes,
                run.seed, masks, tr["check_steps"], stacked_pred(cfg))
        del trainer, adapter
        gc.collect()
        jax.clear_caches()      # the step's executable holds the masks
    else:
        _, masks = weights.draw(shapes, runs[0].seed, prunable=lm_prunable,
                                conv=lambda p: False, density=tr["density"],
                                ticket_seed=tr["ticket_seed"])
    out = {}
    for run in runs:
        batches = [data.TokenSource(run.seed, cfg.vocab_size, tr["seq_len"])
                   .batch(k, tr["batch"]) for k in range(tr["check_steps"])]
        ref = functools.partial(reference, cfg, shapes, run.seed, masks,
                                batches, tr["learning_rate"])
        base = ref()
        if kind == "control":
            for tag, kw in (("control", {"operands": llama.e4m3}),
                            ("half_batch", {"half_batch": True})):
                read[run.seed][tag] = dict(ref(**kw), pruned_nonzero=0)
        out[run.seed] = {t: {k: v for k, (v, _) in compare.numbers(
            r, base).items()} for t, r in read[run.seed].items()}
    return out


def run(run: Run) -> Outcome:
    tr = run.cell.traffic
    run.mark("start")
    adapter, trainer, masks, shapes, cfg = build(run)
    run.mark("trainer built")
    tiles = live_tiles(masks, cfg)
    print_ticket(tiles, cfg, adapter.last_plan_stats, run.log)
    prog = compare.readings(trainer, first_moment, 1.0 / (1.0 - ADAM_B1),
                            shapes, run.seed, masks, tr["check_steps"],
                            stacked_pred(cfg))
    tokens = tr["batch"] * tr["seq_len"]
    run.mark("first steps read")
    w = run.repeat(lambda: trainer.run(1)["loss"])
    run.read_memory()
    del trainer, adapter
    gc.collect()
    jax.clear_caches()          # the step's executable holds the masks
    run.mark("window closed")
    batches = [data.TokenSource(run.seed, cfg.vocab_size, tr["seq_len"])
               .batch(k, tr["batch"]) for k in range(tr["check_steps"])]
    ref = reference(cfg, shapes, run.seed, masks, batches,
                    tr["learning_rate"])
    return Outcome(
        attempted=w.units, failed=w.failed,
        metrics={"train_tokens_per_s": w.units * tokens / w.elapsed},
        checks=compare.checks(prog, ref, tr["limits"]),
        work=work.lm_train_step(shape_of(cfg), tiles, tr["batch"],
                                tr["seq_len"]))
