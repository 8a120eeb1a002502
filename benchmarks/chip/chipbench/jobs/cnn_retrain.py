"""Job ``cnn_retrain``: retrain a CNN's ticket through the program's
training step, back to back for the whole window.

``CNNAdapter`` builds its ``Trainer`` inside ``train()`` and keeps no
handle on it, so set-up builds the same ``Trainer`` the same way,
from the adapter's own pieces: its configuration, BatchNorm state,
batch function over the benchmark's image source (``data=``), loss,
quantization hook (none) and SGD recipe, with the learning rate
decaying once per pass over the images (``decay_every``).  The copy
follows ``train(params, masks)`` statement for statement; a change to
``train()`` has to be made here too, until the adapter hands out its
trainer as ``LMAdapter.make_trainer`` does.

Set-up drives that one object through its first ``check_steps`` steps
with ``Trainer.run``, reading the losses, the first gradient (SGD's
momentum after step 1) and the change of the weights; the window keeps
calling ``Trainer.run(1)`` on it.  After the window the reference
retrains the same ticket on the same rows.

Traffic keys: ``batch``, ``images``, ``density``, ``ticket_seed``,
``learning_rate``, ``lr_decay``, ``momentum``, ``check_steps`` and
``limits``.
"""
from __future__ import annotations

import functools
import gc
from typing import Dict, List

import jax

from chipbench import compare, data, weights, work
from chipbench.harness import Outcome, Run
from chipbench.reference import vgg


def cnn_config(config: Dict):
    from repro.configs.base import CNNConfig, ConvSpec
    a = dict(config["cnn"])
    a["convs"] = tuple(ConvSpec(**c) for c in a["convs"])
    a["fc"] = tuple(a.get("fc", ()))
    return CNNConfig(**a)


def layout(cfg):
    from repro.models import cnn
    return jax.eval_shape(lambda k: cnn.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def conv_layers(cfg, masks) -> List[Dict]:
    """Per convolution (and the head): its live and total weights, its
    output positions and the feature maps it reads and writes, for
    ``work.conv_train_step``."""
    fm = compare.flat(masks)
    out, size, ic = [], cfg.image_size, cfg.in_channels
    for i, c in enumerate(cfg.convs):
        size_out = size // c.stride
        out.append({"hw": size_out * size_out, "x": size * size * ic,
                    "y": size_out * size_out * c.out_channels,
                    "live": float(jax.device_get(fm[f"convs/{i}/w"].sum())),
                    "weights": ic * c.kernel * c.kernel * c.out_channels,
                    "data": i == 0})
        size, ic = (size_out // 2 if c.pool else size_out), c.out_channels
    out.append({"hw": 1, "x": ic, "y": cfg.num_classes,
                "live": float(jax.device_get(fm["head/w"].sum())),
                "weights": ic * cfg.num_classes})
    return out


def momentum(opt_state):
    """SGD's momentum: after step 1 it is the gradient the optimizer got."""
    return opt_state["mu"]


def build(run: Run):
    """(trainer, masks, layout, cfg, source) for the run's seed, built as
    ``CNNAdapter.train`` builds its trainer."""
    from repro.api.adapters import CNNAdapter
    from repro.core.masks import apply_masks, cnn_conv_path, cnn_prunable
    from repro.data import DataPipeline
    from repro.optim import exponential_epoch_decay, masked, sgd
    from repro.train import Trainer, cnn_train_plan
    tr = run.cell.traffic
    cfg = cnn_config(run.cell.config)
    # float32 as the configuration states: JAX's default on a TPU runs a
    # float32 convolution as one bfloat16 pass
    jax.config.update("jax_default_matmul_precision",
                      run.cell.config["matmul_precision"])
    shapes, bn_shapes = layout(cfg)
    params, masks = weights.draw(
        shapes, run.seed, prunable=cnn_prunable, conv=cnn_conv_path,
        density=tr["density"], ticket_seed=tr["ticket_seed"])
    bn0, _ = weights.draw(bn_shapes, run.seed, prunable=lambda p, x: False,
                          conv=lambda p: False, density=1.0,
                          ticket_seed=tr["ticket_seed"])
    src = data.ImageSource(run.seed, tr["images"], cfg.image_size,
                           cfg.in_channels, cfg.num_classes)
    adapter = CNNAdapter(cfg, data=src, batch_size=tr["batch"],
                         lr=tr["learning_rate"], lr_decay=tr["lr_decay"],
                         decay_every=tr["images"] // tr["batch"],
                         momentum=tr["momentum"])
    # from here on as CNNAdapter.train(params, masks) builds its trainer
    opt = masked(sgd(exponential_epoch_decay(adapter.lr, adapter.lr_decay,
                                             adapter.decay_every),
                     momentum=adapter.momentum), masks)
    plans, stats = (cnn_train_plan(masks, interpret=adapter.bsmm_interpret)
                    if adapter.use_bsmm else (None, None))
    model, qat = adapter._cnn, adapter._qat(None)

    def loss(p, state, batch):
        value, (new_state, _) = model.loss_fn(qat(p), state, cfg, batch,
                                              train=True, plans=plans)
        return value, (new_state, {})

    trainer = Trainer(
        loss_fn=loss, optimizer=opt, params=apply_masks(params, masks),
        data_iter=DataPipeline(lambda s: adapter._batch(s, adapter.batch_size),
                               prefetch=0),
        ckpt_dir=None, aux_state=bn0, donate=False)
    if stats is not None:
        run.log(f"plan: routed {stats.routed}, dense fallback "
                f"{stats.dense_fallback} (a (512, 10) head does not tile)")
    return trainer, masks, shapes, cfg, src


def reference(cfg, shapes, seed, masks, batches, tr, *, half_batch=False,
              precision="highest") -> Dict:
    """The reference's readings of the same steps."""
    w0 = compare.flat(weights.initial(shapes, seed, masks))
    convs = [{"pool": c.pool} for c in cfg.convs]
    losses, grad, r = vgg.retrain(
        convs, w0, compare.flat(masks), batches, lr=tr["learning_rate"],
        momentum=tr["momentum"], half_batch=half_batch, precision=precision)
    return {"losses": losses, "grad": grad,
            "change": weights.change_norms(shapes, seed, masks, r.w,
                                           lambda p: False)}


def calibrate(runs, kind: str) -> Dict[int, Dict[str, Dict]]:
    """Every number the check can compare, per seed of ``runs`` (one Run
    per seed): for the program (``kind`` "program"); for the control,
    the reference at ``high`` (three bfloat16 passes, the precision below
    the float32 at ``highest`` the configuration states), and the
    half-batch fault planted in the reference (``kind`` "control"); or
    for the reference's float32 twin, which differs from it by round-off
    alone (``kind`` "twin")."""
    extra = {"program": (), "twin": (("twin", {"precision": "twin"}),),
             "control": (("control", {"precision": "high"}),
                         ("half_batch", {"half_batch": True}))}[kind]
    out = {}
    for run in runs:
        tr = run.cell.traffic
        trainer, masks, shapes, cfg, src = build(run)
        read = {}
        if kind == "program":
            read["program"] = compare.readings(
                trainer, momentum, 1.0, shapes, run.seed, masks,
                tr["check_steps"], lambda p: False)
        del trainer
        gc.collect()
        batches = [src.batch(k, tr["batch"])
                   for k in range(tr["check_steps"])]
        ref = functools.partial(reference, cfg, shapes, run.seed, masks,
                                batches, tr)
        base = ref()
        for tag, kw in extra:
            read[tag] = dict(ref(**kw), pruned_nonzero=0)
        out[run.seed] = {t: {k: v for k, (v, _) in compare.numbers(
            r, base).items()} for t, r in read.items()}
    return out


def run(run: Run) -> Outcome:
    tr = run.cell.traffic
    run.mark("start")
    trainer, masks, shapes, cfg, src = build(run)
    run.mark("trainer built")
    layers = conv_layers(cfg, masks)
    for i, c in enumerate(layers):
        run.log(f"ticket {'head' if i == len(layers) - 1 else f'conv {i}'}: "
                f"{c['live']:.0f} of {c['weights']} weights live")
    prog = compare.readings(trainer, momentum, 1.0, shapes, run.seed, masks,
                            tr["check_steps"], lambda p: False)
    run.mark("first steps read")
    w = run.repeat(lambda: trainer.run(1)["loss"])
    run.read_memory()
    del trainer
    gc.collect()
    run.mark("window closed")
    batches = [src.batch(k, tr["batch"]) for k in range(tr["check_steps"])]
    ref = reference(cfg, shapes, run.seed, masks, batches, tr)
    return Outcome(
        attempted=w.units, failed=w.failed,
        metrics={"train_images_per_s": w.units * tr["batch"] / w.elapsed},
        checks=compare.checks(prog, ref, tr["limits"]),
        work=work.conv_train_step(layers, tr["batch"]))
