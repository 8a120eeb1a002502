"""Training loop: jitted masked train step, microbatching, remat, and a
host-side Trainer that wires data / checkpointing / fault tolerance.

The train step is a pure function (params, opt_state, batch, masks) →
(params, opt_state, metrics); ``Trainer`` adds the operational layer a
real cluster needs: auto-resume from the newest committed checkpoint,
periodic async saves, deterministic data (stateless step streams), and
a straggler/failure policy hook.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.checkpoint import CheckpointManager
from repro.core.masks import apply_masks
from repro.optim import Optimizer

log = logging.getLogger("train")

# the named scope of the gradient compression and optimizer update in
# the step's HLO (each op's ``op_name`` metadata); profilers group the
# device time of the update under it
OPTIMIZER_SCOPE = "train.optimizer"


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0
    aux: Any = None                  # non-gradient model state (e.g. BN stats)


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    microbatch: Optional[int] = None,
                    remat: bool = False,
                    donate: bool = True,
                    compressor=None,
                    has_aux_state: bool = False):
    """Build a jitted train step.

    loss_fn: (params, batch) -> (loss, metrics_dict)
    microbatch: if set, split the batch's leading axis into chunks and
        accumulate gradients with ``lax.scan`` (bitwise-deterministic).
    remat: wrap loss_fn in jax.checkpoint (activation rematerialisation).
    compressor: optional gradient compressor (TopK / MaskAware from
        repro.distributed.compression); its error-feedback residual is
        threaded through opt_state under the key "_compress_residual".
    has_aux_state: the model threads non-gradient state (BatchNorm
        statistics, EMA buffers) through the step.  loss_fn then has
        signature (params, state, batch) -> (loss, (new_state, metrics))
        and the built step is (params, opt_state, state, batch) ->
        (params, opt_state, new_state, metrics).
    """
    lf = jax.checkpoint(loss_fn) if remat else loss_fn
    if has_aux_state:
        if microbatch is not None or compressor is not None:
            raise ValueError("aux state is not supported together with "
                             "microbatching or gradient compression")

        def aux_step_fn(params, opt_state, state, batch):
            def inner(p):
                loss, (new_state, metrics) = lf(p, state, batch)
                return loss, (new_state, metrics)

            (loss, (new_state, metrics)), grads = jax.value_and_grad(
                inner, has_aux=True)(params)
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)
            metrics = dict(metrics)
            metrics["loss"] = loss
            return new_params, new_opt, new_state, metrics

        return jax.jit(aux_step_fn, donate_argnums=(0, 1) if donate else ())
    grad_fn = jax.value_and_grad(lf, has_aux=True)

    def step_fn(params, opt_state, batch):
        if compressor is not None:
            opt_state, residual = (opt_state["_opt"],
                                   opt_state["_compress_residual"])
        if microbatch is None:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def chunk(batch, i):
                return jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * microbatch, microbatch, 0), batch)

            n = jax.tree.leaves(batch)[0].shape[0] // microbatch

            def body(carry, i):
                acc, loss_acc = carry
                (loss, _), g = grad_fn(params, chunk(batch, i))
                acc = jax.tree.map(jnp.add, acc, g)
                return (acc, loss_acc + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), jnp.arange(n))
            grads = jax.tree.map(lambda g: g / n, grads)
            loss = loss / n
            metrics = {}
        metrics = dict(metrics)
        with jax.named_scope(OPTIMIZER_SCOPE):
            if compressor is not None:
                grads, residual, cstats = compressor.compress(grads,
                                                              residual)
                metrics["sent_fraction"] = cstats["sent_fraction"]
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        if compressor is not None:
            new_opt = {"_opt": new_opt, "_compress_residual": residual}
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step_fn, donate_argnums=donate_argnums)


def init_opt_state(optimizer: Optimizer, params, compressor=None):
    """Optimizer state, wrapping the compressor residual when present."""
    state = optimizer.init(params)
    if compressor is not None:
        return {"_opt": state, "_compress_residual": compressor.init(params)}
    return state


def _warn_straggler(step: int, dt: float, *, deadline: Optional[float]):
    log.warning("straggler: step %d took %.2fs (deadline %.2fs)", step, dt,
                deadline)


class Trainer:
    """Operational wrapper: resume → train → checkpoint → (survive)."""

    def __init__(self, *, loss_fn, optimizer: Optimizer, params,
                 data_iter, ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 100, keep: int = 3,
                 async_ckpt: bool = True,
                 microbatch: Optional[int] = None, remat: bool = False,
                 compressor=None,
                 aux_state=None,
                 donate: bool = True,
                 step_deadline_s: Optional[float] = None,
                 on_straggler: Optional[Callable[[int, float], None]] = None):
        self._has_aux = aux_state is not None
        self.step_fn = make_train_step(loss_fn, optimizer,
                                       microbatch=microbatch, remat=remat,
                                       compressor=compressor, donate=donate,
                                       has_aux_state=self._has_aux)
        self.optimizer = optimizer
        self.data_iter = data_iter
        self.ckpt = (CheckpointManager(ckpt_dir, keep=keep,
                                       async_save=async_ckpt)
                     if ckpt_dir else None)
        self.ckpt_every = ckpt_every
        self.state = TrainState(
            params, init_opt_state(optimizer, params, compressor), 0,
            aux_state)
        self.step_deadline_s = step_deadline_s
        # no reference back to self: a Trainer must be freed the moment
        # its caller drops it, not at the next cyclic GC — its state can
        # hold most of the device's memory
        self.on_straggler = on_straggler or functools.partial(
            _warn_straggler, deadline=step_deadline_s)
        self._maybe_resume()

    def _maybe_resume(self):
        if self.ckpt is None:
            return
        tmpl = {"params": self.state.params,
                "opt_state": self.state.opt_state,
                "step": jnp.zeros((), jnp.int32)}
        if self._has_aux:
            tmpl["aux"] = self.state.aux
        step, tree = self.ckpt.restore(tmpl)
        if step is not None:
            self.state = TrainState(tree["params"], tree["opt_state"],
                                    int(tree["step"]),
                                    tree.get("aux", self.state.aux))
            log.info("resumed from checkpoint at step %d", self.state.step)

    def save(self, blocking: bool = False):
        if self.ckpt is None:
            return
        tree = {
            "params": self.state.params,
            "opt_state": self.state.opt_state,
            "step": jnp.asarray(self.state.step, jnp.int32)}
        if self._has_aux:
            tree["aux"] = self.state.aux
        self.ckpt.save(self.state.step, tree, blocking=blocking)

    def run(self, num_steps: int, log_every: int = 50) -> Dict[str, float]:
        metrics = {}
        target = self.state.step + num_steps
        while self.state.step < target:
            # profiler spans (no-ops unless a trace is being recorded):
            # the host's share of a step, split into fetching the batch,
            # dispatching the jitted step and waiting for the device
            with StepTraceAnnotation("train.step",
                                     step_num=self.state.step):
                with TraceAnnotation("train.data"):
                    batch = next(self.data_iter)
                t0 = time.perf_counter()
                with TraceAnnotation("train.dispatch"):
                    if self._has_aux:
                        params, opt_state, aux, metrics = self.step_fn(
                            self.state.params, self.state.opt_state,
                            self.state.aux, batch)
                    else:
                        params, opt_state, metrics = self.step_fn(
                            self.state.params, self.state.opt_state, batch)
                        aux = self.state.aux
                with TraceAnnotation("train.wait"):
                    jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                if (self.step_deadline_s is not None
                        and dt > self.step_deadline_s):
                    self.on_straggler(self.state.step, dt)
                self.state = TrainState(params, opt_state,
                                        self.state.step + 1, aux)
                if self.state.step % self.ckpt_every == 0:
                    self.save()
                if log_every and self.state.step % log_every == 0:
                    log.info("step %d loss %.4f (%.3fs)", self.state.step,
                             float(metrics["loss"]), dt)
        if self.ckpt is not None:
            self.save(blocking=True)
            self.ckpt.wait()
        return {k: float(v) for k, v in metrics.items()}
