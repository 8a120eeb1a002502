"""Model adapters: bundle init/train/eval/prunability behind one protocol.

Algorithm 1 is model-agnostic — the only model-specific pieces are how
to initialise parameters, train them under a mask, score them, and
decide which leaves are prunable.  A ``ModelAdapter`` packages those
four so ``PruningSession`` (and the examples) never hand-roll training
closures.

The family-specific pieces — prunability predicate, conv-path
predicate, granularity schedule — are *data* attached to the adapter
(``prunable_pred`` / ``conv_path_pred`` / ``granularities``), injected
by the family registry (``repro.api.registry.make_adapter``) so one
adapter class covers every architecture of its family.

``CNNAdapter``, ``LMAdapter`` (dense / moe / hybrid / ssm / vlm
transformers) and ``EncDecAdapter`` (whisper-style) are built on
``repro.train.loop.Trainer`` — the same operational layer (jitted
masked steps, data pipeline, checkpoint/resume) used for production
training, so a model pruned through the session fine-tunes and serves
with zero glue code.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.masks import (apply_masks, cnn_conv_path, cnn_prunable,
                              encdec_prunable, lm_prunable, make_masks)
from repro.core.quantize import fake_quantize_tree
from repro.data import (DataPipeline, SyntheticAudio, SyntheticImages,
                        SyntheticLM)
from repro.optim import (adamw, constant, exponential_epoch_decay, masked,
                         sgd, warmup_cosine)
from repro.kernels.bsmm import default_interpret
from repro.models.plans import PlanStats
from repro.train import Trainer, cnn_train_plan, lm_train_plan


class ServeUnsupported(NotImplementedError):
    """An adapter whose family has no ServeEngine path.

    Structured (arch/family/reason) so callers — the CLI ``serve``
    subcommand in particular — can report *why* per architecture
    instead of surfacing a bare traceback.
    """

    def __init__(self, arch: str, family: str, reason: str):
        self.arch = arch
        self.family = family
        self.reason = reason
        super().__init__(f"{arch} ({family}): serving unsupported — "
                         f"{reason}")


class ModelAdapter:
    """Protocol: everything a pruning session needs from a model.

    ``train``/``evaluate`` take ``masks=None`` for the dense model.
    ``evaluate`` returns a scalar where HIGHER IS BETTER (accuracy for
    classifiers; adapters for likelihood models return negative loss).

    ``prunable_pred`` / ``conv_path_pred`` / ``granularities`` /
    ``recipe`` are the per-family registry data; subclasses set
    defaults and ``make_adapter`` overrides them from the family entry.

    ``train`` accepts ``quantize_bits``: when set, the jitted step
    fake-quantizes the prunable weights (straight-through, fixed point
    at that width) so tickets retrain quantization-aware — the
    ``quantize`` recipe stage.  Adapters without a QAT path may ignore
    it.
    """

    cfg: Any = None
    family: str = "custom"
    # None → the session falls back to PruneConfig.granularities
    granularities: Optional[Sequence[str]] = None
    # family-tuned Recipe (or registered recipe name); None → schedule
    recipe: Optional[Any] = None
    prunable_pred: Optional[Callable[[str, Any], bool]] = None
    conv_path_pred: Optional[Callable[[str], bool]] = None

    def init_params(self, rng):
        raise NotImplementedError

    def train(self, params, masks=None, steps: Optional[int] = None,
              *, quantize_bits: Optional[int] = None):
        raise NotImplementedError

    def _qat(self, quantize_bits: Optional[int]):
        """Loss-input transform for quantization-aware retraining."""
        if quantize_bits is None:
            return lambda p: p
        return lambda p: fake_quantize_tree(p, self.prunable,
                                            quantize_bits)

    def evaluate(self, params, masks=None) -> float:
        raise NotImplementedError

    def prunable(self, path: str, leaf) -> bool:
        if self.prunable_pred is None:
            raise NotImplementedError(
                f"{type(self).__name__} has no prunable_pred")
        return self.prunable_pred(path, leaf)

    def conv_pred(self, path: str) -> bool:
        return bool(self.conv_path_pred(path)) if self.conv_path_pred \
            else False

    def serve_fns(self) -> Tuple[Callable, Callable]:
        """(prefill_fn, decode_fn) for ServeEngine handoff."""
        cfg_name = getattr(self.cfg, "name", "<unknown>")
        raise ServeUnsupported(
            cfg_name, self.family,
            f"{type(self).__name__} exposes no prefill/decode pair")


@dataclasses.dataclass
class FunctionAdapter(ModelAdapter):
    """Wrap plain closures — the bridge for ``core.algorithm.realprune``
    callers and for scripted/deterministic tests."""

    params: Any = None
    train_fn: Callable = None           # (params, masks) -> params
    eval_fn: Callable = None            # (params, masks) -> float
    prunable: Callable = None           # (path, leaf) -> bool
    conv_pred: Callable = None          # (path) -> bool
    cfg: Any = None

    def init_params(self, rng):
        return jax.tree.map(lambda x: x, self.params)

    def train(self, params, masks=None, steps=None, *, quantize_bits=None):
        # scripted closures predate QAT; bits are accepted and ignored
        return self.train_fn(params, masks)

    def evaluate(self, params, masks=None) -> float:
        return float(self.eval_fn(params, masks))


class CNNAdapter(ModelAdapter):
    """CNN (VGG/ResNet family) on image batches, trained via ``Trainer``.

    BatchNorm statistics thread through the Trainer's aux-state channel;
    each ``train`` call restarts them from initialisation (every prune
    iteration retrains the rewound ticket from scratch, paper line 3).

    ``use_bsmm``: when retraining under masks, the FC/head matmuls are
    routed through the block-sparse kernel — the plan is rebuilt from
    the CURRENT masks on every ``train`` call, so each deeper prune
    round retrains with proportionally fewer tile passes.  ``None``
    (default) auto-enables on real TPU backends only: under CPU
    interpret emulation the kernels are a correctness path, not a fast
    path, so big CPU runs stay on XLA dense unless you pass ``True``.
    Shapes that don't tile 128 stay dense automatically.
    """

    family = "cnn"

    def __init__(self, cfg, *, data=None, steps: int = 80,
                 batch_size: int = 64, lr: float = 0.05,
                 lr_decay: float = 0.95, decay_every: Optional[int] = None,
                 eval_batches: int = 3, eval_batch_size: int = 128,
                 momentum: float = 0.9, log_every: int = 0,
                 use_bsmm: Optional[bool] = None,
                 bsmm_interpret: Optional[bool] = None):
        from repro.models import cnn as cnn_lib
        self._cnn = cnn_lib
        self.cfg = cfg
        self.prunable_pred = cnn_prunable
        self.conv_path_pred = cnn_conv_path
        self.data = data or SyntheticImages(image_size=cfg.image_size,
                                            noise=0.25)
        self.steps = steps
        self.batch_size = batch_size
        self.lr, self.lr_decay = lr, lr_decay
        self.decay_every = decay_every
        self.eval_batches = eval_batches
        self.eval_batch_size = eval_batch_size
        self.momentum = momentum
        self.log_every = log_every
        self.use_bsmm = (not default_interpret() if use_bsmm is None
                         else use_bsmm)
        self.bsmm_interpret = bsmm_interpret
        self.last_plan_stats = PlanStats()
        self.last_metrics: Dict[str, float] = {}
        self._bn0 = None
        self._bn = None

    # -- protocol ----------------------------------------------------------
    def init_params(self, rng):
        params, bn = self._cnn.init_params(rng, self.cfg)
        self._bn0 = bn
        self._bn = bn
        return params

    def _batch(self, step, size):
        b = self.data.batch(step, size)
        return {"images": jnp.asarray(b["images"]),
                "labels": jnp.asarray(b["labels"])}

    def train(self, params, masks=None, steps=None, *, quantize_bits=None):
        if self._bn0 is None:
            raise RuntimeError("call init_params before train")
        steps = steps or self.steps
        sched = exponential_epoch_decay(
            self.lr, self.lr_decay, self.decay_every or max(steps // 2, 1))
        opt = sgd(sched, momentum=self.momentum)
        if masks is not None:
            opt = masked(opt, masks)
            params = apply_masks(params, masks)
        plans, self.last_plan_stats = (
            cnn_train_plan(masks, interpret=self.bsmm_interpret)
            if masks is not None and self.use_bsmm else (None, PlanStats()))
        qat = self._qat(quantize_bits)

        def loss(p, state, batch):
            l, (new_state, _) = self._cnn.loss_fn(qat(p), state, self.cfg,
                                                  batch, train=True,
                                                  plans=plans)
            return l, (new_state, {})

        # donate=False: the session re-applies masks to the same w_init
        # snapshot across iterations, so caller buffers must survive
        trainer = Trainer(
            loss_fn=loss, optimizer=opt, params=params,
            data_iter=DataPipeline(
                lambda s: self._batch(s, self.batch_size), prefetch=0),
            ckpt_dir=None, aux_state=self._bn0, donate=False)
        self.last_metrics = trainer.run(steps, log_every=self.log_every)
        self._bn = trainer.state.aux
        return trainer.state.params

    def evaluate(self, params, masks=None) -> float:
        accs = []
        for i in range(self.eval_batches):
            b = self._batch(10_000 + i, self.eval_batch_size)
            accs.append(float(self._cnn.accuracy(
                params, self._bn, self.cfg, b["images"], b["labels"])))
        return float(np.mean(accs))


class LMAdapter(ModelAdapter):
    """Decoder-only transformer family — dense, MoE, hybrid
    (attention + RG-LRU), ssm (xLSTM) and vlm (patch-prefix) archs all
    run through ``models.transformer.forward``, so ONE adapter covers
    every block kind; the family registry supplies the per-family
    prunability predicate and granularity schedule as data.

    ``evaluate`` returns NEGATIVE mean cross-entropy on held-out batches
    (higher is better, so the session's accuracy gate applies
    unchanged; set ``PruneConfig.accuracy_tolerance`` in nats).

    ``use_bsmm``: retrain under masks through the block-sparse kernels
    (attention q/k/v/o + MLP + stacked MoE experts, fwd and bwd);
    ``None`` auto-enables on real TPU backends only — see
    ``CNNAdapter``.
    """

    family = "dense"

    def __init__(self, cfg, *, data=None, steps: int = 100,
                 batch_size: int = 8, seq_len: int = 128,
                 peak_lr: float = 3e-4, warmup: int = 20,
                 eval_batches: int = 2, microbatch: Optional[int] = None,
                 remat: bool = False, log_every: int = 0,
                 step_deadline_s: Optional[float] = None,
                 use_bsmm: Optional[bool] = None,
                 bsmm_interpret: Optional[bool] = None):
        from repro.models import transformer as tfm
        self._tfm = tfm
        self.cfg = cfg
        self.family = getattr(cfg, "family", "dense")
        self.prunable_pred = lm_prunable
        self.data = data or SyntheticLM(
            vocab_size=min(int(cfg.vocab_size), 256), seq_len=seq_len,
            seed=0)
        self.steps = steps
        self.batch_size = batch_size
        self.peak_lr, self.warmup = peak_lr, warmup
        self.eval_batches = eval_batches
        self.microbatch, self.remat = microbatch, remat
        self.log_every = log_every
        self.step_deadline_s = step_deadline_s
        # None → auto: block-sparse retraining on real TPU backends only
        # (interpret-mode emulation is for correctness, not speed)
        self.use_bsmm = (not default_interpret() if use_bsmm is None
                         else use_bsmm)
        self.bsmm_interpret = bsmm_interpret
        self.last_plan_stats = PlanStats()
        self.last_metrics: Dict[str, float] = {}
        self.last_comm_stats: Dict[str, float] = {}

    # -- protocol ----------------------------------------------------------
    def init_params(self, rng):
        return self._tfm.init_params(rng, self.cfg)

    def _patches(self, step: int, size: int):
        """Deterministic patch-prefix embeddings for vlm configs
        (stateless: f(step), like the synthetic data sources)."""
        rng = np.random.RandomState((1_000_003 * step + 11) % (2 ** 31 - 1))
        return jnp.asarray(rng.randn(
            size, self.cfg.num_patch_tokens,
            self.cfg.d_model).astype(np.float32))

    def _batch(self, step):
        b = self.data.batch(step, self.batch_size)
        out = {"tokens": jnp.asarray(b["tokens"]),
               "labels": jnp.asarray(b["labels"])}
        if getattr(self.cfg, "num_patch_tokens", 0):
            out["patches"] = self._patches(step, self.batch_size)
        return out

    def make_trainer(self, params, masks=None, *, steps: Optional[int] = None,
                     start_step: int = 0, ckpt_dir: Optional[str] = None,
                     ckpt_every: int = 50, async_ckpt: bool = True,
                     learning_rate: Optional[float] = None,
                     quantize_bits: Optional[int] = None) -> Trainer:
        """A fully-wired Trainer for these weights — the session/ticket
        handoff point for long runs that need their own checkpoints.

        With ``masks`` (and ``use_bsmm``), the train step closes over a
        block-sparse plan derived from the CURRENT masks: forward and
        both backward matmuls of every routed projection skip dead
        128×128 tiles, so retraining a sparser ticket costs fewer MXU
        passes.  The plan is static — re-jitted per prune round.
        """
        steps = steps or self.steps
        sched = (constant(learning_rate) if learning_rate is not None
                 else warmup_cosine(self.peak_lr,
                                    min(self.warmup, max(steps // 2, 1)),
                                    steps))
        opt = adamw(sched)
        compressor = None
        if masks is not None:
            opt = masked(opt, masks)
            params = apply_masks(params, masks)
            # data-parallel gradient exchange only ships live
            # coordinates: the masked optimizer already zeroes pruned
            # grads and re-masks params, so dropping them on the wire
            # is bitwise-neutral (adamw has no global-norm coupling)
            from repro.distributed.compression import MaskAwareCompressor
            compressor = MaskAwareCompressor(masks)
        plan, self.last_plan_stats = (
            lm_train_plan(masks, interpret=self.bsmm_interpret)
            if masks is not None and self.use_bsmm else (None, PlanStats()))
        qat = self._qat(quantize_bits)
        loss = (lambda p, batch:
                self._tfm.loss_fn(qat(p), self.cfg, batch, plan=plan))
        # the step donates params and optimizer state, so a retrain
        # holds one copy of each (at published widths the f32 Adam
        # moments alone fill a third of a 16 GB chip).  It donates a
        # copy: the session rewinds to the caller's w_init every round.
        params = jax.tree.map(jnp.copy, params)
        return Trainer(
            loss_fn=loss, optimizer=opt, params=params,
            data_iter=DataPipeline(self._batch, start_step=start_step,
                                   prefetch=0),
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, async_ckpt=async_ckpt,
            microbatch=self.microbatch, remat=self.remat, donate=True,
            step_deadline_s=self.step_deadline_s, compressor=compressor)

    def train(self, params, masks=None, steps=None, *, start_step: int = 0,
              ckpt_dir: Optional[str] = None,
              learning_rate: Optional[float] = None,
              quantize_bits: Optional[int] = None):
        trainer = self.make_trainer(params, masks, steps=steps,
                                    start_step=start_step, ckpt_dir=ckpt_dir,
                                    learning_rate=learning_rate,
                                    quantize_bits=quantize_bits)
        self.last_metrics = trainer.run(steps or self.steps,
                                        log_every=self.log_every)
        self.last_comm_stats = {}
        if "sent_fraction" in self.last_metrics:
            sf = float(self.last_metrics["sent_fraction"])
            total = sum(int(np.asarray(l).size)
                        for l in jax.tree.leaves(params) if l is not None)
            self.last_comm_stats = {
                "sent_fraction": sf,
                "bytes_per_step": int(round(sf * total)) * 4,
            }
        return trainer.state.params

    def evaluate(self, params, masks=None) -> float:
        losses = []
        for i in range(self.eval_batches):
            loss, _ = self._tfm.loss_fn(params, self.cfg,
                                        self._batch(10_000 + i))
            losses.append(float(loss))
        return -float(np.mean(losses))

    def serve_fns(self):
        # vlm configs serve text-only prompts (no patch prefix): the
        # engine's prompt protocol is token-only, and the transformer
        # treats patches as an optional batch key
        return self._tfm.prefill, self._tfm.decode_step


class EncDecAdapter(ModelAdapter):
    """Whisper-style encoder-decoder on synthetic mel-frame/transcript
    pairs (``SyntheticAudio``), trained via ``Trainer``.

    ``evaluate`` returns NEGATIVE decoder cross-entropy (higher is
    better).  Prunability covers encoder/decoder self-attention, MLPs,
    and the decoder cross-attention (``encdec_prunable``).  Serving
    uses the engine's frames lane: a ``Request`` carries its encoder
    frames alongside the decoder prompt, and the greedy decoder loop
    runs behind the same Request/ServeReport surface as the LM families.
    """

    family = "audio"

    def __init__(self, cfg, *, data=None, steps: int = 60,
                 batch_size: int = 4, seq_len: int = 32,
                 peak_lr: float = 3e-4, warmup: int = 10,
                 eval_batches: int = 2, log_every: int = 0):
        from repro.models import encdec
        self._mod = encdec
        self.cfg = cfg
        self.family = getattr(cfg, "family", "audio")
        self.prunable_pred = encdec_prunable
        self.data = data or SyntheticAudio(
            vocab_size=min(int(cfg.vocab_size), 256), seq_len=seq_len,
            n_frames=int(cfg.encoder_seq_len), d_model=int(cfg.d_model),
            seed=0)
        self.steps = steps
        self.batch_size = batch_size
        self.peak_lr, self.warmup = peak_lr, warmup
        self.eval_batches = eval_batches
        self.log_every = log_every
        self.last_plan_stats = PlanStats()
        self.last_metrics: Dict[str, float] = {}

    # -- protocol ----------------------------------------------------------
    def init_params(self, rng):
        return self._mod.init_params(rng, self.cfg)

    def _batch(self, step):
        b = self.data.batch(step, self.batch_size)
        return {k: jnp.asarray(v) for k, v in b.items()}

    def train(self, params, masks=None, steps=None, *, quantize_bits=None):
        steps = steps or self.steps
        sched = warmup_cosine(self.peak_lr,
                              min(self.warmup, max(steps // 2, 1)), steps)
        opt = adamw(sched)
        if masks is not None:
            opt = masked(opt, masks)
            params = apply_masks(params, masks)
        qat = self._qat(quantize_bits)

        def loss(p, batch):
            return self._mod.loss_fn(qat(p), self.cfg, batch)

        trainer = Trainer(
            loss_fn=loss, optimizer=opt, params=params,
            data_iter=DataPipeline(self._batch, prefetch=0),
            ckpt_dir=None, donate=False)
        self.last_metrics = trainer.run(steps, log_every=self.log_every)
        return trainer.state.params

    def evaluate(self, params, masks=None) -> float:
        losses = []
        for i in range(self.eval_batches):
            loss, _ = self._mod.loss_fn(params, self.cfg,
                                        self._batch(10_000 + i))
            losses.append(float(loss))
        return -float(np.mean(losses))

    def serve_fns(self):
        # the engine routes requests with frames through its enc-dec
        # prefill lane ({"tokens", "frames"} batch, exact-length); the
        # decoder's per-step signature matches the LM protocol
        return self._mod.prefill, self._mod.decode_step

    def serve_frames(self, uid: int = 0) -> np.ndarray:
        """Deterministic synthetic encoder frames for one request —
        the serving-side analogue of the training ``SyntheticAudio``
        batches (CLI/demo input when no real mel frames exist)."""
        rng = np.random.RandomState(uid)
        return rng.randn(self.cfg.encoder_seq_len,
                         self.cfg.d_model).astype(np.float32) * 0.1
