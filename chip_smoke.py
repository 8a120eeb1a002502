#!/usr/bin/env python3
"""Bring-up check: the repo's main path, end to end, on a TPU.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # multi-chip serving only

Everything runs in this one process, through the entry points a user
calls: ``make_adapter`` → ``PruningSession`` → retrain →
``session.serve_engine()``.  Weights are random, made from ``--seed``;
data is synthetic.  Without a TPU the script exits nonzero at once and
prints no result.  Any failed check or exception exits nonzero too —
no phase catches its own failure.  On success the last line of
standard output is exactly one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

One chip, in order:

1. ``cnn`` — the paper's trainer: VGG-16 at CIFAR-10 shapes (32×32×3,
   10 classes, batch 128), one ``xbar`` prune round, then a few retrain
   steps.  Its convolutions run through XLA and its (512, 10) head does
   not tile, so no matmul routes through a kernel (``routed=0``).
2. ``lm`` — the kernel path: yi-6b at its published widths (bf16) with
   depth cut to ``LM_LAYERS``, one ``xbar`` round to ≥90% sparsity so
   whole 128×128 tiles die, retrain steps through the block-sparse
   ``bsmm`` forward/dx/dw kernels, then paged serving of 8 requests.
   Checks (a)–(e) below, each with its tolerance.

``--chips 4`` runs only what exists across chips: the yi ticket on a
``ServeEngine`` over a (1, 4) mesh against the one-chip engine, and a
``FleetRouter`` of four engines, one per chip, with engine 0 killed
mid-run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# -- phase 1: the paper's trainer -------------------------------------------
CNN_ARCH = "vgg16"
CNN_BATCH = 128                 # CIFAR-10 training batch
CNN_STEPS = 3                   # dense, retrain-in-round, and final retrain
CNN_PRUNE_RATE = 0.5

# -- phase 2: the kernel path -----------------------------------------------
LM_ARCH = "yi-6b"
# Depth cut 32 → 2, to fit one 16 GB chip.  Compiled for a described v5e
# chip, the 2-layer retrain step takes 10.2 GB of arguments (params, f32
# Adam moments, masks) and 2.5 GB of temporaries, and the session keeps
# the rewind snapshot and the ticket beside it.
LM_LAYERS = 2
LM_BATCH, LM_SEQ = 4, 512
LM_STEPS = 2
LM_SPARSITY = 0.9               # ≥90% pruned: whole tiles die
SERVE_SLOTS, SERVE_CAPACITY = 8, 1024
N_REQUESTS = 8
PROMPT_MIN, PROMPT_MAX = 64, 512
MAX_NEW = 32

# -- phase --chips 4 ---------------------------------------------------------
FLEET_REQUESTS = 16
FLEET_PROMPT_MIN, FLEET_PROMPT_MAX = 65, 128
FLEET_MAX_NEW = 16

# -- tolerances (normwise: ||got - want|| / ||want||) -----------------------
# (c) Both sides are f32 at highest precision; they differ only in
# summation order over K (≤ K·eps ≈ 4096 · 6e-8 = 2e-4) and, if the
# kernel's f32 dot runs as bf16 passes, by ≤ 2^-8 ≈ 4e-3.  A dropped or
# misplaced 128×128 tile moves its output column by its share of the
# sum — about 1/3 with ~3 live K tiles per column at 90% sparsity.
KERNEL_RTOL = 1e-2
# (d) The engine keeps weights, activations and KV in bf16; the
# reference is f32.  Each of the ~24 bf16 roundings on a two-layer step
# adds ~2^-9 relative error, under 1e-2 together.  Decoding against the
# wrong context or KV block changes the attention output, and with it
# the logits, by a larger share.
LOGIT_RTOL = 5e-2
# the 4-chip engine partitions its reductions; both sides are bf16
MESH_RTOL = 5e-2


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  check ok: {what}", flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling — the union of
    its monitoring events' time spans, so a trace nested in another
    counts once (a persistent-cache hit costs only its retrieval) — and
    the number of persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.spans: list = []
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds_since(self, t0: float) -> float:
        """Union length of the compile spans that began after ``t0``
        (``time.time()`` clock)."""
        total, reach = 0.0, t0
        for start, end in sorted(s for s in self.spans if s[0] >= t0):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total


class Phase:
    """Times one phase: wall seconds split into compile and run."""

    def __init__(self, name: str, clock: CompileClock, log: list):
        self.name, self.clock, self.log = name, clock, log

    def __enter__(self):
        print(f"== phase {self.name}", flush=True)
        self.t0 = time.time()
        self.h0 = self.clock.cache_hits
        return self

    def __exit__(self, exc_type, *_):
        wall = time.time() - self.t0
        comp = self.clock.seconds_since(self.t0)
        self.log.append((self.name, comp, wall - comp,
                         self.clock.cache_hits - self.h0))
        return False                    # never swallow a failure


def one_xbar_round(rate: float, steps: int):
    """One tile-aligned prune round.  The data is synthetic, so the gate
    accepts any accuracy: this checks the mechanism, not accuracy."""
    from repro.api.recipes import Recipe, prune_stage
    return Recipe(name="chip-smoke-xbar", stages=(
        prune_stage("xbar", rate=rate, max_rounds=1, retrain_steps=steps,
                    accuracy_drop=1e9),))


def pruned_stay_zero(params, masks) -> int:
    """Number of weights under a zero mask that are not exactly 0."""
    import jax
    bad = 0
    for p, m in zip(jax.tree.leaves(params),
                    jax.tree.leaves(masks, is_leaf=lambda x: x is None)):
        if m is None:
            continue
        dead = np.asarray(m) == 0
        bad += int(np.count_nonzero(np.asarray(p)[dead]))
    return bad


def device_memory(where: str) -> None:
    """Print device 0's bytes in use and peak so far."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  memory after {where}: bytes_in_use "
          f"{stats.get('bytes_in_use')} peak {stats.get('peak_bytes_in_use')}",
          flush=True)


def tile_plans(tree) -> list:
    import jax
    from repro.kernels.bsmm import TilePlan
    return [leaf for leaf in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, TilePlan))
        if isinstance(leaf, TilePlan)]


def custom_calls(compiled) -> int:
    """Pallas kernels compiled for the chip in one executable."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# phase 1: the paper's trainer
# ---------------------------------------------------------------------------
def phase_cnn(adapter, seed: int, steps: int) -> None:
    from repro.api import PruningSession
    from repro.configs import PruneConfig

    cfg = adapter.cfg
    print(f"  {cfg.name}: {cfg.image_size}x{cfg.image_size}x"
          f"{cfg.in_channels} inputs, {cfg.num_classes} classes, batch "
          f"{adapter.batch_size}, {steps} steps per train call", flush=True)
    session = PruningSession(adapter, PruneConfig(max_iters=1),
                             recipe=one_xbar_round(CNN_PRUNE_RATE, steps),
                             seed=seed)
    res = session.run()
    check(len(res.history) == 1 and res.history[0].accepted,
          "the xbar round ran once and was accepted")
    hw = session.hardware_report()
    print(f"  sparsity {res.sparsity:.4f}; live crossbar tiles "
          f"{hw.xbars_needed_strict}/{hw.xbars_unpruned} "
          f"({hw.xbars_needed_strict / max(hw.xbars_unpruned, 1):.4f})",
          flush=True)
    check(res.sparsity > 0, "the round pruned weights")
    params = session.finetune(steps)
    st = adapter.last_plan_stats
    print(f"  retrain PlanStats: routed={st.routed} "
          f"dense_fallback={st.dense_fallback} (expected routed=0: the "
          f"convolutions run through XLA and the (512, 10) head does not "
          f"tile 128)", flush=True)
    check(st.routed == 0, "no CNN matmul routes through a kernel")
    loss = adapter.last_metrics["loss"]
    check(np.isfinite(loss), f"retrain loss is finite ({loss:.6f})")
    n_bad = pruned_stay_zero(params, res.masks)
    check(n_bad == 0, "pruned weights are exactly 0 after the retrain")


# ---------------------------------------------------------------------------
# phase 2: the kernel path
# ---------------------------------------------------------------------------
class DecodeLogits:
    """Keeps every paged decode step's logits row per request uid.

    Wraps one generation's jitted paged decode step (verification only:
    the engine samples on the host from the same array it returns)."""

    def __init__(self, gen):
        self.gen, self.step = gen, gen.decode_paged
        self.rows: dict = {}
        self.args = None
        gen.decode_paged = self

    def __call__(self, params, caches, tok, tables, lens):
        self.args = (tok, tables, lens)
        logits, caches = self.step(params, caches, tok, tables, lens)
        host = np.asarray(logits[:, 0]).astype(np.float32)
        for s, req in enumerate(self.gen.slot_reqs):
            if req is not None:
                self.rows.setdefault(req.uid, []).append(host[s])
        return logits, caches

    def compiled(self):
        tok, tables, lens = self.args
        return self.step.lower(self.gen.params, self.gen.paged_caches, tok,
                               tables, lens).compile()


def make_requests(rng, n: int, lo: int, hi: int, vocab: int, max_new: int):
    from repro.serve import Request
    lens = [lo, hi] + [int(x) for x in rng.randint(lo, hi + 1, n - 2)]
    return [Request(uid=i, prompt=rng.randint(0, vocab, size=n_tok)
                    .astype(np.int32), max_new_tokens=max_new)
            for i, n_tok in enumerate(lens[:n])]


def reference_logits(params, cfg, seqs):
    """Full forward of the ticket without a plan, in f32 at highest
    precision → logits (B, S, V)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: tfm.forward(p, cfg32, {"tokens": t})[0])
        return np.asarray(fwd(p32, jnp.asarray(seqs)))


def check_kernel_vs_dense(params, masks, rng) -> None:
    """(c) one routed projection's forward, dx and dw against the dense
    product with w ⊙ tile-mask, both f32 at highest precision."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.bsmm import make_tile_plan, plan_matmul, tile_bitmap

    # the layer-0 projection with the most live tiles that still has
    # dead ones: a pruned matmul with work on both sides of the guard
    layer_p, layer_m = params["segments"][0][0], masks["segments"][0][0]
    best = None
    for group, key in (("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
                       ("attn", "wo"), ("mlp", "up"), ("mlp", "gate"),
                       ("mlp", "down")):
        # a scanned segment stacks its layers first: take layer 0
        m = np.asarray(layer_m[group][key])
        m = m.reshape(-1, *m.shape[-2:])[0]
        plan = make_tile_plan(m, strict=True, where=f"{group}.{key}")
        if plan.live_tiles < plan.total_tiles and (
                best is None or plan.live_tiles > best[2].live_tiles):
            w = layer_p[group][key]
            best = (f"{group}.{key}", m, plan,
                    w.reshape(-1, *w.shape[-2:])[0])
    check(best is not None and best[2].live_tiles > 0,
          "(c) a layer-0 projection has both live and dead tiles")
    name, m, plan, w = best
    t = plan.tile
    tmask = jnp.asarray(np.kron(tile_bitmap(m, t, t),
                                np.ones((t, t), np.int32)), jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    x = jnp.asarray(rng.randn(LM_BATCH * LM_SEQ, w.shape[0]), jnp.float32)
    g = jnp.asarray(rng.randn(LM_BATCH * LM_SEQ, w.shape[1]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda a, b: plan_matmul(a, b, plan), x, w)
        want, vjp_ref = jax.vjp(lambda a, b: a @ (b * tmask), x, w)
        gdx, gdw = vjp(g)
        rdx, rdw = vjp_ref(g)
    print(f"  (c) {name} {tuple(w.shape)}: {plan.live_tiles}/"
          f"{plan.total_tiles} tiles live", flush=True)
    for name, a, b in (("forward", got, want), ("dx", gdx, rdx),
                       ("dw", gdw, rdw)):
        e = rel_err(a, b)
        check(e <= KERNEL_RTOL, f"(c) plan_matmul {name} vs dense "
              f"w*tile-mask: rel err {e:.3e} <= {KERNEL_RTOL:g}")


def phase_lm(adapter, seed: int, steps: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.api import PruningSession
    from repro.configs import PruneConfig
    from repro.train import lm_train_plan

    cfg = adapter.cfg
    rng = np.random.RandomState(seed)
    print(f"  {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} q / "
          f"{cfg.n_kv_heads} kv heads, head_dim {cfg.head_dim_}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; depth cut "
          f"to {cfg.n_layers} layer(s); batch {adapter.batch_size} x "
          f"{adapter.data.seq_len} tokens", flush=True)
    session = PruningSession(adapter, PruneConfig(max_iters=1),
                             recipe=one_xbar_round(LM_SPARSITY, steps),
                             seed=seed)
    res = session.run()
    check(len(res.history) == 1 and res.history[0].accepted,
          "the xbar round ran once and was accepted")
    check(res.sparsity >= LM_SPARSITY,
          f"sparsity {res.sparsity:.4f} >= {LM_SPARSITY}")
    device_memory("the prune session")

    # -- retrain the ticket through the block-sparse kernels ------------
    trainer = adapter.make_trainer(res.params, res.masks, steps=steps)
    st = adapter.last_plan_stats
    print(f"  retrain PlanStats: routed={st.routed} dense_fallback="
          f"{st.dense_fallback}, live tiles {st.live_tiles}/"
          f"{st.total_tiles} ({1 - st.skipped_tile_fraction:.4f})",
          flush=True)
    print("  live tiles per projection: " + ", ".join(
        f"{label.split('.', 2)[-1]} {live}/{total}"
        for label, live, total in st.by_layer), flush=True)
    check(st.routed > 0, "retrain routes projections through bsmm")
    b = adapter.data.batch(0, adapter.batch_size)
    batch = {"tokens": jnp.asarray(b["tokens"]),
             "labels": jnp.asarray(b["labels"])}
    n_train = custom_calls(trainer.step_fn.lower(
        trainer.state.params, trainer.state.opt_state, batch).compile())
    check(n_train > 0, f"(b) compiled retrain step holds {n_train} "
          f"tpu_custom_call ops")
    metrics = trainer.run(steps)
    device_memory("the retrain")
    check(np.isfinite(metrics["loss"]),
          f"retrain loss is finite ({metrics['loss']:.6f})")
    n_bad = pruned_stay_zero(trainer.state.params, res.masks)
    check(n_bad == 0, "(e) pruned weights are exactly 0 after the retrain")
    del trainer

    check_kernel_vs_dense(res.params, res.masks, rng)

    # -- serve the ticket on the paged path ------------------------------
    engine = session.serve_engine(batch_slots=SERVE_SLOTS,
                                  capacity=SERVE_CAPACITY)
    gen = engine.generations[-1]
    check(engine.paged and gen.plan is not None,
          "the engine decodes paged with the ticket's tile plans")
    train_plan, _ = lm_train_plan(res.masks,
                                  interpret=adapter.bsmm_interpret)
    plans = tile_plans(train_plan) + tile_plans(gen.plan)
    check(bool(plans) and all(p.interpret is False for p in plans),
          f"(a) all {len(plans)} TilePlans (retrain + serving) have "
          f"interpret=False")
    rec = DecodeLogits(gen)
    reqs = make_requests(rng, N_REQUESTS, PROMPT_MIN, PROMPT_MAX,
                         cfg.vocab_size, MAX_NEW)
    for r in reqs:
        engine.submit(r)
    done = engine.run()
    device_memory("serving")
    check(len(done) == N_REQUESTS
          and all(r.status == "done" and len(r.tokens) == MAX_NEW
                  for r in done),
          f"{N_REQUESTS} requests ({PROMPT_MIN}-{PROMPT_MAX} prompt "
          f"tokens) each got {MAX_NEW} new tokens")
    rep = engine.report
    print(f"  serve: {rep.decode_steps} decode steps, {rep.prefills} "
          f"prefills, {rep.routed_matmuls} routed matmuls, skipped tile "
          f"fraction {rep.skipped_tile_fraction:.4f}, kv blocks peak "
          f"{rep.kv_blocks_peak}/{rep.kv_blocks}", flush=True)
    n_dec = custom_calls(rec.compiled())
    check(n_dec > 0, f"(b) compiled paged decode step holds {n_dec} "
          f"tpu_custom_call ops")

    # (d) engine decode logits vs a plain f32 forward of the same ticket
    pick = [min(reqs, key=lambda r: len(r.prompt)),
            max(reqs, key=lambda r: len(r.prompt))]
    seqs = [np.concatenate([r.prompt, r.tokens[:-1]]) for r in pick]
    # right-padding is invisible to a causal forward at earlier positions;
    # past one query block (512, models.attention.causal_attention) the
    # forward needs a whole number of blocks
    width = -(-max(len(s) for s in seqs) // 512) * 512
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    # the same decode positions after another prompt: how far a wrong
    # context moves the logits, i.e. what the tolerance can see
    other = toks.copy()
    for i, r in enumerate(pick):
        other[i, :len(r.prompt)] = rng.randint(0, cfg.vocab_size,
                                               len(r.prompt))
    ref, ref_other = (reference_logits(res.params, cfg, t)
                      for t in (toks, other))
    sens = np.inf
    for i, r in enumerate(pick):
        n = len(r.prompt)
        rows = np.stack(rec.rows[r.uid])          # step j feeds tokens[j]
        want = ref[i, n:n + len(rows)]
        errs = [rel_err(a, b) for a, b in zip(rows, want)]
        sens = min(sens, min(rel_err(a, b) for a, b in
                             zip(ref_other[i, n:n + len(rows)], want)))
        check(len(rows) == MAX_NEW - 1 and max(errs) <= LOGIT_RTOL,
              f"(d) uid {r.uid} (prompt {n}): {len(rows)} decode logits "
              f"vs f32 forward, max rel err {max(errs):.3e} <= "
              f"{LOGIT_RTOL:g}")
    check(sens > LOGIT_RTOL, f"(d) the tolerance sees context: another "
          f"prompt moves the reference logits by rel err >= {sens:.3e}")


# ---------------------------------------------------------------------------
# --chips 4: the paths that exist only across chips
# ---------------------------------------------------------------------------
def phase_multichip(adapter, seed: int, n_chips: int) -> None:
    import jax
    from repro.analysis import verify_fleet
    from repro.api import structured_prune
    from repro.configs import PruneConfig
    from repro.core.masks import apply_masks
    from repro.launch.mesh import make_fleet_meshes, make_test_mesh
    from repro.serve import FleetRouter, ServeEngine

    cfg = adapter.cfg
    rng = np.random.RandomState(seed)
    params = adapter.init_params(jax.random.PRNGKey(seed))
    masks = structured_prune(params, [("xbar", LM_SPARSITY)],
                             prunable=adapter.prunable,
                             conv_pred=adapter.conv_pred,
                             cfg=PruneConfig())
    params = apply_masks(params, masks)
    prefill_fn, decode_fn = adapter.serve_fns()

    def engine(mesh):
        return ServeEngine(params=params, cfg=cfg, prefill_fn=prefill_fn,
                           decode_fn=decode_fn, batch_slots=SERVE_SLOTS,
                           capacity=SERVE_CAPACITY, masks=masks, mesh=mesh)

    # -- one ticket over a (1, n) mesh vs the one-chip engine ------------
    outs = {}
    for tag, mesh in (("one-chip", None),
                      (f"(1, {n_chips}) mesh", make_test_mesh(1, n_chips))):
        eng = engine(mesh)
        rec = DecodeLogits(eng.generations[-1])
        for r in make_requests(np.random.RandomState(seed), N_REQUESTS,
                               PROMPT_MIN, PROMPT_MAX, cfg.vocab_size,
                               MAX_NEW):
            eng.submit(r)
        outs[tag] = ({r.uid: r.tokens for r in eng.run()}, rec.rows)
        print(f"  {tag} engine: {eng.report.decode_steps} decode steps",
              flush=True)
    (tok1, rows1), (tokn, rowsn) = outs.values()
    compared, worst, agree = 0, 0.0, 0
    for uid, t1 in tok1.items():
        tn = tokn[uid]
        agree += sum(a == b for a, b in zip(t1, tn))
        # decode step j feeds token j: comparable while both streams agree
        for j, (a, b) in enumerate(zip(rows1[uid], rowsn[uid])):
            if t1[:j + 1] != tn[:j + 1]:
                break
            worst = max(worst, rel_err(b, a))
            compared += 1
    print(f"  greedy tokens agree at {agree}/{sum(map(len, tok1.values()))}"
          f" positions", flush=True)
    check(compared > 0 and worst <= MESH_RTOL,
          f"(1, {n_chips})-mesh decode logits vs one-chip engine on "
          f"{compared} steps with identical inputs: max rel err "
          f"{worst:.3e} <= {MESH_RTOL:g}")

    # -- a fleet of one-chip replicas, engine 0 killed mid-run -----------
    router = FleetRouter([engine(m) for m in make_fleet_meshes(n_chips)])
    homes = []
    for i, fe in enumerate(router.frontends):
        devs = {d for leaf in jax.tree.leaves(fe.engine.generations[-1]
                                              .params)
                for d in leaf.devices()}
        homes.append(frozenset(devs))
        print(f"  fleet engine {i} params on {sorted(map(str, devs))}",
              flush=True)
    check(len(set(homes)) == n_chips and all(len(h) == 1 for h in homes),
          f"the {n_chips} fleet engines sit on {n_chips} distinct devices")
    recs = [router.submit(r.prompt, uid=r.uid, max_new_tokens=r.max_new_tokens)
            for r in make_requests(rng, FLEET_REQUESTS, FLEET_PROMPT_MIN,
                                   FLEET_PROMPT_MAX, cfg.vocab_size,
                                   FLEET_MAX_NEW)]
    for _ in range(10 * FLEET_MAX_NEW):
        if any(rec.engine == 0 and len(rec.tokens) >= 4 for rec in recs):
            break
        router.pump(1)
    moved = router.kill(0)
    router.drain()
    print(f"  killed engine 0 mid-run: {len(moved)} requests re-dispatched",
          flush=True)
    finished = [rec.uid for rec in router.finished]
    check(sorted(finished) == sorted(r.uid for r in recs)
          and all(r.status == "done" and len(r.tokens) == FLEET_MAX_NEW
                  for r in recs) and moved,
          f"every uid of {FLEET_REQUESTS} finished exactly once with "
          f"{FLEET_MAX_NEW} tokens after the failover")
    errors = [f for f in verify_fleet(router) if f.severity == "error"]
    check(not errors, f"fleet invariants hold (P116): {errors}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip serving paths")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)

    from repro.api import make_adapter
    from repro.configs import get_arch

    clock = CompileClock()
    log: list = []
    t0 = time.perf_counter()
    lm_cfg = dataclasses.replace(get_arch(LM_ARCH), n_layers=LM_LAYERS)
    lm = make_adapter(lm_cfg, scale="full", steps=LM_STEPS,
                      batch_size=LM_BATCH, seq_len=LM_SEQ, warmup=1,
                      eval_batches=1)
    if args.chips == 1:
        cnn = make_adapter(CNN_ARCH, scale="full", steps=CNN_STEPS,
                           batch_size=CNN_BATCH, eval_batches=1,
                           eval_batch_size=CNN_BATCH)
        with Phase("cnn", clock, log):
            phase_cnn(cnn, args.seed, CNN_STEPS)
        with Phase("lm", clock, log):
            phase_lm(lm, args.seed, LM_STEPS)
    else:
        with Phase(f"multichip x{args.chips}", clock, log):
            phase_multichip(lm, args.seed, args.chips)

    for name, comp, run, hits in log:
        print(f"phase {name}: compile {comp:.1f} s, run {run:.1f} s, "
              f"persistent-cache hits {hits}", flush=True)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        print(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
              flush=True)
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
