"""Trainer: convergence, microbatching, checkpoint-resume, stragglers,
profiler spans."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import DataPipeline, SyntheticLM
from repro.optim import adamw, constant, masked, sgd
from repro.train import Trainer, make_train_step


def _tiny_lm():
    """2-layer MLP LM on the markov stream."""
    import jax.random as jr
    V, D, S = 32, 16, 16
    ks = jr.split(jr.PRNGKey(0), 3)
    params = {"emb": jr.normal(ks[0], (V, D)) * 0.1,
              "w1": jr.normal(ks[1], (2 * D, 4 * D)) * 0.1,
              "w2": jr.normal(ks[2], (4 * D, V)) * 0.1}

    def loss_fn(params, batch):
        x = params["emb"][batch["tokens"]]              # (B,S,D)
        prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        h = jnp.concatenate([x, prev], -1)
        h = jax.nn.relu(h @ params["w1"])
        logits = h @ params["w2"]
        ll = jax.nn.log_softmax(logits)
        loss = -jnp.take_along_axis(
            ll, batch["labels"][..., None], -1).mean()
        return loss, {}

    gen = SyntheticLM(vocab_size=V, seq_len=S, seed=0, noise=0.0)
    return params, loss_fn, gen


def test_train_step_reduces_loss():
    params, loss_fn, gen = _tiny_lm()
    opt = adamw(constant(1e-2))
    step = make_train_step(loss_fn, opt, donate=False)
    opt_state = opt.init(params)
    losses = []
    for i in range(150):
        b = {k: jnp.asarray(v) for k, v in gen.batch(i, 16).items()}
        params, opt_state, m = step(params, opt_state, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.85


def test_microbatching_matches_full_batch():
    params, loss_fn, gen = _tiny_lm()
    opt = sgd(constant(0.1), momentum=0.0)
    full = make_train_step(loss_fn, opt, donate=False)
    micro = make_train_step(loss_fn, opt, microbatch=4, donate=False)
    b = {k: jnp.asarray(v) for k, v in gen.batch(0, 16).items()}
    s0 = opt.init(params)
    p1, _, m1 = full(params, s0, b)
    s0 = opt.init(params)
    p2, _, m2 = micro(params, s0, b)
    for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-6)


def test_trainer_checkpoint_resume(tmp_path):
    params, loss_fn, gen = _tiny_lm()

    def make_trainer():
        pipe = DataPipeline(
            lambda s: {k: jnp.asarray(v) for k, v in gen.batch(s, 8).items()},
            prefetch=0)
        return Trainer(loss_fn=loss_fn, optimizer=adamw(constant(1e-3)),
                       params=params, data_iter=pipe,
                       ckpt_dir=str(tmp_path), ckpt_every=5,
                       async_ckpt=False)

    t1 = make_trainer()
    t1.run(10, log_every=0)
    w_after_10 = np.asarray(t1.state.params["w1"]).copy()
    # new trainer resumes from step 10, not 0
    t2 = make_trainer()
    assert t2.state.step == 10
    np.testing.assert_allclose(np.asarray(t2.state.params["w1"]),
                               w_after_10, rtol=1e-6)
    t2.run(5, log_every=0)
    assert t2.state.step == 15


def test_straggler_callback_fires():
    params, loss_fn, gen = _tiny_lm()
    events = []
    pipe = DataPipeline(
        lambda s: {k: jnp.asarray(v) for k, v in gen.batch(s, 8).items()},
        prefetch=0)
    t = Trainer(loss_fn=loss_fn, optimizer=adamw(constant(1e-3)),
                params=params, data_iter=pipe, ckpt_dir=None,
                step_deadline_s=0.0,          # everything is a straggler
                on_straggler=lambda step, dt: events.append((step, dt)))
    t.run(3, log_every=0)
    assert len(events) == 3


def test_trainer_run_records_its_phases_as_profiler_spans(tmp_path):
    """Under a profiler session each step of ``Trainer.run`` is a
    ``train.step`` span holding ``train.data``, ``train.dispatch`` and
    ``train.wait``, once each and in that order."""
    import glob

    from jax.profiler import ProfileData

    params, loss_fn, gen = _tiny_lm()
    pipe = DataPipeline(
        lambda s: {k: jnp.asarray(v) for k, v in gen.batch(s, 8).items()},
        prefetch=0)
    t = Trainer(loss_fn=loss_fn, optimizer=adamw(constant(1e-3)),
                params=params, data_iter=pipe, ckpt_dir=None)
    t.run(1, log_every=0)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        t.run(2, log_every=0)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for p in ProfileData.from_file(path).planes
                    for line in p.lines for e in line.events
                    if e.name.startswith("train.")), key=lambda s: s[1])
    steps = [s for s in spans if s[0] == "train.step"]
    assert [int(s[3]["step_num"]) for s in steps] == [1, 2]
    for _, t0, t1, _ in steps:
        inner = [s for s in spans if s[0] != "train.step"
                 and t0 <= s[1] and s[2] <= t1]
        assert [s[0] for s in inner] == ["train.data", "train.dispatch",
                                         "train.wait"]
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    assert len(spans) == 4 * len(steps)


@pytest.mark.parametrize("aux_state", [False, True])
def test_optimizer_update_runs_under_its_named_scope(aux_state):
    """Both step bodies put the update under ``train.optimizer``, the
    scope a profiler trace groups its device ops by; the gradient's ops
    stay outside it."""
    import re
    from repro.train.loop import OPTIMIZER_SCOPE
    params, loss_fn, gen = _tiny_lm()
    opt = adamw(constant(1e-3))
    batch = {k: jnp.asarray(v) for k, v in gen.batch(0, 4).items()}
    if aux_state:
        step = make_train_step(
            lambda p, s, b: (loss_fn(p, b)[0], (s, {})), opt,
            donate=False, has_aux_state=True)
        args = (params, opt.init(params), {"n": jnp.zeros(())}, batch)
    else:
        step = make_train_step(loss_fn, opt, donate=False)
        args = (params, opt.init(params), batch)
    text = step.lower(*args).as_text(debug_info=True)
    scopes = set(re.findall(r'loc\("([^"]*)"', text))
    assert any(f"/{OPTIMIZER_SCOPE}/" in s for s in scopes)
    assert any("dot_general" in s and OPTIMIZER_SCOPE not in s
               for s in scopes)
