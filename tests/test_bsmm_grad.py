"""The block-sparse training path: custom-VJP gradients vs the dense
masked oracle, and the tile-pass accounting behind the paper's
"pruning makes retraining faster" claim."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.masks import mask_grads
from repro.kernels import ref
from repro.kernels.bsmm import make_tile_plan, plan_matmul
from repro.kernels.ops import sparse_dense
from repro.models.attention import gqa_forward, gqa_init
from repro.models.layers import mlp, mlp_init
from repro.train.plans import cnn_train_plan, lm_train_plan

TOL = dict(rtol=1e-5, atol=1e-4)


def _random_mask(rng, K, N, density=0.4, tile=128):
    """Elementwise mask with ~``density`` live elements AND at least one
    fully-dead 128x128 tile column when the shape allows."""
    m = (rng.rand(K, N) < density).astype(np.float32)
    if N >= 2 * tile:
        m[:, tile:2 * tile] = 0.0          # all-dead output tile column
    return m


def _grads(fn, *args):
    return jax.grad(lambda *a: jnp.sum(jnp.square(fn(*a))),
                    argnums=tuple(range(len(args))))(*args)


# -- sparse_dense: direct oracle equivalence --------------------------------
@pytest.mark.parametrize("M,K,N", [
    (8, 256, 128),       # MLP up-proj shape
    (16, 128, 128),      # attention projection shape
    (64, 256, 256),      # FC shape (all-dead tile column case)
    (5, 256, 128),       # ragged-M retrain microbatch
    (3, 128, 384),       # ragged M, wide N
    (384, 256, 384),     # one 384-row block
    (2048, 256, 256),    # a 4 x 512 retrain batch: one 2048-row block
])
def test_sparse_dense_grads_match_dense_oracle(M, K, N):
    rng = np.random.RandomState(M * 7 + K + N)
    mask = _random_mask(rng, K, N)
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)

    def s_fn(x, w):
        return sparse_dense(x, w, mask)

    def d_fn(x, w):
        return ref.masked_matmul_ref(x, w, jnp.asarray(mask))

    np.testing.assert_allclose(np.asarray(s_fn(x, w)),
                               np.asarray(d_fn(x, w)), **TOL)
    (dxs, dws), (dxd, dwd) = _grads(s_fn, x, w), _grads(d_fn, x, w)
    # grads: same math, different accumulation order → slightly wider tol
    np.testing.assert_allclose(np.asarray(dxs), np.asarray(dxd),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(dws), np.asarray(dwd),
                               rtol=1e-4, atol=1e-3)


def test_sparse_dense_grad_all_dead_mask_is_zero():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 128), jnp.float32)
    mask = np.zeros((128, 128), np.float32)
    out = sparse_dense(x, w, mask)
    assert float(jnp.abs(out).max()) == 0.0
    dx, dw = _grads(lambda x, w: sparse_dense(x, w, mask), x, w)
    assert float(jnp.abs(dx).max()) == 0.0
    assert float(jnp.abs(dw).max()) == 0.0


def test_sparse_dense_ragged_m_stays_on_kernel(monkeypatch):
    """M that doesn't tile is sublane-padded through the kernel now —
    the dense oracle fallback is reserved for ragged K/N."""
    def boom(*a, **k):
        raise AssertionError("dense fallback used for ragged M")
    monkeypatch.setattr(ref, "masked_matmul_ref", boom)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 128), jnp.float32)
    out = sparse_dense(x, w, np.ones((128, 128), np.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), **TOL)
    # ragged K still falls back (and the monkeypatch proves it)
    with pytest.raises(AssertionError, match="dense fallback"):
        sparse_dense(jnp.asarray(rng.randn(4, 100), jnp.float32),
                     jnp.asarray(rng.randn(100, 128), jnp.float32),
                     np.ones((100, 128), np.float32))


# -- fused bias+activation epilogue -----------------------------------------
@pytest.mark.parametrize("act", [None, "relu", "gelu", "silu"])
@pytest.mark.parametrize("with_bias", [True, False])
def test_epilogue_fused_matches_unfused_oracle(act, with_bias):
    """``plan_matmul(..., bias=b, act=a)`` fuses the epilogue into the
    kernel flush; forward and all grads (incl. db) must match the
    unfused two-pass oracle on live tiles."""
    if act is None and not with_bias:
        pytest.skip("no epilogue — identical to the plain path")
    from repro.kernels.bsmm import _EPILOGUE_ACTS
    rng = np.random.RandomState(11)
    M, K, N = 24, 256, 384
    mask = _random_mask(rng, K, N)
    plan = make_tile_plan(mask)
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N) * mask, jnp.float32)
    b = jnp.asarray(rng.randn(N), jnp.float32) if with_bias else None
    fn = _EPILOGUE_ACTS.get(act, lambda z: z)

    def fused(x, w, b):
        return plan_matmul(x, w, plan, bias=b, act=act)

    def oracle(x, w, b):
        z = plan_matmul(x, w, plan)
        return fn(z if b is None else z + b)

    np.testing.assert_allclose(np.asarray(fused(x, w, b)),
                               np.asarray(oracle(x, w, b)), **TOL)
    args = (x, w, b) if with_bias else (x, w)
    loss_f = lambda *a: jnp.sum(jnp.sin(fused(*a, *(() if with_bias else (None,)))))
    loss_o = lambda *a: jnp.sum(jnp.sin(oracle(*a, *(() if with_bias else (None,)))))
    gf = jax.grad(loss_f, argnums=tuple(range(len(args))))(*args)
    go = jax.grad(loss_o, argnums=tuple(range(len(args))))(*args)
    names = ("dx", "dw", "db")[:len(args)]
    for name, a, o in zip(names, gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(o),
                                   rtol=1e-4, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("M", [384, 2048])
@pytest.mark.parametrize("act", [None, "silu"])
def test_epilogue_tall_row_block_matches_dense_oracle(M, act):
    """One row block of all M rows (``row_block``), over a mask with an
    all-dead tile column and columns/rows with dead plan slots: the
    fused forward and its dx/dw/db match the dense masked oracle."""
    from repro.kernels.bsmm import _EPILOGUE_ACTS, row_block
    rng = np.random.RandomState(M)
    K, N = 256, 384
    mask = _random_mask(rng, K, N)
    mask[:128, 256:] = 0.0              # column 2 and row 0: one dead slot
    plan = make_tile_plan(mask)
    assert (plan.counts.tolist(), plan.counts_t.tolist()) == ([2, 0, 1],
                                                              [1, 2])
    assert row_block(M, jnp.dtype(jnp.float32)) == (M, M)
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    b = jnp.asarray(rng.randn(N), jnp.float32)
    fn = _EPILOGUE_ACTS.get(act, lambda z: z)

    def fused(x, w, b):
        return plan_matmul(x, w * jnp.asarray(mask), plan, bias=b, act=act)

    def oracle(x, w, b):
        return fn(ref.masked_matmul_ref(x, w, jnp.asarray(mask)) + b)

    np.testing.assert_allclose(np.asarray(fused(x, w, b)),
                               np.asarray(oracle(x, w, b)), **TOL)
    gf = jax.grad(lambda *a: jnp.sum(jnp.sin(fused(*a))),
                  argnums=(0, 1, 2))(x, w, b)
    go = jax.grad(lambda *a: jnp.sum(jnp.sin(oracle(*a))),
                  argnums=(0, 1, 2))(x, w, b)
    for name, a, o in zip(("dx", "dw", "db"), gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(o),
                                   rtol=1e-4, atol=2e-3, err_msg=name)


def test_epilogue_rejects_unknown_activation():
    rng = np.random.RandomState(12)
    mask = np.ones((128, 128), np.float32)
    plan = make_tile_plan(mask)
    x = jnp.asarray(rng.randn(8, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 128), jnp.float32)
    with pytest.raises(ValueError, match="unsupported epilogue act"):
        plan_matmul(x, w, plan, act="tanh")
    with pytest.raises(ValueError, match="unsupported epilogue act"):
        plan_matmul(x, w, None, act="tanh")


# -- model layers: plan path vs dense on pre-masked params ------------------
# Inside a live tile the kernel's dw covers the whole tile (the
# elementwise mask is the masked optimizer's job), so the comparison
# against the dense path is through ``mask_grads`` — the quantity the
# optimizer actually consumes.
def test_mlp_plan_grads_match_dense():
    rng = np.random.RandomState(2)
    d_model, d_ff, B, S = 128, 256, 2, 8
    params = mlp_init(jax.random.PRNGKey(0), d_model, d_ff, gated=True)
    masks = {k: jnp.asarray(_random_mask(rng, *params[k].shape))
             for k in ("up", "gate", "down")}
    params = {k: params[k] * masks[k] for k in params}
    plan = {k: make_tile_plan(np.asarray(masks[k])) for k in masks}
    assert all(p is not None for p in plan.values())
    x = jnp.asarray(rng.randn(B, S, d_model), jnp.float32)

    def loss_plan(p):
        return jnp.sum(jnp.square(mlp(p, x, plan=plan)))

    def loss_dense(p):
        return jnp.sum(jnp.square(mlp(p, x)))

    np.testing.assert_allclose(float(loss_plan(params)),
                               float(loss_dense(params)), rtol=1e-5)
    gp = mask_grads(jax.grad(loss_plan)(params), masks)
    gd = mask_grads(jax.grad(loss_dense)(params), masks)
    for k in params:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gd[k]),
                                   rtol=1e-4, atol=1e-3)


def test_gqa_forward_plan_grads_match_dense():
    rng = np.random.RandomState(3)
    d_model, n_heads, head_dim, B, S = 128, 2, 64, 2, 8
    params = gqa_init(jax.random.PRNGKey(0), d_model, n_heads, n_heads,
                      head_dim)
    keys = ("wq", "wk", "wv", "wo")
    masks = {k: jnp.asarray(_random_mask(rng, *params[k].shape))
             for k in keys}
    params = {k: params[k] * masks[k] for k in params}
    plan = {k: make_tile_plan(np.asarray(masks[k])) for k in keys}
    assert all(p is not None for p in plan.values())
    x = jnp.asarray(rng.randn(B, S, d_model), jnp.float32)
    kw = dict(n_heads=n_heads, n_kv_heads=n_heads, head_dim=head_dim,
              rope_theta=10_000.0)

    def loss_plan(p):
        return jnp.sum(jnp.square(gqa_forward(p, x, plan=plan, **kw)))

    def loss_dense(p):
        return jnp.sum(jnp.square(gqa_forward(p, x, **kw)))

    np.testing.assert_allclose(float(loss_plan(params)),
                               float(loss_dense(params)), rtol=1e-5)
    gp = mask_grads(jax.grad(loss_plan)(params), masks)
    gd = mask_grads(jax.grad(loss_dense)(params), masks)
    for k in keys:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gd[k]),
                                   rtol=1e-4, atol=1e-3)


def test_cnn_non_tiling_shapes_stay_dense():
    """Shapes that don't tile 128 get no plan and the forward still runs
    (everything dense) — the small-config safety net."""
    from repro.configs.base import CNNConfig, ConvSpec
    from repro.models import cnn as cnn_lib
    rng = np.random.RandomState(4)
    cfg = CNNConfig(name="tiny-fc", family="vgg", convs=(ConvSpec(16),),
                    fc=(128,), num_classes=10, image_size=8)
    params, state = cnn_lib.init_params(jax.random.PRNGKey(0), cfg)
    masks = {"convs": [None], "bns": [None],
             "shortcuts": {},
             "fc": [{"w": jnp.asarray(_random_mask(rng, 16, 128)),
                     "b": None}],
             "head": {"w": jnp.asarray(_random_mask(rng, 128, 10)),
                      "b": None}}
    plans, stats = cnn_train_plan(masks)
    # neither (16,128) nor (128,10) tiles at 128 — everything stays dense
    assert plans is None and stats.routed == 0 and stats.dense_fallback == 2
    images = jnp.asarray(rng.randn(4, 8, 8, 3), jnp.float32)
    logits, _ = cnn_lib.forward(params, state, cfg, images, plans=plans)
    assert logits.shape == (4, 10)


def test_cnn_fc_plan_grads_match_dense():
    """A CNN whose GAP feature width tiles 128: the FC layer is routed
    block-sparse through ``cnn.forward`` and the loss/grads of the plan
    path agree with the dense path on pre-masked weights."""
    from repro.configs.base import CNNConfig, ConvSpec
    from repro.models import cnn as cnn_lib
    rng = np.random.RandomState(5)
    cfg = CNNConfig(name="fc-128", family="vgg", convs=(ConvSpec(128),),
                    fc=(256,), num_classes=10, image_size=8)
    params, state = cnn_lib.init_params(jax.random.PRNGKey(0), cfg)
    m1 = jnp.asarray(_random_mask(rng, 128, 256))
    masks = {"fc": [{"w": m1, "b": None}], "head": None}
    plans, stats = cnn_train_plan(masks)
    assert plans is not None and stats.routed == 1
    assert plans["fc"][0] is not None and plans["head"] is None
    params["fc"][0]["w"] = params["fc"][0]["w"] * m1
    images = jnp.asarray(rng.randn(4, 8, 8, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, size=(4,)), jnp.int32)
    batch = {"images": images, "labels": labels}

    def loss(p, plans):
        l, _ = cnn_lib.loss_fn(p, state, cfg, batch, train=True, plans=plans)
        return l

    lp = float(loss(params, plans))
    ld = float(loss(params, None))
    np.testing.assert_allclose(lp, ld, rtol=1e-5)
    gp = jax.grad(loss)(params, plans)
    gd = jax.grad(loss)(params, None)
    grad_masks = jax.tree.map(lambda _: None, params)
    grad_masks["fc"][0]["w"] = m1
    gp, gd = mask_grads(gp, grad_masks), mask_grads(gd, grad_masks)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4), gp, gd)


# -- the acceptance accounting: fewer passes at low density -----------------
def test_retrain_step_low_density_executes_fewer_passes():
    """A <=10%-tile-density plan must run strictly fewer K-grid passes
    (fwd), N-grid passes (dx) and weight-grad tiles (dw) than dense —
    the static counts the TPU grid actually executes — and a jitted
    train step closed over the plan must still descend the loss."""
    rng = np.random.RandomState(6)
    K = N = 512
    tile = 128
    Kt, Nt = K // tile, N // tile
    mask = np.zeros((K, N), np.float32)
    mask[:tile, :tile] = 1.0               # 1 of 16 tiles live (6.25%)
    plan = make_tile_plan(mask)
    assert plan.live_tiles / plan.total_tiles <= 0.10
    # strict pass reductions vs the dense grid
    assert plan.kmax < Kt                  # forward: K-grid passes
    assert plan.nmax < Nt                  # dx: transposed N-grid passes
    assert plan.live_tiles < Kt * Nt       # dw: materialised grad tiles
    assert int(plan.counts.sum()) == plan.live_tiles

    w = jnp.asarray(rng.randn(K, N) * mask, jnp.float32)
    x = jnp.asarray(rng.randn(16, K), jnp.float32)
    y = jnp.asarray(rng.randn(16, N), jnp.float32)

    @jax.jit
    def step(w):
        def loss(w):
            return jnp.mean(jnp.square(plan_matmul(x, w, plan) - y))
        l, g = jax.value_and_grad(loss)(w)
        return l, w - 0.01 * g

    l0, w1 = step(w)
    l1, _ = step(w1)
    assert np.isfinite(float(l0)) and float(l1) < float(l0)
    # weight grads outside live tiles are identically zero → the update
    # never resurrects a dead tile
    dead = np.asarray(w1)[tile:, tile:]
    assert float(np.abs(dead).max()) == 0.0


def test_lm_adapter_retrains_through_bsmm():
    """End to end: LMAdapter with use_bsmm=True closes a mask-derived
    plan into the jitted train step, trains without NaNs, and records
    the routed-matmul stats the session logs per retrain round."""
    from repro.api import LMAdapter
    from repro.configs import get_arch, scaled_down
    from repro.core.masks import lm_prunable, make_masks
    cfg = scaled_down(get_arch("llama3.2-3b"), d_model=128, n_layers=2,
                      n_heads=2, n_kv_heads=2, d_ff=256, head_dim=64,
                      vocab_size=128)
    ad = LMAdapter(cfg, steps=2, batch_size=2, seq_len=16, use_bsmm=True,
                   bsmm_interpret=True)
    params = ad.init_params(jax.random.PRNGKey(0))
    masks = make_masks(params, lm_prunable)
    rng = np.random.RandomState(7)
    masks = jax.tree.map(
        lambda m: (m * jnp.asarray(_random_mask(rng, *m.shape[-2:]))
                   if m is not None and m.ndim >= 2 else m),
        masks, is_leaf=lambda x: x is None)
    p2 = ad.train(params, masks, steps=2)
    assert ad.last_plan_stats.routed > 0
    assert 0.0 < ad.last_plan_stats.skipped_tile_fraction < 1.0
    assert np.isfinite(ad.evaluate(p2, masks))


def test_lm_train_plan_matches_decode_plan_structure():
    from repro.configs import get_arch, scaled_down
    from repro.core.masks import lm_prunable, make_masks
    from repro.models import transformer as tfm
    from repro.models.plans import build_decode_plan
    cfg = scaled_down(get_arch("llama3.2-3b"), d_model=128, n_layers=2,
                      n_heads=2, n_kv_heads=2, d_ff=256, head_dim=64,
                      vocab_size=128)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    masks = make_masks(params, lm_prunable)
    train_plan, t_stats = lm_train_plan(masks, interpret=True)
    decode_plan, d_stats = build_decode_plan(masks, interpret=True)
    assert t_stats.routed == d_stats.routed > 0
    assert jax.tree.structure(train_plan) == jax.tree.structure(decode_plan)
