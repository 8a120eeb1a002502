"""Sharding rules (pure PartitionSpec math + an 8-device subprocess run)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


class FakeMesh:
    """Duck-typed mesh for pure spec tests (no devices)."""
    def __init__(self, shape):
        self.axis_names = tuple(shape)
        self.shape = dict(shape)


def rules(shape=None):
    from repro.distributed.sharding import ShardingRules
    mesh = FakeMesh(shape or {"data": 16, "model": 16})
    return ShardingRules(mesh)


def test_col_parallel_shards_last_dim():
    r = rules()
    spec = r.param_spec("segments/0/0/attn/wq", (8192, 8192))
    assert spec == __import__("jax").sharding.PartitionSpec(None, "model")


def test_row_parallel_shards_first_matrix_dim():
    r = rules()
    spec = r.param_spec("segments/0/0/attn/wo", (64, 8192, 8192))
    # stacked (reps, in, out): row-parallel on in
    assert tuple(spec) == (None, "model", None)


def test_moe_experts_sharded():
    r = rules()
    spec = r.param_spec("segments/1/0/moe/up", (58, 256, 7168, 2048))
    assert tuple(spec) == (None, "model", None, None)


def test_vocab_parallel_embed():
    r = rules()
    spec = r.param_spec("embed/table", (131072, 4096))
    assert tuple(spec) == ("model", None)


def test_indivisible_falls_back():
    r = rules()
    # 10 heads × 256 = 2560 — divisible; but a 10-dim leaf is not
    spec = r.param_spec("segments/0/0/attn/wq", (2560, 10))
    assert tuple(spec) == ("model", None)   # falls back to in-dim
    spec = r.param_spec("x/unknown", (6, 10))
    assert tuple(spec) == (None, None)


def test_norms_replicated():
    r = rules()
    assert tuple(r.param_spec("norm1/scale", (8192,))) == (None,)


def test_batch_spec_dp_axes():
    r = rules({"pod": 2, "data": 16, "model": 16})
    spec = r.batch_spec((256, 4096))
    assert tuple(spec) == (("pod", "data"), None)
    # batch=1 (long_500k): unshardable → replicated
    assert tuple(r.batch_spec((1, 4096))) == (None, None)


def _norm(spec):
    out = []
    for s in tuple(spec):
        out.append(s[0] if isinstance(s, tuple) and len(s) == 1 else s)
    return tuple(out)


def test_cache_spec_prefers_heads_then_seq():
    r = rules()
    # (B, C, Hkv, hd): heads=32 divisible → heads sharded
    spec = r.cache_spec("c", (128, 32768, 32, 128))
    assert _norm(spec) == ("data", None, "model", None)
    # kv=8 heads < 16: falls to the sequence dim (SP decode)
    spec = r.cache_spec("c", (128, 32768, 8, 128))
    assert _norm(spec) == ("data", "model", None, None)


SUBPROCESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch, scaled_down
    from repro.distributed.sharding import ShardingRules, install
    from repro.launch.mesh import make_test_mesh
    from repro.models import transformer as tfm

    mesh = make_test_mesh(2, 4)
    rules = ShardingRules(mesh)
    install(rules)
    cfg = scaled_down(get_arch("yi-6b"), dtype="float32", d_model=128,
                      n_heads=4, n_kv_heads=4, head_dim=32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    shardings = rules.params_shardings(params)
    params = jax.device_put(params, shardings)
    batch = {"tokens": jnp.ones((8, 32), jnp.int32),
             "labels": jnp.ones((8, 32), jnp.int32)}
    batch = jax.device_put(batch, rules.batch_shardings(batch))
    with mesh:
        loss, _ = jax.jit(lambda p, b: tfm.loss_fn(p, cfg, b))(params, batch)
    # compare against single-device value
    install(None)
    params_local = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), params)
    batch_local = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), batch)
    loss2, _ = tfm.loss_fn(params_local, cfg, batch_local)
    assert abs(float(loss) - float(loss2)) < 1e-3, (float(loss), float(loss2))
    print("SHARDED_OK", float(loss))
""")


def test_sharded_loss_matches_single_device():
    """Real 8-device (host platform) run in a subprocess: the sharded
    jitted loss equals the unsharded value."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_TEST],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert "SHARDED_OK" in out.stdout, out.stderr[-2000:]
