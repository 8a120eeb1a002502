"""True elastic restart: checkpoint on an 8-device mesh, restore and
continue on a 4-device mesh (subprocess with forced host devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.checkpoint import CheckpointManager
    from repro.distributed.sharding import ShardingRules, install
    from repro.launch.mesh import make_test_mesh
    from repro.models import transformer as tfm
    from repro.configs import get_arch, scaled_down

    ckpt_dir = sys.argv[1]
    cfg = scaled_down(get_arch("llama3.2-3b"), dtype="float32",
                      d_model=128, n_heads=4, n_kv_heads=4, head_dim=32)

    def make(mesh_shape):
        mesh = make_test_mesh(*mesh_shape)
        rules = ShardingRules(mesh)
        install(rules)
        return mesh, rules

    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.ones((8, 16), jnp.int32),
             "labels": jnp.ones((8, 16), jnp.int32)}

    # phase 1: big mesh (2 data × 4 model) — train one step, checkpoint
    mesh, rules = make((2, 4))
    p1 = jax.device_put(params, rules.params_shardings(params))
    with mesh:
        loss1, _ = jax.jit(lambda p, b: tfm.loss_fn(p, cfg, b))(p1, batch)
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(1, {"params": p1})

    # phase 2: "lost half the hosts" — restore onto (2 data × 2 model)
    mesh2, rules2 = make((2, 2))
    template = {"params": jax.tree.map(jnp.zeros_like, params)}
    step, tree = mgr.restore(
        template, shardings={"params": rules2.params_shardings(params)})
    assert step == 1
    with mesh2:
        loss2, _ = jax.jit(lambda p, b: tfm.loss_fn(p, cfg, b))(
            tree["params"], batch)
    assert abs(float(loss1) - float(loss2)) < 1e-3, (float(loss1),
                                                     float(loss2))
    # verify the restored leaves really live on the new 4-device mesh
    leaf = jax.tree.leaves(tree["params"])[0]
    assert len(leaf.sharding.mesh.devices.reshape(-1)) == 4
    print("ELASTIC_OK", float(loss1), float(loss2))
""")


def test_elastic_restore_across_meshes(tmp_path):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(tmp_path)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600)
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]
