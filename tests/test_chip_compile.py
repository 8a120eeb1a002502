"""Compile the main path's Pallas kernels for a TPU v5e, without one.

The TPU compiler is installed with jaxlib: it compiles for a *described*
``v5e:2x2`` topology, so unaligned slices, VMEM overruns and Mosaic
lowering faults that interpret mode cannot see fail here, at no chip
time.  Nothing runs — each test checks that the compiled program holds
the kernel (``tpu_custom_call``), in an instruction named after its
``KernelSpec`` (``bsmm_fwd``, ``bsmm_dx``, ...): the name a profiler
trace gives the launch.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file.
"""
import re
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bsmm import make_tile_plan, plan_matmul
from repro.kernels.paged_attention import BLOCK_TOKENS, paged_attention

# yi-6b widths: the MLP up/gate projection at one tenth of its tiles
K, N = 4096, 11008
HQ, HKV, HD = 32, 4, 128
LIVE = 0.1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it off around these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def plan():
    rng = np.random.RandomState(0)
    tiles = rng.rand(K // 128, N // 128) < LIVE
    mask = np.kron(tiles, np.ones((128, 128), np.int8))
    # interpret=False explicitly: on this CPU the default emulates
    return make_tile_plan(mask, interpret=False, strict=True)


# a launch's instruction is named after its kernel, wrapped in the
# transformations JAX traced it under: ``jvp_bsmm_fwd_``,
# ``transpose_jvp_bsmm_dx__``
KINDS = re.compile(r"(?:^|_)(bsmm_fwd_epilogue|bsmm_fwd|bsmm_dx|bsmm_dw|"
                   r"paged_attention_gqa)(?:_|$)")


def _kernels(fn, *args) -> List[str]:
    """The kernel names of the compiled program's Pallas launches,
    sorted (an instruction that carries none reads as itself)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%?([\w.-]+) =", line).group(1)
            kind = KINDS.search(name.split(".")[0])
            out.append(kind.group(1) if kind else name)
    return sorted(out)


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", [8, 2048, 3584])
def test_bsmm_forward_compiles(one_chip, plan, m):
    x = _arg((m, K), jnp.bfloat16, one_chip)
    w = _arg((K, N), jnp.bfloat16, one_chip)
    assert _kernels(lambda x, w: plan_matmul(x, w, plan), x, w) == [
        "bsmm_fwd"]


def test_bsmm_fused_epilogue_compiles(one_chip, plan):
    x = _arg((8, K), jnp.bfloat16, one_chip)
    w = _arg((K, N), jnp.bfloat16, one_chip)
    b = _arg((N,), jnp.bfloat16, one_chip)
    assert _kernels(lambda x, w, b: plan_matmul(x, w, plan, bias=b,
                                                act="silu"), x, w, b) == [
        "bsmm_fwd_epilogue"]


@pytest.mark.parametrize("m", [2048, 12800])
def test_bsmm_fused_epilogue_forward_backward_compiles(one_chip, plan, m):
    # one 2048-row block, and at 12800 rows the tallest block VMEM
    # allows (6400): the epilogue's f32 temporaries fit the chip too
    x = _arg((m, K), jnp.bfloat16, one_chip)
    w = _arg((K, N), jnp.bfloat16, one_chip)
    b = _arg((N,), jnp.bfloat16, one_chip)

    def loss(x, w, b):
        y = plan_matmul(x, w, plan, bias=b, act="silu")
        return y.astype(jnp.float32).sum()

    assert _kernels(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    x, w, b) == ["bsmm_dw", "bsmm_dx", "bsmm_fwd_epilogue",
                                 "bsmm_fwd_epilogue"]


def _forward_backward(one_chip, plan, remat: bool) -> List[str]:
    x = _arg((2048, K), jnp.bfloat16, one_chip)
    w = _arg((K, N), jnp.bfloat16, one_chip)

    def loss(x, w):
        return plan_matmul(x, w, plan).astype(jnp.float32).sum()

    if remat:
        loss = jax.checkpoint(loss)
    return _kernels(jax.value_and_grad(loss, argnums=(0, 1)), x, w)


def test_bsmm_forward_backward_compiles(one_chip, plan):
    # forward, dx (transposed plan) and dw (live tiles only)
    assert _forward_backward(one_chip, plan, remat=False) == [
        "bsmm_dw", "bsmm_dx", "bsmm_fwd"]


def test_bsmm_forward_backward_keeps_the_names_under_remat(one_chip, plan):
    # jax.checkpoint rematerialises the forward inside a call: each
    # launch keeps its kernel's name, not the call's
    assert _forward_backward(one_chip, plan, remat=True) == [
        "bsmm_dw", "bsmm_dx", "bsmm_fwd"]


@pytest.mark.parametrize("slots,blocks", [(8, 65), (1, 9)])
def test_paged_attention_gqa_compiles(one_chip, slots, blocks):
    q = _arg((slots, HQ, HD), jnp.bfloat16, one_chip)
    pool = _arg((blocks, BLOCK_TOKENS, HKV, HD), jnp.bfloat16, one_chip)
    tables = _arg((slots, blocks - 1), jnp.int32, one_chip)
    lens = _arg((slots,), jnp.int32, one_chip)

    def attend(q, k, v, t, n):
        return paged_attention(q, k, v, t, n, scale=HD ** -0.5,
                               interpret=False)

    assert _kernels(attend, q, pool, pool, tables, lens) == [
        "paged_attention_gqa"]
