"""Flash attention Pallas TPU kernel (causal, GQA-aware).

Online-softmax attention with VMEM-resident running (max, denom, acc)
scratch; grid (B, Hq, q_blocks, k_blocks) with the k dimension
'arbitrary' so the scratch accumulates across k steps.  GQA is handled
in the BlockSpec index map (kv head = q head // group) — grouped keys
are never materialised.  Fully-masked causal blocks are skipped with
``pl.when`` (≈2× fewer MXU passes at long seq).

Used by the 32k-prefill cells on real TPU; validated in interpret mode
against the pure-jnp oracle (`ref.flash_attention_ref` ==
`models.attention.causal_attention` math).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import MXU_TILE
from repro.kernels.bsmm import default_interpret, launch
from repro.kernels.spec import BlockMap, KernelSpec, ScratchSpec

NEG_INF = -1e30


def flash_attention_spec(*, B: int, S: int, Hq: int, Hkv: int, hd: int,
                         bq: int = MXU_TILE, bk: int = MXU_TILE,
                         causal: bool = True,
                         dtype=jnp.float32) -> KernelSpec:
    """Launch geometry of the flash kernel over the (B, H, S, hd)
    layout: GQA via the ``h // G`` kv index map, causal block skip as
    the host guard."""
    G = Hq // Hkv

    def kv_map(b, h, i, j):
        return (b, h // G, j, 0)

    return KernelSpec(
        name="flash_attention",
        grid=(B, Hq, S // bq, S // bk),
        dims=("parallel", "parallel", "parallel", "arbitrary"),
        inputs=(
            BlockMap("q", (1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0),
                     (B, Hq, S, hd), dtype),
            BlockMap("k", (1, 1, bk, hd), kv_map,
                     (B, Hkv, S, hd), dtype),
            BlockMap("v", (1, 1, bk, hd), kv_map,
                     (B, Hkv, S, hd), dtype),
        ),
        outputs=(BlockMap("out", (1, 1, bq, hd),
                          lambda b, h, i, j: (b, h, i, 0),
                          (B, Hq, S, hd), dtype),),
        scratch=(ScratchSpec((bq, hd), jnp.float32, "accumulator"),
                 ScratchSpec((bq, 1), jnp.float32, "softmax_state"),
                 ScratchSpec((bq, 1), jnp.float32, "softmax_state")),
        guard=(lambda b, h, i, j: bool(j * bk <= i * bq + bq - 1))
        if causal else None,
        cell_flops=4.0 * bq * bk * hd,
        notes="causal fully-masked (i, j) blocks skipped via pl.when",
    )


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # k block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (j * bk <= i * bq + bq - 1) if causal else True

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]                             # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    bq: int = MXU_TILE, bk: int = MXU_TILE,
                    interpret: Optional[bool] = None):
    """q: (B, S, Hq, hd); k/v: (B, S, Hkv, hd) → (B, S, Hq, hd)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / math.sqrt(hd)
    # layout: (B, H, S, hd)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    spec = flash_attention_spec(B=B, S=S, Hq=Hq, Hkv=Hkv, hd=hd, bq=bq,
                                bk=bk, causal=causal, dtype=q.dtype)
    kernel = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=spec.grid,
        in_specs=spec.pallas_in_specs(),
        out_specs=spec.pallas_out_specs()[0],
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, hd), q.dtype),
        scratch_shapes=spec.pallas_scratch(),
        compiler_params=pltpu.CompilerParams(dimension_semantics=spec.dims),
        interpret=default_interpret(interpret),
        name=spec.name,
    )
    out = launch(kernel, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
