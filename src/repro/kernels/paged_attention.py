"""Paged decode attention Pallas TPU kernel — live-*block* KV reads.

The serving analogue of the paper's turned-off crossbar: just as
``bsmm`` makes weight traffic scale with live 128×128 tiles, this
kernel makes decode KV traffic scale with *live context* instead of
allocated capacity.  The KV cache is a shared pool of fixed-size blocks
(``MXU_TILE`` tokens each); every sequence owns an indirection row — a
*block table* — listing the physical blocks that hold its context in
logical order.  The kernel walks the table with scalar-prefetched index
maps, so the DMA engine only ever touches blocks the sequence actually
filled:

    grid = (B, NB)                          NB = table width
    q block   (1, Hq, hd)      at (b, 0, 0)
    kv block  (1, T, Hkv, hd)  at (table[b, j], 0, 0, 0)
    out block (1, Hq, dv)      at (b, 0, 0)

Scores/out are fused per block with a streaming (flash) softmax held in
f32 VMEM scratch; blocks past a sequence's live length are masked with
``pl.when`` (their index-map entry points at the scratch block, so the
revolving-window DMA re-reads one already-resident block instead of
streaming dead capacity — the same argument ``bsmm`` makes for dead
K-tiles).

Grouped-query attention is computed per block in grouped form
(``(Hkv, G, hd)`` queries against ``(Hkv, T, hd)`` keys), matching
``models.attention.attend``'s head grouping.  The MLA absorbed form
rides the same kernel: pass ``v_pool=None`` and ``v_dim=r`` and values
are the first ``r`` lanes of the key block (the latent cache stores
``concat(c_kv, k_rope)``), halving MLA pool reads as a bonus.

``paged_attention_ref`` is the exact dense-oracle path: gather the
table rows into a dense cache and run single-pass masked softmax —
the same math ``attend`` does, for oracle tests and debugging.
Conventions mirror ``bsmm``: ``interpret=None`` auto-enables interpret
mode everywhere except a real TPU backend.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import MXU_TILE
from repro.kernels.bsmm import GeometryError, default_interpret, launch
from repro.kernels.spec import BlockMap, KernelSpec, ScratchSpec

#: tokens per KV block — one MXU tile edge, like the bsmm tile
BLOCK_TOKENS = MXU_TILE

_NEG = -1e30    # finite mask value (matches models.attention.attend)


class PagedGeometry(NamedTuple):
    """Validated shapes for one paged-attention call."""
    B: int
    Hq: int
    hd: int
    Hkv: int
    T: int          # tokens per block
    NB: int         # table width (logical blocks per sequence)
    P: int          # physical blocks in the pool
    dv: int         # value head dim


def _check_geometry(q, k_pool, v_pool, tables, lengths,
                    v_dim: Optional[int]) -> PagedGeometry:
    if q.ndim != 3:
        raise GeometryError("q must be (B, Hq, hd)", shape=q.shape,
                            where="paged_attention")
    if k_pool.ndim != 4:
        raise GeometryError("k_pool must be (P, T, Hkv, hd)",
                            shape=k_pool.shape, where="paged_attention")
    B, Hq, hd = q.shape
    P, T, Hkv, hdk = k_pool.shape
    if hdk != hd:
        raise GeometryError("q/k head dims disagree", shape=(hd, hdk),
                            where="paged_attention")
    if Hq % Hkv:
        raise GeometryError(f"Hq={Hq} not a multiple of Hkv={Hkv}",
                            where="paged_attention")
    if tables.ndim != 2 or tables.shape[0] != B:
        raise GeometryError("tables must be (B, NB)", shape=tables.shape,
                            where="paged_attention")
    if lengths.shape != (B,):
        raise GeometryError("lengths must be (B,)", shape=lengths.shape,
                            where="paged_attention")
    if v_pool is None:
        if v_dim is None or not (0 < v_dim <= hd):
            raise GeometryError(
                f"v_pool=None needs 0 < v_dim <= hd, got v_dim={v_dim}",
                shape=(hd,), where="paged_attention")
        dv = v_dim
    else:
        if v_pool.shape[:3] != (P, T, Hkv):
            raise GeometryError("k_pool/v_pool pools disagree",
                                shape=v_pool.shape, where="paged_attention")
        dv = v_pool.shape[3]
    return PagedGeometry(B=B, Hq=Hq, hd=hd, Hkv=Hkv, T=T,
                         NB=tables.shape[1], P=P, dv=dv)


def _block_scores(q, k, scale):
    """q (Hq, hd) × k (T, Hkv, hd) → grouped scores (Hq, T) f32."""
    Hq, hd = q.shape
    T, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(Hkv, G, hd)
    kt = k.transpose(1, 0, 2)                       # (Hkv, T, hd)
    s = jax.lax.dot_general(qg, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    return s.reshape(Hq, T) * scale                 # (Hkv, G, T) → (Hq, T)


def _block_out(p, v):
    """p (Hq, T) × v (T, Hkv, dv) → (Hq, dv) f32 (grouped)."""
    Hq, T = p.shape
    Hkv, dv = v.shape[1], v.shape[2]
    G = Hq // Hkv
    pg = p.reshape(Hkv, G, T)
    vt = v.transpose(1, 0, 2)                       # (Hkv, T, dv)
    o = jax.lax.dot_general(pg, vt, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    return o.reshape(Hq, dv)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, v_dim, T):
    """One (sequence b, logical block j) grid cell; v_dim selects the
    fused MLA form (values = first v_dim key lanes)."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    n = len_ref[b]

    @pl.when(j * T < n)
    def _accum():
        q = q_ref[0].astype(jnp.float32)            # (Hq, hd)
        k = k_ref[0].astype(jnp.float32)            # (T, Hkv, hd)
        s = _block_scores(q, k, scale)              # (Hq, T)
        tpos = j * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tpos < n, s, _NEG)
        m_prev = m_ref[:, :1]                       # (Hq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                      # (Hq, T)
        corr = jnp.exp(m_prev - m_new)              # (Hq, 1)
        v = k[..., :v_dim]                          # fused MLA values
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = (l_ref[...] * corr
                      + jnp.broadcast_to(p.sum(-1, keepdims=True),
                                         l_ref.shape))
        acc_ref[...] = acc_ref[...] * corr + _block_out(p, v)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def _paged_kernel_kv(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                     acc_ref, m_ref, l_ref, *, scale, T):
    """Separate-V variant (GQA): same streaming softmax, v from its own
    pool block."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    n = len_ref[b]

    @pl.when(j * T < n)
    def _accum():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = _block_scores(q, k, scale)
        tpos = j * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tpos < n, s, _NEG)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = (l_ref[...] * corr
                      + jnp.broadcast_to(p.sum(-1, keepdims=True),
                                         l_ref.shape))
        acc_ref[...] = acc_ref[...] * corr + _block_out(p, v)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def paged_attention_spec(geo: PagedGeometry, tables, lengths, *,
                         fused_v: bool,
                         dtype=jnp.float32) -> KernelSpec:
    """Launch geometry of one paged-attention call: the block-table
    gather in the kv index map, the ``j*T < len`` liveness guard, and
    the f32 streaming-softmax scratch — exactly what the pallas_call
    below executes."""
    # tables/lengths may be tracers (the jitted decode path); keep them
    # as-is — the auditor builds its specs from concrete numpy arrays
    if isinstance(tables, np.ndarray):
        tables = np.asarray(tables, np.int32)
    if isinstance(lengths, np.ndarray):
        lengths = np.asarray(lengths, np.int32)
    T = geo.T
    inputs = [
        BlockMap("q", (1, geo.Hq, geo.hd),
                 lambda b, j, tbl, ln: (b, 0, 0),
                 (geo.B, geo.Hq, geo.hd), dtype),
        BlockMap("k_pool", (1, T, geo.Hkv, geo.hd),
                 lambda b, j, tbl, ln: (tbl[b, j], 0, 0, 0),
                 (geo.P, T, geo.Hkv, geo.hd), dtype, gather=True),
    ]
    if not fused_v:
        inputs.append(
            BlockMap("v_pool", (1, T, geo.Hkv, geo.dv),
                     lambda b, j, tbl, ln: (tbl[b, j], 0, 0, 0),
                     (geo.P, T, geo.Hkv, geo.dv), dtype, gather=True))
    return KernelSpec(
        name="paged_attention_mla" if fused_v else "paged_attention_gqa",
        grid=(geo.B, geo.NB),
        dims=("parallel", "arbitrary"),
        inputs=tuple(inputs),
        outputs=(BlockMap("out", (1, geo.Hq, geo.dv),
                          lambda b, j, tbl, ln: (b, 0, 0),
                          (geo.B, geo.Hq, geo.dv), dtype),),
        scratch=(ScratchSpec((geo.Hq, geo.dv), jnp.float32,
                             "accumulator"),
                 ScratchSpec((geo.Hq, T), jnp.float32, "softmax_state"),
                 ScratchSpec((geo.Hq, T), jnp.float32, "softmax_state")),
        scalars=(tables, lengths),
        guard=lambda b, j, tbl, ln: bool(j * T < ln[b]),
        cell_flops=2.0 * geo.Hq * T * geo.hd + 2.0 * geo.Hq * T * geo.dv,
        notes="block-table gather; dead entries must point at a valid "
              "pool block (the engine's scratch block 0)",
    )


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    scale: float, v_dim: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Paged decode attention over a block pool.

    q:        (B, Hq, hd) — one query per sequence (decode step)
    k_pool:   (P, T, Hkv, hd) — the shared physical block pool
    v_pool:   like ``k_pool`` (separate dv allowed), or None with
              ``v_dim=r`` for the fused MLA form (v = k[..., :r])
    tables:   (B, NB) int32 — physical block id per logical block.
              Entries past a sequence's live blocks must still be valid
              pool ids (the engine points them at its scratch block).
    lengths:  (B,) int32 — live context length per sequence, **including
              the just-appended token**; must be >= 1 (an all-masked row
              would divide by a zero softmax denominator).

    Returns (B, Hq, dv) in q's dtype.  Exact (streaming softmax in f32);
    the per-block masked softmax matches ``attend``'s ``-1e30`` finite
    masking.  ``interpret=None`` auto-enables interpret mode off-TPU,
    mirroring ``bsmm``.
    """
    geo = _check_geometry(q, k_pool, v_pool, tables, lengths, v_dim)
    fused = v_pool is None
    spec = paged_attention_spec(geo, tables, lengths, fused_v=fused,
                                dtype=q.dtype)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    body = functools.partial(_paged_kernel, scale=scale, v_dim=geo.dv,
                             T=geo.T) if fused \
        else functools.partial(_paged_kernel_kv, scale=scale, T=geo.T)
    kernel = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=spec.num_scalar_prefetch,
            grid=spec.grid,
            in_specs=spec.pallas_in_specs(),
            out_specs=spec.pallas_out_specs()[0],
            scratch_shapes=spec.pallas_scratch()),
        out_shape=jax.ShapeDtypeStruct((geo.B, geo.Hq, geo.dv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=spec.dims),
        interpret=default_interpret(interpret),
        name=spec.name,
    )
    if fused:
        return launch(kernel, tables, lengths, q, k_pool)
    return launch(kernel, tables, lengths, q, k_pool, v_pool)


def paged_gather(pool, tables):
    """Gather table rows into a dense per-sequence cache.

    pool (P, T, ...) × tables (B, NB) → (B, NB*T, ...) — logical token
    order.  The oracle view of the paged state: position ``t`` of
    sequence ``b`` lives at ``pool[tables[b, t // T], t % T]``.
    """
    B, NB = tables.shape
    T = pool.shape[1]
    dense = jnp.asarray(pool)[jnp.asarray(tables, jnp.int32)]
    return dense.reshape(B, NB * T, *pool.shape[2:])


def paged_attention_ref(q, k_pool, v_pool, tables, lengths, *,
                        scale: float, v_dim: Optional[int] = None):
    """Exact dense-oracle path: gather blocks, single-pass masked
    softmax — the same grouped math ``models.attention.attend`` uses."""
    geo = _check_geometry(q, k_pool, v_pool, tables, lengths, v_dim)
    k = paged_gather(k_pool, tables)                 # (B, L, Hkv, hd)
    if v_pool is None:
        v = k[..., :geo.dv]
    else:
        v = paged_gather(v_pool, tables)
    B, L = k.shape[0], k.shape[1]
    G = geo.Hq // geo.Hkv
    qg = q.reshape(B, geo.Hkv, G, geo.hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(L)[None] < jnp.asarray(lengths)[:, None]   # (B, L)
    s = jnp.where(valid[:, None, None], s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))
    return o.reshape(B, geo.Hq, geo.dv).astype(q.dtype)
