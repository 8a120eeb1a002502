"""Decoder-only LM assembly for all assigned architectures.

A model is a sequence of *segments*; each segment is a repeating pattern
of block signatures (block kind × is-MoE).  Within a segment the
per-layer parameters are stacked on a leading axis and the segment runs
under ``lax.scan`` — one traced block body per segment regardless of
depth, which keeps multi-hundred-layer compiles tractable and is the
idiomatic pjit pattern (param shardings broadcast over the scan axis).

Block kinds: global attention, sliding-window attention, MLA attention,
RG-LRU, mLSTM, sLSTM.  FFN: dense MLP or MoE per layer.  Everything is
pre-norm residual.

Three entry points per architecture:
  ``forward``      — full-sequence logits (training);
  ``prefill``      — full sequence → last-position logits + caches;
  ``decode_step``  — one token with caches (serving).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, ArchConfig, LOCAL_ATTN, MLSTM, RGLRU,
                                SLSTM)
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import recurrent as rec_lib
from repro.models.layers import (_dtype, apply_norm, embed, embed_init, mlp,
                                 mlp_init, norm_init, softmax_cross_entropy,
                                 unembed, xavier)

# Sharding-constraint hook (set by repro.distributed.sharding at launch)
from repro.models.hooks import constrain, set_constrain_fn  # noqa: F401,E402

# Activation rematerialisation for the training path: recompute block
# internals in the backward pass instead of storing them (needed for
# scan-over-layers at production batch×seq; ~+1/3 fwd FLOPs).
# Policy "full" recomputes everything; "dots" saves matmul outputs
# (jax.checkpoint_policies.checkpoint_dots) — compute↓ memory↑.
_REMAT_TRAIN = True
_REMAT_POLICY = "full"


def set_remat(flag: bool, policy: str = "full"):
    global _REMAT_TRAIN, _REMAT_POLICY
    _REMAT_TRAIN = flag
    _REMAT_POLICY = policy


def _checkpoint(fn):
    if _REMAT_POLICY == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Segmentation
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Segment:
    sigs: Tuple[Tuple[str, bool], ...]   # per-position (kind, is_moe)
    reps: int                            # how many times the pattern repeats
    first_layer: int                     # absolute index of first layer


def layer_signature(cfg: ArchConfig, i: int) -> Tuple[str, bool]:
    kind = cfg.blocks[i]
    is_moe = (cfg.moe is not None and cfg.d_ff > 0
              and kind in (ATTN, LOCAL_ATTN, RGLRU)
              and cfg.moe.is_moe_layer(i))
    return (kind, is_moe)


def segments_of(cfg: ArchConfig) -> List[Segment]:
    sigs = [layer_signature(cfg, i) for i in range(cfg.n_layers)]
    segs: List[Segment] = []
    if cfg.block_pattern is not None:
        P = len(cfg.block_pattern)
        if cfg.moe is not None:
            P = _lcm(P, cfg.moe.moe_every)
        reps = cfg.n_layers // P
        if reps >= 1 and all(sigs[i] == sigs[i % P] for i in range(reps * P)):
            segs.append(Segment(tuple(sigs[:P]), reps, 0))
            start = reps * P
        else:
            start = 0
        for i in range(start, cfg.n_layers):
            segs.append(Segment((sigs[i],), 1, i))
        return segs
    # no explicit pattern: group maximal runs of identical signature
    i = 0
    while i < cfg.n_layers:
        j = i
        while j < cfg.n_layers and sigs[j] == sigs[i]:
            j += 1
        segs.append(Segment((sigs[i],), j - i, i))
        i = j
    return segs


def _lcm(a, b):
    return a * b // math.gcd(a, b)


# ---------------------------------------------------------------------------
# Per-layer parameter init
# ---------------------------------------------------------------------------
def _layer_init(rng, cfg: ArchConfig, sig, dtype):
    kind, is_moe = sig
    d = cfg.d_model
    ks = jax.random.split(rng, 4)
    p: dict = {"norm1": norm_init(cfg.norm, d, dtype)}
    if kind in (ATTN, LOCAL_ATTN):
        if cfg.mla is not None:
            p["attn"] = attn_lib.mla_init(ks[0], d, cfg.n_heads, cfg.mla, dtype)
        else:
            p["attn"] = attn_lib.gqa_init(ks[0], d, cfg.n_heads,
                                          cfg.n_kv_heads, cfg.head_dim_,
                                          cfg.qkv_bias, dtype)
    elif kind == RGLRU:
        p["rnn"] = rec_lib.rglru_init(ks[0], d, cfg.rnn_width or d,
                                      cfg.n_heads, cfg.conv1d_width, dtype)
    elif kind == MLSTM:
        w = cfg.rnn_width or 2 * d
        cell = rec_lib.mlstm_cell_init(ks[0], w, cfg.n_heads, dtype)
        p["rnn"] = {
            "cell": cell,
            "up": xavier(ks[1], (d, w), dtype),
            "gate": xavier(ks[2], (d, w), dtype),
            "down": xavier(ks[3], (w, d), dtype),
        }
    elif kind == SLSTM:
        cell = rec_lib.slstm_cell_init(ks[0], d, d, cfg.n_heads, dtype)
        # post-cell gated MLP: up d→2·ff (split gate/value), down ff→d
        p["rnn"] = {
            "cell": cell,
            "up": xavier(ks[1], (d, 4 * d), dtype),
            "down": xavier(ks[2], (2 * d, d), dtype),
        }
    if cfg.d_ff > 0 and kind in (ATTN, LOCAL_ATTN, RGLRU):
        p["norm2"] = norm_init(cfg.norm, d, dtype)
        if is_moe:
            p["moe"] = moe_lib.moe_init(ks[3], d, cfg.moe, cfg.gated_mlp, dtype)
        else:
            p["mlp"] = mlp_init(ks[3], d, cfg.d_ff, cfg.gated_mlp,
                                cfg.mlp_bias, dtype)
    return p


def init_params(rng, cfg: ArchConfig):
    """Full parameter pytree (embed, stacked segments, final norm, head)."""
    dtype = _dtype(cfg.dtype)
    ks = jax.random.split(rng, cfg.n_layers + 3)
    layers = [_layer_init(ks[i], cfg, layer_signature(cfg, i), dtype)
              for i in range(cfg.n_layers)]
    segs = segments_of(cfg)
    seg_params = []
    for seg in segs:
        P = len(seg.sigs)
        pos_trees = []
        for pos in range(P):
            idx = [seg.first_layer + r * P + pos for r in range(seg.reps)]
            if seg.reps == 1:
                pos_trees.append(layers[idx[0]])
            else:
                pos_trees.append(jax.tree.map(
                    lambda *xs: jnp.stack(xs), *[layers[i] for i in idx]))
        seg_params.append(pos_trees)
    params = {
        "embed": embed_init(ks[-1], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
        "segments": seg_params,
    }
    if not cfg.tie_embeddings:
        params["unembed"] = {
            "table": xavier(ks[-2], (cfg.padded_vocab, cfg.d_model), dtype,
                            in_axis=1, out_axis=0)}
    if cfg.num_patch_tokens:
        # vlm stub: a learned projection applied to precomputed patch embeds
        params["patch_proj"] = xavier(ks[-3], (cfg.d_model, cfg.d_model), dtype)
    return params


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------
def _apply_block(cfg: ArchConfig, sig, p, x, mode: str, cache,
                 capacity: Optional[int], valid_len=None, plan=None,
                 paged=None):
    """Returns (x, new_cache, aux_loss).

    ``valid_len`` (B,) marks right-padded prefill batches (masked
    prefill — attention kinds only); ``plan`` routes decode projections
    through the block-sparse kernel (keys "attn"/"mlp").  ``paged``
    (tables, lens) switches decode onto the paged KV path: ``cache`` is
    then a ``PagedKVCache``/``PagedLatentCache`` pool and attention runs
    the paged Pallas kernel over live blocks only.
    """
    kind, is_moe = sig
    window = cfg.local_window if kind == LOCAL_ATTN else None
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg.norm, p["norm1"], x)
    new_cache = cache
    # plans apply on every mode — training forward, prefill, decode —
    # so each projection a pruned ticket executes can skip dead tiles
    plan = plan or {}
    if valid_len is not None and (kind not in (ATTN,) or mode != "prefill"):
        raise ValueError(
            f"valid_len is only supported for full-attention prefill, "
            f"got kind={kind!r} mode={mode!r}; use exact-length prefill "
            "for windowed/recurrent blocks")
    if kind in (ATTN, LOCAL_ATTN):
        if cfg.mla is not None:
            kw = dict(n_heads=cfg.n_heads, mla=cfg.mla,
                      rope_theta=cfg.rope_theta)
            if mode == "forward":
                out = attn_lib.mla_forward(p["attn"], h, **kw)
            elif mode == "prefill":
                out, new_cache = attn_lib.mla_make_cache(
                    p["attn"], h, capacity=capacity, valid_len=valid_len,
                    **kw)
            elif paged is not None:
                out, new_cache = attn_lib.mla_paged_decode(
                    p["attn"], cache, h, tables=paged[0], lens=paged[1],
                    **kw)
            else:
                out, new_cache = attn_lib.mla_decode(p["attn"], cache, h, **kw)
        else:
            kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                      head_dim=cfg.head_dim_, rope_theta=cfg.rope_theta)
            if mode == "forward":
                out = attn_lib.gqa_forward(p["attn"], h, window=window,
                                           plan=plan.get("attn"), **kw)
            elif mode == "prefill":
                out, new_cache = attn_lib.gqa_make_cache(
                    p["attn"], h, capacity=capacity, window=window,
                    valid_len=valid_len, plan=plan.get("attn"), **kw)
            elif paged is not None:
                out, new_cache = attn_lib.gqa_paged_decode(
                    p["attn"], cache, h, tables=paged[0], lens=paged[1],
                    plan=plan.get("attn"), **kw)
            else:
                out, new_cache = attn_lib.gqa_decode(
                    p["attn"], cache, h, window=window,
                    plan=plan.get("attn"), **kw)
    elif kind == RGLRU:
        if mode == "forward":
            out = rec_lib.rglru_forward(p["rnn"], h)
        elif mode == "prefill":
            out, new_cache = rec_lib.rglru_make_cache(p["rnn"], h)
        else:
            out, new_cache = rec_lib.rglru_step(p["rnn"], cache, h)
    elif kind == MLSTM:
        rp = p["rnn"]
        u = h @ rp["up"]
        g = h @ rp["gate"]
        if mode == "forward":
            hc, _ = rec_lib.mlstm_chunkwise(rp["cell"], u, cfg.n_heads)
        elif mode == "prefill":
            hc, new_cache = rec_lib.mlstm_chunkwise(rp["cell"], u, cfg.n_heads)
        else:
            hc, new_cache = rec_lib.mlstm_step(rp["cell"], cache, u,
                                               cfg.n_heads)
        out = (hc.astype(x.dtype) * jax.nn.silu(g)) @ rp["down"]
    elif kind == SLSTM:
        rp = p["rnn"]
        if mode in ("forward", "prefill"):
            hc, st = rec_lib.slstm_forward(rp["cell"], h)
            new_cache = st if mode == "prefill" else cache
        else:
            hc, new_cache = rec_lib.slstm_step(rp["cell"], cache, h)
        y = hc.astype(x.dtype) @ rp["up"]
        out = jax.nn.gelu(y[..., : y.shape[-1] // 2]) * y[..., y.shape[-1] // 2:]
        out = out @ rp["down"]
    else:  # pragma: no cover
        raise ValueError(kind)
    x = x + out
    x = constrain(x, ("dp", None, None))
    if cfg.d_ff > 0 and kind in (ATTN, LOCAL_ATTN, RGLRU):
        h2 = apply_norm(cfg.norm, p["norm2"], x)
        if is_moe:
            mo = moe_lib.moe_forward(p["moe"], h2, cfg.moe, cfg.act,
                                     cfg.gated_mlp, plan=plan.get("moe"))
            x = x + mo.y
            aux = mo.aux_loss
        else:
            x = x + mlp(p["mlp"], h2, cfg.act, plan=plan.get("mlp"))
        x = constrain(x, ("dp", None, None))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Segment runners (scan when reps > 1)
# ---------------------------------------------------------------------------
def _run_segments(cfg, params, x, mode, caches, capacity, valid_len=None,
                  plan=None, paged=None):
    """caches: None or same structure as params['segments'] holding states.

    ``plan``: None or a nested list mirroring params['segments'] — one
    (static) per-position dict of tile plans, shared across a segment's
    scanned repeats (the bitmaps are unioned over the scan axis, so a
    tile is skipped only when it is dead in *every* layer of the
    segment — skipping is sound because pruned weights are exact zeros).
    """
    new_caches = []
    total_aux = jnp.zeros((), jnp.float32)
    remat = _REMAT_TRAIN and mode == "forward"
    for s_idx, (seg, pos_trees) in enumerate(zip(segments_of(cfg),
                                                 params["segments"])):
        seg_caches = caches[s_idx] if caches is not None else None
        seg_plan = plan[s_idx] if plan is not None else None

        def super_block(xc, aux_acc, ptrees, cs, seg=seg, seg_plan=seg_plan):
            c_outs = []
            for pos in range(len(seg.sigs)):
                c = cs[pos] if cs is not None else None
                pe = seg_plan[pos] if seg_plan is not None else None
                xc, c_new, aux = _apply_block(cfg, seg.sigs[pos],
                                              ptrees[pos], xc, mode, c,
                                              capacity, valid_len=valid_len,
                                              plan=pe, paged=paged)
                aux_acc = aux_acc + aux
                c_outs.append(c_new)
            return xc, aux_acc, c_outs

        if remat:
            super_block = _checkpoint(super_block)

        if seg.reps == 1:
            cs = seg_caches if seg_caches is not None else None
            x, total_aux, out_caches = super_block(x, total_aux, pos_trees,
                                                   cs)
            new_caches.append(out_caches)
        else:
            def body(carry, xs, super_block=super_block):
                xc, aux_acc = carry
                ptrees, cs = xs
                xc, aux_acc, c_outs = super_block(xc, aux_acc, ptrees, cs)
                ys = c_outs if cs is not None else None
                return (xc, aux_acc), ys

            xs = (pos_trees, seg_caches)
            (x, total_aux), ys = jax.lax.scan(body, (x, total_aux), xs)
            new_caches.append(ys)
    return x, (new_caches if caches is not None else None), total_aux


# ---------------------------------------------------------------------------
# Embedding front end (handles the vlm patch stub)
# ---------------------------------------------------------------------------
def _embed_inputs(cfg, params, batch):
    tokens = batch["tokens"]
    x = embed(params["embed"], tokens)
    if cfg.num_patch_tokens and "patches" in batch:
        patches = batch["patches"].astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([patches, x], axis=1)
    return x


def forward(params, cfg: ArchConfig, batch, plan=None):
    """Training forward: full-sequence logits. batch['tokens']: (B, S).

    ``plan`` (from ``repro.train.plans.lm_train_plan``) routes the
    attention/MLP projections through the block-sparse Pallas kernel —
    forward and backward — so the Algorithm-1 retrain loop's cost
    scales with the pruned ticket's live tiles.
    """
    x = _embed_inputs(cfg, params, batch)
    x = constrain(x, ("dp", None, None))
    x, _, aux = _run_segments(cfg, params, x, "forward", None, None,
                              plan=plan)
    x = apply_norm(cfg.norm, params["final_norm"], x)
    head = params.get("unembed", params["embed"])
    logits = unembed(head, x)
    logits = constrain(logits, ("dp", None, "model"))
    return logits, aux


def loss_fn(params, cfg: ArchConfig, batch, aux_weight: float = 0.01,
            plan=None):
    logits, aux = forward(params, cfg, batch, plan=plan)
    labels = batch["labels"]
    if cfg.num_patch_tokens and "patches" in batch:
        # loss only over text positions (the tail of the sequence)
        logits = logits[:, -labels.shape[1]:]
    mask = batch.get("loss_mask")
    ce = softmax_cross_entropy(logits, labels, mask)
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Cache construction / serving steps
# ---------------------------------------------------------------------------
def _block_cache_spec(cfg: ArchConfig, sig, batch: int, capacity: int, dtype):
    kind, _ = sig
    if kind in (ATTN, LOCAL_ATTN):
        cap = capacity if kind == ATTN else min(cfg.local_window, capacity)
        if cfg.mla is not None:
            return attn_lib.mla_cache_spec(batch, cap, cfg.mla, dtype)
        return attn_lib.gqa_cache_spec(batch, cap, cfg.n_kv_heads,
                                       cfg.head_dim_, dtype)
    if kind == RGLRU:
        return rec_lib.rglru_state_spec(batch, cfg.rnn_width or cfg.d_model,
                                        cfg.conv1d_width, dtype)
    if kind == MLSTM:
        w = cfg.rnn_width or 2 * cfg.d_model
        return rec_lib.mlstm_state_spec(batch, cfg.n_heads, w // cfg.n_heads)
    if kind == SLSTM:
        return rec_lib.slstm_state_spec(batch, cfg.d_model)
    raise ValueError(kind)


def cache_spec(cfg: ArchConfig, batch: int, capacity: int):
    """ShapeDtypeStruct pytree mirroring params['segments'] structure."""
    dtype = _dtype(cfg.dtype)

    def stack_spec(spec, reps):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((reps, *s.shape), s.dtype), spec)

    out = []
    for seg in segments_of(cfg):
        pos_specs = []
        for sig in seg.sigs:
            s = _block_cache_spec(cfg, sig, batch, capacity, dtype)
            pos_specs.append(s if seg.reps == 1 else stack_spec(s, seg.reps))
        out.append(pos_specs)
    return out


def supports_masked_prefill(cfg: ArchConfig) -> bool:
    """True when ``prefill`` accepts a per-row ``valid_len`` for this
    architecture: every block is full (global) attention, dense FFN, and
    no patch-token prefix.  Windowed/recurrent blocks carry state
    through the padded tail, and MoE routing computes expert capacity
    over *all* positions (pad tokens shift which real tokens are
    dropped), so those need exact-length prefill instead.
    Encoder-decoder configs prefill through ``models.encdec`` (no
    ``valid_len`` lane), so they are exact-length too."""
    try:
        kinds = set(cfg.blocks)
    except AttributeError:          # not a decoder-LM config (CNN)
        return False
    return (kinds == {ATTN} and not cfg.num_patch_tokens
            and cfg.moe is None and not cfg.is_encoder_decoder)


def cache_batch_axes(cfg: ArchConfig, caches):
    """Pytree of ints matching ``caches``: the batch axis of each leaf.

    Scan-stacked segments carry the layer (repeat) axis first, so their
    cache leaves are (reps, B, ...) — batch axis 1; single-layer
    segments are (B, ...) — axis 0.  Scalar cache indices have *no*
    batch axis yet (leaf.ndim == axis); consumers append one.
    ``serve.ServeEngine`` uses this to splice one request's prefill
    caches into the right slot lane of the decode batch.
    """
    out = []
    segs = segments_of(cfg)
    if len(segs) != len(caches):
        raise ValueError(f"cache structure has {len(caches)} segments, "
                         f"config implies {len(segs)}")
    for seg, seg_c in zip(segs, caches):
        a = 1 if seg.reps > 1 else 0
        out.append(jax.tree.map(lambda leaf, a=a: a, seg_c))
    return out


def prefill(params, cfg: ArchConfig, batch, capacity: int, valid_len=None,
            plan=None):
    """Full-sequence prefill → (last-position logits, caches).

    With ``valid_len`` (B,), batch['tokens'] is right-padded and the
    logits are taken at each row's last *valid* position; cache indices
    start at ``valid_len`` so per-request decode is batch-invariant
    (no request ever attends to a batch-mate's padding).

    ``plan`` (from ``repro.models.plans.build_decode_plan`` — the same
    structure decode uses) routes the attention/MLP projections through
    the block-sparse Pallas kernel, so a pruned ticket's prefill cost
    scales with its live tiles exactly like its decode cost.
    """
    x = _embed_inputs(cfg, params, batch)
    x = constrain(x, ("dp", None, None))
    x, caches, _ = _run_segments(cfg, params, x, "prefill",
                                 _none_caches(cfg), capacity,
                                 valid_len=valid_len, plan=plan)
    if valid_len is None:
        x_last = x[:, -1:]
    else:
        last = (jnp.asarray(valid_len, jnp.int32) - 1)[:, None, None]
        last = jnp.broadcast_to(last, (x.shape[0], 1, x.shape[2]))
        x_last = jnp.take_along_axis(x, last, axis=1)
    x_last = apply_norm(cfg.norm, params["final_norm"], x_last)
    head = params.get("unembed", params["embed"])
    logits = unembed(head, x_last)
    return logits, caches


def _none_caches(cfg):
    return [[None for _ in seg.sigs] for seg in segments_of(cfg)]


def decode_step(params, cfg: ArchConfig, caches, token, plan=None):
    """token: (B, 1) int32 → (logits (B,1,V), new caches).

    ``plan`` (from ``repro.models.plans.build_decode_plan``) routes the
    dense attention/MLP projections through the block-sparse Pallas
    kernel so decode cost scales with the pruned ticket's live tiles.
    """
    x = embed(params["embed"], token)
    x = constrain(x, ("dp", None, None))
    x, caches, _ = _run_segments(cfg, params, x, "decode", caches, None,
                                 plan=plan)
    x = apply_norm(cfg.norm, params["final_norm"], x)
    head = params.get("unembed", params["embed"])
    logits = unembed(head, x)
    logits = constrain(logits, ("dp", None, "model"))
    return logits, caches


# ---------------------------------------------------------------------------
# Paged decode: shared block pools instead of per-slot dense caches
# ---------------------------------------------------------------------------
def supports_paged_decode(cfg: ArchConfig) -> bool:
    """True when ``decode_step_paged`` covers this architecture: every
    block is full (global) attention — GQA or MLA — so all per-layer
    decode state is a KV (or latent) pool.  Windowed attention has ring
    semantics and recurrent blocks carry non-KV state, neither of which
    pages; encoder-decoder archs decode through ``models.encdec``."""
    try:
        kinds = set(cfg.blocks)
    except AttributeError:          # not a decoder-LM config (CNN)
        return False
    return kinds == {ATTN} and not cfg.is_encoder_decoder


def paged_cache_spec(cfg: ArchConfig, num_blocks: int):
    """ShapeDtypeStruct pytree mirroring params['segments']: one block
    pool per attention layer (scan-stacked segments get a leading reps
    axis, same convention as ``cache_spec``)."""
    if not supports_paged_decode(cfg):
        raise ValueError(f"paged decode unsupported for blocks={cfg.blocks}")
    dtype = _dtype(cfg.dtype)

    def block_spec():
        if cfg.mla is not None:
            return attn_lib.mla_paged_spec(num_blocks, cfg.mla, dtype)
        return attn_lib.gqa_paged_spec(num_blocks, cfg.n_kv_heads,
                                       cfg.head_dim_, dtype)

    def stack_spec(spec, reps):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((reps, *s.shape), s.dtype), spec)

    out = []
    for seg in segments_of(cfg):
        pos_specs = []
        for _sig in seg.sigs:
            s = block_spec()
            pos_specs.append(s if seg.reps == 1 else stack_spec(s, seg.reps))
        out.append(pos_specs)
    return out


def make_paged_caches(cfg: ArchConfig, num_blocks: int):
    """Zero-initialised block pools (see ``paged_cache_spec``)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_cache_spec(cfg, num_blocks))


def adopt_prefill(cfg: ArchConfig, paged_caches, dense_caches, blocks):
    """Scatter one request's dense prefill caches into pool blocks.

    ``dense_caches``: output of ``prefill`` for a single request (B=1,
    capacity == padded prefill length S).  ``blocks``: (⌈S/BLOCK⌉,)
    int32 physical block ids (logical order; ids past the real length
    may be the scratch block).  Returns updated ``paged_caches``.
    Layer-for-layer the adopt runs per-segment, vmapped over scanned
    repeats, mirroring the structure conventions of ``cache_spec``.
    """
    blocks = jnp.asarray(blocks, jnp.int32)
    if cfg.mla is not None:
        adopt = attn_lib.mla_paged_adopt
    else:
        adopt = attn_lib.gqa_paged_adopt
    out = []
    segs = segments_of(cfg)
    if len(segs) != len(paged_caches) or len(segs) != len(dense_caches):
        raise ValueError("cache structure does not match config segments")
    for seg, seg_p, seg_d in zip(segs, paged_caches, dense_caches):
        pos_out = []
        for pc, dc in zip(seg_p, seg_d):
            if seg.reps == 1:
                pos_out.append(adopt(pc, dc, blocks))
            else:
                # drop the scalar cache index from vmap (no reps axis
                # semantics needed for adopt — only k/v rows matter)
                if cfg.mla is None:
                    def one(kp, vp, k, v):
                        return adopt(attn_lib.PagedKVCache(kp, vp),
                                     attn_lib.KVCache(k, v, None), blocks)
                    new = jax.vmap(one)(pc.k_pool, pc.v_pool, dc.k, dc.v)
                else:
                    def one(pool, c_kv, k_rope):
                        return adopt(attn_lib.PagedLatentCache(pool),
                                     attn_lib.MLACache(c_kv, k_rope, None),
                                     blocks)
                    new = jax.vmap(one)(pc.pool, dc.c_kv, dc.k_rope)
                pos_out.append(new)
        out.append(pos_out)
    return out


def decode_step_paged(params, cfg: ArchConfig, caches, token, tables, lens,
                      plan=None):
    """Paged decode step: token (B,1) int32, block ``tables`` (B, NB)
    int32, per-slot ``lens`` (B,) int32 → (logits (B,1,V), new pools).

    Every attention layer appends the new token's KV into its pool at
    ``tables[b, lens[b]//BLOCK]`` and attends over ``lens[b]+1`` tokens
    through the paged Pallas kernel — bytes read scale with live
    context, not allocated capacity.
    """
    tables = jnp.asarray(tables, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    x = embed(params["embed"], token)
    x = constrain(x, ("dp", None, None))
    x, caches, _ = _run_segments(cfg, params, x, "decode", caches, None,
                                 plan=plan, paged=(tables, lens))
    x = apply_norm(cfg.norm, params["final_norm"], x)
    head = params.get("unembed", params["embed"])
    logits = unembed(head, x)
    logits = constrain(logits, ("dp", None, "model"))
    return logits, caches
