"""Plain float32 reference of a llama-architecture decoder and its
retraining under a ticket.

The published block (Touvron et al. 2023; Yi, arXiv:2403.04652): token
embedding; per layer ``x += Wo . attn(RoPE(Wq h), RoPE(Wk h), Wv h)``
with ``h = RMSNorm(x)``, causal softmax attention with grouped kv heads
(query head i reads kv head i // (n_heads / n_kv_heads)), then
``x += Wdown (silu(Wgate h2) * Wup h2)`` with ``h2 = RMSNorm(x)``; a
final RMSNorm and an untied output head.  RoPE rotates the two halves
of each head, ``[x1 cos - x2 sin, x2 cos + x1 sin]``, as the published
implementations do.  The loss is the mean cross-entropy of the next
token over every row of the head.

It runs layer by layer, so that it fits beside nothing else on one
chip: the forward keeps only each layer's input, and the backward
recomputes one layer at a time and updates that layer's weights at
once.  Weights are held in the configuration's storage dtype and every
update is rounded to it, as the configuration states; all arithmetic
is float32 at ``highest`` precision.  The control (``operands=e4m3``)
rounds both operands of every projection and of the head to float8
e4m3 before the product, the precision below the configuration's
bfloat16; the rest stays as it is.

``weights`` is a flat dict keyed by each leaf's path in the program's
layout (``segments/0/0/attn/wq``); a stacked leaf keeps its layers on
its leading axis, and a layer's slice is taken inside the jitted call
that uses it, so no slice is ever copied.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EPS = 1e-6
LAYER_KEYS = ("norm1/scale", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
              "norm2/scale", "mlp/up", "mlp/gate", "mlp/down")


def layer_paths() -> Dict[str, str]:
    """LAYER_KEYS -> the path of its leaf in the flat dict."""
    return {k: f"segments/0/0/{k}" for k in LAYER_KEYS}


def pick(ws: Dict[str, jax.Array], layer: Optional[int]):
    """Layer ``layer`` of stacked leaves (all of them when None)."""
    return ws if layer is None else {k: v[layer] for k, v in ws.items()}


def rmsnorm(x, scale):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale.astype(F32)


def rope(x, theta: float):
    """x (B, S, H, hd) rotated by its position along S."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv           # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def exact(x):
    return x.astype(F32)


def e4m3(x):
    """``x`` rounded to float8 e4m3 under one scale for the whole tensor
    (its largest magnitude to e4m3's largest, 448), held in float32.
    The gradient passes through unchanged."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def layer(shape, w, x, operands=exact):
    """One decoder layer: x (B, S, d) f32 -> (B, S, d) f32.  Both
    operands of each projection pass through ``operands`` first."""
    B, S, d = x.shape
    H, Hkv, hd = shape["n_heads"], shape["n_kv_heads"], shape["head_dim"]
    mm = lambda a, b: jnp.matmul(operands(a), operands(b))  # noqa: E731
    h = rmsnorm(x, w["norm1/scale"])
    qh = rope(mm(h, w["attn/wq"]).reshape(B, S, H, hd), shape["rope_theta"])
    kh = rope(mm(h, w["attn/wk"]).reshape(B, S, Hkv, hd),
              shape["rope_theta"])
    vh = mm(h, w["attn/wv"]).reshape(B, S, Hkv, hd)
    kv_of = jnp.arange(H) // (H // Hkv)
    kh, vh = kh[:, :, kv_of], vh[:, :, kv_of]               # (B, S, H, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(B, S, H * hd)
    x = x + mm(a, w["attn/wo"])
    h2 = rmsnorm(x, w["norm2/scale"])
    f = jax.nn.silu(mm(h2, w["mlp/gate"])) * mm(h2, w["mlp/up"])
    return x + mm(f, w["mlp/down"])


def head_loss(final_scale, table, x, labels, operands=exact):
    """Mean next-token cross-entropy over every row of the head."""
    logits = jnp.matmul(operands(rmsnorm(x, final_scale)),
                        operands(table).T)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


class Retrain:
    """AdamW retraining of a ticket, as the configuration states it."""

    def __init__(self, shape: Dict, weights: Dict[str, jax.Array],
                 masks: Dict[str, Optional[jax.Array]], *, lr: float,
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, store_dtype=jnp.bfloat16,
                 operands=exact):
        self.shape, self.n = shape, shape["n_layers"]
        self.operands = operands
        self.w = {k: v.astype(store_dtype) for k, v in weights.items()}
        self.masks = masks
        self.m = {k: jnp.zeros(v.shape, F32) for k, v in self.w.items()}
        self.v = {k: jnp.zeros(v.shape, F32) for k, v in self.w.items()}
        self.hp = (lr, b1, b2, eps, weight_decay)
        self.t = 0
        self.paths = layer_paths()
        self.layers = [None] if self.n == 1 else list(range(self.n))
        self.first_grad: Dict[str, float] = {}
        self._fwd = jax.jit(self._layer_fwd, static_argnums=(1,))
        self._bwd = jax.jit(self._layer_vjp, static_argnums=(1,))
        self._head = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, operands=operands),
            argnums=(0, 1, 2)))
        self._embed_grad = jax.jit(_embed_vjp, static_argnums=(2,))

    def _layer_fwd(self, ws, i, x):
        return layer(self.shape, pick(ws, i), x, self.operands)

    def _layer_vjp(self, ws, i, x, g):
        _, vjp = jax.vjp(functools.partial(layer, self.shape,
                                           operands=self.operands),
                         pick(ws, i), x)
        return vjp(g)

    def _ws(self):
        return {k: self.w[p] for k, p in self.paths.items()}

    def _update(self, path: str, i: Optional[int], g) -> None:
        lr, b1, b2, eps, wd = self.hp
        self.w[path], self.m[path], self.v[path], norm = _adamw(
            self.w[path], self.m[path], self.v[path], g,
            self.masks.get(path), i, self.t, lr, b1, b2, eps, wd)
        if self.t == 1:
            self.first_grad[path if i is None else f"{path}[{i}]"] = \
                float(norm)

    def step(self, tokens, labels) -> float:
        """One step on a batch; returns the loss before the update."""
        self.t += 1
        tokens = jnp.asarray(tokens)
        with jax.default_matmul_precision("highest"):
            x = self.w["embed/table"].astype(F32)[tokens]
            xs = []
            for i in self.layers:
                xs.append(x)
                x = self._fwd(self._ws(), i, x)
            loss, (g_fs, g_tab, g) = self._head(
                self.w["final_norm/scale"], self.w["unembed/table"], x,
                jnp.asarray(labels))
            self._update("final_norm/scale", None, g_fs)
            self._update("unembed/table", None, g_tab)
            for i, x_in in zip(reversed(self.layers), reversed(xs)):
                gw, g = self._bwd(self._ws(), i, x_in, g)
                for k, p in self.paths.items():
                    self._update(p, i, gw[k])
            rows = self.w["embed/table"].shape[0]
            self._update("embed/table", None,
                         self._embed_grad(tokens, g, rows))
        return float(loss)


def _embed_vjp(tokens, g, rows):
    return jnp.zeros((rows, g.shape[-1]), F32).at[tokens].add(g)


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2))
def _adamw(w, m, v, g, mask, i, t, lr, b1, b2, eps, wd):
    """AdamW on ``w`` (its layer ``i`` when given); returns the new
    (w, m, v) and the norm of the gradient the update used."""
    at = (lambda a: a) if i is None else (lambda a: a[i])
    if mask is not None:
        g = g * at(mask)
    mi = b1 * at(m) + (1 - b1) * g
    vi = b2 * at(v) + (1 - b2) * g * g
    mh = mi / (1 - b1 ** t)
    vh = vi / (1 - b2 ** t)
    p = at(w).astype(F32)
    p = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    if mask is not None:
        p = p * at(mask)
    p = p.astype(w.dtype)
    if i is None:
        return p, mi, vi, jnp.sqrt(jnp.sum(g * g))
    return (w.at[i].set(p), m.at[i].set(mi), v.at[i].set(vi),
            jnp.sqrt(jnp.sum(g * g)))


def first_rows(batch: Dict[str, np.ndarray], half: bool):
    """The batch, or its first half of rows (the half-batch fault)."""
    if not half:
        return batch["tokens"], batch["labels"]
    n = batch["tokens"].shape[0] // 2
    return batch["tokens"][:n], batch["labels"][:n]


def retrain(shape: Dict, weights, masks, batches: Sequence[Dict],
            *, lr: float, half_batch: bool = False, operands=exact):
    """Run ``len(batches)`` steps.  Returns (losses, first-step gradient
    norms per leaf, the Retrain object with the final weights)."""
    r = Retrain(shape, weights, masks, lr=lr, operands=operands)
    losses = [r.step(*first_rows(b, half_batch)) for b in batches]
    return losses, r.first_grad, r
