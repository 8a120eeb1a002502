"""Plain float32 reference of a VGG-style CNN and its SGD retraining
under a ticket.

VGG (Simonyan and Zisserman 2014, config D) in its CIFAR-10 form: 3x3
convolutions with 'same' padding, each followed by BatchNorm over the
batch (biased variance, eps 1e-5, learned scale and shift) and ReLU,
2x2 max-pooling where the configuration says, global average pooling
and a linear head; the loss is the mean cross-entropy.  Training is
SGD with momentum on a ticket: the gradient is masked before the
update and the weights after it.

Weights and arithmetic are float32; every convolution and matmul runs
at float32 (``highest``) in the reference.  The control runs them at
``high``: three bfloat16 passes (hi x hi + hi x lo + lo x hi, each
operand split into a bfloat16 high part and the bfloat16 remainder),
written out so that it reads the same on any backend.  Its ``twin``
is the reference again at float32, each convolution summed over its
input channels in the reverse order: a second exact reading that
differs from the first by round-off alone.  ``weights`` is a flat
dict keyed by each leaf's path in the program's layout
(``convs/3/w``, ``bns/3/scale``, ``head/w``).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
BN_EPS = 1e-5


def conv(x, k):
    return jax.lax.conv_general_dilated(
        x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def reversed_conv(x, k):
    """``conv`` summed in another order: the input channels reversed in
    both operands, the same value in exact arithmetic."""
    return conv(x[..., ::-1], k[:, :, ::-1, :])


def matmul(a, b):
    return jnp.matmul(a, b)


def _split(a):
    """a = hi + lo + rest, hi and lo bfloat16 values (held in float32)."""
    hi = a.astype(jnp.bfloat16).astype(F32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(F32)


def three_pass(op):
    """The bilinear ``op`` at ``high`` precision, forward and backward:
    each product is hi x hi + hi x lo + lo x hi of bfloat16 parts, every
    part-product exact and summed in float32."""
    def passes(f, x, y):
        (xh, xl), (yh, yl) = _split(x), _split(y)
        return f(xh, yh) + f(xh, yl) + f(xl, yh)

    @jax.custom_vjp
    def run(a, b):
        return passes(op, a, b)

    def fwd(a, b):
        return passes(op, a, b), (a, b)

    def bwd(res, g):
        a, b = res
        da = passes(lambda gi, bj: jax.vjp(lambda x: op(x, bj), a)[1](gi)[0],
                    g, b)
        db = passes(lambda gi, aj: jax.vjp(lambda y: op(aj, y), b)[1](gi)[0],
                    g, a)
        return da, db

    run.defvjp(fwd, bwd)
    return run


def forward(convs: Sequence[Dict], w: Dict[str, jax.Array], images,
            precision: str = "highest"):
    """``convs``: per layer {"pool": bool}; images (B, H, W, C) -> logits."""
    cv, mm = {"highest": (conv, matmul),
              "high": (three_pass(conv), three_pass(matmul)),
              "twin": (reversed_conv, matmul)}[precision]
    x = images.astype(F32)
    for i, spec in enumerate(convs):
        y = cv(x, w[f"convs/{i}/w"].astype(F32))
        mean = jnp.mean(y, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
        y = (y - mean) * jax.lax.rsqrt(var + BN_EPS)
        y = y * w[f"bns/{i}/scale"].astype(F32) + w[f"bns/{i}/bias"].astype(F32)
        x = jax.nn.relu(y)
        if spec["pool"]:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = jnp.mean(x, axis=(1, 2))
    return mm(x, w["head/w"].astype(F32)) + w["head/b"].astype(F32)


def loss(convs, precision, w, images, labels):
    logits = forward(convs, w, images, precision)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - ll)


class Retrain:
    """SGD with momentum on a ticket, ``lr`` constant over the steps
    compared."""

    def __init__(self, convs, weights: Dict[str, jax.Array],
                 masks: Dict[str, Optional[jax.Array]], *, lr: float,
                 momentum: float = 0.9, precision: str = "highest"):
        self.w = {k: v.astype(F32) for k, v in weights.items()}
        self.masks = {k: m for k, m in masks.items() if m is not None}
        self.mu = {k: jnp.zeros(v.shape, F32) for k, v in self.w.items()}
        self.lr, self.momentum = lr, momentum
        self.first_grad: Dict[str, float] = {}
        self.t = 0
        self._grad = jax.jit(jax.value_and_grad(
            functools.partial(loss, tuple(convs), precision), argnums=0))

    def step(self, images, labels) -> float:
        self.t += 1
        with jax.default_matmul_precision("highest"):
            value, g = self._grad(self.w, jnp.asarray(images),
                                  jnp.asarray(labels))
            self.w, self.mu, norms = _sgd(self.w, self.mu, g, self.masks,
                                          self.lr, self.momentum)
        if self.t == 1:
            self.first_grad = {k: float(v) for k, v in norms.items()}
        return float(value)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _sgd(w, mu, g, masks, lr, momentum):
    new_w, new_mu, norms = {}, {}, {}
    for k in w:
        gk = g[k] * masks[k] if k in masks else g[k]
        new_mu[k] = momentum * mu[k] + gk
        p = w[k].astype(F32) - lr * new_mu[k]
        if k in masks:
            p = p * masks[k]
        new_w[k] = p.astype(w[k].dtype)
        norms[k] = jnp.sqrt(jnp.sum(gk * gk))
    return new_w, new_mu, norms


def retrain(convs, weights, masks, batches: Sequence[Dict], *, lr: float,
            momentum: float = 0.9, half_batch: bool = False,
            precision: str = "highest"):
    """Run ``len(batches)`` steps.  Returns (losses, first-step gradient
    norms per leaf, the Retrain object with the final weights)."""
    r = Retrain(convs, weights, masks, lr=lr, momentum=momentum,
                precision=precision)
    losses = []
    for b in batches:
        n = len(b["labels"]) // 2 if half_batch else len(b["labels"])
        losses.append(r.step(b["images"][:n], b["labels"][:n]))
    return losses, r.first_grad, r
