"""The work a step requires, counted from the ticket, not from the
implementation.

Operations are multiply-adds times two.  A routed projection of a layer
needs ``live tiles x 128 x 128`` weights: its forward, its input
gradient and its weight gradient each need ``2 x rows x live weights``
operations.  Bytes are what each product must at least move: its input
and its output tensors once each, and its live weights, in the dtype
they are stored in.  A convolution's input is its feature map, read
once, not the im2col unroll that repeats it kernel-area times.  A tile
that is dead in this layer costs nothing here, whatever a kernel does
with it, so the count stays the same whatever implements the step.
Recomputation is not counted.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

TILE = 128

Call = Tuple[float, float]          # (operations, bytes) of one product


def passes(macs: float, x: float, w: float, y: float, itemsize: int,
           dx: bool = True) -> Dict[str, Call]:
    """Forward, input-gradient (when ``dx``) and weight-gradient work of
    a product of ``macs`` multiply-adds that reads an input of ``x``
    elements with ``w`` live weights and writes ``y``: each pass moves
    the three tensors once (the forward reads x and w and writes y, the
    input gradient reads y's gradient and w and writes x's, the weight
    gradient reads x and y's gradient and writes w's)."""
    fl, moved = 2.0 * macs, itemsize * (x + w + y)
    out = {"fwd": (fl, moved)}
    if dx:
        out["dx"] = (fl, moved)
    out["dw"] = (fl, moved)
    return out


def product(rows: int, k: int, n: int, live: float, itemsize: int
            ) -> Dict[str, Call]:
    """Forward, input-gradient and weight-gradient work of ``x (rows, k)
    @ w (k, n)`` with ``live`` weights of w kept."""
    return passes(rows * live, rows * k, live, rows * n, itemsize)


def lm_train_step(shape: Dict, live_tiles: Dict[str, Sequence[int]],
                  batch: int, seq: int, itemsize: int = 2
                  ) -> Dict[str, object]:
    """One retrain step of a decoder whose projection ``name`` (key of
    ``projection_dims``) keeps ``live_tiles[name][layer]`` tiles."""
    rows = batch * seq
    d, hd = shape["d_model"], shape["head_dim"]
    H, L = shape["n_heads"], shape["n_layers"]
    dims = projection_dims(shape)
    calls: List[Call] = []
    for name, per_layer in live_tiles.items():
        k, n = dims[name]
        for live in per_layer:
            calls += product(rows, k, n, live * TILE * TILE,
                             itemsize).values()
    pairs = batch * H * seq * (seq + 1) / 2          # causal (q, k) pairs
    attention = L * 3 * (4.0 * pairs * hd)           # QK and PV, fwd+bwd
    head = 3 * 2.0 * rows * d * shape["vocab_rows"]
    routed = sum(c[0] for c in calls)
    return {"bsmm": calls, "attention_flops": attention,
            "head_flops": head, "flops": routed + attention + head}


def projection_dims(shape: Dict) -> Dict[str, Tuple[int, int]]:
    d, hd, ff = shape["d_model"], shape["head_dim"], shape["d_ff"]
    q, kv = shape["n_heads"] * hd, shape["n_kv_heads"] * hd
    return {"attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
            "attn/wo": (q, d), "mlp/up": (d, ff), "mlp/gate": (d, ff),
            "mlp/down": (ff, d)}


def conv_train_step(layers: Sequence[Dict], batch: int, itemsize: int = 4
                    ) -> Dict[str, object]:
    """One training step of a CNN.  Each entry of ``layers`` is one
    product, a convolution or the head, and gives its ``live`` weights
    (of the (IC x kh x kw, OC) unroll), its output positions per image
    ``hw`` (``hw x live`` multiply-adds an image), and the elements per
    image of its input feature map ``x`` and of its output ``y``.  A
    product whose input is the data (``data`` true) needs no input
    gradient."""
    calls: List[Call] = []
    for c in layers:
        calls += passes(batch * c["hw"] * c["live"], batch * c["x"],
                        c["live"], batch * c["y"], itemsize,
                        dx=not c.get("data", False)).values()
    return {"conv": calls, "flops": sum(fl for fl, _ in calls)}


def least_seconds(calls: Sequence[Call], peak_flops: float,
                  peak_bytes_per_s: float) -> Tuple[float, str]:
    """Least time of a set of products, each bound by its larger term,
    and which bound holds for most of that time."""
    t_f = t_b = total = 0.0
    for fl, by in calls:
        a, b = fl / peak_flops, by / peak_bytes_per_s
        total += max(a, b)
        if a >= b:
            t_f += a
        else:
            t_b += b
    return total, ("flops" if t_f >= t_b else "bytes")
