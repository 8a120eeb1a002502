"""Global activation-sharding-constraint hook.

``repro.distributed.sharding.install`` points this at
``lax.with_sharding_constraint`` with the active mesh rules; outside a
mesh it is the identity.  Tags per dimension: "dp" (batch axes),
"model" (tensor/expert axis), None (replicated).
"""
from __future__ import annotations

_CONSTRAIN = lambda x, tags: x  # noqa: E731
_MOE_GROUPS = 1
_KERNEL_MESH = None


def set_constrain_fn(fn):
    global _CONSTRAIN
    _CONSTRAIN = fn


def constrain(x, tags):
    return _CONSTRAIN(x, tags)


def set_moe_groups(g: int):
    """Dispatch groups for MoE (= data-parallel shard count).

    Grouped dispatch keeps the sort/scatter/gather of the capacity
    buffer local to each data shard (GShard/Switch 'groups'), removing
    the (T,d)-sized all-gather + all-reduce per MoE layer.
    """
    global _MOE_GROUPS
    _MOE_GROUPS = max(1, int(g))


def moe_groups() -> int:
    return _MOE_GROUPS


def set_kernel_mesh(mesh):
    """The mesh Pallas kernels launch over (``kernels.bsmm.launch``);
    None — or a one-device mesh — launches them as plain calls."""
    global _KERNEL_MESH
    size = 1 if mesh is None else mesh.devices.size
    _KERNEL_MESH = mesh if size > 1 else None


def kernel_mesh():
    return _KERNEL_MESH
