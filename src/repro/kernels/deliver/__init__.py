"""Fused incidence delivery.

``repro.core.engine.deliver`` routes through here when a
``DeliveryLayout`` is supplied (the ``delivery='pallas_fused'`` design
point).  Delivery has one fused data path: the degree-class layout
(``layout.build_delivery_layout``) lowered through stock XLA ops
(``xla.deliver_ell_leaf``) — dense slot-major ELL reduces per class
plus a sorted-COO residual — on every platform, the TPU included.
Non-monoid programs take the reference ``deliver`` instead.
"""
from __future__ import annotations

import math
import weakref
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.deliver.layout import (
    ClassPlan,
    DeliveryLayout,
    build_delivery_layout,
    classify_degrees,
    delivery_structure,
    layout_pair,
    layout_span_args,
    plan_degree_classes,
    plan_ell_width,
)
from repro.kernels.deliver.xla import deliver_ell_leaf

__all__ = [
    "DELIVERY_MODES",
    "ClassPlan",
    "DeliveryLayout",
    "build_delivery_layout",
    "classify_degrees",
    "deliver_ell_leaf",
    "delivery_structure",
    "fused_deliver",
    "gathered_lanes",
    "layout_pair",
    "layout_span_args",
    "plan_degree_classes",
    "plan_ell_width",
]

# The ``ExecutionConfig.delivery`` axis values.
DELIVERY_MODES = ("auto", "xla", "pallas_fused")

Pytree = Any

# The number of leaf groups each sender program's message was delivered
# in, recorded when ``fused_deliver`` is traced.  A job that reuses a
# compiled program (equal static programs, no retrace) finds the count
# its first trace left here.
_LEAF_GROUPS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def fused_deliver(
    out_msg: Pytree,
    active,
    layout: DeliveryLayout,
    program,
) -> Pytree:
    """Deliver + combine a message pytree through the fused layout.

    Drop-in for the reference gather/mask/segment path of
    ``repro.core.engine.deliver`` on the monoid fast path (the caller
    guarantees ``program.reducer is None`` and no ``edge_transform``);
    per-leaf monoids resolve exactly as in the reference.

    Leaves that share a monoid, a dtype and a trailing shape form one
    group, and a group of two or more goes through the layout once:
    its leaves sit side by side on a new minor axis (``[n_src, ΣD]``),
    so every class table, ``inv_perm`` and the residual are gathered
    once for the group rather than once per leaf.  Each leaf's result
    is the same adds over the same slots.  A group of one takes
    ``deliver_ell_leaf`` as it is.
    """
    leaves, treedef = jax.tree.flatten(out_msg)
    monoids = [program.monoid_for(leaf) for leaf in leaves]
    groups: dict = {}
    for i, (leaf, monoid) in enumerate(zip(leaves, monoids)):
        key = (monoid, jnp.dtype(leaf.dtype), tuple(leaf.shape[1:]))
        groups.setdefault(key, []).append(i)
    _LEAF_GROUPS[program] = len(groups)

    out = [None] * len(leaves)
    for members in groups.values():
        monoid = monoids[members[0]]
        if len(members) == 1:
            i, = members
            out[i] = deliver_ell_leaf(leaves[i], layout, monoid, active)
            continue
        trail = leaves[members[0]].shape[1:]
        width = math.prod(trail)
        stacked = jnp.concatenate(
            [leaves[i].reshape(len(leaves[i]), width) for i in members],
            axis=1,
        )
        got = deliver_ell_leaf(stacked, layout, monoid, active)
        for j, i in enumerate(members):
            out[i] = got[:, j * width:(j + 1) * width].reshape(
                got.shape[:1] + trail
            )
    return jax.tree.unflatten(treedef, out)


def gathered_lanes(layouts, programs) -> dict:
    """What a job that delivered through ``layouts`` (a ``layout_pair``)
    records on its ``engine.run`` span once its program is traced:
    ``delivery_gathered_lanes``, each direction's ELL slots plus residual
    lanes times the leaf groups its sender's message (``programs``,
    v->he then he->v) went through the layout in.  Empty when no trace
    recorded a sender's groups."""
    groups = [_LEAF_GROUPS.get(p) for p in programs]
    if None in groups:
        return {}
    return {"delivery_gathered_lanes": sum(
        (l.ell_slots + l.rem_len) * g for l, g in zip(layouts, groups)
    )}
