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

from typing import Any

import jax

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
    "layout_pair",
    "layout_span_args",
    "plan_degree_classes",
    "plan_ell_width",
]

# The ``ExecutionConfig.delivery`` axis values.
DELIVERY_MODES = ("auto", "xla", "pallas_fused")

Pytree = Any


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
    """
    return jax.tree.map(
        lambda leaf: deliver_ell_leaf(
            leaf, layout, program.monoid_for(leaf), active
        ),
        out_msg,
    )
