"""Fused incidence delivery — the delivery-kernel registry.

``repro.core.engine.deliver`` routes through here when a
``DeliveryLayout`` is supplied (the ``delivery='pallas_fused'`` design
point).  One fused data path, two lowerings:

* ``ell`` — the layout driven through stock XLA ops
  (``xla.deliver_ell_leaf``): dense ELL reduce + sorted-COO overflow.
  The lowering on every platform, the TPU included;
* ``pallas`` — the scalar-prefetch gather + mask + segment-combine
  kernel (``fused.deliver_fused_pallas``), exercised in interpret mode
  by the test suite.  Mosaic refuses its in-kernel row gather
  (``jnp.take`` on a VMEM ref), and it holds the whole message table
  in VMEM, which a full-size hypergraph overflows
  (``repro.analysis.shapes.check_width_gate``).  So it is never
  selected; ``REPRO_DELIVERY_LOWERING`` (``ell`` | ``pallas`` |
  ``pallas_interpret``) reaches it for tests and experiments.

A layout is built for one lowering (``build_delivery_layout``'s
``lowering``, by default ``select_lowering()``): only a Pallas layout
carries the kernel's CSR arrays, and it serves both lowerings.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.deliver.fused import (
    deliver_fused_classes,
    deliver_fused_pallas,
)
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
    select_lowering,
    tile_block_bounds,
)
from repro.kernels.deliver.xla import deliver_ell_leaf
from repro.sparse.segment import MONOIDS

__all__ = [
    "DELIVERY_MODES",
    "ClassPlan",
    "DeliveryLayout",
    "build_delivery_layout",
    "classify_degrees",
    "deliver_ell_leaf",
    "deliver_fused_classes",
    "deliver_fused_pallas",
    "delivery_structure",
    "fused_deliver",
    "layout_pair",
    "layout_span_args",
    "plan_degree_classes",
    "plan_ell_width",
    "select_lowering",
    "tile_block_bounds",
]

# The ``ExecutionConfig.delivery`` axis values.
DELIVERY_MODES = ("auto", "xla", "pallas_fused")

Pytree = Any


def _pallas_leaf(leaf, layout, monoid, active, *, interpret):
    """Shape-normalize one leaf for the per-class 2-D Pallas kernels."""
    if not layout.serves("pallas"):
        raise ValueError(
            "this DeliveryLayout was built for the ell lowering and has "
            "no CSR arrays; build it with lowering='pallas'"
        )
    shape = leaf.shape
    msgs2d = leaf.reshape(shape[0], -1)
    if monoid.name == "or":
        # bool has no MXU contraction: lower "or" as int32 max.
        out = _pallas_leaf(
            msgs2d.astype(jnp.int32), layout, MONOIDS["max"], active,
            interpret=interpret,
        )
        # > 0, not astype(bool): empty destinations hold the max
        # identity (iinfo.min), which must read back as False.
        return (out > 0).reshape((layout.n_dst,) + shape[1:])
    ident = monoid.identity(msgs2d.dtype)
    msgs_aug = jnp.concatenate(
        [msgs2d, jnp.full((1, msgs2d.shape[1]), ident, msgs2d.dtype)]
    )
    act_aug = None
    if active is not None:
        act_aug = jnp.concatenate(
            [active.astype(jnp.int32), jnp.ones((1,), jnp.int32)]
        )
    out = deliver_fused_classes(
        msgs_aug, act_aug, layout, monoid.name, interpret=interpret
    )
    return out.reshape((layout.n_dst,) + shape[1:])


def fused_deliver(
    out_msg: Pytree,
    active,
    layout: DeliveryLayout,
    program,
    lowering: str | None = None,
) -> Pytree:
    """Deliver + combine a message pytree through the fused layout.

    Drop-in for the reference gather/mask/segment path of
    ``repro.core.engine.deliver`` on the monoid fast path (the caller
    guarantees ``program.reducer is None`` and no ``edge_transform``);
    per-leaf monoids resolve exactly as in the reference.
    """
    lowering = lowering or select_lowering()

    def one(leaf):
        monoid = program.monoid_for(leaf)
        if lowering == "ell":
            return deliver_ell_leaf(leaf, layout, monoid, active)
        return _pallas_leaf(
            leaf, layout, monoid, active,
            interpret=(lowering == "pallas_interpret"),
        )

    return jax.tree.map(one, out_msg)
