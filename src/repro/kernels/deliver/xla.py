"""The fused delivery data path expressed to XLA (sliced-ELL + sorted COO).

Mask folded into the layout, message rows read once, combine without a
serialized scatter, all through stock XLA ops.  This is delivery's one
fused lowering, on every platform, the TPU included:

* each degree class's incidences sit in its own dense slot-major
  ``[k_c, rows_c]`` id table: one vectorized gather and one dense
  reduction over the ``k_c`` slots per class replace the scatter
  (XLA's CPU scatter-add serializes; a ``[k_c, rows_c, D]`` reduce
  vectorizes).  Slot-major keeps the destinations on the minor axis,
  so a class may hold any multiple of 8 rows: row-major
  ``[rows_c, k_c]`` tables whose row counts were not powers of two
  made the v5e compiler emit a program 16x larger (184 MB against
  11.5 MB at dblp's size), reloaded on every retrace.  Class widths
  track the degree histogram, so hubs stay dense and the tail stays
  narrow;
* the per-class partials concatenate (plus one identity row for
  zero-degree destinations) and assemble with ONE gather through the
  layout's ``inv_perm`` — no scatter anywhere on the dense path;
* hub incidences past the last class width take a segment reduce over
  *dst-sorted* ids (``indices_are_sorted=True``) and merge in with one
  ``combine`` — statically skipped when the layout has no residual.

Statically-dead lanes were dropped at layout-build time, so only a
dynamic ``active`` vector costs a mask here — and it is a per-class
``[k_c, rows_c]`` byte mask, not an ``[nnz, D]`` float ``where``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.deliver.layout import DeliveryLayout
from repro.sparse.segment import Monoid

_AXIS_REDUCE = {
    "sum": jnp.sum,
    "min": jnp.min,
    "max": jnp.max,
    "prod": jnp.prod,
}


def _reduce_axis0(x: jnp.ndarray, monoid: Monoid) -> jnp.ndarray:
    if monoid.name == "or":
        return jnp.any(x, axis=0)
    return _AXIS_REDUCE[monoid.name](x, axis=0)


def deliver_ell_leaf(
    msgs: jnp.ndarray,
    layout: DeliveryLayout,
    monoid: Monoid,
    active: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """One leaf's fused delivery: ``[n_src, ...] -> [n_dst, ...]``."""
    ident = monoid.identity(msgs.dtype)
    ident_row = jnp.full((1,) + msgs.shape[1:], ident, msgs.dtype)
    msgs_aug = jnp.concatenate([msgs, ident_row], axis=0)

    act_aug = None
    if active is not None:
        act_aug = jnp.concatenate(
            [active.astype(bool), jnp.ones((1,), bool)]
        )

    trail = (1,) * (msgs.ndim - 1)

    outs = []
    for ell in layout.class_ell:
        k, rows_c = ell.shape
        rows = jnp.take(
            msgs_aug, ell.reshape(-1), axis=0
        ).reshape((k, rows_c) + msgs.shape[1:])
        if act_aug is not None:
            live = jnp.take(act_aug, ell, axis=0)  # [k, rows_c]
            rows = jnp.where(live.reshape((k, rows_c) + trail), rows, ident)
        outs.append(_reduce_axis0(rows, monoid))
    # Assembly is a pure gather: slot order is class-major, and the
    # appended identity row serves every zero-degree destination.
    out = jnp.take(
        jnp.concatenate(outs + [ident_row], axis=0),
        layout.inv_perm, axis=0,
    )

    if layout.rem_nnz == 0:
        return out
    rem_rows = jnp.take(msgs_aug, layout.rem_src, axis=0)
    if act_aug is not None:
        rem_live = jnp.take(act_aug, layout.rem_src, axis=0)
        rem_rows = jnp.where(
            rem_live.reshape((-1,) + trail), rem_rows, ident
        )
    overflow = monoid.segment(
        rem_rows, layout.rem_dst, num_segments=layout.n_dst,
        indices_are_sorted=True,
    )
    return monoid.combine(out, overflow)
