"""Fused incidence delivery: gather + mask + segment-combine in one
Pallas kernel over a dst-sorted, degree-classed CSR layout.

The reference delivery path (gather -> ``where`` mask -> segment reduce)
materializes a ``[nnz, D]`` rows array in HBM and re-reads it — ~3x the
traffic the combine fundamentally needs, plus a serialized scatter.
This kernel runs the whole half-superstep data path per output tile:

    for edge block b incident to destination tile i (block-sparse skip):
        rows   = msgs[sorted_src[b]]            # gather, in VMEM
        hit    = dst in tile i  AND  dynamically live
        out[i] = combine(out[i], mask_to_identity(rows, hit))

Message rows stream through VMEM once; the ``[nnz, D]`` intermediate
never exists.  Two combine lowerings:

* ``sum`` (and ``or`` via int cast outside): a ``[BN, BE]`` one-hot
  built with ``broadcasted_iota`` + compare contracts against the
  gathered rows on the MXU (fp32-friendly systolic work — the segsum
  kernel's trick, but fed by the in-kernel gather);
* ``min`` / ``max`` / ``prod``: a masked ``[BN, BE, D]`` select reduced
  on the VPU (no matmul identity exists), so ``block_e x block_d`` must
  be sized to VMEM.

Block-sparse skip: grid is ``(n_dst_tiles, max_blocks)``; a
scalar-prefetched ``[n_tiles, 2]`` table (from
``layout.tile_block_bounds``, i.e. CSR row offsets at ``block_e``
granularity) gives each tile its first edge block and block count, so a
tile only ever reads its incident edges — unlike the segsum kernel's
full j-sweep, work scales with the tile's degree sum, not with nnz.

Degree classes (``deliver_fused_classes``): heavy-tailed degree
distributions inflate a single grid's ``max_blocks`` to the hub tile's
block count — every tail tile then pays the hub's grid extent in
skipped steps.  The degree-class layout runs ONE ``pallas_call`` per
class over the class's own destination rows, with class-local
``block_e`` and ``max_blocks``; the per-class partial outputs
concatenate and assemble through the layout's ``inv_perm`` gather.
The CSR form has no width cap, so the Pallas path needs no residual.

Static liveness (``e_mask``) is folded into the layout (dead lanes are
dropped from the class edge lists); only the dynamic ``active`` vector
costs a per-edge mask at runtime.

The kernel is written for TPU (scalar prefetch via
``pltpu.PrefetchScalarGridSpec``; in-kernel row gather) and validated
on CPU in interpret mode only.  Compiled for a v5e, Mosaic refuses the
in-kernel gather (``jnp.take`` on the VMEM message block: "Shape
mismatch in input, indices and output"), and the whole ``[n_src+1, D]``
table as one VMEM block overflows VMEM at full-size hypergraphs.  So
``repro.kernels.deliver.select_lowering`` never picks it;
``repro.kernels.deliver.xla`` is the fused data path every platform
runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sparse.segment import resolve_monoid

# Monoids whose combine the kernel can lower (sum via MXU one-hot
# contraction, the rest via masked select-reduce).  "or" is handled by
# the wrapper as an int32 max.
_MATMUL_MONOIDS = ("sum",)
_SELECT_MONOIDS = ("min", "max", "prod")


def _combine_kernel(
    bounds_ref, src_ref, dst_ref, live_ref, msg_ref, out_ref,
    *, block_n: int, monoid_name: str,
):
    i = pl.program_id(0)  # destination tile
    j = pl.program_id(1)  # local edge-block index within this tile
    monoid = resolve_monoid(monoid_name)
    ident = monoid.identity(out_ref.dtype)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, ident)

    n_blocks = bounds_ref[i, 1]

    @pl.when(j < n_blocks)
    def _accumulate():
        src = src_ref[...]                    # [BE] int32 (dst-sorted)
        dst = dst_ref[...]                    # [BE] int32 (non-decreasing)
        live = live_ref[...] != 0             # [BE] dynamic activity
        # THE fused gather: message rows land directly in VMEM registers,
        # never in an HBM-resident [nnz, D] intermediate.
        rows = jnp.take(msg_ref[...], src, axis=0)     # [BE, D]

        base = i * block_n
        local = dst - base
        iota = jax.lax.broadcasted_iota(
            jnp.int32, (block_n, src.shape[0]), 0
        )
        # [BN, BE]: edge e feeds local destination row (boundary blocks
        # carry neighbors' edges -> masked off here, not re-read).
        hit = (iota == local[None, :]) & live[None, :]

        if monoid_name in _MATMUL_MONOIDS:
            onehot = hit.astype(rows.dtype)
            out_ref[...] += jax.lax.dot_general(
                onehot, rows,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=out_ref.dtype,
            )
        else:
            picked = jnp.where(
                hit[:, :, None], rows[None, :, :], ident
            )                                  # [BN, BE, D] in VMEM
            reduced = {
                "min": jnp.min, "max": jnp.max, "prod": jnp.prod,
            }[monoid_name](picked, axis=1)
            out_ref[...] = monoid.combine(out_ref[...], reduced)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_dst", "monoid_name", "max_blocks", "block_n", "block_e",
        "interpret",
    ),
)
def deliver_fused_pallas(
    msgs_aug: jnp.ndarray,
    sorted_src: jnp.ndarray,
    sorted_dst: jnp.ndarray,
    live: jnp.ndarray,
    tile_bounds: jnp.ndarray,
    n_dst: int,
    monoid_name: str,
    max_blocks: int = 1,
    *,
    block_n: int = 128,
    block_e: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """One leaf's fused delivery over a prepared dst-sorted layout.

    msgs_aug: ``[n_src + 1, D]`` — messages with the monoid identity row
      appended (index ``n_src``; statically-dead lanes point there).
    sorted_src / sorted_dst: ``[nnz_pad]`` int32, dst-sorted, padded to
      a ``block_e`` multiple (padding: identity row / out-of-range dst).
    live: ``[nnz_pad]`` int32 — dynamic activity per lane (1 = live).
    tile_bounds: ``[n_tiles, 2]`` int32 (first block, n blocks) per
      ``block_n``-destination tile — scalar-prefetched for the skip.
    max_blocks: static grid extent — the widest tile's block count (one
      entry of ``DeliveryLayout.class_max_blocks``; ``deliver_fused_classes``
      passes each class's own).

    Returns ``[n_dst, D]`` combined messages.
    """
    nnz_pad = sorted_src.shape[0]
    assert nnz_pad % block_e == 0, (nnz_pad, block_e)
    d = msgs_aug.shape[1]
    n_src_aug = msgs_aug.shape[0]
    n_dst_pad = -(-max(n_dst, 1) // block_n) * block_n
    n_tiles = n_dst_pad // block_n
    assert tile_bounds.shape == (n_tiles, 2), (
        tile_bounds.shape, n_tiles,
    )
    total_blocks = nnz_pad // block_e
    max_blocks = max(int(max_blocks), 1)

    def edge_map(i, j, b):
        start = b[i, 0]
        nb = b[i, 1]
        # Clamp: steps past this tile's range (and empty tiles) map to a
        # valid block; the kernel's ``j < nb`` guard skips the work.
        safe = start + jnp.minimum(j, jnp.maximum(nb - 1, 0))
        return (jnp.clip(safe, 0, total_blocks - 1),)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, max_blocks),
        in_specs=[
            pl.BlockSpec((block_e,), edge_map),
            pl.BlockSpec((block_e,), edge_map),
            pl.BlockSpec((block_e,), edge_map),
            pl.BlockSpec((n_src_aug, d), lambda i, j, b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j, b: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(
            _combine_kernel, block_n=block_n, monoid_name=monoid_name
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_dst_pad, d), msgs_aug.dtype),
        interpret=interpret,
    )(tile_bounds, sorted_src, sorted_dst, live, msgs_aug)
    return out[:n_dst]


def deliver_fused_classes(
    msgs_aug: jnp.ndarray,
    act_aug: jnp.ndarray | None,
    layout,
    monoid_name: str,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """One leaf's fused delivery over a degree-classed layout: one
    per-class Pallas grid each, assembled with the ``inv_perm`` gather.

    msgs_aug: ``[n_src + 1, D]`` — messages with the monoid identity row
      appended (index ``n_src``; padding lanes point there).
    act_aug: optional ``[n_src + 1]`` int32 dynamic activity (identity
      row live), or None.

    Returns ``[n_dst, D]`` combined messages.
    """
    outs = []
    for c in range(layout.n_classes):
        src_c = layout.class_src[c]
        live = (
            jnp.take(act_aug, src_c, axis=0)
            if act_aug is not None
            else jnp.ones_like(src_c)
        )
        outs.append(
            deliver_fused_pallas(
                msgs_aug,
                src_c,
                layout.class_dst[c],
                live,
                layout.class_bounds[c],
                layout.class_rows[c],
                monoid_name,
                layout.class_max_blocks[c],
                block_n=layout.block_n,
                block_e=layout.class_block_e[c],
                interpret=interpret,
            )
        )
    # Class partials stack class-major (matching slot assignment); the
    # appended identity row serves every zero-degree destination.
    return jnp.take(
        jnp.concatenate(outs + [msgs_aug[-1:]], axis=0),
        layout.inv_perm, axis=0,
    )
