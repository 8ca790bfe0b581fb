"""Segment-sum as a blocked one-hot matmul on the MXU.

The MESH combine step (deliver: scatter-reduce messages by destination) is
an irregular scatter on GPUs; on TPU the winning shape is dense systolic
work.  Per grid step (i, j):

    out[i*BN:(i+1)*BN, :] += onehot(dst_block_j)[BN, BE] @ msg_block_j[BE, D]

The one-hot is built in VMEM with ``broadcasted_iota`` + compare (no
gather/scatter at all); the contraction runs on the MXU with fp32
accumulation.  Grid dim j is the reduction dimension: the out BlockSpec
maps both j's to the same tile, initialized at j==0 (standard Pallas
revisiting-accumulator pattern).

Tiling: BE x D msg block and BN x D out tile must fit VMEM; BN/BE chosen
as multiples of the 128-lane MXU edge.

Two grid regimes:

* **unsorted fallback (reference)**: every output tile sweeps every
  edge block — O(n_tiles * n_blocks) grid steps regardless of where a
  tile's edges actually live.  Correct for any ``dst`` order; this is
  the oracle form.
* **sorted + block-sparse skip**: for dst-sorted inputs, a
  scalar-prefetched ``[n_tiles, 2]`` bounds table (CSR row offsets at
  ``block_e`` granularity, ``ops.tile_block_bounds``) restricts each
  tile to its incident edge blocks, so grid work scales with the tile's
  degree sum instead of nnz.

For the full fused half-superstep (gather + mask + combine) see
``repro.kernels.deliver`` — this kernel is a combine-only form fed by
pre-gathered rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segsum_kernel(dst_ref, msg_ref, out_ref, *, block_n: int):
    i = pl.program_id(0)   # output row-tile index
    j = pl.program_id(1)   # edge-block index (reduction dim)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dst = dst_ref[...]                       # [BE] int32 (block of ids)
    msgs = msg_ref[...]                      # [BE, D]
    base = i * block_n
    # one-hot [BN, BE]: rows = local segment ids, cols = edges
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, dst.shape[0]), 0)
    onehot = (rows + base == dst[None, :]).astype(msgs.dtype)
    out_ref[...] += jax.lax.dot_general(
        onehot, msgs,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=out_ref.dtype,
    )


def _segsum_sorted_kernel(bounds_ref, dst_ref, msg_ref, out_ref,
                          *, block_n: int):
    i = pl.program_id(0)
    j = pl.program_id(1)   # LOCAL block index within tile i's range

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j < bounds_ref[i, 1])
    def _accumulate():
        dst = dst_ref[...]
        msgs = msg_ref[...]
        base = i * block_n
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_n, dst.shape[0]), 0
        )
        onehot = (rows + base == dst[None, :]).astype(msgs.dtype)
        out_ref[...] += jax.lax.dot_general(
            onehot, msgs,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=out_ref.dtype,
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_segments", "max_blocks", "block_n", "block_e", "interpret",
    ),
)
def segsum_pallas(
    msgs: jnp.ndarray,
    dst: jnp.ndarray,
    num_segments: int,
    tile_bounds: jnp.ndarray | None = None,
    max_blocks: int | None = None,
    *,
    block_n: int = 128,
    block_e: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """msgs [E, D], dst [E] -> [num_segments, D] (f32 accumulate).

    E must be a multiple of block_e and num_segments of block_n (the ops.py
    wrapper pads; padding edges carry dst == num_segments_padded, which no
    output tile matches, so they contribute nothing).

    ``tile_bounds`` + ``max_blocks`` (from
    ``repro.kernels.segsum.ops.tile_block_bounds`` over dst-SORTED input)
    enable the block-sparse skip; omitted, the kernel runs the unsorted
    fallback's full j-sweep.
    """
    e, d = msgs.shape
    assert e % block_e == 0, (e, block_e)
    n_pad = -(-num_segments // block_n) * block_n

    if tile_bounds is None:
        grid = (n_pad // block_n, e // block_e)
        out = pl.pallas_call(
            functools.partial(_segsum_kernel, block_n=block_n),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_e,), lambda i, j: (j,)),
                pl.BlockSpec((block_e, d), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
            interpret=interpret,
        )(dst, msgs)
        return out[:num_segments]

    total_blocks = e // block_e
    n_tiles = n_pad // block_n
    assert tile_bounds.shape == (n_tiles, 2), (
        tile_bounds.shape, n_tiles,
    )

    def edge_map(i, j, b):
        safe = b[i, 0] + jnp.minimum(j, jnp.maximum(b[i, 1] - 1, 0))
        return (jnp.clip(safe, 0, total_blocks - 1),)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, max(int(max_blocks or 1), 1)),
        in_specs=[
            pl.BlockSpec((block_e,), edge_map),
            pl.BlockSpec((block_e, d), lambda i, j, b: (edge_map(i, j, b)[0], 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j, b: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_segsum_sorted_kernel, block_n=block_n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret,
    )(tile_bounds, dst, msgs)
    return out[:num_segments]
