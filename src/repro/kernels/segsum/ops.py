"""jit'd public wrapper for the segsum MXU kernel (handles padding)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segsum.segsum import segsum_pallas


def tile_block_bounds(
    row_offsets: np.ndarray, n_dst_pad: int, block_n: int, block_e: int
) -> tuple[np.ndarray, int]:
    """Per-output-tile edge-block ranges from CSR row offsets.

    Tile ``i`` covers destinations ``[i*block_n, (i+1)*block_n)``; its
    incident edges are CSR rows ``[row_offsets[lo], row_offsets[hi])``,
    which span edge blocks ``[floor(lo_e/block_e), ceil(hi_e/block_e))``.
    Boundary blocks contain neighbors' edges; the kernel masks them by
    destination.  Returns ``([n_tiles, 2] (start, count), max_count)``.
    """
    n_tiles = n_dst_pad // block_n
    bounds = np.zeros((n_tiles, 2), np.int32)
    n_real = len(row_offsets) - 1
    for i in range(n_tiles):
        lo = row_offsets[min(i * block_n, n_real)]
        hi = row_offsets[min((i + 1) * block_n, n_real)]
        b_lo = lo // block_e
        b_hi = -(-hi // block_e)
        bounds[i] = (b_lo, max(b_hi - b_lo, 0))
    max_blocks = int(bounds[:, 1].max()) if n_tiles else 0
    return bounds, max(max_blocks, 1)


def segment_sum_mxu(
    msgs: jnp.ndarray,
    dst: jnp.ndarray,
    num_segments: int,
    *,
    sorted_dst: bool = False,
    block_n: int = 128,
    block_e: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in for ``jax.ops.segment_sum(msgs, dst, num_segments)`` running
    the blocked one-hot MXU kernel.  Pads E to a block multiple (padding
    edges point past every output tile).

    ``sorted_dst=True`` asserts ``dst`` is non-decreasing (a
    ``HyperGraph.sorted_by_dst`` product) and routes through the
    block-sparse skip: per-tile CSR block bounds are computed host-side
    (``dst`` must be concrete) so each output tile reads only its
    incident edge blocks instead of the unsorted fallback's full
    j-sweep.
    """
    e, d = msgs.shape
    e_pad = -(-e // block_e) * block_e
    n_pad = -(-num_segments // block_n) * block_n
    tile_bounds = None
    max_blocks = None
    if sorted_dst and e:
        dst_host = np.asarray(dst)
        assert (np.diff(dst_host) >= 0).all(), (
            "sorted_dst=True needs non-decreasing dst ids (see "
            "HyperGraph.sorted_by_dst)"
        )
        counts = np.bincount(
            dst_host, minlength=max(num_segments, 1)
        )[: max(num_segments, 1)]
        offsets = np.zeros(max(num_segments, 1) + 1, np.int64)
        np.cumsum(counts, out=offsets[1:])
        bounds, max_blocks = tile_block_bounds(
            offsets, n_pad, block_n, block_e
        )
        tile_bounds = jnp.asarray(bounds)
    if e_pad != e:
        msgs = jnp.concatenate(
            [msgs, jnp.zeros((e_pad - e, d), msgs.dtype)], axis=0
        )
        dst = jnp.concatenate(
            [dst, jnp.full((e_pad - e,), n_pad, dst.dtype)], axis=0
        )
    out = segsum_pallas(
        msgs, dst, num_segments, tile_bounds, max_blocks,
        block_n=block_n, block_e=block_e, interpret=interpret,
    )
    return out.astype(msgs.dtype)
