"""The plain reference the benchmark holds the program's answers to.

A numpy float64 PageRank from the incidence arrays alone, with the
semantics of the program's spec (a copy of the program's
``repro/reference.py``, kept here so that no change to the program can
move it): the same superstep pairs, the same initial message, the same
per-entity rules.  It imports nothing of the program.

``pagerank_lowp`` is the same reference in ``jax.numpy`` at a chosen
dtype: the benchmark's control runs it in bfloat16, the precision below
the float32 the configurations state, in the program's place.
"""
from __future__ import annotations

import numpy as np


def _edges(inc):
    return inc.src.astype(np.int64), inc.dst.astype(np.int64)


def pagerank_np(inc, iters: int, alpha: float):
    """``(vertex_ranks, hyperedge_ranks)`` after ``iters`` (vertex,
    hyperedge) superstep pairs, unit hyperedge weights."""
    src, dst = _edges(inc)
    nv, ne = inc.n_vertices, inc.n_hyperedges
    card = np.maximum(np.bincount(dst, minlength=ne), 1).astype(np.float64)
    total_w = np.ones(nv)
    rank_in = np.ones(nv)
    v_rank = he_rank = None
    for _ in range(iters):
        v_rank = alpha + (1.0 - alpha) * rank_in
        share = v_rank / np.maximum(total_w, 1e-12)
        he_rank = np.bincount(dst, weights=share[src], minlength=ne)
        total_w = np.bincount(src, minlength=nv).astype(np.float64)
        rank_in = np.bincount(src, weights=(he_rank / card)[dst],
                              minlength=nv)
    return v_rank, he_rank


# -- the same reference at a lower precision (the control) --------------

def pagerank_lowp(inc, iters: int, alpha: float, dtype):
    import jax
    import jax.numpy as jnp

    src, dst = jnp.asarray(inc.src), jnp.asarray(inc.dst)
    nv, ne = inc.n_vertices, inc.n_hyperedges

    @jax.jit
    def run():
        one = jnp.ones_like(src, dtype)
        card = jnp.maximum(jax.ops.segment_sum(one, dst, ne), 1)
        total_w = jax.ops.segment_sum(one, src, nv)
        tw, rank_in = jnp.ones(nv, dtype), jnp.ones(nv, dtype)
        v_rank = he_rank = None
        for _ in range(iters):
            v_rank = (alpha + (1.0 - alpha) * rank_in).astype(dtype)
            share = v_rank / jnp.maximum(tw, 1e-12).astype(dtype)
            he_rank = jax.ops.segment_sum(share[src], dst, ne)
            tw = total_w
            rank_in = jax.ops.segment_sum((he_rank / card)[dst], src, nv)
        return v_rank, he_rank

    return tuple(np.asarray(x, np.float64) for x in run())
