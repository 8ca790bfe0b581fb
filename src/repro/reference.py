"""Plain numpy references for the served algorithms.

Each function recomputes one algorithm's result on the host from the
incidence lists alone — no engine, no delivery layout, no JAX — with the
same semantics as its spec in ``repro.algorithms``: the same superstep
pairs, the same initial message, the same per-entity rules.  They are
the oracles ``chip_smoke.py`` holds the chip's results to, and the tests
hold them to the engine at small sizes.

Float results are computed in float64; the engine runs float32 and its
sums run in another order, so callers compare them with a tolerance.
Hop distances are exact.
"""
from __future__ import annotations

import numpy as np


def _incidence(hg) -> tuple[np.ndarray, np.ndarray]:
    src = np.asarray(hg.src)
    dst = np.asarray(hg.dst)
    if hg.e_mask is not None:
        live = np.asarray(hg.e_mask) != 0
        src, dst = src[live], dst[live]
    return src.astype(np.int64), dst.astype(np.int64)


def pagerank_np(hg, iters: int = 30, alpha: float = 0.15):
    """``pagerank_spec`` (unit hyperedge weights): ``(vertex_ranks,
    hyperedge_ranks)`` after ``iters`` (vertex, hyperedge) pairs."""
    src, dst = _incidence(hg)
    nv, ne = hg.n_vertices, hg.n_hyperedges
    card = np.maximum(np.bincount(dst, minlength=ne), 1).astype(np.float64)
    # he -> v message: (sum of incident weights, sum of rank / card);
    # the initial message is (1, 1) at every vertex.
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


def hop_distances_np(hg, source: int, max_iters: int):
    """``shortest_paths_spec``: ``(vertex_hops, hyperedge_hops)`` from
    ``source`` after ``max_iters`` pairs, by a level-synchronous
    frontier BFS.  A vertex->hyperedge hop costs 1; after ``K`` pairs
    hyperedges within ``K`` hops and vertices within ``K - 1`` are
    final and the rest still read ``inf``."""
    src, dst = _incidence(hg)
    nv, ne = hg.n_vertices, hg.n_hyperedges
    dv = np.full(nv, np.inf, np.float32)
    de = np.full(ne, np.inf, np.float32)
    dv[source] = 0.0
    frontier = np.zeros(nv, bool)
    frontier[source] = True
    for level in range(1, max_iters + 1):
        hit = np.zeros(ne, bool)
        hit[dst[frontier[src]]] = True
        new_e = hit & np.isinf(de)
        if not new_e.any():
            break
        de[new_e] = level
        if level == max_iters:
            break
        reach = np.zeros(nv, bool)
        reach[src[new_e[dst]]] = True
        frontier = reach & np.isinf(dv)
        dv[frontier] = level
    return dv, de


def random_walk_np(hg, seed: int, iters: int, alpha: float = 0.15):
    """``random_walk_spec`` personalized at ``seed`` (the served PPR
    query): the visit distribution over vertices after ``iters``
    pairs."""
    src, dst = _incidence(hg)
    nv, ne = hg.n_vertices, hg.n_hyperedges
    deg = np.bincount(src, minlength=nv)
    card = np.maximum(np.bincount(dst, minlength=ne), 1).astype(np.float64)
    dangling = (deg == 0).astype(np.float64)
    d = np.maximum(deg, 1).astype(np.float64)
    restart = np.zeros(nv)
    restart[seed] = 1.0
    p = restart.copy()
    msg = np.zeros(nv)
    for step in range(iters):
        if step > 0:
            p = (1.0 - alpha) * (msg + p * dangling) + alpha * restart
        out = p / d * (1.0 - dangling)
        he = np.bincount(dst, weights=out[src], minlength=ne)
        msg = np.bincount(src, weights=(he / card)[dst], minlength=nv)
    return p
