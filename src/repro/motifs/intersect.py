"""Tiled hyperedge-pair intersection kernels.

Motif classification (``repro.motifs.hmotifs``) reduces to one primitive:
given batches of hyperedge id pairs (or triples), return the size of the
member-set intersection.  This is exactly the clique-vs-bipartite tension
MESH §IV-A studies — clique expansion *precomputes* every pairwise
intersection while the bipartite incidence must derive them — so the
kernel ships two interchangeable paths behind one cost model:

* ``bitset`` — pack each hyperedge's member set into uint32 lanes
  (``[E, ceil(|V|/32)]``); an intersection is AND + popcount over the
  word lanes.  Dense, branch-free, MXU/VPU-shaped (the Pallas version
  lives in ``repro.kernels.isect``); wins for small vertex vocabularies
  where the word count stays below the sort-merge work.
* ``merge`` — pad each hyperedge's *sorted* member list to the max
  cardinality (built from the CSR arrays ``sorted_by_dst`` produces) and
  count membership via per-row ``searchsorted``.  O(K log K) per pair
  independent of |V|; wins for large vocabularies.

Both paths are jit-able and tiled (``lax.map`` over fixed-size pair
tiles, so peak memory is ``tile x max(W, K)`` regardless of batch size)
and both can tile across a device mesh (``shard_map`` over pair blocks,
each device reducing its slice — the sharded analytics backend).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.hypergraph import HyperGraph

INTERSECT_KERNELS = ("auto", "bitset", "merge")


@dataclasses.dataclass(frozen=True)
class PairIndex:
    """Preprocessed per-hyperedge member structure for one kernel path.

    ``data`` is ``[E, W] uint32`` bit lanes (bitset) or ``[E, K] int32``
    sorted members padded with the sentinel ``n_vertices`` (merge).
    """

    kind: str                 # "bitset" | "merge"
    n_vertices: int
    n_hyperedges: int
    data: jnp.ndarray

    @property
    def width(self) -> int:
        return int(self.data.shape[1])

    @property
    def nbytes(self) -> int:
        return int(self.data.size) * 4

    def cardinalities(self) -> np.ndarray:
        """|e| per hyperedge, recovered from the index itself."""
        if self.kind == "merge":
            return np.asarray(
                (np.asarray(self.data) < self.n_vertices).sum(axis=1),
                np.int64,
            )
        return np.asarray(
            jax.lax.population_count(self.data).astype(jnp.int32).sum(axis=1),
            np.int64,
        )


def _clean_incidence(hg: HyperGraph) -> tuple[np.ndarray, np.ndarray]:
    """Host-side (src, dst) with masked incidences dropped and duplicate
    memberships collapsed (intersection counts are *set* sizes)."""
    src = np.asarray(hg.src)
    dst = np.asarray(hg.dst)
    if hg.e_mask is not None:
        keep = np.asarray(hg.e_mask) > 0
        src, dst = src[keep], dst[keep]
    if len(src) == 0:
        return src.astype(np.int32), dst.astype(np.int32)
    key = dst.astype(np.int64) * np.int64(max(hg.n_vertices, 1)) + src
    _, first = np.unique(key, return_index=True)
    return src[first].astype(np.int32), dst[first].astype(np.int32)


def build_index(hg: HyperGraph, kernel: str) -> PairIndex:
    """Build the per-hyperedge member structure for one kernel path
    (host-side preprocessing, like the representation builds of §IV-A)."""
    src, dst = _clean_incidence(hg)
    nv, ne = hg.n_vertices, hg.n_hyperedges
    if kernel == "bitset":
        w = max((nv + 31) // 32, 1)
        bits = np.zeros((max(ne, 1), w), np.uint32)
        if len(src):
            np.bitwise_or.at(
                bits,
                (dst, src >> 5),
                np.left_shift(np.uint32(1), (src & 31).astype(np.uint32)),
            )
        return PairIndex("bitset", nv, ne, jnp.asarray(bits))
    if kernel == "merge":
        if len(src):
            card = np.bincount(dst, minlength=ne)
            k = max(int(card.max()), 1)
        else:
            k = 1
        members = np.full((max(ne, 1), k), nv, np.int32)
        if len(src):
            order = np.lexsort((src, dst))
            s, d = src[order], dst[order]
            bounds = np.searchsorted(d, np.arange(ne + 1))
            pos = np.arange(len(s)) - bounds[d]
            members[d, pos] = s
        return PairIndex("merge", nv, ne, jnp.asarray(members))
    raise ValueError(
        f"unknown intersection kernel {kernel!r}; pick one of "
        f"{INTERSECT_KERNELS[1:]}"
    )


def select_intersect_kernel(
    hg: HyperGraph, *, bitset_budget_bytes: int = 256 << 20
) -> tuple[str, dict]:
    """Bitset vs sorted-merge for one hypergraph — the PR-1-style cost
    model.

    Per-pair work: bitset touches ``W = ceil(|V|/32)`` uint32 lanes;
    merge does ``K (log2 K + 1)`` compares for max cardinality ``K``.
    Small vocabularies keep ``W`` below the merge work (pick bitset);
    large vocabularies blow the word count (and the ``E x W`` index
    memory) up, so merge wins.
    """
    nv, ne = hg.n_vertices, hg.n_hyperedges
    card = np.asarray(hg.cardinalities())
    k = max(int(card.max()) if card.size else 1, 1)
    w = max((nv + 31) // 32, 1)
    bitset_cost = float(w)
    merge_cost = float(k * (math.log2(k) + 1.0))
    bitset_bytes = ne * w * 4
    why: dict[str, Any] = {
        "bitset_words_per_pair": w,
        "merge_ops_per_pair": merge_cost,
        "bitset_index_bytes": bitset_bytes,
        "bitset_budget_bytes": bitset_budget_bytes,
    }
    if bitset_bytes > bitset_budget_bytes:
        why["reason"] = "bitset index exceeds memory budget"
        return "merge", why
    if bitset_cost <= merge_cost:
        why["reason"] = "vocabulary small: word lanes beat sort-merge"
        return "bitset", why
    why["reason"] = "vocabulary large: sort-merge beats word lanes"
    return "merge", why


# --------------------------------------------------------------------------
# tile bodies (shared by the local and sharded drivers)
# --------------------------------------------------------------------------

def _tile_bitset(bits, a, b, c):
    inter = jnp.take(bits, a, axis=0) & jnp.take(bits, b, axis=0)
    if c is not None:
        inter = inter & jnp.take(bits, c, axis=0)
    return jax.lax.population_count(inter).astype(jnp.int32).sum(axis=-1)


def _tile_merge(members, nv, a, b, c):
    ra = jnp.take(members, a, axis=0)

    def contains(rows, probe):
        idx = jax.vmap(jnp.searchsorted)(rows, probe)
        idx = jnp.minimum(idx, rows.shape[1] - 1)
        return jnp.take_along_axis(rows, idx, axis=1) == probe

    hit = contains(jnp.take(members, b, axis=0), ra) & (ra < nv)
    if c is not None:
        hit = hit & contains(jnp.take(members, c, axis=0), ra)
    return hit.sum(axis=1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("kind", "nv", "tile", "with_c"))
def _batch_tiled(data, ea, eb, ec, *, kind, nv, tile, with_c):
    """[n] pair/triple intersection sizes, n a static multiple of tile."""
    nt = ea.shape[0] // tile
    resh = lambda x: x.reshape(nt, tile)

    def body(args):
        a, b, c = args
        c = c if with_c else None
        if kind == "bitset":
            return _tile_bitset(data, a, b, c)
        return _tile_merge(data, nv, a, b, c)

    return jax.lax.map(body, (resh(ea), resh(eb), resh(ec))).reshape(-1)


def batch_intersections(
    index: PairIndex,
    ea,
    eb,
    ec=None,
    *,
    tile: int = 2048,
    mesh=None,
    axis: str = "data",
) -> np.ndarray:
    """Intersection size per (ea[i], eb[i]) pair — or per triple when
    ``ec`` is given.  Tiled locally; with a mesh, pair blocks are tiled
    across ``mesh[axis]`` (each device reduces its slice, the index is
    replicated) — the sharded batch-analytics backend.
    """
    ea = np.asarray(ea, np.int32)
    eb = np.asarray(eb, np.int32)
    n = len(ea)
    if n == 0:
        return np.zeros(0, np.int32)
    with_c = ec is not None
    ec = np.asarray(ec, np.int32) if with_c else np.zeros(n, np.int32)

    n_parts = int(mesh.shape[axis]) if mesh is not None else 1
    block = -(-n // (n_parts * tile)) * tile
    n_pad = block * n_parts
    pad = lambda x: np.pad(x, (0, n_pad - n)) if n_pad > n else x
    ea_p, eb_p, ec_p = map(
        jnp.asarray, (pad(ea), pad(eb), pad(ec))
    )
    kw = dict(kind=index.kind, nv=index.n_vertices, tile=tile,
              with_c=with_c)

    if mesh is None:
        out = _batch_tiled(index.data, ea_p, eb_p, ec_p, **kw)
        return np.asarray(out[:n])

    def run(data, a, b, c):
        return _batch_tiled(data, a, b, c, **kw)

    mapped = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    with mesh:
        out = jax.jit(mapped)(index.data, ea_p, eb_p, ec_p)
    return np.asarray(out)[:n]
