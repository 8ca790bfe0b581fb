"""The benchmark's data: a hypergraph deployment made from the seed.

A copy of the power-law generator the program ships
(``repro.data.generators.powerlaw_hypergraph``), kept here so that no
later change to the program can move the yardstick.  It returns plain
numpy incidence arrays; the program's own ``HyperGraph.from_coo`` is the
entry that turns them into device arrays.

The structure (cardinalities, popularity, membership) is drawn from the
configuration's fixed ``structure_seed``; the run's ``--seed`` then
relabels vertices and hyperedges by two random permutations and orders
the incidences by their new ids.  Every seed therefore gives the same
sizes and degree multisets in another order, so every seed does the same
amount of work and runs the same compiled programs, while the ids the
program sees, and so every answer, differ from seed to seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Incidence:
    src: np.ndarray        # [nnz] int32 vertex id per incidence
    dst: np.ndarray        # [nnz] int32 hyperedge id per incidence
    n_vertices: int
    n_hyperedges: int

    @property
    def nnz(self) -> int:
        return int(self.src.shape[0])


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator for any whole-number seed, negative or past 2**63."""
    return np.random.default_rng([seed % 2**64, *salt])


def _powerlaw_ints(rng, n, alpha, xmin, xmax):
    """Discrete power law by inverse transform on the continuous Pareto,
    clipped to [xmin, xmax]."""
    u = rng.random(n)
    x = xmin * (1.0 - u) ** (-1.0 / (alpha - 1.0))
    return np.clip(x.astype(np.int64), xmin, xmax)


def powerlaw_incidence(n_vertices, n_hyperedges, mean_cardinality,
                       cardinality_alpha, popularity_alpha,
                       max_cardinality=None, seed=0) -> Incidence:
    """Power-law cardinalities and Zipf vertex popularity; duplicate
    members of one hyperedge are dropped (first kept)."""
    rng = np.random.default_rng(seed)
    max_card = max_cardinality or max(int(mean_cardinality * 40), 16)
    card = _powerlaw_ints(rng, n_hyperedges, cardinality_alpha, 1, max_card)
    ratio = mean_cardinality / max(card.mean(), 1e-9)
    if ratio > 1.0:
        card = np.minimum((card * ratio).astype(np.int64) + 1, max_card)
    card = np.maximum(card, 1)
    nnz = int(card.sum())

    pop = 1.0 / np.arange(1, n_vertices + 1) ** (1.0 / popularity_alpha)
    pop /= pop.sum()
    perm = rng.permutation(n_vertices)
    members = rng.choice(n_vertices, size=nnz, p=pop)
    members = perm[members].astype(np.int32)

    dst = np.repeat(np.arange(n_hyperedges, dtype=np.int32), card)
    key = dst.astype(np.int64) * np.int64(n_vertices) + members
    _, first_idx = np.unique(key, return_index=True)
    keep = np.zeros(nnz, bool)
    keep[first_idx] = True
    return Incidence(members[keep], dst[keep], n_vertices, n_hyperedges)


def relabel(inc: Incidence, seed: int) -> Incidence:
    """The same structure under seed-drawn vertex and hyperedge ids,
    incidences ordered by (hyperedge, vertex)."""
    rng = seed_rng(seed, 1)
    pv = rng.permutation(inc.n_vertices).astype(np.int32)
    pe = rng.permutation(inc.n_hyperedges).astype(np.int32)
    src, dst = pv[inc.src], pe[inc.dst]
    order = np.argsort(dst.astype(np.int64) * inc.n_vertices + src)
    return Incidence(src[order], dst[order], inc.n_vertices,
                     inc.n_hyperedges)


def make_incidence(config: dict, seed: int) -> Incidence:
    """The configuration's hypergraph for this run's seed."""
    base = powerlaw_incidence(
        n_vertices=config["n_vertices"],
        n_hyperedges=config["n_hyperedges"],
        mean_cardinality=config["mean_cardinality"],
        cardinality_alpha=config["cardinality_alpha"],
        popularity_alpha=config["popularity_alpha"],
        max_cardinality=config.get("max_cardinality"),
        seed=config["structure_seed"],
    )
    return relabel(base, seed)
