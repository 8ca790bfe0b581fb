"""The numpy references (``repro.reference``) agree with the engine at
small seeded sizes, on both delivery paths — the oracles ``chip_smoke.py``
holds the chip's full-size results to."""
from __future__ import annotations

import numpy as np
import pytest

from repro import algorithms as alg
from repro.core import Engine
from repro.data import make_dataset
from repro.reference import hop_distances_np, pagerank_np, random_walk_np


@pytest.fixture(scope="module")
def hg():
    return make_dataset("dblp", scale=0.003, seed=3)


@pytest.mark.parametrize("delivery", ["xla", "pallas_fused"])
def test_pagerank_matches_numpy(hg, delivery):
    v, he = Engine(delivery=delivery).run(alg.pagerank_spec(hg, iters=30)).value
    v_ref, he_ref = pagerank_np(hg, iters=30)
    np.testing.assert_allclose(np.asarray(v), v_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(he), he_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("max_iters", [3, 64])
def test_batched_hop_distances_equal_numpy(hg, max_iters):
    """Exact, including the ``inf`` a capped run leaves past its reach."""
    sources = np.asarray([0, 5, 17, hg.n_vertices - 1], np.int32)
    comp = Engine().compile(alg.shortest_paths_spec(hg, 0, max_iters))
    v, he = comp.run_batch(sources).value
    for i, s in enumerate(sources):
        dv, de = hop_distances_np(hg, int(s), max_iters)
        np.testing.assert_array_equal(np.asarray(v[i]), dv)
        np.testing.assert_array_equal(np.asarray(he[i]), de)


def test_personalized_walk_matches_numpy(hg):
    comp = Engine().compile(alg.random_walk_spec(hg, iters=12))
    for seed in (0, 9):
        got = np.asarray(comp.run(query=seed).value)
        ref = random_walk_np(hg, seed, iters=12)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-7)
        assert abs(got.sum() - 1.0) < 1e-4
