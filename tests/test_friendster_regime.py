"""The heavy-tailed Friendster regime on the fused-delivery path.

A friendster-shaped hypergraph (the generator's ``friendster`` regime:
mean cardinality 14.5, cardinality exponent 1.9, popularity exponent
2.0) at about 8k vertices and 1.6k hyperedges has a 512-wide hub class
and a non-empty residual in both directions, so the degree-class and
sorted-COO paths of ``deliver_ell_leaf`` both run.  The layout builder
makes only what the selected lowering reads: the ``ell`` build has no
CSR arrays, and its ELL tables, ``inv_perm`` and residual are bitwise a
Pallas build's.
"""
import jax
import numpy as np
import pytest

from repro import algorithms as alg
from repro.core import Engine
from repro.data import powerlaw_hypergraph
from repro.data.generators import DATASET_REGIMES
from repro.kernels.deliver import layout_pair, layout_span_args
from repro.obs.trace import Tracer
from repro.reference import pagerank_np

ITERS = 10


def friendster_like(n_vertices=8000, n_hyperedges=1600, seed=0):
    r = DATASET_REGIMES["friendster"]
    return powerlaw_hypergraph(
        n_vertices, n_hyperedges, r.mean_cardinality,
        r.cardinality_alpha, r.popularity_alpha, seed=seed,
    )


@pytest.fixture(scope="module")
def hg():
    return friendster_like()


def _layouts(hg, lowering):
    return layout_pair(hg.src, hg.dst, hg.e_mask, hg.n_vertices,
                       hg.n_hyperedges, lowering=lowering)


def _assert_close_to_reference(value, hg):
    want = pagerank_np(hg, iters=ITERS)
    for got, ref in zip(value, want):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                                   atol=1e-7)


def test_pagerank_matches_the_float64_reference(hg, monkeypatch):
    monkeypatch.delenv("REPRO_DELIVERY_LOWERING", raising=False)
    eng = Engine(delivery="pallas_fused")
    res = eng.run(alg.pagerank_spec(hg, iters=ITERS))
    _assert_close_to_reference(res.value, hg)
    fwd, bwd = eng._structures[-1].layouts
    # the heavy tail really ran: a hub class of width >= 128 and a
    # residual in both directions
    assert max(fwd.class_widths) >= 128
    assert fwd.rem_nnz > 0 and bwd.rem_nnz > 0
    # the ell build carries no CSR form
    for lay in (fwd, bwd):
        assert lay.class_src == lay.class_dst == lay.class_bounds == ()
        assert lay.serves("ell") and not lay.serves("pallas")


@pytest.mark.parametrize("pallas", ["pallas", "pallas_interpret"])
def test_ell_build_is_bitwise_the_pallas_build(hg, pallas):
    """What the ``ell`` lowering reads is the same in both builds; only
    the Pallas build adds the per-class CSR arrays and tile bounds."""
    for ell, full in zip(_layouts(hg, "ell"), _layouts(hg, pallas)):
        assert full.serves("pallas") and full.serves("ell")
        assert len(full.class_src) == full.n_classes
        assert ell.class_widths == full.class_widths
        assert ell.class_rows == full.class_rows
        assert ell.rem_nnz == full.rem_nnz
        for a, b in zip(ell.class_ell, full.class_ell, strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for name in ("inv_perm", "rem_src", "rem_dst"):
            a, b = getattr(ell, name), getattr(full, name)
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
        csr_bytes = sum(a.nbytes for a in jax.tree.leaves(
            (full.class_src, full.class_dst, full.class_bounds)))
        assert (sum(a.nbytes for a in jax.tree.leaves(full))
                == sum(a.nbytes for a in jax.tree.leaves(ell)) + csr_bytes)


def test_pallas_interpret_builds_its_own_layout_and_matches(monkeypatch):
    """The structure cache does not serve an ``ell`` build to a Pallas
    run: the layout is rebuilt with the CSR form, and the answers
    agree."""
    small = friendster_like(2000, 400)
    spec = lambda: alg.pagerank_spec(small, iters=ITERS)
    monkeypatch.delenv("REPRO_DELIVERY_LOWERING", raising=False)
    eng = Engine(delivery="pallas_fused")
    ell = eng.run(spec())
    assert not eng._structures[-1].layouts[0].serves("pallas")
    monkeypatch.setenv("REPRO_DELIVERY_LOWERING", "pallas_interpret")
    got = eng.run(spec())
    assert eng.cache_stats()["layout_builds"] == 2
    assert eng._structures[-1].layouts[0].serves("pallas")
    for a, b in zip(ell.value, got.value):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    _assert_close_to_reference(got.value, small)
    # a Pallas build serves ell too: back on ell, nothing is rebuilt
    monkeypatch.delenv("REPRO_DELIVERY_LOWERING")
    eng.run(spec())
    assert eng.cache_stats()["layout_builds"] == 2


def test_engine_run_span_records_the_layout_counts(hg, monkeypatch):
    """On a miss and on a hit, a fused job's ``engine.run`` span carries
    the live incidences, both directions' lanes and the cached pair's
    device bytes; a job through the reference delivery carries none."""
    monkeypatch.delenv("REPRO_DELIVERY_LOWERING", raising=False)
    tr = Tracer()
    eng = Engine(tracer=tr, delivery="pallas_fused")
    for _ in range(2):
        eng.run(alg.pagerank_spec(hg, iters=2))
    eng.run(alg.pagerank_spec(hg, iters=2), delivery="xla")
    runs = [s for s in tr.spans() if s.name == "engine.run"]
    assert [s.args["structure_cache"] for s in runs] == [
        "miss", "hit", "hit"]
    layouts = eng._structures[-1].layouts
    want = {
        "live_nnz": hg.nnz,
        "delivery_lanes": sum(
            int(np.prod(t.shape)) for l in layouts for t in l.class_ell
        ) + sum(l.rem_src.shape[0] for l in layouts),
        "layout_bytes": sum(a.nbytes for a in jax.tree.leaves(layouts)),
    }
    assert layout_span_args(layouts, hg.nnz) == want
    for s in runs[:2]:
        assert {k: s.args[k] for k in want} == want
    assert not set(want) & set(runs[2].args)
    # rows and residual padded to multiples of 8, not to powers of two
    lanes = want["delivery_lanes"] / (2 * hg.nnz)
    assert lanes < _pow2_lanes(hg, layouts) / (2 * hg.nnz)


def _pow2_lanes(hg, layouts):
    """The lanes of ``layouts``' classes with each class's real rows and
    the residual padded to the next power of two (at least 8)."""
    pow2 = lambda n: max(1 << (max(int(n), 1) - 1).bit_length(), 8)
    src, dst = np.asarray(hg.src), np.asarray(hg.dst)
    total = 0
    for ids, n_dst, lay in ((dst, hg.n_hyperedges, layouts[0]),
                            (src, hg.n_vertices, layouts[1])):
        deg = np.bincount(ids, minlength=n_dst)
        widths = np.asarray(lay.class_widths)
        cls = np.minimum(np.searchsorted(widths, deg), len(widths) - 1)
        for c, k in enumerate(widths):
            total += pow2(((cls == c) & (deg > 0)).sum()) * int(k)
        total += pow2(np.maximum(deg - widths[-1], 0).sum())
    return total
