"""The heavy-tailed Friendster regime on the fused-delivery path.

A friendster-shaped hypergraph (the generator's ``friendster`` regime:
mean cardinality 14.5, cardinality exponent 1.9, popularity exponent
2.0) at about 8k vertices and 1.6k hyperedges has a 512-wide hub class
and a non-empty residual in both directions, so the degree-class and
sorted-COO paths of ``deliver_ell_leaf`` both run, with messages as
wide as the cost model's width gate admits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import algorithms as alg
from repro.core import Engine
from repro.core.api import Program
from repro.core.engine import deliver
from repro.core.executor import FUSED_MAX_WIDTH_BYTES
from repro.data import powerlaw_hypergraph
from repro.data.generators import DATASET_REGIMES
from repro.kernels.deliver import fused_deliver, layout_pair, layout_span_args
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


def _assert_close_to_reference(value, hg):
    want = pagerank_np(hg, iters=ITERS)
    for got, ref in zip(value, want):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                                   atol=1e-7)


def test_pagerank_matches_the_float64_reference(hg):
    eng = Engine(delivery="pallas_fused")
    res = eng.run(alg.pagerank_spec(hg, iters=ITERS))
    _assert_close_to_reference(res.value, hg)
    fwd, bwd = eng._structures[-1].layouts
    # the heavy tail really ran: a hub class of width >= 128 and a
    # residual in both directions
    assert max(fwd.class_widths) >= 128
    assert fwd.rem_nnz > 0 and bwd.rem_nnz > 0


@pytest.mark.parametrize("monoid", ["sum", "min", "max", "or", "prod"])
def test_ell_delivery_is_bitwise_the_reference_at_the_width_gate(
        hg, monoid):
    """Messages as wide as ``FUSED_MAX_WIDTH_BYTES`` admits (16 float32,
    or 64 bools for ``or``) through both directions' degree classes and
    residuals: the fused delivery equals the reference ``deliver``
    bitwise.  Values are exact under any association order (small
    integers; +-1 for ``prod``)."""
    rng = np.random.default_rng(5)
    prog = Program(procedure=lambda *a: None, combiner=monoid)
    src, dst = np.asarray(hg.src), np.asarray(hg.dst)
    pair = layout_pair(hg.src, hg.dst, hg.e_mask, hg.n_vertices,
                       hg.n_hyperedges)
    directions = ((src, dst, hg.n_vertices, hg.n_hyperedges),
                  (dst, src, hg.n_hyperedges, hg.n_vertices))
    for layout, (s, d, n_src, n_dst) in zip(pair, directions, strict=True):
        assert layout.rem_nnz > 0
        if monoid == "or":
            msg = rng.random((n_src, 64)) > 0.5
        elif monoid == "prod":
            msg = rng.choice([-1.0, 1.0], (n_src, 16))
        else:
            msg = rng.integers(-4, 5, (n_src, 16))
        if monoid != "or":
            msg = msg.astype(np.float32)
        assert msg[0].nbytes == FUSED_MAX_WIDTH_BYTES
        msg = jnp.asarray(msg)
        active = jnp.asarray(rng.random(n_src) > 0.2)
        for act in (None, active):
            want = deliver(msg, act, jnp.asarray(s), jnp.asarray(d),
                           n_dst, prog, e_mask=hg.e_mask)
            got = fused_deliver(msg, act, layout, prog)
            assert got.dtype == want.dtype
            assert np.array_equal(np.asarray(got), np.asarray(want))


def test_engine_run_span_records_the_layout_counts(hg):
    """On a miss and on a hit, a fused job's ``engine.run`` span carries
    the live incidences, both directions' lanes and the cached pair's
    device bytes; a job through the reference delivery carries none."""
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
