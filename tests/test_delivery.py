"""Fused incidence delivery: degree-class layout, kernels, engine seam,
serving.

The tentpole contracts, asserted:

* **Kernel parity** (property-tested): the fused lowering — the
  sliced-ELL + sorted-COO XLA form — equals the reference
  gather/mask/segment path across monoids (sum, min, max, or, prod),
  dtypes, dead-row masks, dynamic activity, empty segments and padded
  buckets.  Equality is BITWISE:
  order-insensitive monoids (min/max/or) on arbitrary values, sum/prod
  on integer-valued payloads where every association order is exact.
  (Float sums across different reduce algorithms differ by
  reassociation; the tight-allclose case is covered separately.)
* **Planner** (property-tested): the vectorized
  ``plan_ell_width``/class-planner overflow stats agree with a naive
  per-width rescan loop; class plans are deterministic in the degree
  histogram and structurally sound (ascending pow2 widths, rows
  conserved, residual == spill past the last width).
* **Pathological degree histograms**: single mega-hub, uniform, empty,
  all-overflow (forced width-1 plan), hub-on-shard-boundary — all
  monoids, bitwise vs the reference; shard-harmonized
  class plans in the subprocess distributed suite.
* **Engine seam**: ``delivery='pallas_fused'`` matches ``'xla'``
  end-to-end through ``Engine.run`` and ``Engine.compile``; ``auto``
  resolves via the cost model and reports its reasoning; non-monoid
  specs fall back (auto) or raise (explicit).
* **Distributed**: fused == reference on the replicated AND sharded
  backends, padded (serving) and unpadded (one-shot), in a
  forced-host-device subprocess — including a mega-hub destination
  whose id sits exactly on a shard boundary.
* **Batch-aware halting**: ``run_batch`` stops at the slowest query's
  convergence — fewer supersteps than ``max_iters``, bitwise-equal
  results, on the local backend (``tests/test_compile.py``) AND the
  distributed backends (the serving subprocess there asserts
  ``supersteps_executed`` agrees with the local backend).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.algorithms import (
    label_propagation_spec,
    pagerank_entropy_spec,
    pagerank_spec,
    shortest_paths_spec,
)
from repro.core import Engine
from repro.core.api import Program
from repro.core.engine import deliver
from repro.core.executor import select_delivery
from repro.core.hypergraph import HyperGraph
from repro.data import powerlaw_hypergraph
from repro.obs.trace import Tracer
from repro.sparse.segment import resolve_monoid
from repro.kernels.deliver import (
    ClassPlan,
    build_delivery_layout,
    classify_degrees,
    deliver_ell_leaf,
    fused_deliver,
    layout_pair,
    plan_degree_classes,
    plan_ell_width,
)
from repro.kernels.deliver.layout import (
    CLASS_K_CAP,
    ELL_K_CAP,
    ELL_REMAINDER_FRACTION,
    MAX_CLASSES,
)

settings.register_profile("ci", max_examples=12, deadline=None)
settings.load_profile("ci")

MONOIDS_UNDER_TEST = ("sum", "min", "max", "or", "prod")


@st.composite
def incidence_case(draw):
    """A random incidence list + messages: the deliver() input space."""
    n_src = draw(st.integers(1, 60))
    n_dst = draw(st.integers(1, 50))
    nnz = draw(st.integers(0, 220))
    seed = draw(st.integers(0, 100_000))
    monoid = draw(st.sampled_from(MONOIDS_UNDER_TEST))
    dtype = draw(st.sampled_from(["float32", "int32"]))
    width = draw(st.sampled_from([(), (3,), (2, 2)]))
    with_mask = draw(st.booleans())
    with_active = draw(st.booleans())
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_src, nnz).astype(np.int32)
    dst = rng.integers(0, n_dst, nnz).astype(np.int32)
    mask = (
        (rng.random(nnz) > 0.25).astype(np.float32) if with_mask else None
    )
    if monoid == "or":
        msg = rng.random((n_src,) + width) > 0.5
    elif dtype == "int32":
        msg = rng.integers(-4, 5, (n_src,) + width).astype(np.int32)
    else:
        # Integer-valued float32: every association order is exact, so
        # sum/prod parity is bitwise (the contract under test is the
        # data path — which rows combine where — not fp rounding).
        msg = rng.integers(-4, 5, (n_src,) + width).astype(np.float32)
    active = rng.random(n_src) > 0.3 if with_active else None
    return (src, dst, mask, n_src, n_dst, monoid, msg, active)


@given(incidence_case())
def test_fused_delivery_bitwise_equals_reference(case):
    src, dst, mask, n_src, n_dst, monoid, msg, active = case
    prog = Program(procedure=lambda *a: None, combiner=monoid)
    act_j = jnp.asarray(active) if active is not None else None
    ref = deliver(
        jnp.asarray(msg), act_j, jnp.asarray(src), jnp.asarray(dst),
        n_dst, prog,
        e_mask=jnp.asarray(mask) if mask is not None else None,
    )
    layout = build_delivery_layout(src, dst, mask, n_src, n_dst)
    got = fused_deliver(jnp.asarray(msg), act_j, layout, prog)
    assert np.array_equal(
        np.asarray(ref), np.asarray(got), equal_nan=True
    ), (monoid, msg.dtype)


@given(incidence_case())
def test_fused_delivery_padded_layout_invariance(case):
    """Forcing larger per-class row/remainder pads (the shard
    harmonization path) must not change any result."""
    src, dst, mask, n_src, n_dst, monoid, msg, active = case
    prog = Program(procedure=lambda *a: None, combiner=monoid)
    act_j = jnp.asarray(active) if active is not None else None
    base = build_delivery_layout(src, dst, mask, n_src, n_dst)
    padded = build_delivery_layout(
        src, dst, mask, n_src, n_dst,
        plan=ClassPlan(
            widths=base.class_widths,
            rows=tuple(int(r) for r in base.class_rows),
            residual=base.rem_nnz,
        ),
        class_rows_pad=tuple(r + 24 for r in base.class_rows),
        rem_pad_to=base.rem_len + 19,
    )
    a = fused_deliver(jnp.asarray(msg), act_j, base, prog)
    b = fused_deliver(jnp.asarray(msg), act_j, padded, prog)
    assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)


def test_fused_float_sum_within_reassociation_tolerance():
    """Arbitrary float sums: the fused dense reduce reassociates, so
    parity is tight-allclose, not bitwise."""
    rng = np.random.default_rng(7)
    n_src, n_dst, nnz = 200, 90, 4000
    src = rng.integers(0, n_src, nnz).astype(np.int32)
    dst = rng.integers(0, n_dst, nnz).astype(np.int32)
    msg = rng.standard_normal((n_src, 4)).astype(np.float32)
    prog = Program(procedure=lambda *a: None, combiner="sum")
    ref = deliver(
        jnp.asarray(msg), None, jnp.asarray(src), jnp.asarray(dst),
        n_dst, prog,
    )
    layout = build_delivery_layout(src, dst, None, n_src, n_dst)
    got = fused_deliver(jnp.asarray(msg), None, layout, prog)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(got), rtol=1e-5, atol=1e-5
    )


def test_plan_ell_width_remainder_rule():
    deg = np.array([1, 1, 2, 40])
    k, rem = plan_ell_width(deg, int(deg.sum()))
    # k grows until <= 25% of incidences overflow (cap 64)
    assert rem <= 0.25 * deg.sum()
    assert k & (k - 1) == 0  # power of two
    k_uniform, rem_uniform = plan_ell_width(np.full(16, 4), 64)
    assert (k_uniform, rem_uniform) == (4, 0)


# --------------------------------------------------------------------------
# planners: vectorized histogram stats vs the naive loop; class plans
# --------------------------------------------------------------------------

def _loop_plan_ell_width(degrees, nnz):
    """The pre-vectorization reference: rescan the degree array at every
    doubling of k."""
    if nnz <= 0 or degrees.size == 0:
        return 1, 0
    k = 1
    while True:
        remainder = int(np.maximum(degrees - k, 0).sum())
        if remainder <= ELL_REMAINDER_FRACTION * nnz or k >= ELL_K_CAP:
            return k, remainder
        k *= 2


@st.composite
def degree_case(draw):
    n = draw(st.integers(0, 200))
    seed = draw(st.integers(0, 100_000))
    profile = draw(st.sampled_from(["uniform", "zipfish", "hub", "zero"]))
    rng = np.random.default_rng(seed)
    if profile == "uniform":
        deg = rng.integers(0, 9, n)
    elif profile == "zipfish":
        deg = (rng.pareto(1.2, n) * 3).astype(np.int64)
    elif profile == "hub":
        deg = rng.integers(0, 4, n)
        if n:
            deg[rng.integers(0, n)] = draw(st.integers(100, 200_000))
    else:
        deg = np.zeros(n, np.int64)
    return deg.astype(np.int64)


@given(degree_case())
def test_vectorized_plan_ell_width_agrees_with_loop(deg):
    nnz = int(deg.sum())
    assert plan_ell_width(deg, nnz) == _loop_plan_ell_width(deg, nnz)


@given(degree_case())
def test_class_plan_structurally_sound(deg):
    nnz = int(deg.sum())
    plan = plan_degree_classes(deg, nnz)
    widths = plan.widths
    # 1..MAX_CLASSES ascending power-of-two widths, capped
    assert 1 <= len(widths) <= MAX_CLASSES
    assert all(k & (k - 1) == 0 for k in widths)
    assert list(widths) == sorted(set(widths))
    assert widths[-1] <= CLASS_K_CAP
    # rows conserved: every positive-degree destination sits in exactly
    # one class; residual is exactly the spill past the last width
    cls = classify_degrees(deg, widths)
    assert sum(plan.rows) == int((deg > 0).sum())
    for c, r in enumerate(plan.rows):
        assert int((cls == c).sum()) == r
    spill = int(np.maximum(deg - widths[-1], 0).sum())
    assert plan.residual == spill
    # the plan's weighted objective never exceeds the single-ELL plan's
    # (the DP considers the single class as a candidate)
    k1, rem1 = plan_ell_width(deg, nnz)
    if nnz and widths[-1] >= k1:
        from repro.kernels.deliver.layout import RESIDUAL_WEIGHT
        single = int((deg > 0).sum()) * k1 + RESIDUAL_WEIGHT * rem1
        assert plan.weighted_work <= single + 1e-9
    # deterministic in the histogram
    assert plan == plan_degree_classes(deg.copy(), nnz)


# --------------------------------------------------------------------------
# pathological degree histograms, all monoids
# --------------------------------------------------------------------------

def _assert_fused_matches_reference(src, dst, mask, n_src, n_dst,
                                    layout=None, **build_kw):
    rng = np.random.default_rng(7)
    if layout is None:
        layout = build_delivery_layout(
            src, dst, mask, n_src, n_dst, **build_kw
        )
    for monoid in MONOIDS_UNDER_TEST:
        if monoid == "or":
            msg = rng.random((n_src, 2)) > 0.5
        else:
            msg = rng.integers(-4, 5, (n_src, 2)).astype(np.float32)
        prog = Program(procedure=lambda *a: None, combiner=monoid)
        active = rng.random(n_src) > 0.3
        ref = deliver(
            jnp.asarray(msg), jnp.asarray(active), jnp.asarray(src),
            jnp.asarray(dst), n_dst, prog,
            e_mask=jnp.asarray(mask) if mask is not None else None,
        )
        got = fused_deliver(
            jnp.asarray(msg), jnp.asarray(active), layout, prog
        )
        assert np.array_equal(
            np.asarray(ref), np.asarray(got), equal_nan=True
        ), monoid
    return layout


def test_pathological_single_mega_hub():
    """One destination absorbs ~95% of the incidences: the hub must land
    in its own wide class (dense), not the residual scatter."""
    rng = np.random.default_rng(0)
    n_src, n_dst, nnz = 64, 50, 3000
    dst = np.where(
        rng.random(nnz) < 0.95, 7, rng.integers(0, n_dst, nnz)
    ).astype(np.int32)
    src = rng.integers(0, n_src, nnz).astype(np.int32)
    layout = _assert_fused_matches_reference(src, dst, None, n_src, n_dst)
    hub_deg = int((dst == 7).sum())
    assert layout.class_widths[-1] >= hub_deg  # hub fully dense
    assert layout.rem_nnz == 0
    assert len(layout.class_widths) >= 2  # tail kept narrow


def test_pathological_uniform_degrees_collapse_to_one_class():
    rng = np.random.default_rng(1)
    n, nnz = 100, 800
    dst = np.repeat(np.arange(n), 8).astype(np.int32)  # exactly deg 8
    src = rng.integers(0, n, nnz).astype(np.int32)
    layout = _assert_fused_matches_reference(src, dst, None, n, n)
    assert layout.class_widths == (8,)
    assert layout.rem_nnz == 0


def test_pathological_empty_structures():
    # no incidences at all
    _assert_fused_matches_reference(
        np.zeros(0, np.int32), np.zeros(0, np.int32), None, 5, 4
    )
    # incidences exist but every one statically dead
    rng = np.random.default_rng(2)
    src = rng.integers(0, 6, 20).astype(np.int32)
    dst = rng.integers(0, 5, 20).astype(np.int32)
    layout = _assert_fused_matches_reference(
        src, dst, np.zeros(20, np.float32), 6, 5
    )
    assert layout.ell_slots >= 0 and layout.rem_nnz == 0
    # zero destinations
    lay = build_delivery_layout(
        np.zeros(0, np.int32), np.zeros(0, np.int32), None, 3, 0
    )
    prog = Program(procedure=lambda *a: None, combiner="sum")
    out = fused_deliver(jnp.ones((3, 2), jnp.float32), None, lay, prog)
    assert out.shape == (0, 2)


def test_pathological_all_overflow_forced_plan():
    """A forced width-1 plan pushes nearly every incidence through the
    residual sorted-COO path — the lowering's worst case — and it stays
    bitwise."""
    rng = np.random.default_rng(3)
    n_src, n_dst, nnz = 40, 30, 900
    src = rng.integers(0, n_src, nnz).astype(np.int32)
    dst = rng.integers(0, n_dst, nnz).astype(np.int32)
    layout = build_delivery_layout(
        src, dst, None, n_src, n_dst,
        plan=ClassPlan(widths=(1,), rows=(n_dst,), residual=nnz - n_dst),
    )
    assert layout.rem_nnz > 0.9 * nnz
    _assert_fused_matches_reference(
        src, dst, None, n_src, n_dst, layout=layout
    )


def test_pathological_zero_degree_destinations_read_identity():
    """Bucket padding: destinations with no live incidence own no ELL
    rows at all and read the identity through ``inv_perm``."""
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([2, 2, 2, 2], np.int32)  # only dst 2 is live
    n_src, n_dst = 4, 9
    layout = build_delivery_layout(src, dst, None, n_src, n_dst)
    # every empty destination shares the single identity slot
    inv = np.asarray(layout.inv_perm)
    assert (inv[np.arange(n_dst) != 2] == layout.n_slots).all()
    prog = Program(procedure=lambda *a: None, combiner="min")
    msg = jnp.arange(4, dtype=jnp.float32)
    out = np.asarray(fused_deliver(msg, None, layout, prog))
    assert out[2] == 0.0
    assert np.isposinf(out[np.arange(n_dst) != 2]).all()


def test_shard_harmonized_class_plans_stack():
    """``build_shard_delivery``: one merged-histogram plan, per-class
    pads harmonized to maxima — layouts stack, and a hub destination on
    the shard boundary stays dense on every shard that sees it."""
    from repro.core.distributed import build_shard_delivery

    rng = np.random.default_rng(4)
    n_parts, shard_len = 4, 256
    nv = ne = 64
    hub = 16  # == ne_pad/n_parts: first id of shard 1's range
    dst = np.where(
        rng.random((n_parts, shard_len)) < 0.7, hub,
        rng.integers(0, ne, (n_parts, shard_len)),
    ).astype(np.int32)
    src = rng.integers(0, nv, (n_parts, shard_len)).astype(np.int32)
    mask = (rng.random((n_parts, shard_len)) > 0.1).astype(np.float32)
    fwd, bwd = build_shard_delivery(src, dst, mask, nv, ne)
    for lay in (fwd, bwd):
        # stacked: every child gained one [n_parts] leading dim, with
        # identical per-class shapes across shards
        assert lay.inv_perm.shape[0] == n_parts
        for c in range(lay.n_classes):
            assert lay.class_ell[c].shape[0] == n_parts
            assert lay.class_ell[c].shape[1] == lay.class_widths[c]
    # the hub's shard-local degree fits its class width on every shard
    live = np.asarray(mask) != 0
    for p in range(n_parts):
        hub_deg = int(((dst[p] == hub) & live[p]).sum())
        assert fwd.class_widths[-1] >= hub_deg
    assert fwd.rem_nnz == 0


# --------------------------------------------------------------------------
# the Engine seam
# --------------------------------------------------------------------------

def medium_hypergraph():
    # Large enough to clear the cost model's FUSED_MIN_NNZ floor.
    return powerlaw_hypergraph(1400, 1000, mean_cardinality=7, seed=3)


@pytest.mark.parametrize("make_spec,bitwise", [
    (lambda hg: shortest_paths_spec(hg, 0, 12), True),
    (lambda hg: label_propagation_spec(hg, iters=6), True),
    (lambda hg: pagerank_spec(hg, iters=6), False),
])
def test_engine_run_fused_matches_xla(make_spec, bitwise):
    hg = medium_hypergraph()
    eng = Engine()
    spec = make_spec(hg)
    ref = eng.run(spec, delivery="xla").value
    got = eng.run(spec, delivery="pallas_fused").value
    for a, b in zip(ref, got):
        a, b = np.asarray(a), np.asarray(b)
        if bitwise:
            assert np.array_equal(a, b, equal_nan=True)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_compiled_fused_matches_xla_and_masks_padding():
    hg = medium_hypergraph()
    eng = Engine(collect_stats=True)
    spec = shortest_paths_spec(hg, 0, 12)
    ref = eng.compile(spec, delivery="xla").run()
    got = eng.compile(spec, delivery="pallas_fused").run()
    for a, b in zip(ref.value, got.value):
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)
    # bucket padding must stay invisible in stats on the fused path too
    for r, g in zip(ref.superstep_stats, got.superstep_stats):
        assert np.array_equal(np.asarray(r), np.asarray(g))


def test_delivery_auto_resolves_and_reports():
    """The cost model picks fused for a large narrow-message hypergraph
    and reports the numbers it decided on."""
    hg = medium_hypergraph()
    eng = Engine()
    cfg, _, decision = eng.resolve(shortest_paths_spec(hg, 0, 8))
    why = decision["delivery"]
    assert cfg.delivery == "pallas_fused", why
    assert why["lowering"] == "ell"
    assert "reason" in why and "message_width_bytes" in why

    # tiny structures stay on the reference path (overhead-dominated)
    tiny = powerlaw_hypergraph(30, 20, mean_cardinality=3, seed=0)
    cfg2, _, dec2 = eng.resolve(shortest_paths_spec(tiny, 0, 8))
    assert cfg2.delivery == "xla"


@pytest.mark.parametrize("width,dtype,choice", [
    (16, jnp.float32, "pallas_fused"),   # 64 B: at the gate
    (17, jnp.float32, "xla"),            # 68 B
    (32, jnp.bfloat16, "pallas_fused"),  # 64 B
    (33, jnp.bfloat16, "xla"),           # 66 B
    (64, jnp.float32, "xla"),            # 256 B
])
def test_delivery_auto_rejects_wide_messages_on_ell(width, dtype, choice):
    """Message rows wider than ``FUSED_MAX_WIDTH_BYTES`` (64 B, in
    bytes, whatever the dtype) flip the ELL cost model back to the
    reference path (the dense reduce's padding outweighs the scatter
    win); rows at the gate stay fused."""
    hg = medium_hypergraph()
    spec = pagerank_spec(hg, iters=4)
    msg = spec._replace(initial_msg=jnp.zeros((width,), dtype))
    got, why = select_delivery(msg, hg)
    assert got == choice, why
    assert why["message_width_bytes"] == width * jnp.dtype(dtype).itemsize
    assert ("wide" in why["reason"]) == (choice == "xla")


def test_non_monoid_spec_falls_back_and_explicit_raises():
    hg = powerlaw_hypergraph(60, 40, mean_cardinality=4, seed=1)
    spec = pagerank_spec(hg, iters=4)
    # graft a custom (Seq) reducer onto the vertex program: the fused
    # path must refuse it — reducers consume materialized rows.
    seq_reducer = lambda rows, dst, n, live: jax.tree.map(
        lambda r: jax.ops.segment_sum(r, dst, n), rows
    )
    import dataclasses as dc

    spec = spec._replace(
        v_program=dc.replace(spec.v_program, reducer=seq_reducer)
    )
    eng = Engine()
    cfg, _, decision = eng.resolve(spec)
    assert cfg.delivery == "xla"
    assert "non-monoid" in decision["delivery"]["reason"]
    with pytest.raises(ValueError, match="monoid"):
        eng.resolve(spec, delivery="pallas_fused")


def _numpy_incidence(hg):
    return HyperGraph(np.asarray(hg.src), np.asarray(hg.dst),
                      hg.n_vertices, hg.n_hyperedges)


def _permuted_dst(hg):
    perm = np.random.default_rng(5).permutation(hg.nnz)
    return HyperGraph.from_coo(np.asarray(hg.src), np.asarray(hg.dst)[perm],
                               hg.n_vertices, hg.n_hyperedges)


# (first, second) hypergraphs: whether their specs share one structure
# cache entry (and so one fused layout).
STRUCTURE_PAIRS = {
    # every spec wraps hg anew (init -> with_attrs): same device arrays
    "same_hg": (lambda hg: (hg, hg), True),
    "with_attrs": (lambda hg: (hg, hg.with_attrs(
        v_attr=jnp.zeros((hg.n_vertices,)))), True),
    # same nnz, n_vertices and n_hyperedges, another incidence
    "permuted_dst": (lambda hg: (hg, _permuted_dst(hg)), False),
    "new_mask": (lambda hg: (hg, dataclasses.replace(
        hg, e_mask=jnp.ones((hg.nnz,), jnp.float32))), False),
    "more_vertices": (lambda hg: (hg, dataclasses.replace(
        hg, n_vertices=hg.n_vertices + 8)), False),
    # numpy arrays can be mutated in place: wrapper identity only
    "numpy_arrays": (lambda hg: (_numpy_incidence(hg),) * 2, False),
}


@pytest.mark.parametrize("pair", sorted(STRUCTURE_PAIRS))
def test_delivery_layouts_cached_per_structure(pair):
    make, shared = STRUCTURE_PAIRS[pair]
    first, second = make(medium_hypergraph())
    eng = Engine(delivery="pallas_fused")
    eng.run(pagerank_spec(first, iters=4))
    got = eng.run(pagerank_spec(second, iters=4)).value
    stats = eng.cache_stats()
    assert stats["layout_builds"] == (1 if shared else 2), stats
    assert (stats["structure_hits"], stats["structure_misses"]) == (
        (1, 1) if shared else (0, 2)), stats
    want = Engine(delivery="pallas_fused").run(
        pagerank_spec(second, iters=4)).value
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_structure_cache_holds_four_entries():
    graphs = [powerlaw_hypergraph(40, 30, mean_cardinality=3, seed=s)
              for s in range(5)]
    eng = Engine(delivery="pallas_fused")
    for hg in graphs:
        eng.run(shortest_paths_spec(hg, 0, 3))
    assert len(eng._structures) == 4
    eng.run(shortest_paths_spec(graphs[-1], 0, 3))   # newest: still held
    eng.run(shortest_paths_spec(graphs[0], 0, 3))    # oldest: evicted
    stats = eng.cache_stats()
    assert stats["layout_builds"] == 6, stats
    assert (stats["structure_hits"], stats["structure_misses"]) == (1, 6)
    assert len(eng._structures) == 4


def test_engine_run_span_reports_structure_cache():
    tracer = Tracer()
    eng = Engine(delivery="pallas_fused", tracer=tracer)
    hg = medium_hypergraph()
    for _ in range(2):
        eng.run(shortest_paths_spec(hg, 0, 4))
    runs = [s for s in tracer.spans() if s.name == "engine.run"]
    assert [s.args["structure_cache"] for s in runs] == ["miss", "hit"]
    builds = [s for s in tracer.spans() if s.name == "engine.layout_build"]
    assert len(builds) == 1 and builds[0].parent == runs[0].id


def _masked(hg):
    live = np.random.default_rng(2).random(hg.nnz) > 0.3
    return dataclasses.replace(hg, e_mask=jnp.asarray(live, jnp.float32))


@pytest.mark.parametrize("make_hg,make_spec", [
    (medium_hypergraph, lambda hg: pagerank_spec(hg, iters=4)),
    (medium_hypergraph, lambda hg: shortest_paths_spec(hg, 0, 8)),
    (lambda: _masked(medium_hypergraph()),
     lambda hg: label_propagation_spec(hg, iters=4)),
    (lambda: powerlaw_hypergraph(30, 20, mean_cardinality=3, seed=0),
     lambda hg: pagerank_spec(hg, iters=4)),
])
def test_cached_delivery_decision_equals_uncached(make_hg, make_spec):
    """On a structure-cache hit, ``resolve`` and ``explain`` report the
    delivery decision an uncached ``select_delivery`` computes."""
    hg = make_hg()
    eng = Engine()
    eng.resolve(make_spec(hg))
    spec = make_spec(hg)
    cfg, _, decision = eng.resolve(spec)
    assert eng.cache_stats()["structure_hits"] == 1
    choice, why = select_delivery(spec, spec.hg0)
    assert cfg.delivery == choice
    assert decision["delivery"] == why
    axis = eng.explain(spec)["axes"]["delivery"]
    assert axis == Engine().explain(spec)["axes"]["delivery"]
    assert axis["winner"] == choice and axis["reason"] == why["reason"]
    fused = axis["candidates"]["pallas_fused"]
    for k in ("class_work_slots", "skew_gain", "residual", "class_plans"):
        assert fused.get(k) == why.get(k), k
    # the decision owns its plans: editing one leaves the cache intact
    if "class_plans" in decision["delivery"]:
        decision["delivery"]["class_plans"]["fwd"]["residual"] = -1
        assert eng.resolve(spec)[2]["delivery"] == why


def test_layout_pair_directions():
    hg = powerlaw_hypergraph(50, 30, mean_cardinality=4, seed=2)
    fwd, bwd = layout_pair(
        hg.src, hg.dst, hg.e_mask, hg.n_vertices, hg.n_hyperedges
    )
    assert (fwd.n_src, fwd.n_dst) == (hg.n_vertices, hg.n_hyperedges)
    assert (bwd.n_src, bwd.n_dst) == (hg.n_hyperedges, hg.n_vertices)
    assert fwd.nnz == bwd.nnz == hg.nnz


# --------------------------------------------------------------------------
# row and residual padding: multiples of 8 by default
# --------------------------------------------------------------------------

def _pow2(n, floor=8):
    return max(1 << (max(int(n), 1) - 1).bit_length(), floor)


def _up8(n):
    return max(-(-int(n) // 8) * 8, 8)


def _friendster_like():
    from repro.data.generators import DATASET_REGIMES

    r = DATASET_REGIMES["friendster"]
    return powerlaw_hypergraph(8000, 1600, r.mean_cardinality,
                               r.cardinality_alpha, r.popularity_alpha,
                               seed=0)


PADDING_GRAPHS = {
    "skewed": medium_hypergraph,
    "residual_both_ways": _friendster_like,
}


def _directions(hg):
    """(senders, receivers, n_src, n_dst) for both directions."""
    src, dst = np.asarray(hg.src), np.asarray(hg.dst)
    return ((src, dst, hg.n_vertices, hg.n_hyperedges),
            (dst, src, hg.n_hyperedges, hg.n_vertices))


def _real_rows(d, n_dst, widths):
    cls = classify_degrees(np.bincount(d, minlength=n_dst), widths)
    return tuple(int((cls == c).sum()) for c in range(len(widths)))


@pytest.mark.parametrize("graph", sorted(PADDING_GRAPHS))
def test_default_build_pads_rows_and_residual_to_multiples_of_8(graph):
    hg = PADDING_GRAPHS[graph]()
    rems = []
    for s, d, n_src, n_dst in _directions(hg):
        plan = plan_degree_classes(np.bincount(d, minlength=n_dst), hg.nnz)
        lay = build_delivery_layout(s, d, None, n_src, n_dst)
        real = _real_rows(d, n_dst, lay.class_widths)
        assert lay.class_rows == tuple(_up8(r) for r in real)
        assert lay.rem_len == _up8(lay.rem_nnz)
        assert plan.built_rows == lay.class_rows
        assert plan.built_work == lay.ell_slots + lay.rem_len
        rems.append(lay.rem_nnz)
    if graph == "residual_both_ways":
        assert min(rems) > 0


def _pow2_build(s, d, n_src, n_dst, tight):
    """``tight``'s incidence rebuilt with power-of-two rows and
    residual, through the builder's forcing arguments."""
    plan = ClassPlan(widths=tight.class_widths,
                     rows=_real_rows(d, n_dst, tight.class_widths),
                     residual=tight.rem_nnz)
    return build_delivery_layout(
        s, d, None, n_src, n_dst, plan=plan,
        class_rows_pad=tuple(_pow2(r) for r in plan.rows),
        rem_pad_to=_pow2(plan.residual),
    )


@pytest.mark.parametrize("graph", sorted(PADDING_GRAPHS))
@pytest.mark.parametrize("combiner", ["sum", "min", "max", "two_leaves"])
def test_tight_build_delivers_bitwise_the_pow2_build(graph, combiner):
    """Padding lanes gather the identity row and reduction order is
    unchanged, so a multiple-of-8 build delivers bitwise what a
    power-of-two build of the same incidence delivers."""
    hg = PADDING_GRAPHS[graph]()
    rng = np.random.default_rng(11)
    for s, d, n_src, n_dst in _directions(hg):
        tight = build_delivery_layout(s, d, None, n_src, n_dst)
        wide = _pow2_build(s, d, n_src, n_dst, tight)
        assert wide.ell_slots + wide.rem_len > tight.ell_slots + tight.rem_len
        if combiner == "two_leaves":
            prog = Program(procedure=lambda *a: None, combiner="sum")
            msg = (rng.standard_normal(n_src).astype(np.float32),
                   rng.standard_normal((n_src, 2)).astype(np.float32))
        else:
            prog = Program(procedure=lambda *a: None, combiner=combiner)
            msg = rng.standard_normal(n_src).astype(np.float32)
        msg = jax.tree.map(jnp.asarray, msg)
        active = jnp.asarray(rng.random(n_src) > 0.2)
        for act in (None, active):
            a = fused_deliver(msg, act, tight, prog)
            b = fused_deliver(msg, act, wide, prog)
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                            strict=True):
                assert np.array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# leaf groups: a message's same-monoid leaves go through the layout once
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _ByLeafType(Program):
    """A sender whose combiner follows each leaf's dtype and trailing
    shape (``monoids``: ``((dtype, trailing shape), monoid name)``)."""

    monoids: tuple = ()

    def monoid_for(self, msg_leaf):
        key = (str(msg_leaf.dtype), tuple(msg_leaf.shape[1:]))
        return resolve_monoid(dict(self.monoids)[key])


# (monoid, dtype, trailing shape) of each leaf of a message
LEAF_CASES = {
    "two_sum_f32": (("sum", "float32", ()),) * 2,
    "three_sum_f32": (("sum", "float32", ()),) * 3,
    "mixed_monoids_and_dtypes": (
        ("sum", "float32", ()), ("min", "int32", ()), ("or", "bool", ()),
        ("sum", "float32", ()), ("min", "int32", ()),
    ),
    "trailing_dims": (
        ("sum", "float32", (3,)), ("max", "float32", (2, 2)),
        ("sum", "float32", (3,)), ("max", "float32", (2, 2)),
        ("max", "float32", (2, 2)),
    ),
}


def _leaf_case(name, n_src, rng, batch=()):
    """A ``_ByLeafType`` sender and a message of integer-valued leaves
    (so every sum's association order is exact)."""
    spec = LEAF_CASES[name]
    prog = _ByLeafType(
        procedure=lambda *a: None,
        monoids=tuple({(d, t): m for m, d, t in spec}.items()),
    )
    msg = []
    for _, dtype, trail in spec:
        shape = batch + (n_src,) + trail
        if dtype == "bool":
            msg.append(jnp.asarray(rng.random(shape) > 0.5))
        else:
            msg.append(jnp.asarray(rng.integers(-4, 5, shape).astype(dtype)))
    n_groups = len({(m, d, t) for m, d, t in spec})
    return prog, tuple(msg), n_groups


def _residual_graph():
    hg = _friendster_like()
    return np.asarray(hg.src), np.asarray(hg.dst), hg.n_vertices, \
        hg.n_hyperedges


def _uniform_graph():
    rng = np.random.default_rng(4)
    n_src, n_dst = 70, 50
    dst = np.repeat(np.arange(n_dst), 4).astype(np.int32)
    return rng.integers(0, n_src, dst.size).astype(np.int32), dst, \
        n_src, n_dst


def _grouping_layout(kind):
    """``(src, dst, n_src, n_dst, layout)``: ``residual`` has hub
    incidences past its last class width, ``no_residual`` has none,
    ``padded`` is the residual graph with extra rows and lanes."""
    src, dst, n_src, n_dst = (
        _uniform_graph() if kind == "no_residual" else _residual_graph()
    )
    layout = build_delivery_layout(src, dst, None, n_src, n_dst)
    if kind == "padded":
        layout = build_delivery_layout(
            src, dst, None, n_src, n_dst,
            plan=ClassPlan(widths=layout.class_widths,
                           rows=_real_rows(dst, n_dst, layout.class_widths),
                           residual=layout.rem_nnz),
            class_rows_pad=tuple(r + 24 for r in layout.class_rows),
            rem_pad_to=layout.rem_len + 19,
        )
    assert (layout.rem_nnz > 0) == (kind != "no_residual")
    return src, dst, n_src, n_dst, layout


def _gathers(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "gather"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _gathers(sub)
    return n


@pytest.mark.parametrize("layout_kind", ["residual", "no_residual",
                                         "padded"])
@pytest.mark.parametrize("with_active", [False, True])
@pytest.mark.parametrize("case", sorted(LEAF_CASES))
def test_grouped_delivery_equals_reference_leaf_by_leaf(
    case, with_active, layout_kind
):
    """Each leaf group goes through the layout once (one gather a class
    table, one ``inv_perm`` assembly, one residual gather, one mask
    gather a table when ``active`` is given), and every leaf is bitwise
    what the reference delivers for it."""
    src, dst, n_src, n_dst, layout = _grouping_layout(layout_kind)
    rng = np.random.default_rng(17)
    prog, msg, n_groups = _leaf_case(case, n_src, rng)
    active = jnp.asarray(rng.random(n_src) > 0.3) if with_active else None
    ref = deliver(msg, active, jnp.asarray(src), jnp.asarray(dst), n_dst,
                  prog)
    got = fused_deliver(msg, active, layout, prog)
    assert jax.tree.structure(got) == jax.tree.structure(msg)
    for r, g, m in zip(ref, got, msg, strict=True):
        assert g.shape == (n_dst,) + m.shape[1:] and g.dtype == m.dtype
        assert np.array_equal(np.asarray(r), np.asarray(g)), case
    per_table = 2 if with_active else 1
    per_group = (per_table * len(layout.class_ell) + 1
                 + (per_table if layout.rem_nnz else 0))
    jaxpr = jax.make_jaxpr(
        lambda m, a, lay: fused_deliver(m, a, lay, prog)
    )(msg, active, layout).jaxpr
    assert _gathers(jaxpr) == n_groups * per_group


@pytest.mark.parametrize("case", sorted(LEAF_CASES))
def test_grouped_delivery_under_vmap(case):
    """Stacking on the minor axis holds under ``vmap`` (``run_batch``):
    each batch member equals its own reference delivery."""
    src, dst, n_src, n_dst, layout = _grouping_layout("residual")
    rng = np.random.default_rng(23)
    prog, msg, _ = _leaf_case(case, n_src, rng, batch=(3,))
    active = jnp.asarray(rng.random((3, n_src)) > 0.3)
    got = jax.vmap(lambda m, a: fused_deliver(m, a, layout, prog))(
        msg, active)
    for b in range(3):
        ref = deliver(tuple(m[b] for m in msg), active[b],
                      jnp.asarray(src), jnp.asarray(dst), n_dst, prog)
        for r, g in zip(ref, got, strict=True):
            assert np.array_equal(np.asarray(r), np.asarray(g[b])), (case, b)


@pytest.mark.parametrize("with_active", [False, True])
@pytest.mark.parametrize("monoid", ["sum", "min", "or"])
def test_single_leaf_delivery_traces_as_deliver_ell_leaf(monoid,
                                                         with_active):
    """A group of one takes ``deliver_ell_leaf`` as it is: no stack,
    split or reshape, so single-leaf programs keep their program."""
    *_, n_src, _, layout = _grouping_layout("residual")
    prog = Program(procedure=lambda *a: None, combiner=monoid)
    dtype = jnp.bool_ if monoid == "or" else jnp.float32
    msg = jnp.zeros((n_src,), dtype)
    active = jnp.ones((n_src,), bool) if with_active else None
    fused = jax.make_jaxpr(
        lambda m, a, lay: fused_deliver(m, a, lay, prog))(msg, active,
                                                          layout)
    direct = jax.make_jaxpr(
        lambda m, a, lay: deliver_ell_leaf(m, lay, prog.monoid_for(m), a)
    )(msg, active, layout)
    assert str(fused) == str(direct)


def _mixed_he_message_spec(hg):
    """PageRank whose he->v message also carries a bool flag: two leaf
    groups he->v (the float32 pair, the flag), one v->he."""
    spec = pagerank_spec(hg, iters=3)
    he_proc = spec.he_program.procedure
    v_proc = spec.v_program.procedure

    def hyperedge(step, ids, attr, msg, cards):
        out = he_proc(step, ids, attr, msg, cards)
        return out._replace(msg=out.msg + (cards > 2,))

    def vertex(step, ids, attr, msg, deg):
        return v_proc(step, ids, attr, msg[:2], deg)

    return spec._replace(
        initial_msg=spec.initial_msg + (jnp.bool_(False),),
        v_program=Program(procedure=vertex, combiner="sum"),
        he_program=Program(procedure=hyperedge),
    )


# spec, leaf groups (v->he, he->v) its messages go through the layout in
GATHERED_SPECS = {
    "pagerank": (lambda hg: pagerank_spec(hg, iters=3), (1, 1)),
    "pagerank_entropy": (lambda hg: pagerank_entropy_spec(hg, iters=3),
                         (1, 1)),
    "sssp": (lambda hg: shortest_paths_spec(hg, 0, 6), (1, 1)),
    "mixed_he_message": (_mixed_he_message_spec, (1, 2)),
}


@pytest.mark.parametrize("name", sorted(GATHERED_SPECS))
def test_engine_run_span_counts_the_gathered_lanes(name):
    """``delivery_gathered_lanes`` on the ``engine.run`` span: each
    direction's ELL slots plus residual lanes times the leaf groups its
    message went through the layout in, on jobs that traced the program
    (eager, then jitted) and on a job that reuses the jitted one."""
    hg = medium_hypergraph()
    make, groups = GATHERED_SPECS[name]
    spec = make(hg)
    tracer = Tracer()
    eng = Engine(delivery="pallas_fused", tracer=tracer)
    for jit in (False, True, True):
        jax.block_until_ready(eng.run(spec, jit=jit).value)
    runs = [s for s in tracer.spans() if s.name == "engine.run"]
    layouts = eng._structures[-1].layouts
    want = sum((l.ell_slots + l.rem_len) * g
               for l, g in zip(layouts, groups, strict=True))
    assert [s.args["delivery_gathered_lanes"] for s in runs] == [want] * 3
    # the last job ran the second one's executable without a trace
    last = [s for s in tracer.spans() if s.t0 >= runs[2].t0]
    assert not any(s.name == "jax.trace" for s in last)


# --------------------------------------------------------------------------
# distributed: fused == reference on both backends (subprocess)
# --------------------------------------------------------------------------

DISTRIBUTED_FUSED = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import Engine
    from repro.core.hypergraph import HyperGraph
    from repro.data import powerlaw_hypergraph
    from repro.algorithms import shortest_paths_spec, pagerank_spec

    mesh = Mesh(np.array(jax.devices()).reshape(4), ('data',))
    hg = powerlaw_hypergraph(90, 70, mean_cardinality=5, seed=0)

    # Mega-hub hyperedge whose id sits exactly on a shard boundary
    # (ne_pad=72, he_block=18 -> id 18 opens shard 1's range), plus a
    # mega-hub vertex on a boundary: the shard-harmonized class plans
    # must keep both dense on every shard that sees a piece of them.
    rng = np.random.default_rng(1)
    nv, ne, nnz = 90, 70, 2600
    dst = np.where(rng.random(nnz) < 0.6, 18,
                   rng.integers(0, ne, nnz)).astype(np.int32)
    src = np.where(rng.random(nnz) < 0.4, 23,
                   rng.integers(0, nv, nnz)).astype(np.int32)
    hub = HyperGraph.from_coo(src, dst, nv, ne)

    local = Engine()
    for backend in ('replicated', 'sharded'):
        eng = Engine(mesh=mesh, backend=backend)
        for graph, tag in ((hg, 'powerlaw'), (hub, 'boundary-hub')):
            # min monoid: one-shot (unpadded) run, bitwise vs local xla
            ref = local.run(shortest_paths_spec(graph, 1, 12),
                            delivery='xla')
            got = eng.run(shortest_paths_spec(graph, 1, 12),
                          delivery='pallas_fused')
            for a, b in zip(ref.value, got.value):
                assert np.array_equal(np.asarray(a), np.asarray(b),
                                      equal_nan=True), (backend, tag)
            # sum monoid: reassociation tolerance
            refp = local.run(pagerank_spec(graph, iters=6),
                             delivery='xla')
            gotp = eng.run(pagerank_spec(graph, iters=6),
                           delivery='pallas_fused')
            for a, b in zip(refp.value, gotp.value):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
        # the harmonized shard plans really did keep the boundary hub
        # dense (no residual scatter lanes anywhere)
        from repro.core.distributed import build_shard_delivery, _pad_to
        plan, _ = eng._cached_plan(hub, 4, 'auto')
        fwd, bwd = build_shard_delivery(
            plan.shard_src, plan.shard_dst, plan.shard_mask,
            _pad_to(nv, 4), _pad_to(ne, 4))
        hub_deg = int((np.asarray(plan.shard_dst) == 18)[
            np.asarray(plan.shard_mask) != 0].sum())
        assert fwd.class_widths[-1] >= hub_deg // 4, fwd.class_widths
        assert fwd.rem_nnz == 0, 'boundary hub spilled to the residual'
        # compiled (bucket-PADDED) fused serving, batched: bitwise vs
        # sequential local, and executed on the distributed executable
        compiled = eng.compile(shortest_paths_spec(hg, 0, 12),
                               delivery='pallas_fused')
        sources = np.arange(6, dtype=np.int32)
        vb, heb = compiled.run_batch(sources).value
        for i, s in enumerate(sources):
            r = local.run(shortest_paths_spec(hg, int(s), 12)).value
            assert np.array_equal(np.asarray(r[0]), np.asarray(vb[i]),
                                  equal_nan=True), (backend, i)
            assert np.array_equal(np.asarray(r[1]), np.asarray(heb[i]),
                                  equal_nan=True), (backend, i)
    print('FUSED_DISTRIBUTED_AGREES')
""")


def test_distributed_fused_subprocess():
    # Inherit the full environment (dropping JAX_PLATFORMS makes jax
    # probe for accelerator platforms — minutes of stall per child).
    proc = subprocess.run(
        [sys.executable, "-c", DISTRIBUTED_FUSED],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FUSED_DISTRIBUTED_AGREES" in proc.stdout
