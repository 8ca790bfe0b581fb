"""Observability tier: tracer, metrics registry, explain/calibrate.

The tentpole contracts, asserted:

* ``Tracer`` spans nest per thread with ids and parent ids, the ring
  keeps the newest spans (counting the dropped rest), and every span is
  a host event of its name in the JAX profiler's trace;
* with no tracer attached, ``Engine.run`` records its span tree
  (resolve, layout build, dispatch with JAX's compile spans inside,
  device wait) into ``default_tracer()`` while the profiler records,
  and nothing otherwise;
* ``MetricsRegistry`` get-or-creates typed instruments, suffixes
  colliding provider names, prunes dead weakref providers, and keeps
  snapshotting through a provider that throws;
* ``Engine.explain`` reports per-candidate predicted costs WITHOUT
  executing (trace counter pinned at zero) and its winners match what
  ``resolve``/``run`` of the same inputs picks — axis for axis, also
  as a property over axis overrides;
* ``Engine.run`` enriches ``Result.decision["measured"]`` (wall split,
  executed supersteps, per-class delivery bytes on the fused path);
* ``obs.calibrate`` arithmetic (traffic models, superstep counting,
  log2 residuals, the bench_delivery calibration record);
* ``tools/bench_check.py`` fails only on >2x ratio-metric regressions
  and warns on host-dependent drift.
"""
import gc
import glob
import importlib.util
import json
import os

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import algorithms as alg
from repro.core import AnalyticsSpec, Engine
from repro.data import powerlaw_hypergraph
from repro.kernels.deliver import build_delivery_layout
from repro.obs import (
    MetricsRegistry,
    Tracer,
    decision_residuals,
    default_tracer,
    delivery_calibration,
    executed_supersteps,
    fused_traffic,
    maybe_span,
    reference_traffic,
    reset_default_registry,
    residual_log2,
    weak_provider,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# --------------------------------------------------------------------------
# Tracer: nesting, ring eviction, ids, the profiler's trace
# --------------------------------------------------------------------------

def test_span_nesting_and_durations_fake_clock():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", cat="execute", k=1) as outer:
        clock.t = 1.0
        with tr.span("inner", cat="compile") as inner:
            clock.t = 3.0
        clock.t = 10.0
    spans = tr.spans()
    # completion order: inner closes first
    assert [s.name for s in spans] == ["inner", "outer"]
    assert inner.depth == 1 and outer.depth == 0
    assert inner.dur_s == pytest.approx(2.0)
    assert outer.dur_s == pytest.approx(10.0)
    assert outer.args["k"] == 1
    # siblings after the nest go back to depth 0
    with tr.span("next") as nxt:
        pass
    assert nxt.depth == 0


def test_ring_eviction_keeps_newest_and_counts_dropped():
    tr = Tracer(capacity=4, clock=FakeClock())
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    spans = tr.spans()
    assert len(spans) == 4
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6
    assert tr.total == 10
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def _profiled(log_dir):
    """A CPU profiler session without the Python tracer (fast)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def _host_event_names(log_dir) -> set:
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(str(log_dir), "plugins", "profile", "*", "*.xplane.pb")
    )
    return {
        ev.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }


def test_tracer_span_is_a_host_event_in_the_profiler_trace(tmp_path):
    tr = Tracer()
    with _profiled(tmp_path):
        with tr.span("engine.phase_a", cat="execute"):
            with tr.span("engine.phase_b"):
                pass
    names = _host_event_names(tmp_path)
    assert {"engine.phase_a", "engine.phase_b"} <= names
    assert [s.name for s in tr.spans()] == [
        "engine.phase_b", "engine.phase_a"
    ]


def test_span_ids_and_parents():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer") as outer:
        with tr.span("mid") as mid:
            with tr.span("inner") as inner:
                pass
        with tr.span("sibling") as sib:
            pass
    assert len({outer.id, mid.id, inner.id, sib.id}) == 4
    assert outer.parent is None
    assert mid.parent == outer.id and sib.parent == outer.id
    assert inner.parent == mid.id
    with tr.span("next") as nxt:
        pass
    assert nxt.parent is None and nxt.id > sib.id


def test_maybe_span_is_noop_without_tracer():
    before = default_tracer().total
    with maybe_span(None, "anything", cat="execute", k=2) as sp:
        assert sp is None
    assert default_tracer().total == before


def test_maybe_span_records_into_default_tracer_while_profiling(tmp_path):
    default_tracer().clear()
    with _profiled(tmp_path):
        with maybe_span(None, "engine.probe", cat="execute", k=2) as sp:
            assert sp is not None
    (got,) = default_tracer().spans()
    assert got is sp and got.args == {"k": 2}
    assert "engine.probe" in _host_event_names(tmp_path)
    default_tracer().clear()


def test_compile_spans_join_the_open_span_of_an_attached_tracer():
    tr = Tracer()
    fresh = jax.jit(lambda x: x * 3.0 + 1.0)
    with tr.span("outer") as outer:
        jax.block_until_ready(fresh(np.arange(7, dtype=np.float32)))
    compiled = [s for s in tr.spans() if s.name.startswith("jax.")]
    names = {s.name for s in compiled}
    assert {"jax.trace", "jax.lower", "jax.compile"} <= names
    for s in compiled:
        assert s.parent == outer.id and s.cat == "compile"
        assert "fun_name" in s.args
        assert outer.t0 - 1e-3 <= s.t0
        assert s.t0 + s.dur_s <= outer.t0 + outer.dur_s + 1e-3
    # no span open: a compile records nowhere
    total = tr.total
    jax.block_until_ready(
        jax.jit(lambda x: x - 2.0)(np.arange(5, dtype=np.float32))
    )
    assert tr.total == total


def test_tracer_block_records_device_wait():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("x") as sp:
        clock.t = 1.0
        out = tr.block(sp, np.zeros(3))  # numpy value: no-op block
    assert out.shape == (3,)
    assert sp.args["device_wait_s"] == pytest.approx(0.0)


# --------------------------------------------------------------------------
# MetricsRegistry
# --------------------------------------------------------------------------

def test_registry_instruments_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.inc()
    c.inc(3)
    reg.gauge("g").set(2.5)
    reg.histogram("h").record(0.01)
    assert reg.counter("n") is c  # get-or-create
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("n")
    snap = reg.snapshot()
    assert snap["n"] == 4
    assert snap["g"] == 2.5
    assert snap["h"]["count"] == 1


def test_registry_provider_collision_suffix_and_errors():
    reg = MetricsRegistry()
    n1 = reg.register_provider("cache", lambda: {"a": 1})
    n2 = reg.register_provider("cache", lambda: {"b": 2})
    assert (n1, n2) == ("cache", "cache#2")

    def boom():
        raise RuntimeError("nope")

    reg.register_provider("bad", boom)
    snap = reg.snapshot()
    assert snap["cache"] == {"a": 1}
    assert snap["cache#2"] == {"b": 2}
    assert "error" in snap["bad"]


def test_registry_prunes_dead_weak_providers():
    class Owner:
        def stats(self):
            return {"alive": True}

    reg = MetricsRegistry()
    o = Owner()
    reg.register_provider("owner", weak_provider(o.stats))
    assert reg.snapshot()["owner"] == {"alive": True}
    del o
    gc.collect()
    snap = reg.snapshot()
    assert "owner" not in snap
    assert "owner" not in reg._providers  # pruned, not just skipped


def test_latency_histogram_is_shared_between_obs_and_serve():
    import repro.obs.metrics as obs_metrics
    import repro.serve as serve
    import repro.serve.metrics as serve_metrics

    assert serve.LatencyHistogram is obs_metrics.LatencyHistogram
    assert serve_metrics.LatencyHistogram is obs_metrics.LatencyHistogram


def test_frontend_stats_merges_registry_sections():
    from repro.serve import Frontend

    reset_default_registry()
    eng = Engine()
    fe = Frontend(eng, max_batch=4, max_delay_ms=1.0, clock=FakeClock())
    snap = fe.stats()["registry"]
    assert "engine.exec_cache" in snap
    assert "serve.frontend" in snap
    assert snap["engine.exec_cache"]["entries"] == 0
    reset_default_registry()


def test_delivery_layout_builder_reports_into_registry():
    reg = reset_default_registry()
    rng = np.random.default_rng(0)
    src = rng.integers(0, 64, 512).astype(np.int32)
    dst = rng.integers(0, 64, 512).astype(np.int32)
    layout = build_delivery_layout(src, dst, None, 64, 64)
    snap = reg.snapshot()
    assert snap["delivery.layouts_built"] == 1
    assert snap["delivery.ell_slots"] == layout.ell_slots
    assert snap["delivery.build_s"]["count"] == 1
    reset_default_registry()


# --------------------------------------------------------------------------
# Engine.explain: candidates without executing, agreement with run
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hg():
    return powerlaw_hypergraph(400, 180, mean_cardinality=5, seed=3)


def test_explain_reports_candidates_without_executing(hg):
    eng = Engine()
    spec = alg.pagerank_spec(hg, iters=4)
    ex = eng.explain(spec)
    # no compile, no device work
    assert eng.cache_stats()["traces"] == 0
    assert eng.cache_stats()["entries"] == 0
    axes = ex["axes"]
    assert set(axes) == {
        "representation", "backend", "partition", "delivery",
    }
    for axis, info in axes.items():
        assert "winner" in info and "candidates" in info, axis
    d = axes["delivery"]["candidates"]
    assert d["xla"]["eligible"] is True
    assert d["xla"]["predicted_hbm_bytes"] > 0
    assert "eligible" in d["pallas_fused"]
    r = axes["representation"]["candidates"]
    assert r["bipartite"]["predicted_cost_edges"] == hg.nnz


def test_explain_config_matches_run(hg):
    eng = Engine(collect_stats=True)
    spec = alg.pagerank_spec(hg, iters=4)
    ex = eng.explain(spec)
    res = eng.run(spec)
    assert ex["config"] == res.config
    assert ex["axes"]["representation"]["winner"] == res.representation
    assert ex["axes"]["backend"]["winner"] == res.backend
    assert ex["axes"]["delivery"]["winner"] == res.config.delivery


@given(
    st.sampled_from(["auto", "bipartite"]),
    st.sampled_from(["auto", "xla", "pallas_fused"]),
    st.sampled_from(["auto", "local"]),
)
@settings(max_examples=12, deadline=None)
def test_explain_matches_resolve_under_overrides(
    representation, delivery, backend
):
    # the agreement property: explain is BUILT on resolve, so for any
    # pinning of the axes the explained config IS the resolved config.
    hg = powerlaw_hypergraph(120, 60, mean_cardinality=4, seed=7)
    eng = Engine()
    spec = alg.shortest_paths_spec(hg, 0, 3)
    overrides = dict(
        representation=representation, delivery=delivery, backend=backend,
    )
    ex = eng.explain(spec, **overrides)
    resolved, _, decision = eng.resolve(spec, **overrides)
    assert ex["config"] == resolved
    assert ex["decision"].keys() == decision.keys()
    for axis in ("representation", "backend", "delivery"):
        assert ex["axes"][axis]["winner"] == getattr(
            resolved,
            axis if axis != "backend" else "backend",
        )
    assert eng.cache_stats()["traces"] == 0


def test_explain_analytics_axes(hg):
    eng = Engine()
    ex = eng.explain(AnalyticsSpec(hg, mode="auto"))
    axes = ex["axes"]
    assert {"kernel", "representation", "backend", "mode"} <= set(axes)
    k = axes["kernel"]["candidates"]
    assert k["merge"]["eligible"] is True
    assert k["merge"]["predicted_ops_per_pair"] > 0
    res = eng.analyze(AnalyticsSpec(hg, mode="auto"))
    assert axes["kernel"]["winner"] == res.kernel
    assert axes["mode"]["winner"] == res.mode


def test_run_enriches_decision_with_measured(hg):
    eng = Engine(collect_stats=True)
    res = eng.run(alg.pagerank_spec(hg, iters=4))
    m = res.decision["measured"]
    assert m["wall_s"] >= m["device_wait_s"] >= 0.0
    assert m["max_iters"] == 4
    assert 0 <= m["supersteps"] <= 4


def test_run_measured_delivery_bytes_on_fused_path(hg):
    eng = Engine(delivery="pallas_fused")
    res = eng.run(alg.pagerank_spec(hg, iters=3))
    md = res.decision["measured"]["delivery"]
    assert md["total_bytes"] > 0
    assert md["fwd"]["nnz"] == hg.nnz
    assert md["total_bytes"] == pytest.approx(
        md["fwd"]["total_bytes"] + md["bwd"]["total_bytes"]
    )
    assert md["reference_total_bytes"] > 0
    # the residual record built from the same enriched decision
    rr = decision_residuals(res.decision)
    if "delivery" in rr:
        assert rr["delivery"]["built_work_slots"] > 0


# --------------------------------------------------------------------------
# obs.calibrate arithmetic
# --------------------------------------------------------------------------

def test_reference_and_fused_traffic_models():
    assert reference_traffic(100, 10, 4.0) == 100 * (12 + 8) + 40
    rng = np.random.default_rng(1)
    src = rng.integers(0, 32, 256).astype(np.int32)
    dst = rng.integers(0, 32, 256).astype(np.int32)
    layout = build_delivery_layout(src, dst, None, 32, 32)
    t = fused_traffic(layout, 4.0)
    assert t["total_bytes"] == pytest.approx(
        sum(t["per_class_bytes"]) + t["residual_bytes"] + t["output_bytes"]
    )
    assert t["nnz"] == 256


def test_executed_supersteps_counts_active_pairs():
    assert executed_supersteps(([3, 2, 0, 0], [1, 0, 0, 0])) == 2
    assert executed_supersteps(([1, 0, 0, 0], [0, 0, 0, 0])) == 1
    # batched stats: the slowest query wins
    v = np.array([[3, 2, 0], [1, 0, 0]])
    he = np.zeros_like(v)
    assert executed_supersteps((v, he)) == 2
    assert executed_supersteps((v, he), max_iters=1) == 1
    assert executed_supersteps(None) is None


def test_residual_log2_and_delivery_calibration():
    assert residual_log2(2.0, 1.0) == pytest.approx(1.0)
    assert residual_log2(1.0, 1.0) == pytest.approx(0.0)
    regimes = {
        "perfect": {
            "model_traffic_ratio": 2.0, "fused_speedup": 2.0,
            "auto_picks": "pallas_fused",
        },
        "off": {
            "model_traffic_ratio": 0.5, "fused_speedup": 0.8,
            "auto_picks": "xla",
        },
    }
    cal = delivery_calibration(regimes)
    assert cal["regimes"]["perfect"]["residual_log2"] == pytest.approx(0.0)
    assert cal["regimes"]["perfect"]["decision_agrees"] is True
    assert cal["regimes"]["off"]["measured_winner"] == "xla"
    assert cal["regimes"]["off"]["decision_agrees"] is True
    s = cal["summary"]
    assert s["decision_accuracy"] == 1.0
    assert s["mean_abs_residual_log2"] == pytest.approx(
        abs(np.log2(0.5 / 0.8)) / 2
    )
    assert s["suggested_model_scale"] > 1.0  # model under-predicted "off"


# --------------------------------------------------------------------------
# tools/bench_check.py
# --------------------------------------------------------------------------

def _bench_check():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_check", os.path.join(root, "tools", "bench_check.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_check_leaf_flattening_and_markers():
    bc = _bench_check()
    leaves = bc.numeric_leaves(
        {"a": {"b": 1, "skip": True}, "xs": [{"y": 2.5}, 3]}
    )
    assert leaves == {"a.b": 1.0, "xs[0].y": 2.5, "xs[1]": 3.0}
    assert bc.is_ratio_metric("regimes.n.fused_speedup")
    assert bc.is_ratio_metric("overhead.traced_over_untraced")
    assert bc.is_ratio_metric("summary.decision_accuracy")
    assert not bc.is_ratio_metric("regimes.n.xla_s")


def test_bench_check_fails_only_on_ratio_regression():
    bc = _bench_check()
    baseline = {"fused_speedup": 2.0, "xla_s": 1.0}
    # >2x ratio regression -> failure
    fails, warns = bc.compare(
        {"fused_speedup": 0.9, "xla_s": 1.0}, baseline, 0.5
    )
    assert len(fails) == 1 and "fused_speedup" in fails[0]
    # big timing drift -> warning only
    fails, warns = bc.compare(
        {"fused_speedup": 2.0, "xla_s": 5.0}, baseline, 0.5
    )
    assert fails == []
    assert any("xla_s" in w for w in warns)
    # in-band run -> clean
    fails, warns = bc.compare(
        {"fused_speedup": 1.9, "xla_s": 1.2}, baseline, 0.5
    )
    assert fails == [] and warns == []


def test_bench_check_main_and_update(tmp_path):
    bc = _bench_check()
    fresh = tmp_path / "fresh"
    base = tmp_path / "base"
    fresh.mkdir()
    (fresh / "BENCH_x.json").write_text(
        json.dumps({"speedup": 1.0, "wall_s": 2.0})
    )
    # no baseline yet: skipped, exit 0; --update seeds it
    assert bc.main(
        ["--fresh-dir", str(fresh), "--baseline-dir", str(base)]
    ) == 0
    assert bc.main(
        ["--fresh-dir", str(fresh), "--baseline-dir", str(base),
         "--update"]
    ) == 0
    assert bc.main(
        ["--fresh-dir", str(fresh), "--baseline-dir", str(base)]
    ) == 0
    # regress the ratio metric past 2x -> exit 1
    (fresh / "BENCH_x.json").write_text(
        json.dumps({"speedup": 0.4, "wall_s": 2.0})
    )
    assert bc.main(
        ["--fresh-dir", str(fresh), "--baseline-dir", str(base)]
    ) == 1
    # empty fresh dir -> usage error
    empty = tmp_path / "empty"
    empty.mkdir()
    assert bc.main(
        ["--fresh-dir", str(empty), "--baseline-dir", str(base)]
    ) == 2


# --------------------------------------------------------------------------
# traced execution end-to-end (real jax, local backend)
# --------------------------------------------------------------------------

def test_traced_compile_and_serve_records_phases(hg):
    tr = Tracer()
    # pin the fused path so the delivery-layout build span is in play
    eng = Engine(tracer=tr, delivery="pallas_fused")
    compiled = eng.compile(alg.shortest_paths_spec(hg, 0, 4))
    compiled.run_batch(np.asarray([0, 1, 2], np.int32))
    names = {s.name for s in tr.spans()}
    assert "engine.build_executable" in names
    assert "engine.execute" in names
    assert "serve.layout_build" in names or "engine.layout_build" in names
    ex_spans = [s for s in tr.spans() if s.name == "engine.execute"]
    assert ex_spans and "device_wait_s" in ex_spans[0].args
    # measured enrichment rides the traced serve path
    res = compiled.run_batch(np.asarray([3, 4], np.int32))
    assert "measured" in res.decision
    assert res.decision["measured"]["wall_s"] > 0


MEASURED_KEYS = {"wall_s", "dispatch_s", "device_wait_s", "max_iters"}


def test_profiled_run_records_the_engine_span_tree(hg, tmp_path):
    """No tracer attached: while the profiler records, ``Engine.run``
    records resolve / layout build / dispatch (JAX's trace, lower and
    compile inside) / device wait under ``engine.run``."""
    default_tracer().clear()
    eng = Engine(delivery="pallas_fused")
    with _profiled(tmp_path):
        res = eng.run(alg.pagerank_spec(hg, iters=3))
    spans = default_tracer().spans()
    assert default_tracer().dropped == 0
    by_id = {s.id: s for s in spans}
    (run,) = [s for s in spans if s.name == "engine.run"]
    assert run.parent is None and run.args == {"algorithm": "pagerank"}
    children = sorted(
        (s for s in spans if s.parent == run.id), key=lambda s: s.t0
    )
    assert [s.name for s in children] == [
        "engine.resolve", "engine.layout_build", "engine.dispatch",
        "engine.device_wait",
    ]
    for c in children:
        assert run.t0 <= c.t0
        assert c.t0 + c.dur_s <= run.t0 + run.dur_s
    dispatch = children[2]
    under = [s for s in spans if s.parent == dispatch.id]
    assert any(s.name == "jax.compile" for s in under)
    assert {s.name for s in under} <= {"jax.trace", "jax.lower",
                                       "jax.compile"}
    for s in spans:
        assert s.parent is None or s.parent in by_id
    # the measured decision keeps its keys and its device wait
    m = res.decision["measured"]
    assert MEASURED_KEYS | {"delivery"} == set(m)
    assert m["device_wait_s"] <= children[3].dur_s + 1e-3
    host = _host_event_names(tmp_path)
    assert {c.name for c in children} | {"engine.run"} <= host
    default_tracer().clear()


def test_unprofiled_run_records_nothing(hg):
    default_tracer().clear()
    res = Engine().run(alg.pagerank_spec(hg, iters=3))
    assert default_tracer().total == 0
    assert set(res.decision["measured"]) == MEASURED_KEYS
    res = Engine(collect_stats=True).run(alg.pagerank_spec(hg, iters=3))
    assert default_tracer().total == 0
    assert set(res.decision["measured"]) == MEASURED_KEYS | {"supersteps"}


def test_attached_tracer_records_the_run_span_tree(hg):
    tr = Tracer()
    Engine(tracer=tr).run(alg.pagerank_spec(hg, iters=3))
    names = [s.name for s in tr.spans()
             if s.name.startswith("engine.")]
    assert names[-1] == "engine.run"
    assert {"engine.resolve", "engine.dispatch",
            "engine.device_wait"} <= set(names)


def test_profiled_serve_without_tracer_records_its_spans(hg, tmp_path):
    """No tracer attached: the serve pump's flush and execute spans go
    to ``default_tracer()`` while the profiler records, and the flush
    blocks on its value without an attached tracer's timing."""
    from repro.serve import Frontend

    default_tracer().clear()
    fe = Frontend(Engine(), max_batch=4, max_delay_ms=1.0)
    fe.register("sssp", alg.shortest_paths_spec(hg, 0, 4))
    with _profiled(tmp_path):
        futs = [fe.submit("sssp", query=q) for q in (0, 1, 2)]
        fe.pump(drain=True)
        for f in futs:
            f.result(timeout=60)
    spans = default_tracer().spans()
    by_id = {s.id: s for s in spans}
    (flush,) = [s for s in spans if s.name == "serve.flush"]
    (execute,) = [s for s in spans if s.name == "engine.execute"]
    assert "device_wait_s" not in flush.args and "max_wait_s" in flush.args
    assert by_id[execute.parent].name == "serve.flush"
    default_tracer().clear()


def test_untraced_serve_skips_measured_enrichment(hg):
    eng = Engine()
    compiled = eng.compile(alg.shortest_paths_spec(hg, 0, 4))
    res = compiled.run_batch(np.asarray([0, 1], np.int32))
    assert "measured" not in res.decision  # zero-overhead contract


# --------------------------------------------------------------------------
# registry under concurrency (the serve tier is multi-threaded)
# --------------------------------------------------------------------------

def test_registry_concurrent_registration_snapshot_and_pruning():
    """Registration, owned-instrument writes, weakref pruning, and
    snapshots racing from many threads — including a REAL ``Frontend``
    worker thread serving submits — must neither raise nor corrupt the
    snapshot (every value a snapshot reports is internally consistent)."""
    import threading

    from repro.obs.metrics import (
        MetricsRegistry,
        reset_default_registry,
        weak_provider,
    )
    from repro.serve import Frontend

    reg = reset_default_registry()
    assert isinstance(reg, MetricsRegistry)
    errors: list[BaseException] = []
    stop = threading.Event()

    def snapshotter():
        while not stop.is_set():
            try:
                snap = reg.snapshot()
                # pruning must never surface a dead provider as None
                assert all(v is not None for v in snap.values())
            except BaseException as err:  # noqa: BLE001
                errors.append(err)
                return

    def churn(k):
        # short-lived owners: their weak providers go dead mid-run and
        # must be pruned by concurrent snapshots without KeyErrors
        class Owner:
            def __init__(self, i):
                self.i = i

            def stats(self):
                return {"i": self.i}

        try:
            for i in range(300):
                o = Owner(i)
                reg.register_provider(f"churn{k}", weak_provider(o.stats))
                reg.counter(f"count{k}").inc()
                reg.gauge(f"gauge{k}").set(i)
                reg.histogram(f"hist{k}").record(1e-4 * (i + 1))
                del o
        except BaseException as err:  # noqa: BLE001
            errors.append(err)

    # a real Frontend: its ServeMetrics registers a provider into the
    # default registry and its worker thread completes futures while
    # the snapshotters race
    class Fake:
        def run_batch(self, queries, hg=None):
            import numpy as _np

            class R:
                value = {"out": _np.asarray(queries)}
                supersteps_executed = None

            return R()

    fe = Frontend(Engine(), max_batch=4, max_delay_ms=0.5)
    fe.register("sig", Fake())

    threads = [threading.Thread(target=snapshotter) for _ in range(3)]
    threads += [threading.Thread(target=churn, args=(k,)) for k in range(4)]
    with fe:
        for t in threads:
            t.start()
        futs = [fe.submit("sig", query=q) for q in range(64)]
        for f in futs:
            f.result(timeout=30)
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors[:3]

    gc.collect()
    snap = reg.snapshot()     # post-churn: dead churn providers pruned
    snap2 = reg.snapshot()
    assert not any(k.startswith("churn") for k in snap2)
    for k in range(4):
        assert snap[f"count{k}"] == 300
        assert snap[f"hist{k}"]["count"] == 300
    assert snap["serve.frontend"]["completed"] == 64
