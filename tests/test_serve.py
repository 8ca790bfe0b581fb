"""The serving tier: coalescing front-end + persistent executable cache.

The tentpole contracts, asserted:

* **coalescing is invisible in the numbers**: any arrival order, mixed
  signatures, deadline-forced partial flushes and duplicate in-flight
  queries — every request's resolved value is bitwise identical to a
  sequential ``CompiledAlgorithm.run(query=...)`` of the same query
  (jit-free property tests on the pure batcher + fake-clock front-end,
  plus real-jax integration on the local backend and a sharded-backend
  subprocess);
* **boot-from-disk never retraces**: a second Engine — and, in the slow
  suite, a second *process* — on the same cache dir reaches warm-path
  serving with the trace counter pinned at zero;
* ``bucket_dim`` / batch-bucket edge cases (n=0, exact powers of two,
  floor boundaries) behave (the satellite property tests);
* ``cache_stats`` reports evictions and per-entry bucket shapes.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import Engine, bucket_dim
from repro.core.serving import BATCH_FLOOR, BUCKET_FLOOR
from repro.data import powerlaw_hypergraph
from repro.serve import (
    CoalescingBatcher,
    DiskExecutableCache,
    Frontend,
    LatencyHistogram,
    warm,
)
from repro.serve.cache import stable_digest


# --------------------------------------------------------------------------
# bucket_dim edge cases (the bucketing contract the batcher leans on)
# --------------------------------------------------------------------------

def test_bucket_dim_edges():
    assert bucket_dim(0) == BUCKET_FLOOR
    assert bucket_dim(1) == BUCKET_FLOOR
    assert bucket_dim(BUCKET_FLOOR) == BUCKET_FLOOR
    assert bucket_dim(BUCKET_FLOOR + 1) == 2 * BUCKET_FLOOR
    assert bucket_dim(0, floor=BATCH_FLOOR) == BATCH_FLOOR
    # exact powers of two are their own bucket (no gratuitous doubling)
    for p in (8, 16, 64, 1024):
        if p >= BATCH_FLOOR:
            assert bucket_dim(p, floor=BATCH_FLOOR) == p


@given(st.integers(min_value=0, max_value=1 << 20),
       st.sampled_from([1, 2, 8, 64, 128]))
@settings(max_examples=200, deadline=None)
def test_bucket_dim_properties(n, floor):
    b = bucket_dim(n, floor=floor)
    assert b >= n and b >= floor
    # power-of-two multiple of the floor
    assert b % floor == 0 and (b // floor) & (b // floor - 1) == 0
    # minimal: halving (where legal) undershoots n
    if b > floor:
        assert b // 2 < n
    # monotone
    assert bucket_dim(n + 1, floor=floor) >= b


# --------------------------------------------------------------------------
# the pure batcher (fake clock, no jax)
# --------------------------------------------------------------------------

def test_batcher_full_flush_takes_exactly_capacity():
    b = CoalescingBatcher(capacity=4)
    for i in range(6):
        b.submit("g", i, now=0.0, deadline_s=10.0)
    f = b.poll(0.0)
    assert f is not None and f.reason == "full"
    assert [r.query for r in f.requests] == [0, 1, 2, 3]
    assert b.pending_count() == 2
    # remainder is not due until its deadline
    assert b.poll(1.0) is None
    f2 = b.poll(10.5)
    assert f2.reason == "deadline"
    assert [r.query for r in f2.requests] == [4, 5]
    assert b.pending_count() == 0


def test_batcher_deadline_ordering_and_fairness():
    b = CoalescingBatcher(capacity=8)
    b.submit("late", 0, now=0.0, deadline_s=5.0)
    b.submit("early", 1, now=0.0, deadline_s=1.0)
    assert b.next_deadline() == 1.0
    assert b.poll(0.5) is None
    f = b.poll(6.0)  # both expired: oldest deadline first
    assert f.group == "early"
    assert b.poll(6.0).group == "late"


def test_batcher_rejects_mixed_hypergraph_in_group():
    b = CoalescingBatcher(capacity=8)
    hg1, hg2 = object(), object()
    b.submit("g", 0, now=0.0, deadline_s=1.0, hg=hg1)
    with pytest.raises(ValueError, match="different hypergraph"):
        b.submit("g", 1, now=0.0, deadline_s=1.0, hg=hg2)


@given(st.lists(
    st.tuples(
        st.sampled_from(["a", "b", "c"]),     # group
        st.integers(0, 99),                   # query (duplicates likely)
        st.floats(0.0, 4.0),                  # inter-arrival delta
        st.floats(0.001, 2.0),                # deadline_s
        st.booleans(),                        # poll after this arrival?
    ),
    min_size=1, max_size=60,
))
@settings(max_examples=100, deadline=None)
def test_batcher_flushes_every_request_exactly_once(events):
    """Any arrival order / mixed groups / deadline-forced partial
    flushes / duplicate in-flight queries: each request flushed exactly
    once, FIFO within its group, never above capacity, group-pure."""
    b = CoalescingBatcher(capacity=4)
    now = 0.0
    submitted, flushes = [], []
    for group, query, dt, deadline_s, do_poll in events:
        now += dt
        submitted.append(b.submit(group, query, now=now,
                                  deadline_s=deadline_s))
        if do_poll:
            while (f := b.poll(now)) is not None:
                flushes.append(f)
    flushes.extend(b.drain())
    assert b.pending_count() == 0

    flushed = [r for f in flushes for r in f.requests]
    assert len(flushed) == len(submitted)
    assert {r.seq for r in flushed} == {r.seq for r in submitted}
    per_group_seqs: dict = {}
    for f in flushes:
        assert 1 <= len(f.requests) <= 4
        assert f.reason in ("full", "deadline", "drain")
        for r in f.requests:
            assert r.group == f.group
            per_group_seqs.setdefault(f.group, []).append(r.seq)
    for seqs in per_group_seqs.values():
        assert seqs == sorted(seqs)  # FIFO within a group


# --------------------------------------------------------------------------
# front-end coalescing == sequential (fake compiled, fake clock, no jax)
# --------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class FakeResult:
    def __init__(self, value):
        self.value = value
        self.supersteps_executed = None


class FakeCompiled:
    """``run_batch`` double: value rows are a pure function of the query
    (plus a per-instance salt, so mixed signatures can't alias)."""

    def __init__(self, salt):
        self.salt = salt
        self.batch_sizes = []

    def _one(self, q):
        return {"out": np.asarray([q * 2 + self.salt, q], np.int64)}

    def run(self, query=None, hg=None):
        return FakeResult(self._one(int(query)))

    def run_batch(self, queries, hg=None):
        qs = np.asarray(queries["q"] if isinstance(queries, dict)
                        else queries)
        self.batch_sizes.append(len(qs))
        rows = [self._one(int(q)) for q in qs]
        return FakeResult({
            "out": np.stack([r["out"] for r in rows]),
        })


@given(st.lists(
    st.tuples(
        st.sampled_from(["sssp", "ppr"]),   # signature
        st.integers(0, 30),                 # query (duplicates likely)
        st.floats(0.0, 0.01),               # inter-arrival
        st.booleans(),                      # pump mid-stream?
    ),
    min_size=1, max_size=50,
))
@settings(max_examples=60, deadline=None)
def test_frontend_coalescing_matches_sequential(events):
    clock = FakeClock()
    eng = Engine()  # unused by the fakes; supplies stats plumbing
    fe = Frontend(eng, max_batch=4, max_delay_ms=5.0, clock=clock)
    fakes = {"sssp": FakeCompiled(1000), "ppr": FakeCompiled(7000)}
    for key, fake in fakes.items():
        fe.register(key, fake)

    futs = []
    for key, query, dt, do_pump in events:
        clock.t += dt
        futs.append((key, query, fe.submit(key, query=query)))
        if do_pump:
            fe.pump()
    clock.t += 10.0  # expire every deadline
    fe.pump(drain=True)

    for key, query, fut in futs:
        assert fut.done()
        served = fut.result(timeout=0)
        expected = fakes[key].run(query=query).value
        np.testing.assert_array_equal(served.value["out"],
                                      expected["out"])
        assert served.batch_size <= 4
        assert served.flush_reason in ("full", "deadline", "drain")
    st_ = fe.stats()
    assert st_["submitted"] == st_["completed"] == len(futs)
    assert st_["errors"] == 0
    for fake in fakes.values():
        assert all(b <= 4 for b in fake.batch_sizes)


def test_frontend_error_fans_out_to_futures():
    class Broken:
        def run_batch(self, queries, hg=None):
            raise RuntimeError("boom")

    fe = Frontend(Engine(), max_batch=4, clock=FakeClock())
    fe.register("bad", Broken())
    f1, f2 = fe.submit("bad", query=1), fe.submit("bad", query=2)
    fe.pump(drain=True)
    for f in (f1, f2):
        with pytest.raises(RuntimeError, match="boom"):
            f.result(timeout=0)
    assert fe.stats()["errors"] == 2


def test_frontend_unknown_key_and_queryless_spec():
    fe = Frontend(Engine(), clock=FakeClock())
    with pytest.raises(KeyError, match="register"):
        fe.submit("nope", query=0)
    from repro.algorithms import pagerank_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    with pytest.raises(ValueError, match="bind_query"):
        fe.register("pr", pagerank_spec(hg, iters=4))


# --------------------------------------------------------------------------
# front-end integration: real jax, worker thread, bitwise vs sequential
# --------------------------------------------------------------------------

def test_frontend_threaded_bitwise_local_backend():
    import jax

    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    eng = Engine()
    fe = Frontend(eng, max_batch=8, max_delay_ms=2.0)
    fe.register("sssp", shortest_paths_spec(hg, 0, 12))
    rng = np.random.default_rng(0)
    sources = rng.integers(0, hg.n_vertices, size=13).astype(np.int32)
    with fe:
        futs = [fe.submit("sssp", query=int(s)) for s in sources]
        results = [f.result(timeout=300) for f in futs]
    comp = fe.compiled("sssp")
    for s, served in zip(sources, results):
        ref = comp.run(query=int(s)).value
        for a, b in zip(jax.tree.leaves(ref),
                        jax.tree.leaves(served.value)):
            assert np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True), int(s)
    snap = fe.stats()
    assert snap["completed"] == len(sources)
    assert snap["queue_wait"]["count"] == len(sources)
    assert snap["engine_cache"]["entries"] >= 1


# --------------------------------------------------------------------------
# persistent executable cache
# --------------------------------------------------------------------------

def test_stable_digest_is_stable_across_spec_instances():
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    s1 = shortest_paths_spec(hg, 0, 12)
    s2 = shortest_paths_spec(hg, 0, 12)
    # Program dataclasses hold closures: identity differs, digest must not
    assert s1.v_program is not s2.v_program
    assert stable_digest(s1.v_program) == stable_digest(s2.v_program)
    assert stable_digest(s1.he_program) == stable_digest(s2.he_program)
    # a different closed-over constant MUST change the digest
    s3 = shortest_paths_spec(hg, 0, 13)
    key = (s1.v_program, s1.he_program, 12)
    assert stable_digest(key) != stable_digest(
        (s3.v_program, s3.he_program, 13)
    )


def test_disk_cache_zero_retrace_second_engine(tmp_path, no_retrace):
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    eng1 = Engine(disk_cache=DiskExecutableCache(tmp_path))
    rep1 = warm(eng1, [shortest_paths_spec(hg, 0, 12)], batch_sizes=(8,))
    assert rep1["traces"] > 0 and rep1["from_disk"] == 0
    r1 = eng1.compile(shortest_paths_spec(hg, 0, 12)).run_batch(
        np.arange(8, dtype=np.int32)
    )

    # a fresh Engine + fresh spec objects on the same store: no retrace
    # (require_no_retrace raises from inside warm — the runtime guard a
    # booting replica uses to fail fast instead of eating compiles)
    eng2 = Engine(disk_cache=DiskExecutableCache(tmp_path))
    rep2 = warm(eng2, [shortest_paths_spec(hg, 0, 12)], batch_sizes=(8,),
                require_no_retrace=True)
    assert rep2["from_disk"] == 2  # single + batch8 paths
    with no_retrace(eng2, label="first replay after disk boot"):
        r2 = eng2.compile(shortest_paths_spec(hg, 0, 12)).run_batch(
            np.arange(8, dtype=np.int32)
        )
    for a, b in zip(r1.value, r2.value):
        assert np.array_equal(np.asarray(a), np.asarray(b),
                              equal_nan=True)


def test_disk_cache_respects_env_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "envroot"))
    cache = DiskExecutableCache()
    assert str(cache.root) == str(tmp_path / "envroot")
    monkeypatch.delenv("REPRO_CACHE_DIR")
    assert str(DiskExecutableCache().root) == ".repro_cache"


@pytest.mark.parametrize("disk", [False, True])
def test_unlowerable_args_raise_instead_of_serving_through_jit(tmp_path,
                                                               disk):
    """Arguments AOT lowering cannot take fail loudly: no executable is
    ever served through a plain-jit fallback."""
    import jax

    eng = Engine(disk_cache=DiskExecutableCache(tmp_path) if disk else None)
    exe = eng._executable_for(("exotic",), lambda: jax.jit(lambda x, o: x))
    with pytest.raises(TypeError):
        exe(np.int32(1), object())
    assert exe.source is None
    assert eng.cache_stats()["sources"] == {}
    if disk:
        assert eng.disk_cache.stats()["entries"] == 0


def test_executables_compile_ahead_of_first_call():
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    eng = Engine()
    report = eng.compile(shortest_paths_spec(hg, 0, 6)).warmup(
        batch_sizes=(8,)
    )
    assert {p["source"] for p in report.values()} == {"aot"}
    assert eng.cache_stats()["sources"] == {"aot": 2}


def test_compile_cache_dir_from_env_or_fixed_repo_path(monkeypatch,
                                                       tmp_path):
    import jax

    from repro.launch.compile_cache import repo_root, use_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", prev)
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == str(repo_root() / ".jax_cache")
        assert (repo_root() / "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path                  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_disk_cache_corrupt_blob_degrades_to_miss(tmp_path):
    cache = DiskExecutableCache(tmp_path)
    key = ("k",)
    cache.dir.mkdir(parents=True, exist_ok=True)
    with open(cache._path(stable_digest(key)), "wb") as f:
        f.write(b"not a pickle")
    assert cache.load(key) is None
    assert cache.stats()["disk_errors"] == 1


def test_warm_requires_example_query_for_query0_free_spec(tmp_path):
    from repro.algorithms import random_walk_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    eng = Engine()
    # the unbatched path warms fine without a query...
    rep = warm(eng, [random_walk_spec(hg, iters=4)])
    assert rep["paths"]["0:random_walk"]["single"]["source"] in (
        "aot", "jit"
    )
    # ...but a batched path needs an example (query0 is unset)
    with pytest.raises(ValueError, match="query"):
        warm(eng, [random_walk_spec(hg, iters=4)], batch_sizes=(8,))


# --------------------------------------------------------------------------
# cache_stats: evictions + per-entry bucket shapes
# --------------------------------------------------------------------------

def test_cache_stats_evictions_and_entry_shapes():
    eng = Engine(exec_cache_size=2)
    for i in range(4):
        eng._executable_for(("k", i), lambda: (lambda *a: None),
                            meta={"algorithm": f"alg{i}"})
    s = eng.cache_stats()
    assert s["entries"] == 2 and s["capacity"] == 2
    assert s["evictions"] == 2
    assert [m["algorithm"] for m in s["entry_shapes"]] == ["alg2", "alg3"]
    # hits don't evict
    eng._executable_for(("k", 3), lambda: (lambda *a: None))
    assert eng.cache_stats()["evictions"] == 2
    assert eng.cache_stats()["hits"] == 1


# --------------------------------------------------------------------------
# fused delivery under serving: bucketed layout shapes
# --------------------------------------------------------------------------

def _is_pow2(n):
    return n >= 8 and n & (n - 1) == 0


def _regular_hypergraph(n_hyperedges):
    """Every vertex in 2 hyperedges, every hyperedge of 4 vertices."""
    from repro.core.hypergraph import HyperGraph

    v = np.arange(2 * n_hyperedges, dtype=np.int32)
    src = np.concatenate([v, v])
    dst = np.concatenate([v % n_hyperedges, (v + 1) % n_hyperedges])
    return HyperGraph.from_coo(src, dst, 2 * n_hyperedges, n_hyperedges)


def test_compiled_fused_layouts_keep_pow2_rows_and_residual():
    from repro.algorithms import shortest_paths_spec
    from repro.analysis.retrace import _same_bucket_pair

    hg, hg2 = _same_bucket_pair()
    comp = Engine().compile(shortest_paths_spec(hg, 0, 8),
                            delivery="pallas_fused")
    for g in (hg, hg2):
        for lay in comp._prepared(g, rebind=False)["delivery"]:
            assert all(_is_pow2(r) for r in lay.class_rows), lay.class_rows
            assert _is_pow2(lay.rem_len), lay.rem_len


def test_same_bucket_hypergraphs_share_one_fused_executable():
    """Two hypergraphs of one bucket whose classes hold different real
    row counts (20 and 24 hyperedges, 40 and 48 vertices) share one
    ``delivery_sig`` and so one executable; a multiple-of-8 build would
    give them different shapes."""
    import jax

    from repro.algorithms import shortest_paths_spec
    from repro.kernels.deliver import layout_pair

    small, large = _regular_hypergraph(20), _regular_hypergraph(24)
    eng = Engine()
    comp = eng.compile(shortest_paths_spec(small, 0, 8),
                       delivery="pallas_fused")
    preps = [comp._prepared(g, rebind=False) for g in (small, large)]
    assert preps[0]["delivery_sig"] == preps[1]["delivery_sig"]
    tight = [layout_pair(p["hgp"].src, p["hgp"].dst, p["hgp"].e_mask,
                         p["nv_pad"], p["ne_pad"])[1] for p in preps]
    assert tight[0].class_rows != tight[1].class_rows
    comp.run()
    traces = eng.cache_stats()["traces"]
    got = comp.run(large).value
    assert eng.cache_stats()["traces"] == traces
    want = Engine().run(shortest_paths_spec(large, 0, 8),
                        delivery="xla").value
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

def test_latency_histogram_quantiles():
    h = LatencyHistogram()
    assert h.snapshot()["p99_s"] == 0.0
    for ms in [1.0] * 98 + [100.0, 1000.0]:
        h.record(ms / 1e3)
    snap = h.snapshot()
    assert snap["count"] == 100
    # bin upper bounds: p50 covers 1ms, p99 covers the 100ms outlier
    assert 1e-3 <= snap["p50_s"] < 2e-3
    assert 0.1 <= snap["p99_s"] < 0.2
    assert snap["p999_s"] >= 1.0
    assert snap["max_s"] == 1.0


def test_serve_metrics_occupancy_split():
    from repro.serve import ServeMetrics

    m = ServeMetrics()
    m.note_submit(6)
    m.note_flush("sssp", "full", 4, 4, [0.001] * 4, 0.010)
    m.note_flush("sssp", "deadline", 2, 4, [0.005] * 2, 0.010)
    snap = m.snapshot()
    assert snap["completed"] == 6 and snap["in_flight"] == 0
    assert snap["flush_reasons"] == {"full": 1, "deadline": 1}
    b = snap["buckets"]["sssp/b4"]
    assert b["flushes"] == 2 and b["requests"] == 6
    assert b["mean_occupancy"] == pytest.approx(0.75)
    assert snap["queue_wait"]["count"] == 6


# --------------------------------------------------------------------------
# cross-process boot + distributed front-end (slow: subprocesses)
# --------------------------------------------------------------------------

BOOT_CHILD = textwrap.dedent("""
    import sys
    import numpy as np
    from repro.core import Engine
    from repro.data import powerlaw_hypergraph
    from repro.algorithms import shortest_paths_spec, random_walk_spec
    from repro.serve import DiskExecutableCache, warm

    phase = sys.argv[1]
    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    eng = Engine(disk_cache=DiskExecutableCache(sys.argv[2]))
    specs = [shortest_paths_spec(hg, 0, 12),
             random_walk_spec(hg, iters=6)]
    # replay boots under the runtime retrace guard: RetraceError here
    # means the store missed across the process boundary
    rep = warm(eng, specs, batch_sizes=(8,), queries=[0, 0],
               require_no_retrace=(phase != 'populate'))
    if phase == 'populate':
        assert rep['traces'] > 0, rep
        assert rep['compiled'] == 4, rep
    else:
        assert rep['from_disk'] == 4, rep
    res = eng.compile(specs[0]).run_batch(np.arange(8, dtype=np.int32))
    if phase != 'populate':
        assert eng.cache_stats()['traces'] == 0, eng.cache_stats()
    np.save(sys.argv[3], np.asarray(res.value[0]))
    print('BOOT_OK', rep['traces'], rep['from_disk'])
""")


@pytest.mark.slow
def test_second_process_boots_from_disk_cache(tmp_path):
    def child(phase, out):
        proc = subprocess.run(
            [sys.executable, "-c", BOOT_CHILD, phase, str(tmp_path),
             str(out)],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "PYTHONPATH": "src"},
            cwd=__file__.rsplit("/tests/", 1)[0],
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "BOOT_OK" in proc.stdout
        return proc.stdout

    child("populate", tmp_path / "a.npy")
    out = child("boot", tmp_path / "b.npy")
    assert "BOOT_OK 0 4" in out
    np.testing.assert_array_equal(np.load(tmp_path / "a.npy"),
                                  np.load(tmp_path / "b.npy"))


SHARDED_FRONTEND = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import Engine
    from repro.data import powerlaw_hypergraph
    from repro.algorithms import shortest_paths_spec
    from repro.serve import Frontend

    mesh = Mesh(np.array(jax.devices()).reshape(4), ('data',))
    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    eng = Engine(mesh=mesh, backend='sharded')
    fe = Frontend(eng, max_batch=8, max_delay_ms=2.0)
    fe.register('sssp', shortest_paths_spec(hg, 0, 12))
    sources = np.arange(11, dtype=np.int32) % hg.n_vertices
    with fe:
        futs = [fe.submit('sssp', query=int(s)) for s in sources]
        results = [f.result(timeout=300) for f in futs]
    comp = fe.compiled('sssp')
    for s, served in zip(sources, results):
        ref = comp.run(query=int(s)).value
        for a, b in zip(jax.tree.leaves(ref),
                        jax.tree.leaves(served.value)):
            assert np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True), int(s)
    print('FRONTEND_SHARDED_AGREES')
""")


@pytest.mark.slow
def test_frontend_sharded_backend_subprocess():
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED_FRONTEND],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FRONTEND_SHARDED_AGREES" in proc.stdout


# --------------------------------------------------------------------------
# adaptive flush deadline (the bounded EWMA controller; off by default)
# --------------------------------------------------------------------------

@given(
    st.lists(
        st.tuples(
            st.floats(0.0, 0.2),                      # execute_s
            st.floats(0.0, 1.0),                      # occupancy
            st.sampled_from(["full", "deadline", "drain"]),
        ),
        min_size=1, max_size=60,
    ),
    st.floats(1e-5, 1.0),                             # initial delay
)
@settings(max_examples=80, deadline=None)
def test_adaptive_delay_always_in_bounds(stream, d0):
    from repro.serve import AdaptiveDelay

    ad = AdaptiveDelay(d0, lo_s=1e-3, hi_s=2e-2)
    for execute_s, occupancy, reason in stream:
        d = ad.observe(
            execute_s=execute_s, occupancy=occupancy, reason=reason
        )
        assert 1e-3 <= d <= 2e-2
        assert d == ad.delay_s
    assert ad.observations == len(stream)
    snap = ad.snapshot()
    assert snap["lo_s"] == 1e-3 and snap["hi_s"] == 2e-2


def test_adaptive_delay_converges_down_under_full_flushes():
    from repro.serve import AdaptiveDelay

    ad = AdaptiveDelay(0.02, lo_s=1e-3, hi_s=2e-2)
    for _ in range(50):
        ad.observe(execute_s=0.005, occupancy=1.0, reason="full")
    assert ad.delay_s <= 1.2e-3  # geometrically onto the floor


def test_adaptive_delay_grows_toward_execute_cost_when_starved():
    from repro.serve import AdaptiveDelay

    ad = AdaptiveDelay(0.002, lo_s=1e-3, hi_s=5e-2)
    # mostly-empty deadline flushes with a 30ms execute: waiting up to
    # one execute is worth it, so the delay climbs toward 30ms.
    for _ in range(60):
        ad.observe(execute_s=0.03, occupancy=0.1, reason="deadline")
    assert ad.delay_s == pytest.approx(0.03, rel=0.1)
    # well-filled deadline flushes hold rather than drift
    held = ad.delay_s
    for _ in range(10):
        ad.observe(execute_s=0.03, occupancy=0.9, reason="deadline")
    assert ad.delay_s == pytest.approx(held, rel=1e-6)


def test_adaptive_delay_validates_parameters():
    from repro.serve import AdaptiveDelay

    with pytest.raises(ValueError, match="lo_s"):
        AdaptiveDelay(0.01, lo_s=0.0)
    with pytest.raises(ValueError, match="lo_s"):
        AdaptiveDelay(0.01, lo_s=0.1, hi_s=0.01)
    with pytest.raises(ValueError, match="gain"):
        AdaptiveDelay(0.01, gain=0.0)


def test_frontend_adaptive_delay_shrinks_on_full_traffic():
    clock = FakeClock()
    fe = Frontend(
        Engine(), max_batch=4, max_delay_ms=20.0, clock=clock,
        adaptive_delay=True, min_delay_ms=1.0,
    )
    fe.register("sssp", FakeCompiled(1000))
    assert fe.current_delay_ms == pytest.approx(20.0)
    for _ in range(20):  # every flush full: waiting buys nothing
        for q in range(4):
            fe.submit("sssp", query=q)
        fe.pump(drain=True)
    assert fe.current_delay_ms < 2.0
    snap = fe.stats()["adaptive_delay"]
    assert snap is not None and snap["observations"] == 20
    # error flushes must not feed the controller
    class Broken:
        def run_batch(self, queries, hg=None):
            raise RuntimeError("boom")

    fe.register("bad", Broken())
    fe.submit("bad", query=1)
    fe.pump(drain=True)
    assert fe.stats()["adaptive_delay"]["observations"] == 20


def test_frontend_adaptive_delay_off_by_default():
    fe = Frontend(Engine(), max_batch=4, max_delay_ms=7.0,
                  clock=FakeClock())
    assert fe.stats()["adaptive_delay"] is None
    assert fe.current_delay_ms == pytest.approx(7.0)


# --------------------------------------------------------------------------
# warmup-record fallback: platforms where serialize_executable fails
# --------------------------------------------------------------------------

def test_disk_cache_warmup_record_fallback(tmp_path, monkeypatch):
    """When ``serialize_executable.serialize`` raises (platforms that
    cannot round-trip executables), ``store`` degrades to a warmup
    record, boot still works, and a second replica re-traces instead of
    crashing on the record."""
    from jax.experimental import serialize_executable as se
    from repro.algorithms import shortest_paths_spec

    def boom(compiled):
        raise RuntimeError("platform cannot serialize executables")

    monkeypatch.setattr(se, "serialize", boom)
    hg = powerlaw_hypergraph(61, 37, mean_cardinality=4, seed=1)
    spec = shortest_paths_spec(hg, 0, 6)

    eng1 = Engine(disk_cache=DiskExecutableCache(tmp_path))
    report = warm(eng1, [spec], batch_sizes=(4,), queries=[0])
    s1 = eng1.disk_cache.stats()
    assert report["from_disk"] == 0
    assert s1["disk_stores"] == 0          # nothing fully serialized
    assert s1["disk_errors"] >= 1          # every store degraded
    assert s1["entries"] >= 1              # ... to on-disk warmup records
    res1 = eng1.compile(spec).run_batch(np.asarray([0, 1], np.int32))
    assert res1.value is not None

    # second replica, same dir: loads see warmup records (not payloads),
    # recompile, and still serve.
    eng2 = Engine(disk_cache=DiskExecutableCache(tmp_path))
    report2 = warm(eng2, [spec], batch_sizes=(4,), queries=[0])
    s2 = eng2.disk_cache.stats()
    assert report2["from_disk"] == 0
    assert s2["warm_records"] >= 1
    assert s2["disk_hits"] == 0
    res2 = eng2.compile(spec).run_batch(np.asarray([0, 1], np.int32))
    import jax

    for a, b in zip(jax.tree.leaves(res1.value),
                    jax.tree.leaves(res2.value)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
