"""Fault-tolerance suite: injection, serve resilience, checkpoint/resume.

The tentpole invariant, asserted three ways:

* **everything resolves** — under ANY injected fault plan, every
  submitted request's future resolves (a result or a typed
  ``FaultError``); nothing hangs, nothing is silently dropped
  (the chaos property test);
* **bitwise on success** — every successfully served value equals the
  sequential fault-free path exactly;
* **resume == uninterrupted** — a checkpointed run killed mid-algorithm
  and resumed produces bitwise-identical results to a run that was
  never interrupted (local here; sharded subprocess in the slow suite).

Plus the unit contracts of each resilience mechanism: deterministic
trigger schedules, retry-with-backoff, batch bisect poison isolation,
circuit breaker, worker supervisor, disk-cache quarantine + checksum
migration, and the closed-front-end guarantees.
"""
import os
import pickle
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import Engine
from repro.data import powerlaw_hypergraph
from repro.faults import (
    CircuitOpen,
    DeadlineExceeded,
    FaultError,
    FaultInjector,
    FaultPlan,
    FaultRule,
    FrontendClosed,
    InjectedFault,
    PoisonQuery,
    TransientExecuteError,
    is_transient,
)
from repro.serve import DiskExecutableCache, Frontend, warm
from repro.serve.cache import stable_digest


def _tree_equal(a, b) -> bool:
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb)
    )


# --------------------------------------------------------------------------
# FaultPlan: schedules + JSON round trip
# --------------------------------------------------------------------------

def test_plan_json_round_trip():
    plan = FaultPlan((
        FaultRule(point="execute", trigger="nth", n=3, error="fatal"),
        FaultRule(point="serve.flush", trigger="prob", p=0.25, seed=7,
                  times=2),
        FaultRule(point="disk.read", trigger="every", n=2,
                  error="corrupt"),
    ))
    assert FaultPlan.from_json(plan.to_json()) == plan
    # dict / list forms are accepted too
    assert FaultPlan.from_json({"rules": [r.to_dict() for r in plan.rules]}) \
        == plan
    assert FaultPlan.from_json([r.to_dict() for r in plan.rules]) == plan


def test_rule_validation():
    with pytest.raises(ValueError, match="unknown trigger"):
        FaultRule(point="execute", trigger="sometimes")
    with pytest.raises(ValueError, match="needs n"):
        FaultRule(point="execute", trigger="nth")
    with pytest.raises(ValueError, match="needs p"):
        FaultRule(point="execute", trigger="prob")
    with pytest.raises(ValueError, match="unknown error kind"):
        FaultRule(point="execute", error="explosive")
    with pytest.raises(ValueError, match="unknown FaultRule fields"):
        FaultRule.from_dict({"point": "execute", "when": "later"})


def test_plan_validate_flags_unknown_points():
    plan = FaultPlan((
        FaultRule(point="execute"),
        FaultRule(point="warp.core"),
    ))
    warnings = plan.validate()
    assert len(warnings) == 1 and "warp.core" in warnings[0]


def test_plan_validate_lists_point_inventory():
    # the warning alone is enough to fix a typo'd plan: it quotes the
    # full instrumented-point inventory
    from repro.faults import FAULT_POINTS

    warnings = FaultPlan((FaultRule(point="warp.core"),)).validate()
    for point in FAULT_POINTS:
        assert point in warnings[0]
    assert "replica.crash" in warnings[0]       # the new replica points
    assert "router.route" in warnings[0]


# --------------------------------------------------------------------------
# FaultInjector: deterministic triggers, taxonomy mapping
# --------------------------------------------------------------------------

def _fire_pattern(inj: FaultInjector, point: str, n: int) -> list:
    out = []
    for _ in range(n):
        try:
            inj.maybe_raise(point)
            out.append(None)
        except FaultError as err:
            out.append(type(err).__name__)
    return out


def test_injector_always_nth_every_times():
    inj = FaultInjector(FaultPlan((
        FaultRule(point="a", trigger="always", times=2),
        FaultRule(point="b", trigger="nth", n=3),
        FaultRule(point="c", trigger="every", n=2),
    )))
    t = "TransientExecuteError"
    assert _fire_pattern(inj, "a", 4) == [t, t, None, None]
    assert _fire_pattern(inj, "b", 4) == [None, None, t, None]
    assert _fire_pattern(inj, "c", 5) == [None, t, None, t, None]
    # untargeted points never fire, but calls are still counted
    assert _fire_pattern(inj, "z", 2) == [None, None]
    snap = inj.snapshot()
    assert snap["calls"] == {"a": 4, "b": 4, "c": 5, "z": 2}
    assert snap["fired"] == {"a": 2, "b": 1, "c": 2}
    assert snap["never_fired"] == []         # every planned point fired


def test_snapshot_reports_never_fired_points():
    # a plan whose rule never triggers (nth call never reached) shows up
    # in never_fired — chaos CI asserts on this to prove the plan
    # actually exercised its scheduled failures
    inj = FaultInjector(FaultPlan((
        FaultRule(point="a", trigger="always", times=1),
        FaultRule(point="b", trigger="nth", n=100),
    )))
    _fire_pattern(inj, "a", 2)
    _fire_pattern(inj, "b", 2)
    snap = inj.snapshot()
    assert snap["never_fired"] == ["b"]


def test_injector_prob_is_deterministic_per_seed():
    plan = FaultPlan((
        FaultRule(point="x", trigger="prob", p=0.4, seed=11),
    ))
    p1 = _fire_pattern(FaultInjector(plan), "x", 64)
    p2 = _fire_pattern(FaultInjector(plan), "x", 64)
    assert p1 == p2                      # same plan, same traffic, same faults
    assert any(p1) and not all(p1)       # p=0.4 over 64 draws: mixed
    reseeded = FaultPlan((
        FaultRule(point="x", trigger="prob", p=0.4, seed=12),
    ))
    assert _fire_pattern(FaultInjector(reseeded), "x", 64) != p1


def test_injector_error_kinds_map_to_taxonomy():
    inj = FaultInjector(FaultPlan((
        FaultRule(point="t", error="transient"),
        FaultRule(point="f", error="fatal"),
        FaultRule(point="c", error="corrupt"),
    )))
    with pytest.raises(TransientExecuteError) as e1:
        inj.maybe_raise("t")
    assert is_transient(e1.value)
    with pytest.raises(InjectedFault) as e2:
        inj.maybe_raise("f")
    assert not is_transient(e2.value) and e2.value.point == "f"
    with pytest.raises(FaultError, match="corrupt"):
        inj.maybe_raise("c")
    # every taxonomy error is a RuntimeError: pre-taxonomy callers work
    with pytest.raises(RuntimeError):
        inj.maybe_raise("f")


# --------------------------------------------------------------------------
# serve-tier resilience (fake compiled, fake clock — no jax dispatch)
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
    """``run_batch`` double: rows are a pure function of the query."""

    def __init__(self, salt):
        self.salt = salt

    def _one(self, q):
        return {"out": np.asarray([q * 2 + self.salt, q], np.int64)}

    def run(self, query=None, hg=None):
        return FakeResult(self._one(int(query)))

    def run_batch(self, queries, hg=None):
        qs = np.asarray(queries)
        rows = [self._one(int(q)) for q in qs]
        return FakeResult({"out": np.stack([r["out"] for r in rows])})


class FlakyCompiled(FakeCompiled):
    """Fails transiently the first ``fail_first`` run_batch calls."""

    def __init__(self, salt, fail_first):
        super().__init__(salt)
        self.fail_first = fail_first
        self.calls = 0

    def run_batch(self, queries, hg=None):
        self.calls += 1
        if self.calls <= self.fail_first:
            raise TransientExecuteError(f"flaky call #{self.calls}")
        return super().run_batch(queries, hg=hg)


class PoisonCompiled(FakeCompiled):
    """Deterministically fails any batch containing ``poison``."""

    def __init__(self, salt, poison):
        super().__init__(salt)
        self.poison = poison

    def run_batch(self, queries, hg=None):
        if self.poison in set(np.asarray(queries).tolist()):
            raise RuntimeError(f"poisoned by {self.poison}")
        return super().run_batch(queries, hg=hg)


def _frontend(compiled, **kw):
    kw.setdefault("clock", FakeClock())
    kw.setdefault("max_batch", 4)
    kw.setdefault("retry_backoff_ms", 0.0)
    fe = Frontend(Engine(), **kw)
    fe._sleep = lambda s: None   # retries without wall-clock waits
    fe.register("k", compiled)
    return fe


def _counter(fe, name):
    return fe.metrics.registry.counter(name).value


def test_closed_frontend_fails_queued_and_rejects_new():
    fe = _frontend(FakeCompiled(10))
    f1, f2 = fe.submit("k", query=1), fe.submit("k", query=2)
    fe.close()
    for f in (f1, f2):
        assert f.done()
        with pytest.raises(FrontendClosed, match="still queued"):
            f.result(timeout=0)
    with pytest.raises(FrontendClosed):
        fe.submit("k", query=3)
    with pytest.raises(FrontendClosed):
        fe.register("k2", FakeCompiled(11))
    snap = fe.stats()
    assert snap["errors"] == 2 and snap["in_flight"] == 0


def test_deadline_exceeded_resolves_typed():
    fe = _frontend(FakeCompiled(10))
    late = fe.submit("k", query=1, timeout_ms=5.0)
    ok = fe.submit("k", query=2)
    fe.clock.t += 1.0              # blow way past the 5ms hard deadline
    fe.pump(drain=True)
    with pytest.raises(DeadlineExceeded, match="past its deadline"):
        late.result(timeout=0)
    assert ok.result(timeout=0).value["out"][1] == 2
    assert fe.stats()["in_flight"] == 0


def test_retry_serves_after_transient_failures():
    flaky = FlakyCompiled(10, fail_first=2)
    fe = _frontend(flaky, max_retries=2)
    before = _counter(fe, "faults.serve.retries")
    fut = fe.submit("k", query=5)
    fe.pump(drain=True)
    assert fut.result(timeout=0).value["out"][0] == 20
    assert flaky.calls == 3
    assert _counter(fe, "faults.serve.retries") - before == 2


def test_retry_gives_up_past_max_retries():
    flaky = FlakyCompiled(10, fail_first=10)
    fe = _frontend(flaky, max_retries=2)
    fut = fe.submit("k", query=5)
    fe.pump(drain=True)
    with pytest.raises(TransientExecuteError):
        fut.result(timeout=0)
    assert flaky.calls == 3        # 1 attempt + 2 retries, then surface


def test_bisect_isolates_poison_query():
    fe = _frontend(PoisonCompiled(10, poison=2))
    before = _counter(fe, "faults.serve.bisects")
    futs = {q: fe.submit("k", query=q) for q in (0, 1, 2, 3)}
    fe.pump(drain=True)
    for q, fut in futs.items():
        if q == 2:
            with pytest.raises(PoisonQuery, match="poisoned") as exc:
                fut.result(timeout=0)
            assert "poisoned by 2" in str(exc.value.__cause__)
        else:
            assert fut.result(timeout=0).value["out"][1] == q
    assert _counter(fe, "faults.serve.bisects") - before >= 1
    snap = fe.stats()
    assert snap["completed"] == 3 and snap["errors"] == 1
    assert snap["in_flight"] == 0


def test_circuit_breaker_trips_cools_down_and_probes():
    class Togglable(FakeCompiled):
        broken = True

        def run_batch(self, queries, hg=None):
            if self.broken:
                raise RuntimeError("hard down")
            return super().run_batch(queries, hg=hg)

    dbl = Togglable(10)
    fe = _frontend(dbl, breaker_threshold=2, breaker_cooldown_ms=1000.0)
    trips0 = _counter(fe, "faults.serve.breaker_trips")
    for _ in range(2):             # two consecutive failures: trip
        fut = fe.submit("k", query=1)
        fe.pump(drain=True)
        with pytest.raises(RuntimeError, match="hard down"):
            fut.result(timeout=0)
    assert _counter(fe, "faults.serve.breaker_trips") - trips0 == 1
    # open: fail fast, the (still broken) executable is not even called
    fast = fe.submit("k", query=1)
    fe.pump(drain=True)
    with pytest.raises(CircuitOpen, match="circuit open"):
        fast.result(timeout=0)
    # cooldown elapses; the half-open probe reaches a now-healthy path
    dbl.broken = False
    fe.clock.t += 2.0
    probe = fe.submit("k", query=7)
    fe.pump(drain=True)
    assert probe.result(timeout=0).value["out"][1] == 7
    assert fe.stats()["in_flight"] == 0


def test_worker_supervisor_restarts_and_requeues():
    inj = FaultInjector(FaultPlan((
        FaultRule(point="serve.worker", trigger="nth", n=1),
    )))
    fe = Frontend(Engine(), max_batch=4, max_delay_ms=1.0,
                  fault_injector=inj)
    fake = FakeCompiled(100)
    fe.register("k", fake)
    restarts0 = fe.metrics.registry.counter(
        "faults.serve.worker_restarts").value
    with fe:
        futs = [fe.submit("k", query=q) for q in (3, 4, 5)]
        results = [f.result(timeout=120) for f in futs]
    for q, served in zip((3, 4, 5), results):
        assert _tree_equal(served.value, fake.run(query=q).value)
    assert fe.metrics.registry.counter(
        "faults.serve.worker_restarts").value - restarts0 >= 1
    assert inj.fired("serve.worker") == 1
    assert fe.stats()["in_flight"] == 0


def test_repeated_worker_crash_bounds_requeues():
    inj = FaultInjector(FaultPlan((
        FaultRule(point="serve.worker", trigger="always", error="fatal"),
    )))
    fe = Frontend(Engine(), max_batch=4, max_delay_ms=1.0,
                  fault_injector=inj)
    fe.register("k", FakeCompiled(100))
    with fe:
        fut = fe.submit("k", query=1)
        # the supervisor gives up after MAX_REQUEUES: the future resolves
        # with the crash instead of looping forever
        with pytest.raises(InjectedFault, match="serve.worker"):
            fut.result(timeout=120)
    assert fe.stats()["in_flight"] == 0


# --------------------------------------------------------------------------
# chaos property: random fault plans x arrival orders — everything
# resolves; successes are bitwise-equal to the sequential path
# --------------------------------------------------------------------------

_CHAOS_RULE = st.tuples(
    st.sampled_from(["serve.flush", "serve.flush", "execute"]),
    st.sampled_from(["always", "nth", "every", "prob"]),
    st.integers(1, 3),                    # n (nth / every)
    st.floats(0.0, 0.6),                  # p (prob)
    st.integers(0, 99),                   # seed
    st.sampled_from([1, 2, 3, None]),     # times
    st.sampled_from(["transient", "transient", "fatal"]),
)

_CHAOS_TRAFFIC = st.lists(
    st.tuples(
        st.sampled_from(["sssp", "ppr"]),   # signature
        st.integers(0, 30),                 # query
        st.floats(0.0, 0.01),               # inter-arrival
        st.booleans(),                      # pump mid-stream?
    ),
    min_size=1, max_size=40,
)


@given(st.lists(_CHAOS_RULE, min_size=0, max_size=3), _CHAOS_TRAFFIC)
@settings(max_examples=40, deadline=None)
def test_chaos_every_request_resolves_bitwise_on_success(raw_rules, events):
    rules = tuple(
        FaultRule(point=point, trigger=trigger, n=n, p=p, seed=seed,
                  times=times, error=error)
        for point, trigger, n, p, seed, times, error in raw_rules
    )
    inj = FaultInjector(FaultPlan(rules))
    clock = FakeClock()
    fe = Frontend(Engine(), max_batch=4, max_delay_ms=5.0, clock=clock,
                  retry_backoff_ms=0.0, fault_injector=inj)
    fe._sleep = lambda s: None
    fakes = {"sssp": FakeCompiled(1000), "ppr": FakeCompiled(7000)}
    for key, fake in fakes.items():
        fe.register(key, fake)

    futs = []
    for key, query, dt, do_pump in events:
        clock.t += dt
        futs.append((key, query, fe.submit(key, query=query)))
        if do_pump:
            fe.pump()
    clock.t += 10.0
    fe.pump(drain=True)

    served_ok = 0
    for key, query, fut in futs:
        assert fut.done()        # NOTHING hangs, whatever the plan did
        err = fut.exception(timeout=0)
        if err is None:
            served = fut.result(timeout=0)
            expected = fakes[key].run(query=query).value
            np.testing.assert_array_equal(served.value["out"],
                                          expected["out"])
            served_ok += 1
        else:
            assert isinstance(err, RuntimeError)   # typed, catchable
    snap = fe.stats()
    assert snap["submitted"] == len(futs)
    assert snap["completed"] == served_ok
    assert snap["in_flight"] == 0
    if not rules:
        assert served_ok == len(futs)   # fault-free plans serve everything


# --------------------------------------------------------------------------
# disk-cache integrity: quarantine, checksum, migration
# --------------------------------------------------------------------------

def test_cache_quarantines_garbage_file(tmp_path):
    cache = DiskExecutableCache(tmp_path)
    key = ("unit", "garbage")
    path = cache._path(stable_digest(key))
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(b"not a pickle at all")
    assert cache.load(key) is None
    st_ = cache.stats()
    assert st_["disk_errors"] == 1 and st_["disk_quarantined"] == 1
    assert not path.exists()
    assert path.with_name(path.name + ".corrupt").exists()
    # quarantined: the next load is a clean miss, not another error
    assert cache.load(key) is None
    assert cache.stats()["disk_errors"] == 1


def test_cache_rejects_unknown_format(tmp_path):
    cache = DiskExecutableCache(tmp_path)
    key = ("unit", "foreign")
    path = cache._path(stable_digest(key))
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(pickle.dumps({"format": "alien", "serialized": b""}))
    assert cache.load(key) is None
    assert cache.stats()["disk_quarantined"] == 1


def test_cache_checksum_detects_bitrot_and_migrates_legacy(tmp_path):
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    eng = Engine(disk_cache=DiskExecutableCache(tmp_path))
    rep = warm(eng, [shortest_paths_spec(hg, 0, 12)], batch_sizes=(8,))
    assert rep["traces"] > 0
    entries = sorted(tmp_path.rglob("*.jexe"))
    assert entries
    victim = entries[0]
    payload = pickle.loads(victim.read_bytes())
    assert payload["format"] == "xla-executable"
    assert payload.get("checksum")       # stores are checksummed now

    # Bit-rot: flip bytes but keep the recorded checksum
    rotten = dict(payload)
    rotten["serialized"] = b"\x00" * 16 + payload["serialized"][16:]
    victim.write_bytes(pickle.dumps(rotten))
    cache2 = DiskExecutableCache(tmp_path)
    eng2 = Engine(disk_cache=cache2)
    rep2 = warm(eng2, [shortest_paths_spec(hg, 0, 12)], batch_sizes=(8,))
    st2 = cache2.stats()
    assert st2["disk_quarantined"] >= 1
    assert victim.with_name(victim.name + ".corrupt").exists()
    assert rep2["traces"] > 0            # recompiled past the rot
    # the recompile re-published a GOOD entry in the victim's place
    # (fresh serialized bytes, so a fresh — but self-consistent — sum)
    from repro.serve.cache import _checksum

    republished = pickle.loads(victim.read_bytes())
    assert _checksum(republished["serialized"]) == republished["checksum"]

    # Legacy migration: strip a checksum; the next load verifies the
    # round-trip, serves the hit, and upgrades the entry in place
    other = sorted(tmp_path.rglob("*.jexe"))[-1]
    legacy = pickle.loads(other.read_bytes())
    legacy.pop("checksum")
    other.write_bytes(pickle.dumps(legacy))
    cache3 = DiskExecutableCache(tmp_path)
    eng3 = Engine(disk_cache=cache3)
    warm(eng3, [shortest_paths_spec(hg, 0, 12)], batch_sizes=(8,))
    st3 = cache3.stats()
    assert st3["disk_hits"] >= 1 and st3["disk_migrated"] >= 1
    assert pickle.loads(other.read_bytes()).get("checksum")


# --------------------------------------------------------------------------
# graceful degradation: fused failures fall back to xla delivery
# --------------------------------------------------------------------------

def test_execute_fault_degrades_fused_to_xla_bitwise():
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    spec = shortest_paths_spec(hg, 0, 12)
    ref = Engine().compile(spec, delivery="xla").run(query=3)

    inj = FaultInjector(FaultPlan((
        FaultRule(point="execute", trigger="nth", n=1, error="fatal"),
    )))
    eng = Engine(fault_injector=inj)
    comp = eng.compile(spec, delivery="pallas_fused")
    degraded0 = eng.metrics.counter("faults.delivery_degraded").value
    got = comp.run(query=3)
    assert _tree_equal(got.value, ref.value)
    assert got.decision.get("degraded_from") == "pallas_fused"
    assert eng.metrics.counter(
        "faults.delivery_degraded").value - degraded0 == 1
    # degradation is per-request, not sticky: the injector's nth=1 rule
    # is spent, so the next run serves fused again — same numbers
    again = comp.run(query=3)
    assert _tree_equal(again.value, ref.value)
    assert "degraded_from" not in again.decision


def test_layout_fault_degrades_fused_to_xla():
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    spec = shortest_paths_spec(hg, 0, 12)
    ref = Engine().compile(spec, delivery="xla").run(query=5)
    inj = FaultInjector(FaultPlan((
        FaultRule(point="layout.build", trigger="always", error="fatal"),
    )))
    eng = Engine(fault_injector=inj)
    got = eng.compile(spec, delivery="pallas_fused").run(query=5)
    assert _tree_equal(got.value, ref.value)
    assert got.decision.get("degraded_from") == "pallas_fused"


@pytest.mark.parametrize("disk", [False, True])
def test_compile_error_propagates_without_degrading(tmp_path, monkeypatch,
                                                    disk):
    """A lowering or compile error (Mosaic, XLA) fails the request: it is
    never served by the xla twin, and never by a plain-jit fallback."""
    from repro.algorithms import shortest_paths_spec
    from repro.core.serving import AotExecutable

    def refuse(self, args):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(AotExecutable, "_materialize", refuse)
    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    eng = Engine(disk_cache=DiskExecutableCache(tmp_path) if disk else None)
    comp = eng.compile(shortest_paths_spec(hg, 0, 12),
                       delivery="pallas_fused")
    degraded0 = eng.metrics.counter("faults.delivery_degraded").value
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        comp.run(query=3)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        comp.run_batch(np.asarray([1, 2], np.int32))
    assert eng.metrics.counter(
        "faults.delivery_degraded").value == degraded0
    assert eng.cache_stats()["sources"] == {}
    if disk:
        assert eng.disk_cache.stats()["disk_stores"] == 0


def test_injected_aot_compile_fault_propagates(tmp_path):
    from repro.algorithms import shortest_paths_spec

    inj = FaultInjector(FaultPlan((
        FaultRule(point="compile.aot", trigger="always", error="fatal"),
    )))
    eng = Engine(disk_cache=DiskExecutableCache(tmp_path),
                 fault_injector=inj)
    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    with pytest.raises(InjectedFault):
        eng.compile(shortest_paths_spec(hg, 0, 12)).run(query=1)
    assert eng.cache_stats()["sources"] == {}


# --------------------------------------------------------------------------
# checkpoint/resume: chunked == uninterrupted, bitwise
# --------------------------------------------------------------------------

def test_checkpointed_run_bitwise_equals_plain(tmp_path):
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    spec = shortest_paths_spec(hg, 0, 8)
    eng = Engine()
    plain = eng.run(spec, max_iters=8)
    ck = eng.run(spec, max_iters=8, checkpoint_every=3,
                 checkpoint_dir=str(tmp_path / "ck"))
    assert _tree_equal(ck.value, plain.value)
    steps = sorted(p.name for p in (tmp_path / "ck").iterdir())
    assert steps and steps[0] == "step_00000003"


def test_kill_and_resume_bitwise_equals_uninterrupted(tmp_path):
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    spec = shortest_paths_spec(hg, 0, 8)
    baseline = Engine().run(spec, max_iters=8)

    ckdir = str(tmp_path / "ck")
    inj = FaultInjector(FaultPlan((
        FaultRule(point="checkpoint.chunk", trigger="nth", n=1,
                  error="fatal"),
    )))
    dead = Engine(fault_injector=inj)
    with pytest.raises(InjectedFault, match="checkpoint.chunk"):
        dead.run(spec, max_iters=8, checkpoint_every=3,
                 checkpoint_dir=ckdir)
    # the first chunk's snapshot survived the crash
    assert (tmp_path / "ck" / "step_00000003").exists()

    fresh = Engine()
    restored0 = fresh.metrics.counter("faults.checkpoint.restored").value
    resumed = fresh.run(spec, max_iters=8, checkpoint_every=3,
                        checkpoint_dir=ckdir)
    assert fresh.metrics.counter(
        "faults.checkpoint.restored").value - restored0 == 1
    assert _tree_equal(resumed.value, baseline.value)


def test_corrupt_checkpoint_degrades_to_fresh_start(tmp_path):
    from repro.algorithms import shortest_paths_spec

    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    spec = shortest_paths_spec(hg, 0, 8)
    baseline = Engine().run(spec, max_iters=8)

    junk = tmp_path / "ck" / "step_00000003"
    junk.mkdir(parents=True)
    (junk / "manifest.json").write_text("{ not json")
    eng = Engine()
    failed0 = eng.metrics.counter(
        "faults.checkpoint.restore_failed").value
    res = eng.run(spec, max_iters=8, checkpoint_every=3,
                  checkpoint_dir=str(tmp_path / "ck"))
    assert eng.metrics.counter(
        "faults.checkpoint.restore_failed").value - failed0 == 1
    assert _tree_equal(res.value, baseline.value)


# --------------------------------------------------------------------------
# sharded kill-and-resume (subprocess: forced host devices) — slow suite
# --------------------------------------------------------------------------

_SHARDED_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import Engine
    from repro.data import powerlaw_hypergraph
    from repro.algorithms import shortest_paths_spec
    from repro.faults import FaultInjector, FaultPlan, FaultRule, \\
        InjectedFault
    from repro.partition import partition

    ckdir = sys.argv[1]
    phase = sys.argv[2]
    hg = powerlaw_hypergraph(61, 41, mean_cardinality=4, seed=1)
    spec = shortest_paths_spec(hg, 0, 8)
    mesh = Mesh(np.array(jax.devices()).reshape(4), ('data',))
    plan = partition('random_vertex_cut', hg, 4)

    if phase == 'kill':
        inj = FaultInjector(FaultPlan((
            FaultRule(point='checkpoint.chunk', trigger='nth', n=1,
                      error='fatal'),
        )))
        eng = Engine(plan=plan, mesh=mesh, backend='sharded',
                     fault_injector=inj)
        try:
            eng.run(spec, max_iters=8, checkpoint_every=3,
                    checkpoint_dir=ckdir)
        except InjectedFault:
            print('KILLED_AFTER_CHUNK')
            sys.exit(0)
        sys.exit(3)  # the fault did not fire
    else:
        eng = Engine(plan=plan, mesh=mesh, backend='sharded')
        resumed = eng.run(spec, max_iters=8, checkpoint_every=3,
                          checkpoint_dir=ckdir)
        local = Engine().run(spec, max_iters=8)
        ok = all(
            np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)
            for a, b in zip(jax.tree.leaves(resumed.value),
                            jax.tree.leaves(local.value))
        )
        restored = eng.metrics.counter(
            'faults.checkpoint.restored').value
        assert restored == 1, restored
        print('RESUMED_BITWISE' if ok else 'MISMATCH')
""")


@pytest.mark.slow
def test_sharded_kill_and_resume_bitwise(tmp_path):
    env = {**os.environ, "PYTHONPATH": "src"}
    cwd = __file__.rsplit("/tests/", 1)[0]
    ckdir = str(tmp_path / "ck")
    p1 = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, ckdir, "kill"],
        capture_output=True, text=True, timeout=900, env=env, cwd=cwd,
    )
    assert p1.returncode == 0, p1.stderr[-3000:]
    assert "KILLED_AFTER_CHUNK" in p1.stdout
    assert (tmp_path / "ck" / "step_00000003").exists()
    p2 = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, ckdir, "resume"],
        capture_output=True, text=True, timeout=900, env=env, cwd=cwd,
    )
    assert p2.returncode == 0, p2.stderr[-3000:]
    assert "RESUMED_BITWISE" in p2.stdout
