"""Multi-replica serving: the Router's failover contract, chaos-tested.

The PR 9 invariant crossed the process boundary: every request admitted
by the ``Router`` resolves — a value or a typed error — no matter which
replicas die, when, or how (kill -9, wedged-without-exiting, broken
pipe).  Asserted at three depths:

* fake-clock unit tests against in-memory ``FakeReplica`` handles: no
  processes, no threads, no sleeps — heartbeat expiry, bounded failover
  (``ReplicaLost`` after ``MAX_FAILOVERS``), load shedding
  (``Overloaded``), ``close()`` draining (``FrontendClosed``),
  affinity/least-loaded routing, respawn, the ``router.route`` fault
  point;
* a chaos property: random kill schedules x arrival orders x completion
  interleavings — every future resolves, successes equal the
  deterministic sequential value, ``in_flight == 0`` at drain;
* slow subprocess integration: real replica processes over the real
  shared disk store, one killed -9 mid-replay — survivors' results
  bitwise equal the parent's sequential runs and the respawn boots from
  disk with zero retraces.  Plus the cross-process ``cache.lock`` store
  stress (two simultaneous ``serve.warm`` on one empty dir).
"""
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.faults import (
    FaultInjector,
    FaultPlan,
    FaultRule,
    FrontendClosed,
    InjectedFault,
    Overloaded,
    ReplicaLost,
)
from repro.obs.metrics import MetricsRegistry
from repro.serve.router import MAX_FAILOVERS, Router

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# fakes: a replica handle and a clock, both fully deterministic
# --------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


class FakeReplica:
    """In-memory stand-in for ``ProcessReplica``: the router sends
    requests in, the test decides when (and whether) results come back.
    Deterministic execution model: ``value = f"v:{key}:{query}"``."""

    def __init__(self, index):
        self.index = index
        self.outbox = [("ready", {"index": index, "boot_s": 0.0,
                                  "traces": 0, "from_disk": 1,
                                  "compiled": 0})]
        self.inbox = []          # ("req", id, key, query, hg, deadline)
        self.sent_stop = False
        self._alive = True
        self._broken = False
        self.connection = None

    # -- the ProcessReplica interface -------------------------------------
    def poll_messages(self):
        out, self.outbox = self.outbox, []
        return out

    def send(self, msg):
        if self._broken or not self._alive:
            raise BrokenPipeError(f"fake replica {self.index} down")
        if msg[0] == "stop":
            self.sent_stop = True
        else:
            self.inbox.append(msg)

    def alive(self):
        return self._alive and not self._broken

    def kill(self):
        self._alive = False

    def stop(self, force=False, join_s=None):
        self._alive = False

    # -- test controls -----------------------------------------------------
    def heartbeat(self):
        self.outbox.append(("hb", {"received": len(self.inbox)}))

    def complete(self, n=None):
        """Answer the oldest ``n`` queued requests (all by default)."""
        done = 0
        while self.inbox and (n is None or done < n):
            _, req_id, key, query, _hg, _dl = self.inbox.pop(0)
            self.outbox.append(("res", req_id, f"v:{key}:{query}"))
            done += 1
        return done

    def fail_one(self, err):
        _, req_id, *_ = self.inbox.pop(0)
        self.outbox.append(("err", req_id, err))

    def die(self):
        """Process exit: poll_messages still drains what was written."""
        self._alive = False

    def break_pipe(self):
        self._broken = True


def make_router(n=2, clock=None, registry=None, **kw):
    clock = clock or FakeClock()
    replicas = []

    def factory(i):
        r = FakeReplica(i)
        replicas.append(r)
        return r

    kw.setdefault("heartbeat_timeout_ms", 1000.0)
    kw.setdefault("boot_timeout_s", 100.0)
    router = Router(factory, n, clock=clock,
                    registry=registry or MetricsRegistry(), **kw)
    router.pump(clock.now)      # drain the ready messages
    return router, replicas, clock


def expected(key, query):
    return f"v:{key}:{query}"


# --------------------------------------------------------------------------
# fake-clock units
# --------------------------------------------------------------------------

def test_routes_completes_and_counts():
    router, reps, clock = make_router(2)
    futs = [(k, q, router.submit(k, query=q))
            for k, q in [("sssp", 1), ("ppr", 2), ("sssp", 3), ("ppr", 4)]]
    assert router.in_flight() == 4
    for r in reps:
        r.complete()
    router.pump(clock.now)
    for k, q, f in futs:
        assert f.result(timeout=1) == expected(k, q)
    st_ = router.stats()
    assert st_["served"] == 4 and st_["in_flight"] == 0
    assert st_["deaths"] == 0 and st_["failovers"] == 0


def test_affinity_pins_key_to_home_replica():
    router, reps, clock = make_router(2)
    for q in range(4):
        router.submit("sssp", query=q)
    homes = {i for i, r in enumerate(reps) if r.inbox}
    # All four go to ONE home replica (load within affinity_slack=2 of
    # the empty peer only holds for the first few; 4 - 0 > 2 spills).
    assert len(reps[min(homes)].inbox) >= 3


def test_least_loaded_takes_spill():
    router, reps, clock = make_router(2, affinity_slack=0)
    keys = [("sssp", q) for q in range(6)]
    for k, q in keys:
        router.submit(k, query=q)
    # slack 0: any imbalance spills to the least-loaded peer
    assert abs(len(reps[0].inbox) - len(reps[1].inbox)) <= 1


def test_heartbeat_expiry_fails_over_and_respawns():
    reg = MetricsRegistry()
    router, reps, clock = make_router(2, registry=reg)
    f = router.submit("sssp", query=7)
    serving = next(r for r in reps if r.inbox)
    other = next(r for r in reps if r is not serving)
    # The wedged replica stops heartbeating; the healthy one keeps going.
    for _ in range(3):
        clock.advance(0.5)
        other.heartbeat()
        router.pump(clock.now)
    # > heartbeat_timeout since `serving` last spoke: declared dead, its
    # in-flight request failed over to `other`, and a respawn appeared.
    assert not serving.alive()
    assert len(reps) == 3                      # the respawned instance
    assert any(m[0] == "req" for m in other.inbox)
    other.complete()
    router.pump(clock.now)
    assert f.result(timeout=1) == expected("sssp", 7)
    assert reg.counter("faults.replica.deaths").value == 1
    assert reg.counter("faults.replica.failovers").value == 1
    assert reg.counter("faults.replica.respawns").value == 1


def test_failover_budget_exhausts_to_replica_lost():
    reg = MetricsRegistry()
    router, reps, clock = make_router(2, registry=reg)
    f = router.submit("sssp", query=1)
    deaths = 0
    while not f.done():
        serving = next((r for r in reps if r.inbox and r.alive()), None)
        assert serving is not None, "request parked with no serving replica"
        serving.die()
        deaths += 1
        clock.advance(0.01)
        router.pump(clock.now)
        assert deaths <= MAX_FAILOVERS + 2, "future never resolved"
    with pytest.raises(ReplicaLost):
        f.result(timeout=1)
    # budget: MAX_FAILOVERS re-routes then lost on the next death
    assert deaths == MAX_FAILOVERS + 1
    assert reg.counter("faults.replica.lost").value == 1
    assert router.in_flight() == 0


def test_close_drains_queued_and_in_flight_typed():
    router, reps, clock = make_router(1, max_in_flight=1)
    f1 = router.submit("sssp", query=1)          # dispatched
    f2 = router.submit("sssp", query=2)          # parked (cap 1)
    router.close()
    with pytest.raises(FrontendClosed):
        f1.result(timeout=1)
    with pytest.raises(FrontendClosed):
        f2.result(timeout=1)
    f3 = router.submit("sssp", query=3)          # after close
    with pytest.raises(FrontendClosed):
        f3.result(timeout=1)
    assert router.in_flight() == 0


def test_overload_sheds_typed():
    reg = MetricsRegistry()
    router, reps, clock = make_router(1, max_queue_depth=2, registry=reg)
    keep = [router.submit("sssp", query=q) for q in range(2)]
    shed = router.submit("sssp", query=99)
    with pytest.raises(Overloaded):
        shed.result(timeout=1)
    assert reg.counter("serve.router.shed").value == 1
    reps[0].complete()
    router.pump(clock.now)
    for q, f in enumerate(keep):
        assert f.result(timeout=1) == expected("sssp", q)


def test_route_fault_point_resolves_typed():
    inj = FaultInjector(FaultPlan(rules=(
        FaultRule(point="router.route", trigger="nth", n=2, error="fatal"),
    )))
    router, reps, clock = make_router(2, fault_injector=inj)
    f1 = router.submit("sssp", query=1)
    f2 = router.submit("sssp", query=2)          # nth=2: injected
    with pytest.raises(InjectedFault):
        f2.result(timeout=1)
    for r in reps:
        r.complete()
    router.pump(clock.now)
    assert f1.result(timeout=1) == expected("sssp", 1)
    assert inj.snapshot()["never_fired"] == []


def test_broken_pipe_at_send_fails_over():
    router, reps, clock = make_router(2)
    reps[0].break_pipe()
    futs = [router.submit("sssp", query=q) for q in range(3)]
    router.pump(clock.now)
    alive = [r for r in reps if r.alive()]
    for r in alive:
        r.complete()
    router.pump(clock.now)
    for q, f in enumerate(futs):
        assert f.result(timeout=1) == expected("sssp", q)


def test_all_dead_without_respawn_resolves_replica_lost():
    router, reps, clock = make_router(2, respawn=False)
    futs = [router.submit("sssp", query=q) for q in range(4)]
    for r in reps:
        r.die()
    clock.advance(0.01)
    router.pump(clock.now)
    for f in futs:
        with pytest.raises(ReplicaLost):
            f.result(timeout=1)
    # admission after total loss fails immediately, typed
    with pytest.raises(ReplicaLost):
        router.submit("sssp", query=9).result(timeout=1)


def test_boot_timeout_declares_dead():
    clock = FakeClock()
    spawned = []

    def factory(i):
        r = FakeReplica(i)
        r.outbox.clear()                 # never says ready
        spawned.append(r)
        return r

    router = Router(factory, 1, boot_timeout_s=5.0, max_respawns=1,
                    clock=clock, registry=MetricsRegistry())
    f = router.submit("sssp", query=1)
    clock.advance(6.0)
    router.pump(clock.now)               # boot timeout -> dead -> respawn
    assert len(spawned) == 2
    clock.advance(6.0)
    router.pump(clock.now)               # respawn also times out; budget 1
    with pytest.raises(ReplicaLost):
        f.result(timeout=1)


def test_max_in_flight_caps_dispatch():
    router, reps, clock = make_router(1, max_in_flight=2)
    futs = [router.submit("sssp", query=q) for q in range(5)]
    assert len(reps[0].inbox) == 2
    assert router.stats()["pending"] == 3
    reps[0].complete()
    router.pump(clock.now)
    assert len(reps[0].inbox) == 2       # refilled from pending
    while router.stats()["pending"] or router.in_flight():
        reps[0].complete()
        router.pump(clock.now)
    for q, f in enumerate(futs):
        assert f.result(timeout=1) == expected("sssp", q)


def test_stats_provider_registered():
    reg = MetricsRegistry()
    router, reps, clock = make_router(2, registry=reg)
    router.submit("sssp", query=1)
    snap = reg.snapshot()
    assert snap["serve.router"]["replicas"] == 2
    assert snap["serve.router"]["in_flight"] == 1


# --------------------------------------------------------------------------
# the chaos property: random kill schedules x arrival orders
# --------------------------------------------------------------------------

@given(
    st.lists(st.integers(min_value=0, max_value=5), min_size=4,
             max_size=24),                       # per-step arrivals (key id)
    st.lists(st.integers(min_value=0, max_value=30), min_size=0,
             max_size=6),                        # kill steps
    st.integers(min_value=1, max_value=3),       # completions per step
)
@settings(max_examples=60, deadline=None)
def test_chaos_every_request_resolves(arrivals, kill_steps, per_step):
    router, reps, clock = make_router(
        2, max_respawns=50, heartbeat_timeout_ms=1000.0)
    kills = sorted(set(kill_steps))
    futs = []
    step = 0
    pending_arrivals = list(enumerate(arrivals))
    # Run until every future resolves (bounded: the failover budget plus
    # respawns guarantee progress; 500 steps is far beyond worst case).
    while pending_arrivals or not all(f.done() for _, _, f in futs):
        assert step < 500, "chaos schedule failed to drain"
        if pending_arrivals:
            q, key_id = pending_arrivals.pop(0)
            key = f"k{key_id}"
            futs.append((key, q, router.submit(key, query=q)))
        if step in kills:
            live = [r for r in reps if r.alive() and r.inbox]
            if not live:
                live = [r for r in reps if r.alive()]
            if live:
                live[step % len(live)].die()
        for r in reps:
            if r.alive():
                r.complete(per_step)
                r.heartbeat()
        clock.advance(0.05)
        router.pump(clock.now)
        step += 1
    ok = lost = 0
    for key, q, f in futs:
        try:
            # == the deterministic sequential value, per request
            assert f.result(timeout=0) == expected(key, q)
            ok += 1
        except ReplicaLost:
            lost += 1
    assert ok + lost == len(futs)        # nothing hangs, nothing vanishes
    assert router.in_flight() == 0
    assert router.stats()["pending"] == 0
    if not kills:
        assert lost == 0                 # fault-free: every value lands


# --------------------------------------------------------------------------
# cache.lock: cross-thread contention unit (cross-process stress is slow)
# --------------------------------------------------------------------------

def test_disk_lock_contention_counts_waits(tmp_path):
    from repro.serve import DiskExecutableCache

    cache = DiskExecutableCache(str(tmp_path))
    inside = threading.Event()
    release = threading.Event()
    entered = []

    def holder():
        with cache.lock("k"):
            inside.set()
            release.wait(5)

    def contender():
        inside.wait(5)
        with cache.lock("k"):
            entered.append(True)

    t1 = threading.Thread(target=holder)
    t2 = threading.Thread(target=contender)
    t1.start(); t2.start()
    inside.wait(5)
    time.sleep(0.05)                     # let the contender hit the lock
    release.set()
    t1.join(5); t2.join(5)
    assert entered == [True]
    assert cache.stats()["disk_lock_waits"] >= 1


# --------------------------------------------------------------------------
# slow: real processes over the real shared store
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_pool_survives_kill9_midreplay(tmp_path):
    """Kill -9 one of two real replicas mid-replay: every request
    resolves, survivors' values are bitwise equal to the parent's
    sequential runs, and the respawn boots from disk with zero traces."""
    import numpy as np

    import jax
    from repro import algorithms as alg
    from repro.core import Engine
    from repro.data import make_dataset
    from repro.serve import (
        DiskExecutableCache,
        ProcessReplica,
        ReplicaConfig,
        Router,
        warm,
    )

    cache_dir = str(tmp_path / "store")
    hg = make_dataset("dblp", scale=0.003, seed=0)
    engine = Engine(disk_cache=DiskExecutableCache(cache_dir))
    specs = {
        "sssp": alg.shortest_paths_spec(hg, source=0, max_iters=12),
        "ppr": alg.random_walk_spec(hg, iters=12),
    }
    warm(engine, list(specs.values()), batch_sizes=(8,), queries=[0, 0])

    cfg = ReplicaConfig(
        builder="repro.launch.serve_hypergraph:build_paths",
        kwargs={"regime": "dblp", "scale": 0.003, "seed": 0, "iters": 12},
        cache_dir=cache_dir, max_batch=8, require_no_retrace=True,
    )
    router = Router(lambda i: ProcessReplica(i, cfg), 2,
                    heartbeat_timeout_ms=2000.0, max_in_flight=8,
                    registry=MetricsRegistry()).start()
    try:
        router.wait_ready(timeout_s=180)
        trace = [("sssp" if q % 2 else "ppr", q % hg.n_vertices)
                 for q in range(40)]
        futs = [(k, q, router.submit(k, query=q)) for k, q in trace]
        # kill -9 one replica while the batch is mid-flight
        victim = router.slots[0].handle
        os.kill(victim.pid, 9)
        values, lost = {}, 0
        for k, q, f in futs:
            try:
                values[(k, q)] = f.result(timeout=300)
            except (ReplicaLost, FrontendClosed):
                lost += 1
        assert len(values) + lost == len(trace)      # all resolved
        assert len(values) >= len(trace) - MAX_FAILOVERS  # almost all land
        assert router.in_flight() == 0
        stats = router.stats()
        assert stats["deaths"] >= 1 and stats["respawns"] >= 1
        # the respawned instance booted from disk, zero retraces
        router.wait_ready(timeout_s=180)
        reborn = router.stats()["per_replica"][0]["boot"]
        assert reborn["traces"] == 0 and reborn["from_disk"] > 0
        # bitwise vs the parent's sequential fault-free path
        for (k, q), served in list(values.items())[:8]:
            seq = engine.compile(specs[k]).run(query=q)
            for a, b in zip(jax.tree.leaves(seq.value),
                            jax.tree.leaves(served.value)):
                assert np.array_equal(np.asarray(a), np.asarray(b),
                                      equal_nan=True)
    finally:
        router.close()


CONCURRENT_WARM_CHILD = textwrap.dedent("""
    import os, sys, time
    from repro.core import Engine
    from repro import algorithms as alg
    from repro.data import make_dataset
    from repro.serve import DiskExecutableCache, warm

    cache_dir, barrier = sys.argv[1], sys.argv[2]
    hg = make_dataset("dblp", scale=0.003, seed=0)
    specs = [alg.shortest_paths_spec(hg, source=0, max_iters=8)]
    # barrier: both children reach here, then compile simultaneously
    open(barrier + "." + str(os.getpid()), "w").close()
    deadline = time.time() + 60
    while len([f for f in os.listdir(os.path.dirname(barrier))
               if os.path.basename(barrier) + "." in f]) < 2:
        assert time.time() < deadline, "peer never arrived"
        time.sleep(0.01)
    eng = Engine(disk_cache=DiskExecutableCache(cache_dir))
    report = warm(eng, specs, batch_sizes=(8,), queries=[0])
    res = eng.compile(specs[0]).run(query=0)
    import jax
    import numpy as np
    total = sum(float(np.asarray(x).sum())
                for x in jax.tree.leaves(res.value))
    print("OK", report["traces"], total)
""")


@pytest.mark.slow
def test_concurrent_warm_on_one_empty_store(tmp_path):
    """Two processes ``serve.warm`` the SAME empty store simultaneously:
    the advisory lock serializes compile-and-store, both exit clean, and
    both serve identical results."""
    cache_dir = str(tmp_path / "store")
    barrier = str(tmp_path / "barrier")
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", CONCURRENT_WARM_CHILD, cache_dir,
             barrier],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for _ in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"child failed:\n{err}\n{out}"
        outs.append([ln for ln in out.splitlines() if ln.startswith("OK")][0])
    sums = {o.split()[-1] for o in outs}
    assert len(sums) == 1, f"divergent results: {outs}"
    # the store holds each signature once (no torn/duplicate publish)
    from repro.serve import DiskExecutableCache

    cache = DiskExecutableCache(cache_dir)
    assert cache.stats()["entries"] >= 1


def test_process_replica_refuses_to_spawn_on_a_held_accelerator(monkeypatch):
    """A chip belongs to one process: a parent holding the TPU backend
    must fail fast instead of spawning children that cannot reach it."""
    from repro.serve import replica

    monkeypatch.setattr(replica, "held_accelerator", lambda: "tpu")
    cfg = replica.ReplicaConfig(builder="repro.launch.serve_hypergraph:"
                                        "build_paths")
    with pytest.raises(RuntimeError, match="one process at a time"):
        replica.ProcessReplica(0, cfg)


def test_held_accelerator_is_none_on_cpu():
    import jax

    from repro.serve.replica import held_accelerator

    jax.devices()
    assert held_accelerator() is None
