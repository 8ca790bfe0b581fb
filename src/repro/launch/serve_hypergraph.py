"""Hypergraph query serving: replay a mixed trace through the serve tier.

Naming note: ``repro.launch.serve`` is the *LM decode* driver (prefill +
token generation for the transformer stack); THIS module is the
*hypergraph analytics* serving entry point, built on ``repro.serve``
(async front-end + coalescing batcher + persistent executable cache)
over the compile-once seam (``Engine.compile``).

Replays a mixed SSSP / PPR (random-walk) request trace against one
generated dataset:

  PYTHONPATH=src python -m repro.launch.serve_hypergraph \
      --regime dblp --scale 0.003 --requests 200 \
      --max-batch 16 --max-delay-ms 5

  # replica boot from the persistent cache (second run is warm):
  REPRO_CACHE_DIR=/tmp/repro-cache \
  PYTHONPATH=src python -m repro.launch.serve_hypergraph --warm

Flags of note: ``--mix`` sets the SSSP fraction of the trace;
``--no-warm`` skips the boot-time ``serve.warm`` pass (first requests
then pay the compile); ``--cache-dir`` / ``$REPRO_CACHE_DIR`` place the
on-disk executable store; ``--verify`` cross-checks a sample of served
results bitwise against sequential ``CompiledAlgorithm.run``;
``--fault-plan`` (inline JSON or a file path) arms a ``FaultPlan`` of
scheduled failures — the chaos replay: every request still resolves
(result or typed error), successes stay bitwise-correct, and the
per-point calls/fired report prints after the run, e.g.::

  --fault-plan '{"rules": [{"point": "execute", "trigger": "every",
                            "n": 7, "error": "transient"}]}'

The device-count env fix must run before any jax import, hence the
module-level pattern shared with ``repro.launch.hypergraph``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def serve_specs(hg, iters: int = 12) -> dict:
    """The served paths, keyed as requests name them: SSSP from a
    source vertex and the personalized random walk (PPR) from a seed.
    PPR has no default query; ``WARM_QUERIES`` gives each path one."""
    from repro import algorithms as alg

    return {
        "sssp": alg.shortest_paths_spec(hg, source=0, max_iters=iters),
        "ppr": alg.random_walk_spec(hg, iters=iters),
    }


# One example query per served path, in ``serve_specs`` order.
WARM_QUERIES = [0, 0]


def build_paths(regime: str = "dblp", scale: float = 0.003,
                seed: int = 0, iters: int = 12) -> dict:
    """Replica builder (``ReplicaConfig.builder`` target): constructs
    the served paths INSIDE the worker process, so nothing unpicklable
    crosses the spawn boundary — each replica regenerates the (seeded,
    deterministic) dataset and spec set locally, and ``stable_digest``
    re-keys them onto the same shared disk-store entries."""
    from repro.data import make_dataset

    hg = make_dataset(regime, scale=scale, seed=seed)
    return {"specs": serve_specs(hg, iters), "warm_queries": WARM_QUERIES}


def _parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--regime", default="dblp",
                    help="dataset regime (apache/dblp/friendster/orkut)")
    ap.add_argument("--scale", type=float, default=0.003)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=12,
                    help="superstep budget per query")
    ap.add_argument("--devices", type=int, default=1,
                    help="forced host device count (1 = local execution)")
    ap.add_argument("--requests", type=int, default=200,
                    help="trace length (mixed across algorithms)")
    ap.add_argument("--mix", type=float, default=0.6,
                    help="fraction of the trace that is SSSP "
                         "(the rest is PPR / random-walk)")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="coalescing batch bucket per registered path")
    ap.add_argument("--max-delay-ms", type=float, default=5.0,
                    help="max queue wait before a partial flush")
    ap.add_argument("--adaptive-delay", action="store_true",
                    help="let the front-end adapt the flush deadline "
                         "from the observed wait/execute split "
                         "(bounded EWMA controller; --max-delay-ms "
                         "becomes the upper clamp)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="run under the JAX profiler, writing its trace "
                         "(engine + serve spans and device ops on one "
                         "clock; TensorBoard / XProf) under DIR")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the unified metrics-registry snapshot "
                         "as JSON ('-' for stdout)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent executable cache dir "
                         "(default $REPRO_CACHE_DIR or .repro_cache/)")
    ap.add_argument("--no-warm", dest="warm", action="store_false",
                    help="skip the boot-time warmup pass")
    ap.add_argument("--warm", dest="warm", action="store_true",
                    default=True)
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve through a pool of N replica processes "
                         "behind the heartbeat-failover Router (0 = "
                         "single-process front-end); replicas boot from "
                         "the shared --cache-dir store")
    ap.add_argument("--heartbeat-timeout-ms", type=float, default=2000.0,
                    help="router declares a replica dead after this "
                         "long without a heartbeat")
    ap.add_argument("--fault-plan", default=None, metavar="JSON",
                    help="chaos mode: a FaultPlan as inline JSON or a "
                         "file path; scheduled failures are injected at "
                         "the engine/serve failure points and a per-point "
                         "calls/fired report is printed after the replay")
    ap.add_argument("--verify", type=int, default=8,
                    help="cross-check N served results bitwise against "
                         "sequential run (0 = skip)")
    ap.add_argument("--log-every-s", type=float, default=5.0)
    ap.add_argument("--json", action="store_true",
                    help="dump the full stats snapshot as JSON")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    if args.devices > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    from repro.obs.trace import default_tracer, profile

    with profile(args.trace):
        rc = _run(args)
    if args.trace:
        tr = default_tracer()
        print(f"trace: {len(tr.spans())} program spans "
              f"({tr.dropped} dropped) -> {args.trace}")
    return rc


def _run(args) -> int:
    import jax
    import numpy as np

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from repro.core import Engine
    from repro.data import make_dataset
    from repro.launch.mesh import make_host_mesh
    from repro.serve import DiskExecutableCache, Frontend, warm

    hg = make_dataset(args.regime, scale=args.scale, seed=args.seed)
    print(f"{args.regime}: |V|={hg.n_vertices} |E|={hg.n_hyperedges} "
          f"nnz={hg.nnz}")

    mesh = make_host_mesh(args.devices) if args.devices > 1 else None
    injector, plan_json = None, None
    if args.fault_plan:
        from repro.faults import FaultInjector, FaultPlan

        raw = args.fault_plan
        if os.path.exists(raw):
            with open(raw) as f:
                raw = f.read()
        plan = FaultPlan.from_json(raw)
        for warning in plan.validate():
            print(f"fault-plan: {warning}", file=sys.stderr)
        injector = FaultInjector(plan)
        plan_json = plan.to_json()
        print(f"fault-plan: {len(plan.rules)} rule(s) armed")
    engine = Engine(
        mesh=mesh, disk_cache=DiskExecutableCache(args.cache_dir),
        # In pool mode the parent engine is the prewarmer + verify
        # oracle, never the system under test: the plan is armed inside
        # each replica (and on the router for ``router.route``) instead.
        fault_injector=None if args.replicas > 0 else injector,
    )
    specs = serve_specs(hg, args.iters)

    if args.warm:
        report = warm(
            engine, list(specs.values()),
            batch_sizes=(args.max_batch,),
            queries=WARM_QUERIES,
        )
        print(f"warm boot: {report['boot_s']:.3f}s, "
              f"{report['traces']} traces, "
              f"{report['from_disk']} from disk, "
              f"{report['compiled']} compiled")

    if args.replicas > 0:
        return _serve_pool(args, engine, specs, hg, injector, plan_json)

    fe = Frontend(
        engine, max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms, log_every_s=args.log_every_s,
        adaptive_delay=args.adaptive_delay,
    )
    for key, spec in specs.items():
        fe.register(key, spec)

    rng = np.random.default_rng(args.seed)
    trace = [
        ("sssp" if rng.random() < args.mix else "ppr",
         int(rng.integers(0, hg.n_vertices)))
        for _ in range(args.requests)
    ]

    t0 = time.perf_counter()
    results, failures = [], []
    with fe:
        futs = [(key, q, fe.submit(key, query=q)) for key, q in trace]
        for key, q, f in futs:
            try:
                results.append((key, q, f.result()))
            except RuntimeError as err:
                # Under an injected fault plan, requests may resolve
                # with a typed FaultError instead of a value — counted
                # and reported, never a hang or a crashed replay.
                failures.append((key, q, err))
    wall_s = time.perf_counter() - t0
    if failures and injector is None:
        print(f"{len(failures)} requests failed without a fault plan",
              file=sys.stderr)
        return 1

    st = fe.stats()
    print(f"served {len(results)} requests in {wall_s:.3f}s "
          f"({len(results) / wall_s:.1f} q/s sustained)")
    print(f"  wait    p50={st['queue_wait']['p50_s'] * 1e3:.2f}ms "
          f"p99={st['queue_wait']['p99_s'] * 1e3:.2f}ms")
    print(f"  execute p50={st['execute']['p50_s'] * 1e3:.2f}ms "
          f"p99={st['execute']['p99_s'] * 1e3:.2f}ms")
    print(f"  flushes {st['flush_reasons']}")
    for bucket, occ in st["buckets"].items():
        print(f"  bucket {bucket}: {occ['flushes']} flushes, "
              f"occupancy {occ['mean_occupancy']:.2f}")
    print(f"  engine cache: entries={st['engine_cache']['entries']} "
          f"hits={st['engine_cache']['hits']} "
          f"traces={st['engine_cache']['traces']}")
    if st["disk_cache"] is not None:
        d = st["disk_cache"]
        print(f"  disk cache:   entries={d['entries']} "
              f"hits={d['disk_hits']} stores={d['disk_stores']} "
              f"({d['dir']})")
    if st.get("adaptive_delay") is not None:
        a = st["adaptive_delay"]
        print(f"  adaptive delay: {a['delay_s'] * 1e3:.2f}ms "
              f"(exec ewma {a['exec_ewma_s'] * 1e3:.2f}ms, "
              f"{a['observations']} obs)")
    if injector is not None:
        snap = injector.snapshot()
        print(f"  fault injection: {sum(snap['fired'].values())} fired "
              f"across {sum(snap['calls'].values())} instrumented calls; "
              f"{len(failures)} requests resolved with typed errors")
        for point in sorted(snap["calls"]):
            print(f"    {point}: calls={snap['calls'][point]} "
                  f"fired={snap['fired'].get(point, 0)}")

    if args.verify:
        # The sequential re-runs are the ORACLE, not the system under
        # test: disarm injection so the reference path runs fault-free.
        engine.fault_injector = None
        idx = rng.choice(len(results), size=min(args.verify, len(results)),
                         replace=False)
        for i in idx:
            key, q, served = results[i]
            seq = fe.compiled(key).run(query=q)
            for a, b in zip(jax.tree.leaves(seq.value),
                            jax.tree.leaves(served.value)):
                if not np.array_equal(np.asarray(a), np.asarray(b),
                                      equal_nan=True):
                    print(f"VERIFY FAILED: {key} query={q}",
                          file=sys.stderr)
                    return 1
        print(f"verified {len(idx)} served results bitwise vs "
              f"sequential run")

    if args.json:
        print(json.dumps(st, indent=2, sort_keys=True, default=str))
    if args.metrics_json:
        payload = json.dumps(engine.metrics.snapshot(), indent=2,
                             sort_keys=True, default=str)
        if args.metrics_json == "-":
            print(payload)
        else:
            with open(args.metrics_json, "w") as f:
                f.write(payload + "\n")
            print(f"metrics -> {args.metrics_json}")
    return 0


def _serve_pool(args, engine, specs, hg, injector, plan_json) -> int:
    """Replay the trace through a ``Router`` over N replica processes.

    The parent already prewarmed the shared disk store (under
    ``--warm``), so every replica boots ``require_no_retrace=True``;
    the parent engine stays fault-free and serves as the bitwise
    ``--verify`` oracle.  The chaos invariant being demonstrated:
    every request resolves even when ``replica.crash`` kills workers
    mid-replay, and the survivors' successes match the sequential run.
    """
    import dataclasses
    import itertools

    import jax
    import numpy as np

    from repro.serve import ProcessReplica, ReplicaConfig, Router

    cfg = ReplicaConfig(
        builder="repro.launch.serve_hypergraph:build_paths",
        kwargs={"regime": args.regime, "scale": args.scale,
                "seed": args.seed, "iters": args.iters},
        cache_dir=args.cache_dir,
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        fault_plan=plan_json,
        require_no_retrace=args.warm,
        heartbeat_interval_s=min(0.1, args.heartbeat_timeout_ms / 4e3),
    )
    # Every spawned instance (initial or respawn) gets a distinct prob
    # seed offset, so a respawned replica doesn't replay the exact fault
    # draws that killed its predecessor (see ReplicaConfig.seed_offset).
    spawns = itertools.count()

    def factory(index: int) -> ProcessReplica:
        return ProcessReplica(index, dataclasses.replace(
            cfg, seed_offset=1009 * next(spawns)))

    router = Router(
        factory, args.replicas,
        heartbeat_timeout_ms=args.heartbeat_timeout_ms,
        max_in_flight=2 * args.max_batch,
        fault_injector=injector,
    ).start()
    try:
        t0 = time.perf_counter()
        router.wait_ready()
        boot_s = time.perf_counter() - t0
        boots = [s["boot"] for s in router.stats()["per_replica"]]
        print(f"pool: {args.replicas} replicas ready in {boot_s:.3f}s; "
              f"boots: " + ", ".join(
                  f"#{b['index']} {b['boot_s']:.2f}s "
                  f"(disk={b['from_disk']} aot={b['compiled']} "
                  f"traces={b['traces']})"
                  for b in boots if b))

        rng = np.random.default_rng(args.seed)
        trace = [
            ("sssp" if rng.random() < args.mix else "ppr",
             int(rng.integers(0, hg.n_vertices)))
            for _ in range(args.requests)
        ]
        t0 = time.perf_counter()
        futs = [(key, q, router.submit(key, query=q)) for key, q in trace]
        results, failures = [], []
        for key, q, f in futs:
            try:
                results.append((key, q, f.result(timeout=300)))
            except RuntimeError as err:  # typed FaultError taxonomy
                failures.append((key, q, err))
        wall_s = time.perf_counter() - t0
    finally:
        router.close()

    st = router.stats()
    if st["in_flight"] != 0 or st["pending"] != 0:
        print(f"ROUTER LEAK: in_flight={st['in_flight']} "
              f"pending={st['pending']} after drain", file=sys.stderr)
        return 1
    if failures and injector is None:
        print(f"{len(failures)} requests failed without a fault plan",
              file=sys.stderr)
        return 1
    print(f"served {len(results)}/{len(trace)} requests in {wall_s:.3f}s "
          f"({len(results) / wall_s:.1f} q/s aggregate)")
    print(f"  pool: deaths={st['deaths']} respawns={st['respawns']} "
          f"failovers={st['failovers']} lost={st['lost']} "
          f"shed={st['shed']}")
    for p in st["per_replica"]:
        print(f"  replica {p['index']}: {p['state']} served={p['served']} "
              f"errors={p['errors']} deaths={p['deaths']} "
              f"respawns={p['respawns']}")
    if injector is not None:
        snap = injector.snapshot()
        print(f"  router-side fault injection: "
              f"{sum(snap['fired'].values())} fired across "
              f"{sum(snap['calls'].values())} calls; "
              f"never fired: {snap['never_fired'] or 'none'} "
              f"(replica-side points fire inside the workers); "
              f"{len(failures)} requests resolved with typed errors")

    if args.verify and results:
        idx = rng.choice(len(results),
                         size=min(args.verify, len(results)),
                         replace=False)
        for i in idx:
            key, q, served = results[i]
            seq = engine.compile(specs[key]).run(query=q)
            for a, b in zip(jax.tree.leaves(seq.value),
                            jax.tree.leaves(served.value)):
                if not np.array_equal(np.asarray(a), np.asarray(b),
                                      equal_nan=True):
                    print(f"VERIFY FAILED: {key} query={q}",
                          file=sys.stderr)
                    return 1
        print(f"verified {len(idx)} pool-served results bitwise vs "
              f"sequential run")

    if args.metrics_json:
        from repro.obs.metrics import default_registry

        payload = json.dumps(default_registry().snapshot(), indent=2,
                             sort_keys=True, default=str)
        if args.metrics_json == "-":
            print(payload)
        else:
            with open(args.metrics_json, "w") as f:
                f.write(payload + "\n")
            print(f"metrics -> {args.metrics_json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
