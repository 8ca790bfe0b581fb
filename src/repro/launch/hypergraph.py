"""Launch hypergraph analytics through the ``Engine`` facade.

The hypergraph counterpart of ``repro.launch.dryrun``: run any built-in
algorithm on a generated dataset regime at any design point — or let the
facade's cost models pick representation / partition strategy / backend.

Usage:
  PYTHONPATH=src python -m repro.launch.hypergraph \
      --algorithm pagerank --regime dblp --scale 0.003 \
      --devices 8 --backend auto --partition auto

  # batch analytics (Engine.analyze): the h-motif census
  PYTHONPATH=src python -m repro.launch.hypergraph \
      --algorithm motifs --regime dblp --scale 0.003 \
      --mode auto --kernel auto --devices 4

  # compile-once serve-many (Engine.compile -> run_batch): 64 SSSP
  # sources against one compiled executable
  PYTHONPATH=src python -m repro.launch.hypergraph \
      --algorithm sssp --regime dblp --scale 0.003 --batch 64
  PYTHONPATH=src python -m repro.launch.hypergraph \
      --algorithm random_walk --sources 3,17,99

The device-count env fix must run before any jax import, hence the
module-level XLA_FLAGS block (same pattern as ``dryrun``).
"""
import argparse
import os
import sys
import time


def _parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--algorithm", default="pagerank",
                    choices=["pagerank", "vertex_pagerank",
                             "pagerank_entropy", "label_propagation",
                             "sssp", "random_walk",
                             "connected_components", "motifs"])
    ap.add_argument("--regime", default="dblp",
                    help="dataset regime (apache/dblp/friendster/orkut)")
    ap.add_argument("--scale", type=float, default=0.003)
    ap.add_argument("--iters", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=1,
                    help="forced host device count (1 = local execution)")
    ap.add_argument("--representation", default="auto",
                    choices=["auto", "bipartite", "clique"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "local", "replicated", "sharded"])
    ap.add_argument("--partition", default="auto",
                    help="partition strategy name or 'auto'")
    ap.add_argument("--stats", action="store_true",
                    help="print per-superstep activity")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "exact", "sample"],
                    help="motifs only: census mode")
    ap.add_argument("--samples", type=int, default=4000,
                    help="motifs only: sample count for --mode sample")
    ap.add_argument("--kernel", default="auto",
                    choices=["auto", "bitset", "merge"],
                    help="motifs only: intersection kernel path")
    ap.add_argument("--sources", default=None,
                    help="comma-separated query vertices (sssp sources / "
                         "random_walk seeds): compile once, serve the "
                         "batch via CompiledAlgorithm.run_batch")
    ap.add_argument("--batch", type=int, default=None,
                    help="serve N random query vertices through one "
                         "compiled executable (see --sources)")
    ap.add_argument("--explain", action="store_true",
                    help="print the full auto-axis decision tree "
                         "(per-candidate predicted costs) before running")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="run under the JAX profiler, writing its trace "
                         "(engine spans and device ops on one clock; "
                         "TensorBoard / XProf) under DIR")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the unified metrics-registry snapshot "
                         "as JSON ('-' for stdout)")
    ap.add_argument("--cache-stats", action="store_true",
                    help="print the executable-cache statistics "
                         "(entries, hits/misses, evictions, per-entry "
                         "bucket shapes) after the run")
    return ap.parse_args(argv)


def build_spec(name: str, hg, iters: int):
    from repro import algorithms as alg

    if name == "pagerank":
        return alg.pagerank_spec(hg, iters=iters)
    if name == "vertex_pagerank":
        # vertex ranks only — the clique-eligible variant, so
        # --representation clique/auto can actually constant-fold.
        return alg.vertex_pagerank_spec(hg, iters=iters)
    if name == "pagerank_entropy":
        return alg.pagerank_entropy_spec(hg, iters=iters)
    if name == "label_propagation":
        return alg.label_propagation_spec(hg, iters=iters)
    if name == "sssp":
        return alg.shortest_paths_spec(hg, source=0, max_iters=iters)
    if name == "random_walk":
        return alg.random_walk_spec(hg, iters=iters)
    if name == "connected_components":
        return alg.connected_components_spec(hg, max_iters=iters)
    raise ValueError(name)


def _print_cache_stats(engine) -> None:
    s = engine.cache_stats()
    print(f"cache: entries={s['entries']}/{s['capacity']} "
          f"hits={s['hits']} misses={s['misses']} "
          f"evictions={s['evictions']} traces={s['traces']}")
    for meta in s["entry_shapes"]:
        print(f"  entry: {meta}")
    if s.get("disk") is not None:
        print(f"  disk: {s['disk']}")


def _print_explain(ex: dict) -> None:
    print("explain:")
    for axis, info in ex["axes"].items():
        print(f"  {axis}: winner={info.get('winner')} "
              f"({info.get('reason')})")
        for cand, costs in info.get("candidates", {}).items():
            mark = "*" if cand == info.get("winner") else " "
            kv = " ".join(
                f"{k}={v}" for k, v in costs.items()
                if k not in ("class_plans",) and not isinstance(v, dict)
            )
            print(f"   {mark} {cand}: {kv}")


def _emit_obs(engine, args) -> None:
    if args.metrics_json:
        import json

        payload = json.dumps(engine.metrics.snapshot(), indent=2,
                             sort_keys=True, default=str)
        if args.metrics_json == "-":
            print(payload)
        else:
            with open(args.metrics_json, "w") as f:
                f.write(payload + "\n")
            print(f"metrics -> {args.metrics_json}")


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

    from repro.core import AnalyticsSpec, Engine
    from repro.data import make_dataset
    from repro.launch.mesh import make_host_mesh

    hg = make_dataset(args.regime, scale=args.scale, seed=args.seed)
    print(f"{args.regime}: |V|={hg.n_vertices} |E|={hg.n_hyperedges} "
          f"nnz={hg.nnz}")

    mesh = make_host_mesh(args.devices) if args.devices > 1 else None
    engine = Engine(
        mesh=mesh,
        representation=args.representation,
        backend=args.backend,
        partition_strategy=args.partition,
        collect_stats=args.stats,
        intersect_kernel=args.kernel,
    )

    if args.algorithm == "motifs":
        aspec = AnalyticsSpec(
            hg, mode=args.mode, n_samples=args.samples, seed=args.seed,
        )
        if args.explain:
            _print_explain(engine.explain(aspec))
        res = engine.analyze(aspec)
        print(f"design point: representation={res.representation} "
              f"kernel={res.kernel} backend={res.backend} "
              f"mode={res.mode}")
        for ax, why in res.decision.items():
            reason = why.get("reason") if isinstance(why, dict) else why
            print(f"  {ax}: {reason}")
        c = res.value
        if res.mode == "exact":
            print(f"census: {c.total} connected triples over "
                  f"{c.n_pairs} overlapping pairs "
                  f"({c.n_duplicate_triples} duplicate-hyperedge "
                  f"triples dropped)")
            counts = c.counts
        else:
            print(f"census (estimated from {c.n_samples} sampled "
                  f"linked pairs of {c.n_pairs}): total ~{c.total:.0f}")
            counts = c.counts
        top = np.argsort(counts)[::-1][:6]
        for m in top:
            if counts[m] > 0:
                line = f"  h-motif {m:2d}: {counts[m]:.0f}"
                if res.mode == "sample":
                    line += (f"  [{c.ci_low[m]:.0f}, {c.ci_high[m]:.0f}] "
                             f"@{c.confidence:.0%}")
                print(line)
        _emit_obs(engine, args)
        return 0

    spec = build_spec(args.algorithm, hg, args.iters)
    if args.explain:
        _print_explain(engine.explain(spec))

    if args.sources is not None or args.batch is not None:
        # compile-once serve-many: one executable, B queries.
        if spec.bind_query is None:
            print(f"--sources/--batch need a query-capable algorithm "
                  f"(sssp, random_walk); {args.algorithm} has no query "
                  f"axis", file=sys.stderr)
            return 2
        if args.sources is not None:
            queries = np.asarray(
                [int(s) for s in args.sources.split(",")], np.int32
            )
        else:
            rng = np.random.default_rng(args.seed)
            queries = rng.integers(
                0, hg.n_vertices, size=args.batch
            ).astype(np.int32)
        compiled = engine.compile(spec)
        t0 = time.perf_counter()
        res = compiled.run_batch(queries)
        jax.block_until_ready(res.value)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = compiled.run_batch(queries)
        jax.block_until_ready(res.value)
        warm_s = time.perf_counter() - t0
        print(f"design point: representation={res.representation} "
              f"backend={res.backend} partition={res.partition}")
        print(f"served {len(queries)} queries: cold {cold_s:.3f}s "
              f"({len(queries) / cold_s:.1f} q/s incl. compile), warm "
              f"{warm_s:.3f}s ({len(queries) / warm_s:.1f} q/s)")
        _print_cache_stats(engine)
        leaves = jax.tree.leaves(res.value)
        first = np.asarray(leaves[0])
        for i, q in enumerate(queries[:4]):
            print(f"  query {int(q):4d}: {first[i].ravel()[:5]}")
        _emit_obs(engine, args)
        return 0

    res = engine.run(spec)

    print(f"design point: representation={res.representation} "
          f"backend={res.backend} partition={res.partition}")
    for axis, why in res.decision.items():
        if axis == "measured":
            continue
        reason = why.get("reason") if isinstance(why, dict) else why
        print(f"  {axis}: {reason}")
    m = res.decision.get("measured")
    if m:
        line = (f"  measured: wall={m['wall_s'] * 1e3:.1f}ms "
                f"device_wait={m['device_wait_s'] * 1e3:.2f}ms")
        if m.get("supersteps") is not None:
            line += f" supersteps={m['supersteps']}/{m['max_iters']}"
        print(line)
    if res.partition_stats is not None:
        s = res.partition_stats
        print(f"  plan: vrep={s.vertex_replication:.2f} "
              f"herep={s.hyperedge_replication:.2f} "
              f"sync={s.sync_bytes_per_dim / 1e6:.3f} MB/dim")
    if res.superstep_stats is not None:
        v_act, he_act = res.superstep_stats
        print(f"  activity: v={np.asarray(v_act).tolist()}")
        print(f"            he={np.asarray(he_act).tolist()}")
    leaves = jax.tree.leaves(res.value)
    print(f"result: {len(leaves)} output array(s); "
          f"first = {np.asarray(leaves[0]).ravel()[:6]}")
    if args.cache_stats:
        _print_cache_stats(engine)
    _emit_obs(engine, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
