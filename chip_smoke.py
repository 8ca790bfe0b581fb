"""Chip smoke: the hypergraph engine's main path on a TPU at full dblp size.

    python chip_smoke.py [--seed N]             # one chip
    python chip_smoke.py --chips 4 [--seed N]   # four chips, distributed only

One chip runs five phases through the entry points a user calls:

  (a) generate dblp at the paper's Table-I size (``make_dataset("dblp",
      scale=1.0)``: ~899k vertices, ~783k hyperedges, ~2.8M incidences);
  (b) ``Engine().run(pagerank_spec(hg, iters=30))`` at the ``auto``
      design point, against a numpy PageRank of the same semantics
      (float64; the device sums float32 in another order, hence a
      tolerance);
  (c) ``Engine.compile(shortest_paths_spec(...)).run_batch`` over 16
      sources, equal to a numpy frontier BFS exactly;
  (d) a ``Frontend`` serving the SSSP and PPR paths as
      ``repro.launch.serve_hypergraph`` registers them, booted through
      the disk executable store, answering 64 requests: SSSP exact
      against numpy; PPR against sequential ``run`` and numpy within a
      tolerance (its float sums may reorder between the batched and the
      unbatched executable; the bitwise count is printed);
  (e) design points, compile and execute wall times, trace counts, the
      disk store's counters and the device's peak memory.

``--chips 4`` runs only the distributed path: PageRank and batched SSSP
over a four-device mesh with the ``sharded`` and ``replicated``
backends, each against the local result in the same process and numpy.

Everything runs in this one process, on a TPU only: with no TPU, or
without the repository's ``src/`` next to this file, it exits 1 and
prints no result.  It fails if any check fails, if any delivery degraded
(``faults.delivery_degraded``), or if any executable was served through
the plain-jit fallback.  The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

PR_ITERS = 30
SSSP_ITERS = 64
N_SOURCES = 16
SERVE_ITERS = 12          # repro.launch.serve_hypergraph's default
SERVE_BATCH = 16
N_REQUESTS = 64
SSSP_MIX = 0.6            # fraction of requests that are SSSP
# float32 device vs float64 numpy after 30 pairs of reordered sums
RTOL, ATOL = 1e-4, 1e-6


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def close(name: str, got, ref, rtol: float = RTOL, atol: float = ATOL,
          quiet: bool = False) -> float:
    """Float agreement within (rtol, atol); returns the worst absolute
    error (logged unless ``quiet``)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"{name}: shape {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    err = np.abs(got - ref)
    ok = bool(np.all(err <= atol + rtol * np.abs(ref)))
    if not quiet or not ok:
        rel = float((err / np.maximum(np.abs(ref), atol)).max())
        log(f"    {name}: max abs err {err.max():.3e}, max rel err "
            f"{rel:.3e} (rtol {rtol}, atol {atol}) -> "
            f"{'ok' if ok else 'FAIL'}")
    check(ok, f"{name} outside tolerance")
    return float(err.max())


def exact(name: str, got, ref) -> None:
    check(np.array_equal(np.asarray(got), np.asarray(ref)),
          f"{name} differs from its reference")


def generate(seed: int):
    from repro.data import make_dataset

    t0 = time.perf_counter()
    hg = make_dataset("dblp", scale=1.0, seed=seed)
    log(f"[a] dblp scale 1.0 seed {seed}: |V|={hg.n_vertices} "
        f"|E|={hg.n_hyperedges} nnz={hg.nnz}; generated in "
        f"{time.perf_counter() - t0:.2f}s")
    return hg


def design_point(res) -> str:
    d = res.decision.get("delivery", {})
    return (f"representation={res.representation} backend={res.backend} "
            f"partition={res.partition} delivery={res.config.delivery} "
            f"lowering={d.get('lowering')} ({d.get('reason')})")


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out.value)
    return out, time.perf_counter() - t0


def no_fallbacks(engines, results) -> None:
    """No delivery degraded, no served result came from a degraded
    twin, and every executable was compiled ahead or loaded from disk
    (none through plain jit)."""
    from repro.obs.metrics import default_registry

    degraded = default_registry().counter("faults.delivery_degraded").value
    log(f"    faults.delivery_degraded = {degraded}")
    check(degraded == 0, "a delivery degraded to its xla twin")
    for res in results:
        check("degraded_from" not in res.decision,
              f"a result was served degraded: {res.decision}")
    for eng in engines:
        stats = eng.cache_stats()
        check(set(stats["sources"]) <= {"aot", "disk"},
              f"an executable was not compiled ahead: {stats['sources']}")


def one_chip(seed: int) -> None:
    import jax

    from repro import algorithms as alg
    from repro.core import Engine
    from repro.launch.serve_hypergraph import WARM_QUERIES, serve_specs
    from repro.reference import (
        hop_distances_np,
        pagerank_np,
        random_walk_np,
    )
    from repro.serve import DiskExecutableCache, Frontend, warm

    rng = np.random.default_rng(seed)
    hg = generate(seed)
    results = []

    # (b) one-shot analytics at the auto design point
    eng = Engine()
    res, wall = timed(lambda: eng.run(alg.pagerank_spec(hg, iters=PR_ITERS)))
    results.append(res)
    m = res.decision["measured"]
    log(f"[b] pagerank x{PR_ITERS}: {design_point(res)}")
    log(f"    Engine.run wall {wall:.3f}s: trace+compile+dispatch "
        f"{m['dispatch_s']:.3f}s, device wait {m['device_wait_s']:.3f}s")
    t0 = time.perf_counter()
    v_ref, he_ref = pagerank_np(hg, iters=PR_ITERS)
    log(f"    numpy reference in {time.perf_counter() - t0:.2f}s")
    close("vertex ranks", res.value[0], v_ref)
    close("hyperedge ranks", res.value[1], he_ref)

    # (c) compile once, serve a batch of sources
    sources = rng.choice(hg.n_vertices, N_SOURCES, replace=False)
    sources = sources.astype(np.int32)
    comp = eng.compile(
        alg.shortest_paths_spec(hg, int(sources[0]), SSSP_ITERS)
    )
    traces0 = eng.cache_stats()["traces"]
    cold, cold_s = timed(lambda: comp.run_batch(sources))
    warm_res, warm_s = timed(lambda: comp.run_batch(sources))
    results += [cold, warm_res]
    traces = eng.cache_stats()["traces"] - traces0
    executed = int(np.asarray(warm_res.supersteps_executed))
    log(f"[c] sssp run_batch x{N_SOURCES}: {design_point(cold)}")
    log(f"    cold (compile + execute) {cold_s:.3f}s, warm (execute) "
        f"{warm_s:.3f}s, {traces} trace(s), {executed}/{SSSP_ITERS} "
        f"superstep pairs executed")
    v_b, he_b = warm_res.value
    for i, s in enumerate(sources):
        dv, de = hop_distances_np(hg, int(s), SSSP_ITERS)
        exact(f"sssp vertex hops from {s}", v_b[i], dv)
        exact(f"sssp hyperedge hops from {s}", he_b[i], de)
        exact(f"sssp cold vs warm from {s}", cold.value[0][i], v_b[i])
    reach = np.isfinite(np.asarray(v_b)).sum(axis=1)
    log(f"    {N_SOURCES}/{N_SOURCES} sources equal numpy BFS exactly "
        f"(reached vertices: min {reach.min()}, max {reach.max()})")

    # (d) the serving path: disk-store boot, Frontend, mixed requests
    store_dir = ROOT / ".repro_cache"
    specs = serve_specs(hg, SERVE_ITERS)
    seng = Engine(disk_cache=DiskExecutableCache(store_dir))
    boot = warm(seng, list(specs.values()), batch_sizes=(SERVE_BATCH,),
                queries=WARM_QUERIES)
    log(f"[d] serve boot: {boot['boot_s']:.3f}s, {boot['traces']} "
        f"trace(s), {boot['from_disk']} from disk, {boot['compiled']} "
        f"compiled; paths {boot['paths']}")
    log(f"    disk store after boot: {seng.disk_cache.stats()}")
    # A second engine on the same store: do TPU executables round-trip
    # through serialize_executable?
    eng2 = Engine(disk_cache=DiskExecutableCache(store_dir))
    boot2 = warm(eng2, list(specs.values()), batch_sizes=(SERVE_BATCH,),
                 queries=WARM_QUERIES)
    d2 = eng2.disk_cache.stats()
    n_paths = sum(len(p) for p in boot2["paths"].values())
    log(f"    second boot from the same store: {boot2['boot_s']:.3f}s, "
        f"{boot2['traces']} trace(s), {boot2['from_disk']}/{n_paths} "
        f"from disk; executables round-trip: "
        f"{'yes' if boot2['from_disk'] == n_paths else 'no'} "
        f"(disk_hits={d2['disk_hits']} warm_records={d2['warm_records']} "
        f"disk_errors={d2['disk_errors']} "
        f"last_store_error={d2['last_store_error']})")

    fe = Frontend(seng, max_batch=SERVE_BATCH, max_delay_ms=5.0)
    for key, spec in specs.items():
        fe.register(key, spec)
    trace = [
        ("sssp" if rng.random() < SSSP_MIX else "ppr",
         int(rng.integers(0, hg.n_vertices)))
        for _ in range(N_REQUESTS)
    ]
    t0 = time.perf_counter()
    with fe:
        futs = [(k, q, fe.submit(k, query=q)) for k, q in trace]
        served = [(k, q, f.result(timeout=600)) for k, q, f in futs]
    serve_s = time.perf_counter() - t0
    st = fe.stats()
    log(f"    {len(served)} requests served in {serve_s:.3f}s; execute "
        f"p50 {st['execute']['p50_s'] * 1e3:.2f}ms p99 "
        f"{st['execute']['p99_s'] * 1e3:.2f}ms; flushes "
        f"{st['flush_reasons']}; engine traces "
        f"{seng.cache_stats()['traces']}")
    n_sssp = n_ppr = n_bitwise = 0
    worst_seq = worst_np = 0.0
    for key, q, got in served:
        if key == "sssp":
            dv, de = hop_distances_np(hg, q, SERVE_ITERS)
            exact(f"served sssp vertex hops from {q}", got.value[0], dv)
            exact(f"served sssp hyperedge hops from {q}", got.value[1], de)
            n_sssp += 1
            continue
        # PPR sums floats: the batched executable may reduce in another
        # order than the unbatched one, so bitwise agreement is counted
        # and the check is a tolerance (and numpy, independently).
        seq = fe.compiled("ppr").run(query=q)
        results.append(seq)
        n_bitwise += bool(np.array_equal(np.asarray(got.value),
                                         np.asarray(seq.value)))
        worst_seq = max(worst_seq, close(
            f"served ppr from {q} vs sequential run", got.value,
            seq.value, rtol=1e-5, atol=1e-9, quiet=True))
        worst_np = max(worst_np, close(
            f"served ppr from {q} vs numpy", got.value,
            random_walk_np(hg, q, SERVE_ITERS), atol=1e-8, quiet=True))
        n_ppr += 1
    log(f"    verified {n_sssp} sssp exactly against numpy; {n_ppr} ppr "
        f"against sequential run (bitwise {n_bitwise}/{n_ppr}, worst abs "
        f"err {worst_seq:.3e}) and numpy (worst abs err {worst_np:.3e})")

    # (e) what the device held and what compiled
    for name, e in (("analytics", eng), ("serve", seng),
                    ("second boot", eng2)):
        cs = e.cache_stats()
        log(f"[e] {name} engine: traces={cs['traces']} "
            f"entries={cs['entries']} sources={cs['sources']}")
    no_fallbacks((eng, seng, eng2), results)
    peak(jax.devices())


def four_chips(seed: int) -> None:
    import jax

    from repro import algorithms as alg
    from repro.core import Engine
    from repro.core.executor import select_partition
    from repro.launch.mesh import make_host_mesh
    from repro.reference import hop_distances_np, pagerank_np

    rng = np.random.default_rng(seed)
    hg = generate(seed)
    mesh = make_host_mesh(4)
    pr_spec = alg.pagerank_spec(hg, iters=PR_ITERS)
    sources = rng.choice(hg.n_vertices, N_SOURCES, replace=False)
    sources = sources.astype(np.int32)
    sssp_spec = alg.shortest_paths_spec(hg, int(sources[0]), SSSP_ITERS)

    local = Engine()
    pr_local, wall = timed(lambda: local.run(pr_spec))
    log(f"[local] pagerank {wall:.3f}s: {design_point(pr_local)}")
    sssp_local, wall = timed(
        lambda: local.compile(sssp_spec).run_batch(sources)
    )
    log(f"[local] sssp run_batch x{N_SOURCES} {wall:.3f}s (cold)")
    v_ref, he_ref = pagerank_np(hg, iters=PR_ITERS)
    hops = [hop_distances_np(hg, int(s), SSSP_ITERS) for s in sources]
    results = [pr_local, sssp_local]

    # One partition plan (the auto sweep over the strategy registry)
    # for every spec and both backends: it depends on structure only.
    t0 = time.perf_counter()
    plan, why = select_partition(hg, 4, "auto")
    log(f"[plan] {plan.name} over 4 parts in "
        f"{time.perf_counter() - t0:.2f}s ({why.get('reason')})")
    dist = Engine(plan=plan, mesh=mesh)
    for backend in ("sharded", "replicated"):
        res, wall = timed(lambda: dist.run(pr_spec, backend=backend))
        results.append(res)
        st = res.partition_stats
        log(f"[{backend}] pagerank {wall:.3f}s: {design_point(res)}; vrep="
            f"{st.vertex_replication:.3f} herep="
            f"{st.hyperedge_replication:.3f}")
        close(f"{backend} vertex ranks vs numpy", res.value[0], v_ref)
        close(f"{backend} hyperedge ranks vs numpy", res.value[1], he_ref)
        close(f"{backend} vertex ranks vs local", res.value[0],
              pr_local.value[0])
        comp = dist.compile(sssp_spec, backend=backend)
        cold, cold_s = timed(lambda: comp.run_batch(sources))
        warm_res, warm_s = timed(lambda: comp.run_batch(sources))
        results += [cold, warm_res]
        log(f"[{backend}] sssp run_batch x{N_SOURCES}: cold {cold_s:.3f}s, "
            f"warm {warm_s:.3f}s, "
            f"{int(np.asarray(warm_res.supersteps_executed))}/{SSSP_ITERS} "
            f"pairs executed")
        for i, s in enumerate(sources):
            for j, side in enumerate(("vertex", "hyperedge")):
                exact(f"{backend} sssp {side} hops from {s} vs local",
                      warm_res.value[j][i], sssp_local.value[j][i])
                exact(f"{backend} sssp {side} hops from {s} vs numpy",
                      warm_res.value[j][i], hops[i][j])
        log(f"    {N_SOURCES}/{N_SOURCES} sources equal local and numpy "
            f"exactly")
    no_fallbacks((local, dist), results)
    peak(jax.devices())


def peak(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        peak_b = stats.get("peak_bytes_in_use")
        log(f"    {d}: peak_bytes_in_use="
            + (f"{peak_b} ({peak_b / 2**30:.2f} GiB)" if peak_b is not None
               else "not reported"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated hypergraph and queries")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded/replicated phase")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        print(f"chip_smoke: JAX found no backend: {err}", file=sys.stderr)
        return 1
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import cache_events, use_compile_cache

    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.1f}s; persistent compile "
        f"cache {cache_events()}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
