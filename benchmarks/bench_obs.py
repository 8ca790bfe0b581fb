"""Observability tier: trace coverage + the zero-overhead contract.

Claims the obs layer makes about itself, measured:

* **trace coverage** — a traced end-to-end serve run (Engine(tracer=)
  + Frontend, synchronous ``pump`` mode) under the JAX profiler puts
  every span name on the profiler trace's host plane, and its
  top-level (depth-0) spans account for the serve wall time within
  20%.  A tracer that drops the compile or misattributes the execute
  would show up here as a coverage hole.
* **zero overhead untraced** — steady-state ``run_batch`` through an
  Engine WITHOUT a tracer must cost the same as one WITH a tracer to
  within noise (interleaved rounds, median of per-round ratios — the
  ``bench_delivery`` discipline for this drifting shared host).  With
  no tracer and no profiler session a span site is one
  ``TraceAnnotation.is_enabled()`` check, timed here per call; this is
  the canary that a future edit doesn't move span bookkeeping onto
  the untraced path.

Writes ``BENCH_obs.json`` (uploaded by the nightly CI job).
"""
from __future__ import annotations

import glob
import os
import tempfile
import time

import jax
import numpy as np

from repro.algorithms import random_walk_spec, shortest_paths_spec
from repro.core import Engine
from repro.data import make_dataset
from repro.obs import Tracer, maybe_span
from repro.obs.trace import COMPILE_SPANS
from repro.serve import Frontend

from benchmarks.common import SCALE, emit_json, row

REQUESTS = 32
MAX_BATCH = 16
ITERS = 8
ROUNDS = 9
COVERAGE_BAND = 0.20        # depth-0 span sum within ±20% of wall
OVERHEAD_CEILING = 1.30     # traced/untraced median ratio (noise incl.)


def _specs(hg):
    return {
        "sssp": shortest_paths_spec(hg, 0, ITERS),
        "ppr": random_walk_spec(hg, iters=ITERS),
    }


def _traced_serve(hg) -> dict:
    tracer = Tracer()
    engine = Engine(tracer=tracer)
    fe = Frontend(engine, max_batch=MAX_BATCH, max_delay_ms=5.0)
    for key, spec in _specs(hg).items():
        fe.register(key, spec)
    rng = np.random.default_rng(0)
    trace = [
        ("sssp" if rng.random() < 0.6 else "ppr",
         int(rng.integers(0, hg.n_vertices)))
        for _ in range(REQUESTS)
    ]
    log_dir = tempfile.mkdtemp(prefix="repro-bench-obs-")
    with jax.profiler.trace(log_dir):
        t0 = time.perf_counter()
        futs = [fe.submit(key, query=q) for key, q in trace]
        fe.pump(drain=True)
        for f in futs:
            f.result()
        wall_s = time.perf_counter() - t0

    spans = tracer.spans()
    top_s = sum(sp.dur_s for sp in spans if sp.depth == 0)
    coverage = top_s / max(wall_s, 1e-12)
    by_cat: dict = {}
    for sp in spans:
        by_cat[sp.cat] = by_cat.get(sp.cat, 0) + 1

    # every span the tracer opened is a host event of its name in the
    # profiler's trace (JAX's own compile spans are recorded after the
    # fact, and the profiler has its own events for them)
    host = _host_event_names(log_dir)
    opened = {sp.name for sp in spans} - set(COMPILE_SPANS.values())
    missing = opened - host
    assert opened and not missing, (
        f"spans missing from the profiler trace's host plane: {missing}"
    )

    assert abs(coverage - 1.0) <= COVERAGE_BAND, (
        f"depth-0 span coverage {coverage:.2f} of serve wall "
        f"(outside ±{COVERAGE_BAND:.0%}): the tracer is losing or "
        "double-counting phases"
    )
    row(f"obs/traced_serve{REQUESTS}", wall_s * 1e6,
        f"coverage={coverage:.3f};spans={len(spans)};"
        f"dropped={tracer.dropped}")
    return {
        "wall_s": wall_s,
        "coverage": coverage,
        "n_spans": len(spans),
        "dropped": tracer.dropped,
        "spans_by_cat": by_cat,
        "host_events": len(host),
    }


def _host_event_names(log_dir: str) -> set:
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return {
        ev.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }


def _off_cost_ns(n: int = 200_000) -> float:
    """Nanoseconds per ``maybe_span(None, ...)`` with no profiler
    session: the cost an untraced span site pays."""
    t0 = time.perf_counter()
    for _ in range(n):
        with maybe_span(None, "engine.run"):
            pass
    return (time.perf_counter() - t0) / n * 1e9


def _overhead(hg) -> dict:
    """Interleaved steady-state run_batch: traced vs untraced engine."""
    spec = shortest_paths_spec(hg, 0, ITERS)
    queries = np.arange(MAX_BATCH, dtype=np.int32) % hg.n_vertices
    plain = Engine().compile(spec)
    traced_eng = Engine(tracer=Tracer(capacity=16))
    traced = traced_eng.compile(spec)
    for c in (plain, traced):  # warm both executables
        jax.block_until_ready(c.run_batch(queries).value)
    ratios = []
    t_plain = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        jax.block_until_ready(plain.run_batch(queries).value)
        dt_plain = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(traced.run_batch(queries).value)
        dt_traced = time.perf_counter() - t0
        t_plain.append(dt_plain)
        ratios.append(dt_traced / dt_plain)
    ratios.sort()
    t_plain.sort()
    ratio = ratios[len(ratios) // 2]
    assert ratio <= OVERHEAD_CEILING, (
        f"traced run_batch {ratio:.2f}x untraced "
        f"(> {OVERHEAD_CEILING}x): span bookkeeping leaked onto the "
        "hot path"
    )
    row("obs/untraced_run_batch", t_plain[len(t_plain) // 2] * 1e6,
        f"traced_over_untraced={ratio:.3f}")
    return {
        "untraced_s": t_plain[len(t_plain) // 2],
        "traced_over_untraced": ratio,
        "rounds": ROUNDS,
    }


def run() -> None:
    hg = make_dataset("dblp", scale=0.002 * SCALE, seed=0)
    results = {
        "scale": SCALE,
        "n_vertices": hg.n_vertices,
        "n_hyperedges": hg.n_hyperedges,
        "nnz": hg.nnz,
        "requests": REQUESTS,
        "traced_serve": _traced_serve(hg),
        "overhead": _overhead(hg),
        "span_site_off_ns": _off_cost_ns(),
    }
    emit_json("obs", results)


if __name__ == "__main__":
    run()
