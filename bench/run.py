"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload dblp.pagerank --seed 7 --seconds 45 --trace 0

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the configuration's file of sizes (``bench/configs/<config>.json``), the
traffic mix (``bench/traffic/<traffic>.json``), the limits of the
correctness comparison (``bench/limits/<workload>.json``) and one reader
per metric (``bench/metrics/<metric>.py``).  A new cell or metric is new
files and entries, never an edit here.

A run makes the cell's hypergraph from ``--seed``, sets the program up
(upload, engine, executables from JAX's persistent cache in the
checkout, the cell's own shapes warmed by one job), measures for
``--seconds``, closes the window, reads the device's peak memory, frees
the program's state and compares the answers with the numpy reference.  With ``--trace 1`` the
window runs under the JAX profiler and the per-layer metrics are read
from the trace; with ``--trace 0`` the end-to-end metrics are reported.

Information goes to standard error; its last lines are the compared
numbers beside their limits.  The last line of standard output is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (``breakdown`` with ``--trace 1``) and, last, ``checks``.
With no TPU, fewer chips than the cell asks for, or a device kind
missing from ``bench/harness/peaks.json``, it exits 1 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"


class BenchError(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding a cell by name -------------------------------------------------

def load_cell(workload: str) -> dict:
    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.is_file():
        raise BenchError(f"no {bench_file.name} at {ROOT}")
    bm = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def metrics(kind):
        return [m for m in bm[kind]
                if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": metrics("end_to_end"),
            "per_layer": metrics("per_layer")}


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the run ------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader gets: the driver with its records, the
    window, the set-up time, the trace summary (``--trace 1``), the
    chip's peaks, the hypergraph's sizes and the cell's files."""

    workload: str
    driver: object
    seconds: float
    setup_s: float
    window: tuple
    inc: object
    config: dict
    traffic: dict
    peaks: dict
    trace: object = None


class _CompileCounter:
    """Backend compiles and persistent-cache events seen by JAX."""

    def __init__(self):
        import jax

        self.counts = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def check_devices(chips: int):
    """The chips this run measures; raises when there is no TPU, too few
    of them, or one whose peaks are unknown."""
    import jax

    from harness.roofline import UnknownDevice, peaks

    try:
        devices = jax.devices()
    except RuntimeError as err:
        raise BenchError(f"JAX found no backend: {err}") from err
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips; JAX found "
                         f"{len(devices)}")
    try:
        return devices, peaks(devices[0].device_kind)
    except UnknownDevice as err:
        raise BenchError(str(err)) from err


def memory_peak(devices) -> int | None:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, config: dict | None = None) -> dict:
    """One run of one cell; returns the result line's object.

    ``require_chip=False`` and ``config`` are for the benchmark's own
    tests: they run the rest of a run on the CPU at a small size."""
    found = load_cell(workload)
    cell = found["cell"]
    config = config or found["config"]
    traffic = found["traffic"]
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    if require_chip:
        devices, chip_peaks = check_devices(int(cell["chips"]))
    else:
        devices, chip_peaks = jax.devices(), None
    compiles = _CompileCounter()

    from harness import check, data, drivers

    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; jax {jax.__version__}; workload {workload} "
        f"seed {seed} seconds {seconds} trace {int(trace)}")
    t0 = drivers.clock()
    inc = data.make_incidence(config, seed)
    log(f"data: |V|={inc.n_vertices} |E|={inc.n_hyperedges} "
        f"nnz={inc.nnz}; generated in {drivers.clock() - t0:.3f}s")
    if traffic["kind"] != "jobs":
        raise BenchError(f"unknown traffic kind {traffic['kind']!r}")
    driver = drivers.JobLoop(traffic, inc)
    driver.setup()
    log(f"design point: {driver.design}")
    setup_s = drivers.clock() - T_START
    log(f"setup_s {setup_s:.3f}; compile events in set-up "
        f"{compiles.snapshot()}")

    trace_dir = CACHE / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    before = compiles.snapshot()
    with jax.profiler.TraceAnnotation("bench.window"):
        window = driver.window(seconds)
    during = {k: v - before[k] for k, v in compiles.snapshot().items()}
    if trace:
        jax.profiler.stop_trace()
    # A persistent-cache hit still counts as a backend compile event: the
    # misses are what compiled.
    log(f"compile events in the window: {during} "
        f"({during['cache_misses']} compiled)")
    mem = memory_peak(devices)
    driver.fetch()
    driver.release()

    summary = None
    if trace:
        from harness.trace import find_xplane, summarize

        summary = summarize(find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(workload, driver, seconds, setup_s, window, inc, config,
              traffic, chip_peaks, summary)

    metrics = {}
    for m in found["per_layer" if trace else "end_to_end"]:
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted = len(driver.jobs)
    log(f"jobs: {attempted} in {window[1] - window[0]:.3f}s; "
        f"{driver.summary()}")
    within, checks = check.compare_jobs(driver, inc).judge(
        check.load_limits(workload))
    correct = within and attempted > 0

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # Compile caches inside the checkout, at fixed paths.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH))
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except BenchError as err:
        log(f"bench: {err}")
        return 1
    print(json.dumps(_finite(out)))
    return 0


def _finite(x):
    """JSON has no infinity: a number that is not finite reads null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


if __name__ == "__main__":
    sys.exit(main())
