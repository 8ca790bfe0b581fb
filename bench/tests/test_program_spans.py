"""The readers of the program's own spans (``bench/metrics/*.analytics``
that read ``repro.obs.trace.default_tracer()``), on stub runs."""
import time
import types

import jax
import pytest

import run as bench_run
from repro.obs.trace import default_tracer

READERS = ("resolve_ms.analytics", "layout_build_ms.analytics",
           "retrace_ms.analytics", "compile_ms.analytics",
           "compiles_per_job.analytics")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture
def tracer():
    """``default_tracer()`` on a fake clock, emptied before and after."""
    tr = default_tracer()
    real = tr.clock
    tr.clock = FakeClock()
    tr.clear()
    yield tr
    tr.clock = real
    tr.clear()


def read(name, run):
    return bench_run.metric_reader(name)(run)


def stub(window, jobs, trace=True):
    return types.SimpleNamespace(
        trace=object() if trace else None, window=window,
        driver=types.SimpleNamespace(jobs=[None] * jobs))


def compile_event(event, ago_s, dur_s):
    """One of JAX's compile-path time spans, started ``ago_s`` seconds
    ago on the wall clock: the program's listener maps it onto the open
    span's clock."""
    start = time.time() - ago_s
    jax.monitoring.record_event_time_span(event, start, start + dur_s,
                                          fun_name="f")


def job(tr, *, resolve=0.010, layout=0.200, trace=((0.050, 0.030),),
        lower=0.040, compile_s=0.100, wait=0.500):
    """One ``Engine.run``-shaped span tree on the fake clock.  ``trace``
    holds (start offset into dispatch, duration) pairs of ``jax.trace``
    spans; the lower and compile follow them."""
    clock = tr.clock
    with tr.span("engine.run"):
        with tr.span("engine.resolve"):
            clock.t += resolve
        if layout:
            with tr.span("engine.layout_build"):
                clock.t += layout
        with tr.span("engine.dispatch"):
            end = max(s + d for s, d in trace) + lower + compile_s
            clock.t += end
            for s, d in trace:
                compile_event(TRACE, end - s, d)
            t_lower = max(s + d for s, d in trace)
            compile_event(LOWER, end - t_lower, lower)
            compile_event(COMPILE, compile_s, compile_s)
        with tr.span("engine.device_wait"):
            clock.t += wait


def test_per_job_readings(tracer):
    lo = tracer.clock.t
    job(tracer)
    job(tracer)
    run = stub((lo, tracer.clock.t), jobs=2)
    assert read("resolve_ms.analytics", run) == pytest.approx(10.0)
    assert read("layout_build_ms.analytics", run) == pytest.approx(200.0)
    assert read("retrace_ms.analytics", run) == pytest.approx(
        70.0, abs=0.5)
    assert read("compile_ms.analytics", run) == pytest.approx(
        100.0, abs=0.5)
    assert read("compiles_per_job.analytics", run) == 1.0


def test_nested_traces_count_once(tracer):
    lo = tracer.clock.t
    # an outer trace [0, 60) ms with an inner [10, 30) nested in it and
    # a second outer one [80, 90): 70 ms of tracing, then 40 of lowering
    job(tracer, trace=((0.0, 0.060), (0.010, 0.020), (0.080, 0.010)))
    run = stub((lo, tracer.clock.t), jobs=1)
    assert read("retrace_ms.analytics", run) == pytest.approx(
        110.0, abs=0.5)


def test_window_filter(tracer):
    job(tracer)                          # before the window
    lo = tracer.clock.t
    job(tracer, layout=0.300)
    job(tracer, layout=0.100)
    hi = tracer.clock.t
    job(tracer, layout=0.900)            # after it
    run = stub((lo, hi), jobs=2)
    assert read("layout_build_ms.analytics", run) == pytest.approx(200.0)
    assert read("compiles_per_job.analytics", run) == 1.0


def test_zero_when_the_window_holds_none_of_a_kind(tracer):
    lo = tracer.clock.t
    job(tracer, layout=0.0)
    run = stub((lo, tracer.clock.t), jobs=1)
    assert read("layout_build_ms.analytics", run) == 0.0
    assert read("resolve_ms.analytics", run) > 0.0


def test_compiles_outside_dispatch_are_not_counted(tracer):
    lo = tracer.clock.t
    with tracer.span("engine.run"):
        with tracer.span("engine.layout_build"):
            tracer.clock.t += 0.2
            compile_event(COMPILE, 0.1, 0.1)
    run = stub((lo, tracer.clock.t), jobs=1)
    assert read("compile_ms.analytics", run) == 0.0
    assert read("compiles_per_job.analytics", run) == 0.0
    assert read("layout_build_ms.analytics", run) == pytest.approx(200.0)


@pytest.mark.parametrize("name", READERS)
def test_none_without_something_to_read(tracer, name, monkeypatch):
    lo = tracer.clock.t
    job(tracer)
    hi = tracer.clock.t
    # no trace (a run with --trace 0)
    assert read(name, stub((lo, hi), jobs=1, trace=False)) is None
    # no engine.run span in the window
    assert read(name, stub((hi + 1, hi + 2), jobs=1)) is None
    with tracer.span("bench.other"):
        tracer.clock.t += 1.0
    assert read(name, stub((hi, tracer.clock.t), jobs=1)) is None
    # a program without default_tracer
    import repro.obs.trace

    monkeypatch.delattr(repro.obs.trace, "default_tracer")
    assert read(name, stub((lo, hi), jobs=1)) is None


def test_a_profiled_engine_run_reads_through(tmp_path):
    """The real program under the CPU profiler: a first call compiles."""
    import numpy as np

    from repro import algorithms as alg
    from repro.core import Engine
    from repro.core.hypergraph import HyperGraph

    rng = np.random.default_rng(0)
    hg = HyperGraph.from_coo(rng.integers(0, 60, 400),
                             rng.integers(0, 40, 400), 60, 40)
    default_tracer().clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        lo = time.perf_counter()
        for _ in range(2):
            res = Engine(delivery="pallas_fused").run(
                alg.pagerank_spec(hg, iters=3))
            jax.block_until_ready(res.value)
        hi = time.perf_counter()
    run = stub((lo, hi), jobs=2)
    got = {name: read(name, run) for name in READERS}
    default_tracer().clear()
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    assert got["layout_build_ms.analytics"] > 0.0
    assert got["compiles_per_job.analytics"] >= 0.5
    assert got["compile_ms.analytics"] > 0.0
    assert sum(v for k, v in got.items() if k.endswith("ms.analytics")
               ) <= 1e3 * (hi - lo) / 2
