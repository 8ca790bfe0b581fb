"""The reader of the ``delivery_gathered_lanes`` arg a fused-delivery
job records on its ``engine.run`` span
(``gathered_lanes_per_incidence.analytics``), on stub runs and on the
real program."""
import types

import pytest

import run as bench_run
from repro.obs.trace import default_tracer

NAME = "gathered_lanes_per_incidence.analytics"


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


def read(run):
    return bench_run.metric_reader(NAME)(run)


def stub(window, jobs, trace=True):
    return types.SimpleNamespace(
        trace=object() if trace else None, window=window,
        driver=types.SimpleNamespace(jobs=[None] * jobs))


def job(tr, **args):
    """One ``engine.run`` span of 1 s on the fake clock, with ``args``."""
    with tr.span("engine.run") as sp:
        sp.args.update(args)
        tr.clock.t += 1.0


def test_reading(tracer):
    lo = tracer.clock.t
    for _ in range(2):
        job(tracer, delivery_gathered_lanes=8_012_416, live_nnz=2_838_951,
            delivery_lanes=8_012_416)
    run = stub((lo, tracer.clock.t), jobs=2)
    assert read(run) == pytest.approx(8_012_416 / (2 * 2_838_951))


def test_median_over_the_window_jobs_that_carry_the_arg(tracer):
    job(tracer, delivery_gathered_lanes=900, live_nnz=10)  # before it
    tracer.clock.t += 0.5
    lo = tracer.clock.t
    for lanes in (40, 60, 100):
        job(tracer, delivery_gathered_lanes=lanes, live_nnz=10)
    # a parent's fused job: layout counts, but no gathered lanes
    job(tracer, delivery_lanes=500, live_nnz=10)
    hi = tracer.clock.t
    tracer.clock.t += 0.5
    job(tracer, delivery_gathered_lanes=900, live_nnz=10)  # after it
    assert read(stub((lo, hi), jobs=4)) == pytest.approx(3.0)


def test_none_without_something_to_read(tracer, monkeypatch):
    lo = tracer.clock.t
    job(tracer, delivery_gathered_lanes=50, live_nnz=10)
    hi = tracer.clock.t
    # no trace (a run with --trace 0)
    assert read(stub((lo, hi), jobs=1, trace=False)) is None
    # no engine.run span in the window
    assert read(stub((hi + 1, hi + 2), jobs=1)) is None
    # a program whose engine.run spans lack the arg, as the parent's do
    job(tracer, delivery_lanes=50, live_nnz=10, layout_bytes=4e6)
    assert read(stub((hi, tracer.clock.t), jobs=1)) is None
    # a program without default_tracer
    import repro.obs.trace

    monkeypatch.delattr(repro.obs.trace, "default_tracer")
    assert read(stub((lo, hi), jobs=1)) is None


def test_a_profiled_engine_run_reads_through(tmp_path):
    """The real program under the CPU profiler: PageRank's he->v pair
    goes through the layout as one group, so the reading is both
    directions' lanes over twice the live incidences."""
    import time

    import jax
    import numpy as np

    from repro import algorithms as alg
    from repro.core import Engine
    from repro.core.hypergraph import HyperGraph

    rng = np.random.default_rng(0)
    hg = HyperGraph.from_coo(rng.integers(0, 60, 400),
                             rng.integers(0, 40, 400), 60, 40)
    eng = Engine(delivery="pallas_fused")
    default_tracer().clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        lo = time.perf_counter()
        for _ in range(2):
            jax.block_until_ready(
                eng.run(alg.pagerank_spec(hg, iters=3)).value)
        hi = time.perf_counter()
    got = read(stub((lo, hi), jobs=2))
    default_tracer().clear()
    layouts = eng._structures[-1].layouts
    lanes = sum(l.ell_slots + l.rem_len for l in layouts)
    assert got == pytest.approx(lanes / (2 * hg.nnz))
