"""The readers of the args a fused-delivery job records on its
``engine.run`` span (``lanes_per_incidence.analytics``,
``layout_mb.analytics``), on stub runs and on the real program."""
import types

import pytest

import run as bench_run
from repro.obs.trace import default_tracer

READERS = ("lanes_per_incidence.analytics", "layout_mb.analytics")


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


def job(tr, **args):
    """One ``engine.run`` span of 1 s on the fake clock, with ``args``."""
    with tr.span("engine.run") as sp:
        sp.args.update(args)
        tr.clock.t += 1.0


def counts(lanes, live, nbytes):
    return dict(delivery_lanes=lanes, live_nnz=live, layout_bytes=nbytes)


def test_readings(tracer):
    lo = tracer.clock.t
    job(tracer, **counts(13_701_120, 2_838_951, 61_811_216))
    job(tracer, **counts(13_701_120, 2_838_951, 61_811_216))
    run = stub((lo, tracer.clock.t), jobs=2)
    assert read("lanes_per_incidence.analytics", run) == pytest.approx(
        13_701_120 / (2 * 2_838_951))
    assert read("layout_mb.analytics", run) == pytest.approx(61.811216)


def test_median_over_the_window_jobs_only(tracer):
    job(tracer, **counts(900, 10, 9e9))              # before the window
    tracer.clock.t += 0.5
    lo = tracer.clock.t
    for lanes, nbytes in ((40, 1e6), (60, 3e6), (100, 2e6)):
        job(tracer, **counts(lanes, 10, nbytes))
    hi = tracer.clock.t
    tracer.clock.t += 0.5
    job(tracer, **counts(900, 10, 9e9))              # after it
    run = stub((lo, hi), jobs=3)
    assert read("lanes_per_incidence.analytics", run) == pytest.approx(3.0)
    assert read("layout_mb.analytics", run) == pytest.approx(2.0)


def test_jobs_without_the_args_are_skipped(tracer):
    """A job through the reference delivery records no counts; the
    median is over the jobs that do."""
    lo = tracer.clock.t
    job(tracer, structure_cache="hit")
    job(tracer, **counts(50, 10, 4e6))
    run = stub((lo, tracer.clock.t), jobs=2)
    assert read("lanes_per_incidence.analytics", run) == pytest.approx(2.5)
    assert read("layout_mb.analytics", run) == pytest.approx(4.0)


@pytest.mark.parametrize("name", READERS)
def test_none_without_something_to_read(tracer, name, monkeypatch):
    lo = tracer.clock.t
    job(tracer, **counts(50, 10, 4e6))
    hi = tracer.clock.t
    # no trace (a run with --trace 0)
    assert read(name, stub((lo, hi), jobs=1, trace=False)) is None
    # no engine.run span in the window
    assert read(name, stub((hi + 1, hi + 2), jobs=1)) is None
    # a program whose engine.run spans lack the args
    job(tracer, structure_cache="hit")
    assert read(name, stub((hi, tracer.clock.t), jobs=1)) is None
    # a program without default_tracer
    import repro.obs.trace

    monkeypatch.delattr(repro.obs.trace, "default_tracer")
    assert read(name, stub((lo, hi), jobs=1)) is None


def test_a_profiled_engine_run_reads_through(tmp_path, monkeypatch):
    """The real program under the CPU profiler: the readings equal the
    cached layouts' own counts."""
    import time

    import jax
    import numpy as np

    from repro import algorithms as alg
    from repro.core import Engine
    from repro.core.hypergraph import HyperGraph

    monkeypatch.delenv("REPRO_DELIVERY_LOWERING", raising=False)
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
    run = stub((lo, hi), jobs=2)
    got = {name: read(name, run) for name in READERS}
    default_tracer().clear()
    layouts = eng._structures[-1].layouts
    lanes = sum(l.ell_slots + l.rem_len for l in layouts)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(layouts))
    assert got["lanes_per_incidence.analytics"] == pytest.approx(
        lanes / (2 * hg.nnz))
    assert got["layout_mb.analytics"] == pytest.approx(nbytes / 1e6)
