"""The trace reduction and the metric arithmetic."""
import types
from pathlib import Path

import pytest

from harness import trace
from harness.roofline import UnknownDevice, dense_pair_min_bytes, peaks

DATA = Path(__file__).with_name("data")


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (25, 26)]
    assert trace.union_length(iv, 0, 40) == 25
    assert trace.union_length(iv, 8, 22) == 9
    assert trace.idle_intervals(iv, 0, 40) == [(15, 20), (30, 40)]
    assert trace.idle_intervals(iv, -5, 12) == [(-5, 0)]


def test_self_times_of_nested_ops():
    ev = [("while", 0, 100), ("a", 10, 30), ("b", 40, 50), ("c", 42, 44),
          ("d", 120, 130)]
    got = trace.self_times(ev, 0, 125)
    assert got == {"while": 70, "a": 20, "b": 8, "c": 2, "d": 5}


def test_names():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(%a)") == "fusion.12"
    assert trace.module_name("jit_scan(1696105059892179459)") == "jit_scan"


def test_summary_of_a_chip_trace():
    """Five calls of a small jitted program on a TPU v5 lite, traced in a
    ``bench.window`` span of 59.7 ms with 10 ms sleeps between calls."""
    s = trace.summarize(str(DATA / "tiny.xplane.pb"))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.059727, rel=1e-3)
    assert s.busy_s == pytest.approx(0.000156, rel=0.01)
    assert set(s.modules_s) == {"jit__lambda"}
    assert s.modules_s["jit__lambda"] == pytest.approx(0.000156, rel=0.01)
    assert 99.0 < s.idle_pct < 100.0
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "multiply_add_fusion"
    assert len(bd["device_ops"]) <= 10
    assert bd["idle_gaps"][0][0] == "bench.window"
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)


def test_peaks_are_keyed_by_device_kind():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")


def test_min_bytes_of_a_pagerank_pair():
    # 12 B per incidence to hyperedges, 16 B back; state r+w per side
    assert dense_pair_min_bytes("pagerank", 100, 10, 20) == (
        100 * 12 + 2 * 10 * 4 + 100 * 16 + 2 * 20 * 4)


def _reader(name):
    import run

    return run.metric_reader(name)


def _jobs_run():
    jobs = [types.SimpleNamespace(start=0.0, end=2.0, device_wait_s=1.5),
            types.SimpleNamespace(start=2.0, end=4.5, device_wait_s=1.5)]
    driver = types.SimpleNamespace(kind="jobs", jobs=jobs, iters=10,
                                   algorithm="pagerank")
    summary = types.SimpleNamespace(busy_s=3.0, idle_pct=25.0)
    inc = types.SimpleNamespace(nnz=1000, n_vertices=100, n_hyperedges=50)
    return types.SimpleNamespace(
        driver=driver, window=(0.0, 4.5), seconds=4.0, setup_s=7.0,
        trace=summary, inc=inc, peaks={"hbm_bytes_per_s": 1e9},
        traffic={})


def test_job_metrics():
    run = _jobs_run()
    assert _reader("job_s")(run) == 2.25
    assert _reader("setup_s")(run) == 7.0
    assert _reader("run_dispatch_ms.analytics")(run) == pytest.approx(750.0)
    assert _reader("halfstep_ms.analytics")(run) == pytest.approx(75.0)
    need = 2 * 10 * dense_pair_min_bytes("pagerank", 1000, 100, 50)
    assert _reader("scan_roofline.analytics")(run) == pytest.approx(
        100 * need / (3.0 * 1e9))
    assert _reader("device_idle_pct.analytics")(run) == 25.0
    run.trace = None
    assert _reader("halfstep_ms.analytics")(run) is None
    assert _reader("scan_roofline.analytics")(run) is None
