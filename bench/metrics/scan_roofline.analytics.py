"""Share of the HBM roofline: the least bytes the executed superstep
pairs need, from the algorithm's shapes (``harness.roofline``), over
the device's busy time in the window times its peak bytes per
second."""
from harness.readers import jobs
from harness.roofline import WIDTHS, dense_pair_min_bytes


def read(run):
    done = jobs(run)
    if (not done or run.trace is None or run.peaks is None
            or run.driver.algorithm not in WIDTHS):
        return None
    inc = run.inc
    need = len(done) * run.driver.iters * dense_pair_min_bytes(
        run.driver.algorithm, inc.nnz, inc.n_vertices, inc.n_hyperedges)
    return 100.0 * need / (run.trace.busy_s * run.peaks["hbm_bytes_per_s"])
