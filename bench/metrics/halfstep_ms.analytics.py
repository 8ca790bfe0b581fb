"""Device busy milliseconds per executed half-superstep: the trace's
busy time in the window over jobs x superstep pairs x 2 (the jobs'
scans never halt early: every entity stays active)."""
from harness.readers import jobs


def read(run):
    done = jobs(run)
    if not done or run.trace is None:
        return None
    return 1e3 * run.trace.busy_s / (len(done) * run.driver.iters * 2)
