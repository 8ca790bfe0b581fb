"""Lanes the delivery scans touch a half-step pair per live incidence:
both directions' dense ELL slots plus residual lanes over twice the live
incidences (1.0 is no padding), the median over the window's jobs of the
program's ``engine.run`` span args."""
from metrics._run_args import median_per_job


def read(run):
    return median_per_job(
        run, ("delivery_lanes", "live_nnz"),
        lambda a: a["delivery_lanes"] / (2 * a["live_nnz"]))
