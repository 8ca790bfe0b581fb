"""Index lanes the delivery gathers a half-step pair per live incidence:
each direction's dense ELL slots plus residual lanes, times the leaf
groups its message goes through the layout in, over twice the live
incidences (``lanes_per_incidence`` when every message is one group),
the median over the window's jobs of the program's ``engine.run`` span
args."""
from metrics._run_args import median_per_job


def read(run):
    return median_per_job(
        run, ("delivery_gathered_lanes", "live_nnz"),
        lambda a: a["delivery_gathered_lanes"] / (2 * a["live_nnz"]))
