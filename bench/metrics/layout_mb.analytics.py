"""Device megabytes (1e6 B) of the fused delivery layout pair the jobs
ran on, the median over the window's jobs of the program's
``engine.run`` span arg ``layout_bytes``."""
from metrics._run_args import median_per_job


def read(run):
    return median_per_job(run, ("layout_bytes",),
                          lambda a: a["layout_bytes"] / 1e6)
