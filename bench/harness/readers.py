"""Arithmetic shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations


def jobs(run):
    return run.driver.jobs if run.driver.kind == "jobs" else None


def idle_pct(run):
    if run.trace is None:
        return None
    return run.trace.idle_pct
