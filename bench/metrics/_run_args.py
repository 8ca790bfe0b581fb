"""Arithmetic shared by the readers of the args a fused-delivery job
records on its ``engine.run`` span (``live_nnz``, ``delivery_lanes``,
``layout_bytes``: ``repro.kernels.deliver.layout_span_args``).

A reader takes the median over the window's jobs whose span has every
arg it needs.  It gets ``None`` when there is nothing to read: no trace,
no ``engine.run`` span in the window, or a program whose spans lack the
args.
"""
from __future__ import annotations

import statistics

from metrics._program_spans import named, window_spans


def median_per_job(run, keys, value):
    """The median of ``value(args)`` over the window's ``engine.run``
    spans that carry every one of ``keys``."""
    spans = window_spans(run)
    if spans is None:
        return None
    vals = [value(s.args) for s in named(spans, "engine.run")
            if all(k in s.args for k in keys)]
    return float(statistics.median(vals)) if vals else None
