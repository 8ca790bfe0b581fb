"""Arithmetic shared by the readers of the program's own spans.

While the profiler records the window, the program records its spans
into ``repro.obs.trace.default_tracer()``, on ``time.perf_counter``
like ``drivers.clock``.  A reader takes the spans that start inside
``run.window`` and divides by the jobs run in it.  It gets ``None``
when there is nothing to read: no trace, a program without
``default_tracer``, or no ``engine.run`` span in the window.
"""
from __future__ import annotations

import sys

from harness.trace import union_length

_logged: set = set()


def window_spans(run):
    if run.trace is None:
        return None
    try:
        from repro.obs.trace import default_tracer
    except ImportError:
        return None
    tracer = default_tracer()
    lo, hi = run.window
    spans = [s for s in tracer.spans() if lo <= s.t0 <= hi]
    if not any(s.name == "engine.run" for s in spans):
        return None
    if id(run) not in _logged:
        _logged.add(id(run))
        print(f"program spans: {len(spans)} in the window "
              f"({len(spans) / len(run.driver.jobs):.1f} per job), "
              f"dropped {tracer.dropped}", file=sys.stderr, flush=True)
    return spans


def named(spans, name: str, under: str | None = None) -> list:
    """The spans called ``name``; with ``under``, only those with an
    ancestor called ``under``."""
    by_id = {s.id: s for s in spans}

    def inside(s):
        p = by_id.get(s.parent)
        while p is not None:
            if p.name == under:
                return True
            p = by_id.get(p.parent)
        return False

    return [s for s in spans
            if s.name == name and (under is None or inside(s))]


def ms_per_job(run, seconds: float) -> float:
    return 1e3 * seconds / len(run.driver.jobs)


def total_ms(run, name: str, under: str | None = None):
    """Milliseconds per job in spans called ``name``."""
    spans = window_spans(run)
    if spans is None:
        return None
    return ms_per_job(run, sum(s.dur_s for s in named(spans, name, under)))


def union_ms(run, names, under: str) -> float | None:
    """Milliseconds per job covered by spans called any of ``names``
    under ``under``, nested ones counted once."""
    spans = window_spans(run)
    if spans is None:
        return None
    iv = [(s.t0, s.t0 + s.dur_s) for n in names
          for s in named(spans, n, under)]
    return ms_per_job(run, union_length(iv, float("-inf"), float("inf")))
