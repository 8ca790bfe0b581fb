"""How ``correct`` is decided: the program's answers against the plain
references, each compared number against its limit.

The numbers:

* ``pagerank_rel_err``: the largest relative gap of any vertex or
  hyperedge rank of any job in the window from the float64 reference.

Limits live in ``bench/limits/<workload>.json``, one per number, set
from the readings of sound runs and of the control (see ``PERF.md``).
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from harness import reference as ref

LIMITS_DIR = Path(__file__).resolve().parents[1] / "limits"


def load_limits(workload: str) -> dict:
    return json.loads((LIMITS_DIR / f"{workload}.json").read_text())


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return math.inf
    return float((np.abs(got - want) / np.maximum(np.abs(want), 1e-30))
                 .max(initial=0.0))


class Comparison:
    """Collects the compared numbers; each keeps its worst reading."""

    def __init__(self):
        self.numbers: dict[str, float] = {}

    def add(self, name: str, value: float) -> None:
        old = self.numbers.get(name, -math.inf)
        self.numbers[name] = max(old, float(value))

    def pagerank(self, answers, inc, iters, alpha):
        want = ref.pagerank_np(inc, iters, alpha)
        for got in answers:
            for g, w in zip(got, want):
                self.add("pagerank_rel_err", rel_err(g, w))

    def judge(self, limits: dict) -> tuple[bool, dict]:
        """``(all within limits, {name: {"value", "limit"}})``; a number
        without a limit, or one that is not finite, fails."""
        out, ok = {}, bool(self.numbers)
        for name, value in sorted(self.numbers.items()):
            limit = limits.get(name)
            out[name] = {"value": value, "limit": limit}
            if limit is None or not math.isfinite(value) or value > limit:
                ok = False
        return ok, out


def compare_jobs(loop, inc) -> Comparison:
    cmp = Comparison()
    cmp.pagerank([job.value for job in loop.jobs], inc, loop.iters,
                 loop.alpha)
    return cmp
