"""The traffic a cell drives through the program.

``jobs``: batch analytics, a closed loop of whole-graph jobs through
``Engine.run`` on one Engine and one device-resident hypergraph, back to
back.  It is set up, warmed and then driven for a window by ``run.py``;
the traffic file (``bench/traffic/<name>.json``) holds every parameter.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

clock = time.perf_counter


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def build_spec(hg, kind: str, iters: int, alpha: float = 0.15):
    from repro import algorithms as alg

    if kind == "pagerank":
        return alg.pagerank_spec(hg, iters=iters, alpha=alpha)
    raise ValueError(f"unknown algorithm {kind!r}")


def design_point(config, decision) -> str:
    d = decision.get("delivery", {}) if decision else {}
    return (f"representation={config.representation} "
            f"backend={config.backend} "
            f"partition={config.partition_strategy} "
            f"delivery={config.delivery} lowering={d.get('lowering')}")


@dataclasses.dataclass
class Job:
    start: float
    end: float
    device_wait_s: float
    value: object = None          # device arrays until the check


class JobLoop:
    """Closed-loop jobs: the next starts when the last one's result is
    ready on the device."""

    kind = "jobs"

    def __init__(self, traffic: dict, inc):
        self.traffic = traffic
        self.inc = inc
        self.algorithm = traffic["algorithm"]
        self.iters = int(traffic["iters"])
        self.alpha = float(traffic.get("alpha", 0.15))
        self.jobs: list[Job] = []
        self.design = None

    def setup(self):
        import jax

        from repro.core import Engine
        from repro.core.hypergraph import HyperGraph

        inc = self.inc
        self.hg = HyperGraph.from_coo(inc.src, inc.dst, inc.n_vertices,
                                      inc.n_hyperedges)
        jax.block_until_ready((self.hg.src, self.hg.dst))
        self.engine = Engine()
        job = self._one()        # compiles, or loads
        self.design = design_point(job.value.config, job.value.decision)

    def _one(self) -> Job:
        import jax

        with _annotate("bench.job"):
            t0 = clock()
            res = self.engine.run(build_spec(self.hg, self.algorithm,
                                             self.iters, self.alpha))
            jax.block_until_ready(res.value)
            t1 = clock()
        wait = float(res.decision.get("measured", {}).get(
            "device_wait_s", float("nan")))
        return Job(t0, t1, wait, res)

    def window(self, seconds: float) -> tuple[float, float]:
        """Jobs back to back while the window is open; the window closes
        when the last job started in it is ready."""
        start = clock()
        while not self.jobs or clock() - start < seconds:
            self.jobs.append(self._one())
        return start, self.jobs[-1].end

    def fetch(self):
        """Device results to the host, once the window has closed."""
        for job in self.jobs:
            value = job.value.value
            job.value = [np.asarray(x) for x in
                         (value if isinstance(value, tuple) else [value])]

    def release(self):
        self.hg = self.engine = None

    def summary(self) -> str:
        """Per-job wall and host seconds (min / median / max), so that a
        reader can tell job-to-job noise from run-to-run noise."""
        wall = np.asarray([j.end - j.start for j in self.jobs])
        host = wall - np.asarray([j.device_wait_s for j in self.jobs])
        return "; ".join(
            f"{name} s min {np.min(v):.4f} median {np.median(v):.4f} "
            f"max {np.max(v):.4f}" for name, v in (("wall", wall),
                                                   ("host", host)))
