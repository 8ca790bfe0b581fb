"""Reduction of a JAX profiler trace to device busy time and a breakdown.

The profiler writes one ``.xplane.pb`` per session.  Device planes are
named ``/device:<KIND>:<n>``; on each, the ``XLA Ops`` line holds one
event per operation run on the device (a ``while`` and the ops it runs
nest), and the ``XLA Modules`` line one event per executable run.  Host
planes hold the benchmark's own ``TraceAnnotation`` spans (names that
start with ``bench.``), on the same clock as the device events.

Busy time is the union of a device's op intervals inside the traced
window, averaged over the devices used; the idle share is 1 minus busy
over the window.  Idle gaps are named by the innermost benchmark span
that covers them, so a gap reads as what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_HASH_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class TraceSummary:
    window: tuple[float, float]          # ns, on the trace's clock
    busy_s: float                        # mean over devices
    modules_s: dict[str, float]          # executable name -> seconds
    ops_self_s: dict[str, float]         # op name -> self seconds
    idle_gaps_s: dict[str, float]        # host span name -> idle seconds
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        def largest(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                    [:top]]

        return {"device_ops": largest(self.ops_self_s),
                "idle_gaps": largest(self.idle_gaps_s)}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union_length(intervals, lo: float, hi: float) -> float:
    """Total length covered by ``intervals`` (pairs), clipped to
    [lo, hi]."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_intervals(intervals, lo: float, hi: float):
    """The gaps in [lo, hi] that no interval covers."""
    gaps = []
    t = lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def self_times(events, lo: float, hi: float) -> dict[str, float]:
    """Self time per name of nested events (time not covered by an
    event nested inside), clipped to [lo, hi]."""
    out: dict[str, float] = {}
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    stack: list[list] = []   # [name, start, end, covered_by_children]

    def close(item):
        name, s, e, child = item
        own = max(min(e, hi) - max(s, lo), 0.0) - child
        out[name] = out.get(name, 0.0) + max(own, 0.0)
        if stack:
            stack[-1][3] += max(min(e, hi) - max(s, lo), 0.0)

    for name, s, e in evs:
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head.lstrip("%") or hlo_text[:64]


def module_name(name: str) -> str:
    """``jit_scan(1696105059892179459)`` -> ``jit_scan``."""
    return _HASH_SUFFIX.sub("", name)


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith(
        "/device:CUSTOM")


def summarize(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = []
    device_ops: list[list] = []
    device_modules: list[list] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(ev.name, ev.start_ns, ev.end_ns)
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    mods = [(ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events]
            if ops or mods:
                device_ops.append(ops)
                device_modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    if not device_ops:
        raise ValueError(f"no device plane with XLA ops in {path}")
    lo, hi = windows[0]

    busy, modules, ops_self, gaps = [], {}, {}, {}
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    for ops, mods in zip(device_ops, device_modules):
        intervals = [(s, e) for _, s, e in ops]
        busy.append(union_length(intervals, lo, hi) / 1e9)
        for name, s, e in mods:
            d = max(min(e, hi) - max(s, lo), 0.0) / 1e9
            if d > 0:
                key = module_name(name)
                modules[key] = modules.get(key, 0.0) + d
        for name, sec in self_times(
                [(op_name(n), s, e) for n, s, e in ops], lo, hi).items():
            ops_self[name] = ops_self.get(name, 0.0) + sec / 1e9
        for s, e in idle_intervals(intervals, lo, hi):
            label = _covering_span(inner, (s + e) / 2) or WINDOW_SPAN
            gaps[label] = gaps.get(label, 0.0) + (e - s) / 1e9
    n = len(busy)
    return TraceSummary(
        window=(lo, hi),
        busy_s=sum(busy) / n,
        modules_s={k: v / n for k, v in modules.items()},
        ops_self_s={k: v / n for k, v in ops_self.items()},
        idle_gaps_s={k: v / n for k, v in gaps.items()},
        n_devices=n,
    )


def _covering_span(spans, t: float) -> str | None:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None
