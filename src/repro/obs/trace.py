"""Structured trace spans: where an engine call's wall time actually goes.

``Tracer`` records phase spans — resolve, layout build, dispatch (with
JAX's trace / lower / compile inside it), device wait, disk-cache load,
per-flush serve pump — into a bounded ring buffer.  Every span also
enters a ``jax.profiler.TraceAnnotation`` of its name, so a profiler
session (``jax.profiler.trace(dir)``) shows the program's phases on the
host plane, on the same clock as the device's ops.

Two ways to record, one span system:

* **an attached tracer** — ``Engine(tracer=Tracer())`` records always
  (duck-typed like ``disk_cache``: anything with ``span``/``block``);
* **the process-wide** ``default_tracer()`` — with no tracer attached,
  ``maybe_span(None, ...)`` records into it while the JAX profiler is
  recording and is a no-op otherwise.  The profiler session is the
  switch: there is no flag.  Off, a span site costs one
  ``TraceAnnotation.is_enabled()`` check (benchmarked in
  ``benchmarks/bench_obs.py``).

Design constraints, in order:

* **causal** — each ``Span`` carries a process-unique ``id`` and its
  ``parent``'s id (the innermost span open on the same thread, of
  whichever tracer);
* **compile-aware** — one ``jax.monitoring`` time-span listener,
  registered once per process, turns JAX's own trace / lower / compile
  durations into ``jax.trace`` / ``jax.lower`` / ``jax.compile`` child
  spans (``fun_name`` in their args) of the span open on the calling
  thread, in that span's tracer;
* **clock-injected** — ``Tracer(clock=...)`` like the front-end, so
  span timing is deterministic under test;
* **bounded** — a ``deque(maxlen=capacity)`` ring; long serve loops
  keep the newest spans and count the ``dropped`` rest;
* **device-time aware** — jax dispatch returns before the device
  finishes, so a span around ``exe(*args)`` alone measures enqueue
  time.  ``tracer.block(span, value)`` runs ``block_until_ready`` and
  records the wait as ``args["device_wait_s"]``.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any

import jax
from jax.profiler import TraceAnnotation

# JAX's compile-path durations, as the child spans they become.
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}

_ids = itertools.count(1)
_open = threading.local()          # .stack: [(tracer, span)] per thread
_listener_lock = threading.Lock()
_listening = False


def _open_stack() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


class Span:
    """One completed (or open) phase: name, category, [t0, t0+dur)."""

    __slots__ = ("name", "cat", "t0", "dur_s", "tid", "depth", "args",
                 "id", "parent")

    def __init__(self, name, cat, t0, tid, depth, args, parent=None):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.dur_s = 0.0
        self.tid = tid
        self.depth = depth
        self.args = args
        self.id = next(_ids)
        self.parent = parent

    def __repr__(self):
        return (
            f"Span({self.name!r}, cat={self.cat!r}, "
            f"dur={self.dur_s * 1e3:.3f}ms, depth={self.depth}, "
            f"id={self.id}, parent={self.parent})"
        )


class Tracer:
    """Ring-buffered span recorder; thread-safe, nesting per thread."""

    def __init__(self, capacity: int = 4096, clock=time.perf_counter):
        self.capacity = int(capacity)
        self.clock = clock
        self._lock = threading.Lock()
        self._spans: deque[Span] = deque(maxlen=self.capacity)
        self.total = 0  # spans ever recorded; dropped = total - len(ring)
        _listen_for_compiles()

    @property
    def dropped(self) -> int:
        return max(self.total - len(self._spans), 0)

    def _record(self, sp: Span) -> None:
        with self._lock:
            self._spans.append(sp)
            self.total += 1

    @contextmanager
    def span(self, name: str, cat: str = "engine", **args):
        """Record one phase; yields the ``Span`` so callers can attach
        measurements (``sp.args[...] = ...``) before it closes.  Spans
        nest per thread (``depth`` and ``parent`` reflect the enclosing
        stack); the phase is also a profiler ``TraceAnnotation``."""
        stack = _open_stack()
        sp = Span(
            name, cat, self.clock(), threading.get_ident(), len(stack),
            dict(args), stack[-1][1].id if stack else None,
        )
        stack.append((self, sp))
        try:
            with TraceAnnotation(name):
                yield sp
        finally:
            sp.dur_s = self.clock() - sp.t0
            stack.pop()
            self._record(sp)

    def block(self, sp: Span, value: Any) -> Any:
        """``block_until_ready(value)``, recording the device wait on
        the span; returns ``value``.  The dispatch/completion split is
        the one number XLA won't tell you from wall time alone."""
        t0 = self.clock()
        try:
            jax.block_until_ready(value)
        except Exception:  # numpy-only values / test doubles
            pass
        sp.args["device_wait_s"] = self.clock() - t0
        return value

    # -- inspection ---------------------------------------------------------

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.total = 0


def _on_compile_span(event: str, start: float, end: float, **kw) -> None:
    """JAX's trace / lower / compile time spans (wall-clock ``start`` /
    ``end``) as child spans of the span open on this thread, on its
    tracer's clock; nothing when no span is open."""
    name = COMPILE_SPANS.get(event)
    stack = getattr(_open, "stack", None)
    if name is None or not stack:
        return
    tracer, parent = stack[-1]
    sp = Span(
        name, "compile", start + (tracer.clock() - time.time()),
        threading.get_ident(), len(stack), dict(kw), parent.id,
    )
    sp.dur_s = end - start
    tracer._record(sp)


def _listen_for_compiles() -> None:
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_time_span_listener(
                _on_compile_span
            )
            _listening = True


# -- the process-wide default (what untraced engines record into) ----------

# Room for every span of a long profiled session: a one-chip analytics
# job records a handful of engine spans and tens of compile spans.
DEFAULT_CAPACITY = 1 << 15

_DEFAULT = Tracer(capacity=DEFAULT_CAPACITY)


def default_tracer() -> Tracer:
    """The tracer ``maybe_span(None, ...)`` records into while the JAX
    profiler is recording (``time.perf_counter`` clock)."""
    return _DEFAULT


def profile(log_dir: str | None):
    """``jax.profiler.trace(log_dir)`` — the program's spans and the
    device's ops in one trace — or a no-op context for ``None``."""
    return jax.profiler.trace(log_dir) if log_dir else nullcontext()


def maybe_span(tracer, name: str, cat: str = "engine", **args):
    """``tracer.span(...)``; with no tracer, ``default_tracer().span``
    while the JAX profiler records, else a no-op context yielding
    ``None``."""
    if tracer is None:
        if not TraceAnnotation.is_enabled():
            return nullcontext(None)
        tracer = _DEFAULT
    return tracer.span(name, cat=cat, **args)
