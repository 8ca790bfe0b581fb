"""One serving replica: a worker *process* booted from the shared store.

The serving tier through PR 9 is one ``Frontend`` owning one Engine in
one process — resilient to thread crashes and poisoned batches, but a
single point of failure at the process level.  This module is the unit
the ``Router`` (``repro.serve.router``) replicates:

* ``ReplicaConfig`` — everything a replica needs to boot, picklable
  across a ``spawn`` boundary: a **builder reference**
  (``"pkg.mod:function"`` resolved by import, never a pickled closure)
  plus its kwargs, the shared ``DiskExecutableCache`` directory, the
  coalescing knobs, and an optional ``FaultPlan`` JSON armed *inside*
  the replica.
* ``replica_main(conn, config)`` — the child-process entry point: build
  the engine, ``serve.warm(..., require_no_retrace=config.
  require_no_retrace)`` from the shared disk store (a respawned replica
  reaches warm q/s with ZERO retraces), then serve a pipe loop — one
  ``Frontend`` coalesces and executes, the loop receives requests and
  streams results + periodic heartbeats back.
* ``ProcessReplica`` — the router-side handle: spawn, non-blocking
  message drain, liveness (pipe EOF / exit code), kill (-9, for chaos
  tests) and stop.

Fault points (armed via ``config.fault_plan``): ``replica.crash`` fires
``os._exit`` — the in-process model of kill -9, losing every in-flight
request exactly like a real crash — and ``replica.hang`` stops
heartbeats without exiting, so the router's missed-heartbeat detector
(not pipe EOF) has to catch it.

Wire protocol (pickled tuples over a ``multiprocessing.Pipe``):
router->replica ``("req", id, spec_key, query, hg_ref, deadline_ms)``
and ``("stop",)``; replica->router ``("ready", boot_report)``,
``("hb", stats)``, ``("res", id, ServedResult)``, ``("err", id, exc)``,
``("fatal", repr)`` on a boot failure, ``("bye", stats)`` on a clean
stop.  At-least-once execution is safe: a failed-over request re-runs
the same compiled executable on a peer, and the compiled paths are
deterministic, so a duplicate execute returns the bitwise-same value.
"""
from __future__ import annotations

import dataclasses
import importlib
import multiprocessing
import os
import pickle
import threading
import time
from functools import partial

_CRASH_EXIT = 13      # replica.crash's exit code: distinguishable from 0


@dataclasses.dataclass(frozen=True)
class ReplicaConfig:
    """Everything one replica process needs to boot, picklable.

    ``builder`` is an import reference ``"package.module:function"``;
    called with ``**kwargs`` in the CHILD process it returns::

        {"specs": {spec_key: AlgorithmSpec},        # required, ordered
         "warm_queries": [example per spec] | None, # for query0-free specs
         "hypergraphs": {hg_ref: HyperGraph} | None}

    so nothing unpicklable (specs close over functions) ever crosses
    the process boundary.  ``require_no_retrace=True`` is the fleet
    contract: the shared store was pre-populated, so a boot that
    compiles anyway raises ``RetraceError`` instead of silently paying
    trace latency on first requests.
    """

    builder: str
    kwargs: dict = dataclasses.field(default_factory=dict)
    cache_dir: str | None = None
    max_batch: int = 16
    max_delay_ms: float = 5.0
    heartbeat_interval_s: float = 0.1
    fault_plan: str | None = None
    seed_offset: int = 0
    require_no_retrace: bool = True
    hang_s: float = 60.0
    index: int = 0


def resolve_builder(ref: str):
    """``"pkg.mod:function"`` -> the callable (child-side import)."""
    mod, _, fn = ref.partition(":")
    if not mod or not fn:
        raise ValueError(
            f"builder reference {ref!r} must be 'package.module:function'"
        )
    return getattr(importlib.import_module(mod), fn)


def _picklable(err: BaseException) -> BaseException:
    """The error as something the pipe can carry; typed errors from the
    taxonomy round-trip as themselves, exotic ones degrade to repr."""
    try:
        pickle.loads(pickle.dumps(err))
        return err
    except Exception:
        return RuntimeError(f"{type(err).__name__}: {err}")


def replica_main(conn, config: ReplicaConfig) -> None:
    """Child-process entry point: boot from the shared store, serve the
    pipe loop until ``("stop",)`` or pipe EOF."""
    try:
        _serve_replica(conn, config)
    except BaseException as err:
        # Boot failures (builder import, warm RetraceError, ...) reach
        # the router as one typed message; the exit code seals it.
        try:
            conn.send(("fatal", f"{type(err).__name__}: {err}"))
        except Exception:
            pass
        raise


def _serve_replica(conn, config: ReplicaConfig) -> None:
    from repro.core import Engine
    from repro.serve.cache import DiskExecutableCache, warm
    from repro.serve.frontend import Frontend

    injector = None
    if config.fault_plan:
        from repro.faults import FaultInjector, FaultPlan

        plan = FaultPlan.from_json(config.fault_plan)
        if config.seed_offset:
            # Each spawned INSTANCE draws a distinct probabilistic fault
            # stream.  Without this a respawned replica re-arms the same
            # seed, replays the same draws against the requeued backlog,
            # and deterministically crashes at the same received-count —
            # a respawn cascade that serves nothing forever.
            plan = FaultPlan(rules=tuple(
                dataclasses.replace(r, seed=r.seed + config.seed_offset)
                if r.trigger == "prob" else r
                for r in plan.rules
            ))
        injector = FaultInjector(plan)
    engine = Engine(
        disk_cache=DiskExecutableCache(config.cache_dir),
        fault_injector=injector,
    )
    built = resolve_builder(config.builder)(**config.kwargs)
    specs = built["specs"]
    hgs = built.get("hypergraphs") or {}
    report = warm(
        engine, list(specs.values()),
        batch_sizes=(config.max_batch,),
        queries=built.get("warm_queries"),
        require_no_retrace=config.require_no_retrace,
    )
    fe = Frontend(
        engine, max_batch=config.max_batch,
        max_delay_ms=config.max_delay_ms,
    )
    for key, spec in specs.items():
        fe.register(key, spec)

    # One pipe, two writers: this loop (heartbeats) and the front-end's
    # worker thread (done callbacks) — Connection is not thread-safe.
    send_lock = threading.Lock()
    counts = {"received": 0, "completed": 0, "errors": 0}

    def _send(msg) -> bool:
        with send_lock:
            try:
                conn.send(msg)
                return True
            except (BrokenPipeError, OSError, ValueError):
                return False   # router gone; the loop will exit

    def _on_done(req_id: int, fut) -> None:
        try:
            served = fut.result()
        except BaseException as err:  # typed FaultError fans back typed
            counts["errors"] += 1
            _send(("err", req_id, _picklable(err)))
        else:
            counts["completed"] += 1
            _send(("res", req_id, served))

    fe.start()
    stop = False
    try:
        _send(("ready", {
            "index": config.index,
            "pid": os.getpid(),
            "boot_s": report["boot_s"],
            "traces": report["traces"],
            "from_disk": report["from_disk"],
            "compiled": report["compiled"],
        }))
        next_hb = time.monotonic() + config.heartbeat_interval_s
        while not stop:
            if conn.poll(max(next_hb - time.monotonic(), 0.0)):
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    break          # router died: no one left to serve
                if msg[0] == "stop":
                    stop = True
                elif msg[0] == "req":
                    _, req_id, spec_key, query, hg_ref, deadline_ms = msg
                    counts["received"] += 1
                    if injector is not None and not _chaos_gate(
                        injector, config
                    ):
                        continue   # hang fired: request lost, as planned
                    try:
                        hg = hgs[hg_ref] if hg_ref is not None else None
                        fut = fe.submit(
                            spec_key, hg=hg, query=query,
                            deadline_ms=deadline_ms,
                        )
                    except Exception as err:   # unknown key / closed
                        counts["errors"] += 1
                        _send(("err", req_id, _picklable(err)))
                    else:
                        fut.add_done_callback(partial(_on_done, req_id))
            now = time.monotonic()
            if now >= next_hb:
                if not _send(("hb", dict(counts))):
                    break
                next_hb = now + config.heartbeat_interval_s
    finally:
        # Graceful stop: requests still queued fail typed
        # (FrontendClosed) and their callbacks stream the errors back
        # before the pipe closes.
        fe.close()
        _send(("bye", dict(counts)))
        try:
            conn.close()
        except Exception:  # analysis: ignore[swallowed-error] — last act
            pass           # of a dying process; no one left to tell


def _chaos_gate(injector, config: ReplicaConfig) -> bool:
    """Fire the per-request replica fault points.  ``replica.crash``
    hard-exits (the kill -9 model: in-flight requests are simply gone);
    ``replica.hang`` sleeps without heartbeating so ONLY the router's
    missed-heartbeat detector can declare this replica dead.  Returns
    False when the current request should be dropped (hang fired)."""
    try:
        injector.maybe_raise("replica.crash", replica=config.index)
    except BaseException:
        os._exit(_CRASH_EXIT)
    try:
        injector.maybe_raise("replica.hang", replica=config.index)
    except BaseException:
        time.sleep(config.hang_s)   # the router will kill us first
        return False
    return True


def held_accelerator() -> str | None:
    """The non-CPU platform whose backend THIS process has already
    initialized, or ``None``.  Never initializes a backend itself."""
    import sys

    if "jax" not in sys.modules:
        return None
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


class ProcessReplica:
    """Router-side handle on one spawned replica process.

    A chip belongs to one process at a time.  A parent that already
    holds an accelerator backend would leave every spawned child unable
    to reach the device (it fails or hangs at boot), so the constructor
    refuses to spawn in that case.  Replicas pinned to devices of one
    multi-chip host are not implemented.

    The interface the ``Router`` consumes (and chaos tests fake):
    ``poll_messages`` (non-blocking drain), ``send`` (raises on a
    broken pipe), ``alive`` (pipe + exit-code liveness), ``stop``
    (graceful or forced), ``kill`` (SIGKILL, for chaos tests) and
    ``connection`` (waitable, for the router thread's poll).
    """

    def __init__(self, index: int, config: ReplicaConfig):
        platform = held_accelerator()
        if platform is not None:
            raise RuntimeError(
                f"refusing to spawn replica {index}: this process already "
                f"holds the {platform} backend, and a device belongs to "
                "one process at a time, so the child could not reach it. "
                "Serve in one process (Frontend) on an accelerator host."
            )
        ctx = multiprocessing.get_context("spawn")
        parent, child = ctx.Pipe()
        self.index = index
        self.process = ctx.Process(
            target=replica_main,
            args=(child, dataclasses.replace(config, index=index)),
            name=f"repro-replica-{index}",
            daemon=True,
        )
        self.process.start()
        child.close()
        self.connection = parent
        self._broken = False

    @property
    def pid(self) -> int | None:
        return self.process.pid

    def poll_messages(self) -> list:
        """Drain every message currently in the pipe, non-blocking.
        A broken pipe marks the handle dead instead of raising — the
        messages drained before the break are still delivered."""
        out: list = []
        try:
            while not self._broken and self.connection.poll(0):
                out.append(self.connection.recv())
        except (EOFError, OSError):
            self._broken = True
        return out

    def send(self, msg) -> None:
        if self._broken:
            raise BrokenPipeError(f"replica {self.index} pipe is down")
        try:
            self.connection.send(msg)
        except (BrokenPipeError, OSError, ValueError):
            self._broken = True
            raise

    def alive(self) -> bool:
        return not self._broken and self.process.exitcode is None

    def kill(self) -> None:
        """SIGKILL, no warning — the chaos tests' real kill -9."""
        try:
            self.process.kill()
        except Exception:
            pass

    def stop(self, force: bool = False, join_s: float = 5.0) -> None:
        """Tear the process down.  Graceful sends ``("stop",)`` and
        waits; ``force=True`` (death declaration: the replica missed
        heartbeats or broke its pipe) goes straight to terminate so a
        wedged process can't stall the failover path."""
        if not force:
            try:
                self.send(("stop",))
            except Exception:
                pass
            self.process.join(join_s)
        if self.process.exitcode is None:
            self.process.terminate()
            self.process.join(1.0)
        if self.process.exitcode is None:
            self.process.kill()
            self.process.join(1.0)
        self._broken = True
        try:
            self.connection.close()
        except Exception:
            pass
