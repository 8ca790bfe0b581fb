"""Persistent cross-process executable cache + replica-boot warmup.

The Engine's executable LRU (``Engine._exec_cache``) is per-process:
every replica of a serving fleet re-pays the cold compile that
``BENCH_serving.json`` measures at ~144x the warm-path cost.  This
module closes that gap:

* ``stable_digest(key)`` maps a ``repro.core.serving.signature`` tuple —
  which keys programs by *object identity* in memory — onto a digest
  that is stable ACROSS processes running the same code: functions
  contribute their qualified name, bytecode and closure values instead
  of their id.
* ``DiskExecutableCache`` stores serialized XLA executables
  (``jax.experimental.serialize_executable``) under
  ``$REPRO_CACHE_DIR`` (default ``.repro_cache/``), namespaced by
  platform / device count / jax version so a blob is only ever loaded
  into the environment that produced it.  Where the platform cannot
  round-trip a serialized executable, ``store`` degrades to a
  *warmup record* — a marker telling the next boot to re-trace eagerly
  rather than on first request — so ``warm`` keeps its contract.  The
  degradation is reported, not silent: ``stats()`` counts it
  (``disk_stores`` vs ``disk_errors``) and names the cause
  (``last_store_error``).
* ``warm(engine, specs)`` is the replica-boot API: compile every spec
  and materialize its executables — deserializing from disk (ZERO
  retraces, asserted by tests) or AOT-compiling and populating the
  store for the next replica.

The Engine integration is one seam: when ``Engine.disk_cache`` is set,
``Engine._executable_for`` wraps each freshly-built executable in
``_DiskBackedExecutable``, which resolves disk-load vs AOT-compile
lazily on first use (the call site in ``serving._execute`` is unchanged).
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import pickle
import tempfile
import time
import types
import weakref
from functools import partial
from pathlib import Path
from typing import Any, Iterable

try:
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX: publish stays atomic
    fcntl = None

import numpy as np

from repro.core.serving import AotExecutable
from repro.obs.metrics import default_registry, weak_provider
from repro.obs.trace import maybe_span

_SCHEMA = 1
_FORMAT_EXECUTABLE = "xla-executable"
_FORMAT_WARMUP = "warmup-record"
DEFAULT_CACHE_DIR = ".repro_cache"


def _checksum(data: bytes) -> str:
    """Content checksum over the serialized executable bytes: detects
    truncation and bit-rot that still unpickle cleanly."""
    return hashlib.sha256(data).hexdigest()


def cache_root(path: str | os.PathLike | None = None) -> Path:
    """The on-disk cache location: explicit path, else ``$REPRO_CACHE_DIR``,
    else ``.repro_cache/`` under the working directory (gitignored)."""
    return Path(
        path or os.environ.get("REPRO_CACHE_DIR") or DEFAULT_CACHE_DIR
    )


# --------------------------------------------------------------------------
# stable signature digests
# --------------------------------------------------------------------------

def _hash_code(code: types.CodeType, h) -> None:
    h.update(code.co_code)
    h.update(repr(code.co_names).encode())
    h.update(repr(code.co_varnames).encode())
    for const in code.co_consts:
        if isinstance(const, types.CodeType):
            _hash_code(const, h)
        else:
            h.update(repr(const).encode())


def _hash_function(fn, h) -> None:
    """Qualified name + bytecode + closure values: two processes running
    the same source produce the same token; an edited algorithm (or a
    different closed-over constant, e.g. ``alpha``) changes it."""
    h.update(f"fn:{fn.__module__}:{fn.__qualname__}".encode())
    code = getattr(fn, "__code__", None)
    if code is not None:
        _hash_code(code, h)
    for cell in fn.__closure__ or ():
        try:
            _token(cell.cell_contents, h)
        except ValueError:  # an unhashable self-reference: name only
            h.update(b"cell:opaque")
    defaults = getattr(fn, "__defaults__", None)
    if defaults:
        _token(defaults, h)


def _token(obj: Any, h) -> None:
    """Fold one signature component into the hash, by value."""
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        h.update(f"{type(obj).__name__}:{obj!r}".encode())
    elif isinstance(obj, partial):
        h.update(b"partial")
        _hash_function(obj.func, h)
        _token(obj.args, h)
        _token(tuple(sorted(obj.keywords.items())), h)
    elif isinstance(obj, types.FunctionType) or isinstance(
        obj, types.MethodType
    ):
        _hash_function(
            obj.__func__ if isinstance(obj, types.MethodType) else obj, h
        )
    elif isinstance(obj, dict):
        h.update(b"dict")
        for k in sorted(obj, key=repr):
            _token(k, h)
            _token(obj[k], h)
    elif isinstance(obj, (tuple, list)):
        h.update(f"seq:{len(obj)}".encode())
        for item in obj:
            _token(item, h)
    elif isinstance(obj, np.ndarray):
        h.update(f"nd:{obj.dtype}:{obj.shape}".encode())
        h.update(np.ascontiguousarray(obj).tobytes())
    elif hasattr(obj, "dtype") and hasattr(obj, "shape"):  # jax array
        _token(np.asarray(obj), h)
    elif dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        # Program / Monoid / spec-level containers: field-by-field, so
        # function-valued fields hash by bytecode, not memory address.
        h.update(
            f"dc:{type(obj).__module__}.{type(obj).__qualname__}".encode()
        )
        for field in dataclasses.fields(obj):
            h.update(field.name.encode())
            _token(getattr(obj, field.name), h)
    elif callable(obj) and hasattr(obj, "__qualname__"):
        # builtins / callables without python code objects
        h.update(
            f"call:{getattr(obj, '__module__', '?')}:"
            f"{obj.__qualname__}".encode()
        )
    else:
        # treedefs, enums, misc hashables: their repr is stable for the
        # types the serving signature actually contains.
        h.update(
            f"obj:{type(obj).__module__}.{type(obj).__qualname__}:"
            f"{obj!r}".encode()
        )


def stable_digest(key: Any) -> str:
    """A cross-process digest of an executable-cache signature tuple."""
    h = hashlib.sha256()
    _token(key, h)
    return h.hexdigest()


# --------------------------------------------------------------------------
# the disk store
# --------------------------------------------------------------------------

class DiskExecutableCache:
    """Serialize compiled executables to a per-platform on-disk store.

    >>> engine = Engine(disk_cache=DiskExecutableCache())
    >>> warm(engine, [spec], batch_sizes=(8,))   # boot: load or compile
    >>> engine.compile(spec).run_batch(queries)  # zero retraces if warm

    Blobs live under ``<root>/<platform>-<ndev>dev-jax<version>-v<N>/``:
    an executable is only ever deserialized into the environment shape
    that produced it.  Every entry is either a serialized executable or
    a warmup record (the fallback where ``serialize_executable`` cannot
    round-trip this platform's executables); records never satisfy
    ``load`` but tell ``warm`` the compile is expected and intentional.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        import jax

        self.root = cache_root(path)
        self.dir = self.root / (
            f"{jax.default_backend()}-{jax.device_count()}dev-"
            f"jax{jax.__version__}-v{_SCHEMA}"
        )
        self._stats = {
            "disk_hits": 0,
            "disk_misses": 0,
            "disk_stores": 0,
            "disk_errors": 0,
            "warm_records": 0,
            "disk_quarantined": 0,
            "disk_migrated": 0,
            "disk_lock_waits": 0,
        }
        # Why the last ``store`` wrote a warmup record instead of an
        # executable (``None`` while every store round-tripped).
        self.last_store_error: str | None = None
        # Duck-typed like Engine.tracer: Engine(fault_injector=...)
        # forwards its injector here so the disk.read / disk.write /
        # disk.deserialize chaos points fire inside the real try blocks.
        self.fault_injector = None
        default_registry().register_provider(
            "serve.disk_cache", weak_provider(self.stats)
        )

    # -- paths -------------------------------------------------------------

    def _path(self, digest: str) -> Path:
        return self.dir / f"{digest}.jexe"

    def _write(self, digest: str, payload: dict) -> None:
        """Atomic publish: a concurrently-booting replica never reads a
        torn blob."""
        self.dir.mkdir(parents=True, exist_ok=True)
        blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
        fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._path(digest))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    @contextlib.contextmanager
    def lock(self, key: Any):
        """Advisory cross-process claim on one signature.

        Two replicas booting concurrently from one store race the same
        miss: both would pay the AOT compile and rename over each other
        (safe — the publish is atomic — but one whole compile is
        wasted).  Holding the signature's ``flock`` while compiling
        serializes the claim: the loser blocks (counted as a
        ``disk_lock_waits``), then finds the winner's entry on its
        re-check load.  The lock lives next to the entry
        (``<digest>.lock``) and the kernel releases it on process death,
        so a replica killed -9 mid-compile never wedges its peers.
        No-op where ``fcntl`` is unavailable (the atomic publish is the
        only guarantee there)."""
        if fcntl is None:
            yield
            return
        self.dir.mkdir(parents=True, exist_ok=True)
        with open(self.dir / f"{stable_digest(key)}.lock", "ab") as f:
            try:
                fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                self._stats["disk_lock_waits"] += 1
                fcntl.flock(f, fcntl.LOCK_EX)
            try:
                yield
            finally:
                fcntl.flock(f, fcntl.LOCK_UN)

    def _quarantine(self, path: Path, err: Exception) -> None:
        """Move a bad entry aside (``<name>.corrupt``, never deleted —
        post-mortem evidence) so the next boot recompiles instead of
        re-tripping over the same blob."""
        try:
            os.replace(path, str(path) + ".corrupt")
            self._stats["disk_quarantined"] += 1
        except OSError:
            pass

    # -- load / store ------------------------------------------------------

    def load(self, key: Any):
        """A loaded ``jax.stages.Compiled`` for ``key``, or ``None``.

        Loading never traces: the deserialized executable answers the
        first request at warm-path cost (the zero-retrace boot
        property the serve-tier tests assert).

        Verification: executable entries carry a sha256 over the
        serialized bytes; a truncated, bit-rotten, or foreign file —
        unpicklable, unknown format, checksum mismatch, or failing
        deserialization — is quarantined (renamed ``.corrupt``) and
        reported as a miss, so the caller recompiles and re-publishes.
        Legacy pre-checksum entries that still round-trip are upgraded
        in place (``disk_migrated``)."""
        from repro.faults.errors import CorruptCacheEntry

        digest = stable_digest(key)
        path = self._path(digest)
        if not path.exists():
            self._stats["disk_misses"] += 1
            return None
        recorded = None
        try:
            if self.fault_injector is not None:
                self.fault_injector.maybe_raise(
                    "disk.read", digest=digest[:16]
                )
            with open(path, "rb") as f:
                payload = pickle.load(f)
            fmt = (
                payload.get("format") if isinstance(payload, dict) else None
            )
            if fmt == _FORMAT_WARMUP:
                self._stats["warm_records"] += 1
                self._stats["disk_misses"] += 1
                return None
            if fmt != _FORMAT_EXECUTABLE:
                raise CorruptCacheEntry(
                    f"unrecognized cache entry format {fmt!r}"
                )
            serialized = payload["serialized"]
            recorded = payload.get("checksum")
            if recorded is not None and _checksum(serialized) != recorded:
                raise CorruptCacheEntry(
                    f"checksum mismatch for {path.name}"
                )
            if self.fault_injector is not None:
                self.fault_injector.maybe_raise(
                    "disk.deserialize", digest=digest[:16]
                )
            from jax.experimental import serialize_executable as se

            compiled = se.deserialize_and_load(
                serialized, payload["in_tree"], payload["out_tree"],
            )
        except Exception as err:  # corrupt blob / incompatible runtime
            self._stats["disk_errors"] += 1
            self._stats["disk_misses"] += 1
            self._quarantine(path, err)
            return None
        if recorded is None:
            # Migration: a pre-checksum entry that round-trips fine is
            # rewritten with its checksum so the next boot verifies it.
            try:
                payload["checksum"] = _checksum(serialized)
                self._write(digest, payload)
                self._stats["disk_migrated"] += 1
            except Exception:
                pass  # upgrade is best-effort; the load itself succeeded
        self._stats["disk_hits"] += 1
        return compiled

    def store(self, key: Any, compiled) -> bool:
        """Serialize ``compiled`` under ``key``; on platforms that cannot
        round-trip executables, degrade to a warmup record so the next
        boot knows to re-trace eagerly.  Returns True on a full store."""
        digest = stable_digest(key)
        try:
            if self.fault_injector is not None:
                self.fault_injector.maybe_raise(
                    "disk.write", digest=digest[:16]
                )
            from jax.experimental import serialize_executable as se

            serialized, in_tree, out_tree = se.serialize(compiled)
            self._write(digest, {
                "format": _FORMAT_EXECUTABLE,
                "schema": _SCHEMA,
                "serialized": serialized,
                "checksum": _checksum(serialized),
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
        except Exception as err:
            self._stats["disk_errors"] += 1
            self.last_store_error = f"{type(err).__name__}: {err}"
            try:
                self._write(digest, {
                    "format": _FORMAT_WARMUP,
                    "schema": _SCHEMA,
                    "error": repr(err),
                })
            except Exception:
                pass
            return False
        self._stats["disk_stores"] += 1
        return True

    def wrap(self, engine, key: Any, jitted):
        """Engine seam: wrap a freshly-built jitted executable so its
        first use resolves disk-load vs AOT-compile (see
        ``Engine._executable_for``)."""
        return _DiskBackedExecutable(self, key, jitted, engine=engine)

    def stats(self) -> dict:
        entries = 0
        if self.dir.is_dir():
            entries = sum(1 for _ in self.dir.glob("*.jexe"))
        return {
            **self._stats, "entries": entries, "dir": str(self.dir),
            "last_store_error": self.last_store_error,
        }


class _DiskBackedExecutable(AotExecutable):
    """An Engine LRU entry backed by the disk store.

    First use resolves, in order: deserialize from disk (no trace, no
    compile), else AOT ``lower().compile()`` + store for the next
    process.  A lowering or compile error propagates: there is no
    plain-jit fallback.  ``source`` records which path won.
    """

    __slots__ = ("cache", "key", "_engine_ref")

    def __init__(self, cache: DiskExecutableCache, key, jitted, engine=None):
        super().__init__(jitted)
        self.cache = cache
        self.key = key
        # weak: the Engine's LRU owns this object, never the reverse
        self._engine_ref = weakref.ref(engine) if engine is not None else None

    def _tracer(self):
        engine = self._engine_ref() if self._engine_ref is not None else None
        return getattr(engine, "tracer", None)

    def _injector(self):
        engine = self._engine_ref() if self._engine_ref is not None else None
        return getattr(engine, "fault_injector", None)

    def _materialize(self, args: tuple) -> None:
        tracer = self._tracer()
        with maybe_span(tracer, "serve.disk_load", cat="compile") as sp:
            loaded = self.cache.load(self.key)
        if loaded is not None:
            self.compiled, self.source = loaded, "disk"
            if sp is not None:
                sp.args["source"] = "disk"
            return
        # Miss: claim the signature before compiling so concurrently
        # booting replicas don't duplicate the AOT work — the loser of
        # the claim blocks, then finds the winner's entry on re-check.
        with self.cache.lock(self.key):
            with maybe_span(tracer, "serve.disk_load", cat="compile") as sp:
                loaded = self.cache.load(self.key)
            if loaded is not None:
                self.compiled, self.source = loaded, "disk"
                if sp is not None:
                    sp.args["source"] = "disk"
                return
            with maybe_span(tracer, "serve.aot_compile", cat="compile") as sp:
                inj = self._injector()
                if inj is not None:
                    inj.maybe_raise("compile.aot")
                super()._materialize(args)
                if sp is not None:
                    sp.args["source"] = self.source
            self.cache.store(self.key, self.compiled)


# --------------------------------------------------------------------------
# replica-boot warmup
# --------------------------------------------------------------------------

def warm(
    engine,
    specs: Iterable[Any],
    *,
    batch_sizes: tuple[int, ...] = (),
    queries: list[Any] | None = None,
    hg=None,
    require_no_retrace: bool = False,
) -> dict:
    """Boot-time warmup: bring ``engine`` to warm-path q/s before the
    first request.

    For each spec (an ``AlgorithmSpec``, or an already-compiled
    ``CompiledAlgorithm``) materialize the unbatched executable plus one
    per batch bucket in ``batch_sizes`` — loading from the engine's
    ``disk_cache`` when the store holds the signature (zero retraces)
    and AOT-compiling (and storing) otherwise.

    ``queries``: per-spec example query for specs whose ``query0`` is
    unset (e.g. an unseeded ``random_walk_spec``); ignored where the
    spec carries its own.  Returns a report::

        {"boot_s": ..., "traces": ..., "paths": {name: {path: source}}}

    where each source is ``disk`` (deserialized) or ``aot`` (compiled,
    and stored when a disk cache is attached).

    ``require_no_retrace=True`` wraps the boot in the analysis-layer
    retrace sentinel: a replica that was expected to come up entirely
    from the disk store raises ``RetraceError`` instead of silently
    paying compile latency on its first requests.
    """
    from repro.analysis.retrace import assert_no_retrace

    if require_no_retrace:
        with assert_no_retrace(engine, label="serve.warm"):
            return warm(
                engine, specs, batch_sizes=batch_sizes, queries=queries,
                hg=hg, require_no_retrace=False,
            )
    t0 = time.perf_counter()
    before = engine.cache_stats()["traces"]
    paths: dict[str, dict] = {}
    for i, item in enumerate(specs):
        compiled = item if hasattr(item, "warmup") else engine.compile(item)
        example = None
        if queries is not None and i < len(queries):
            example = queries[i]
        name = getattr(compiled.spec, "name", f"spec{i}")
        paths[f"{i}:{name}"] = compiled.warmup(
            query=example, batch_sizes=batch_sizes, hg=hg
        )
    sources = [
        rep.get("source") for per in paths.values() for rep in per.values()
    ]
    return {
        "boot_s": time.perf_counter() - t0,
        "traces": engine.cache_stats()["traces"] - before,
        "from_disk": sum(1 for s in sources if s == "disk"),
        "compiled": sum(1 for s in sources if s == "aot"),
        "paths": paths,
    }
