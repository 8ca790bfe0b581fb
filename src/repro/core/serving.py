"""Compile-once serve-many: shape-bucketed executables for ``Engine.compile``.

``Engine.run`` re-resolves the design point and re-traces on every call —
fine for one-shot analytics, fatal for serving millions of per-source
queries (SSSP sources, personalized-restart seeds) against one partitioned
hypergraph.  This module is the serving half of the facade:

* ``bucket_dim`` quantizes ``n_vertices`` / ``n_hyperedges`` / ``nnz``
  (and batch sizes) to power-of-two buckets, so a stream of
  slightly-varying hypergraphs maps onto a bounded set of padded shapes;
* ``signature`` canonicalizes (programs, design point, bucket dims,
  attribute dtypes, query structure, batch bucket) into the hashable key
  of the Engine's LRU executable cache;
* ``CompiledAlgorithm`` is the serve-many handle ``Engine.compile``
  returns: ``run(hg, query=...)`` executes with zero retracing for any
  same-bucket hypergraph, and ``run_batch(queries)`` vmaps the whole
  executable over the spec's query axis so one compile serves B requests.

Real (unpadded) sizes flow through the executables as *traced* int32
scalars — activity stats and the halting decision mask padding slots
dynamically (``repro.core.engine.compute(n_real=...)``,
``repro.core.distributed.build_distributed_runner``), so results are
bitwise identical to an unpadded run while shapes stay bucket-stable.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import constant_initial_msg
from repro.core.engine import compute, compute_batch
from repro.core.hypergraph import HyperGraph
from repro.faults.errors import is_transient
from repro.kernels.deliver import layout_pair
from repro.obs.trace import maybe_span

Pytree = Any

# Smallest entity/incidence bucket: graphs below this all share one shape.
BUCKET_FLOOR = 64
# Batch-size buckets start lower — single-digit batches are common.
BATCH_FLOOR = 8


def bucket_dim(n: int, floor: int = BUCKET_FLOOR) -> int:
    """Smallest power-of-two ≥ ``n`` (and ≥ ``floor``).

    Bounded buckets are the compile-amortization contract: padded work
    grows at most 2x, while the number of distinct executables a
    workload can touch is O(log max_size).
    """
    b = int(floor)
    n = int(n)
    while b < n:
        b *= 2
    return b


def _round_up(n: int, mult: int) -> int:
    return -(-int(n) // int(mult)) * int(mult)


def _attr_sig(tree: Pytree):
    """Hashable (treedef, per-leaf dtype + trailing shape): the leading
    entity dim is the bucket's business, dtype/feature-shape changes must
    miss the cache."""
    leaves, treedef = jax.tree.flatten(tree)
    return (
        treedef,
        tuple(
            (jnp.asarray(leaf).dtype.name, tuple(jnp.shape(leaf)[1:]))
            for leaf in leaves
        ),
    )


def _query_sig(query: Pytree):
    """Hashable full dtype/shape structure of one (unbatched) query."""
    if query is None:
        return None
    leaves, treedef = jax.tree.flatten(query)
    return (
        treedef,
        tuple(
            (jnp.asarray(leaf).dtype.name, tuple(jnp.shape(leaf)))
            for leaf in leaves
        ),
    )


def _canon_query(query: Pytree) -> Pytree:
    """Strong-typed device arrays: python ints must produce the same
    signature (and no weak-type retrace) as explicit numpy scalars."""
    # analysis: ignore[host-sync] — queries arrive as host values;
    # strong-typing them IS the ingest contract (scalar-sized)
    return jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), query)


def _initial_msg_sig(initial_msg: Pytree):
    """Hashable VALUE signature of a spec's initial message.

    Unlike the programs (keyed by identity), ``initial_msg`` can be
    swapped via ``spec._replace`` without changing any function object —
    and it is baked into the executable as a traced constant, so its
    concrete bytes must participate in the cache key."""
    leaves, treedef = jax.tree.flatten(initial_msg)
    return (
        treedef,
        tuple(
            # analysis: ignore[host-sync] — memoized once per
            # CompiledAlgorithm (see _execute), not per request
            (arr.dtype.name, arr.shape, arr.tobytes())
            # analysis: ignore[host-sync] — same memo
            for arr in (np.asarray(leaf) for leaf in leaves)
        ),
    )


def signature(
    spec,
    cfg,
    *,
    nv_pad: int,
    ne_pad: int,
    nnz_pad: int,
    shard_len_pad: int,
    n_parts: int,
    v_attr_sig,
    he_attr_sig,
    e_attr_sig,
    query_sig,
    batch_pad: int | None,
    delivery_sig=None,
    initial_msg_sig=None,
):
    """The executable cache key.

    Program objects participate by identity (their closures bake in
    algorithm constants), so distinct specs never collide; everything
    else is the padded-shape/dtype/design-point signature the tentpole
    names: same bucket + same design point = same executable.

    ``delivery_sig``: the fused-delivery layout shapes (ELL width,
    remainder pad, tile geometry — data-dependent within a shape
    bucket); ``None`` on the reference path.  Same-bucket hypergraphs
    usually share them, but a degree-regime shift legitimately
    recompiles.

    ``initial_msg_sig``: the precomputed ``_initial_msg_sig`` value.
    Callers on the per-request path (``CompiledAlgorithm._execute``)
    pass their memo so the key never re-serializes the initial message
    per request (host-sync lint finding, fixed by memoization); ``None``
    recomputes for one-shot callers.
    """
    return (
        spec.v_program,
        spec.he_program,
        spec.bind_query if query_sig is not None else None,
        (initial_msg_sig if initial_msg_sig is not None
         else _initial_msg_sig(spec.initial_msg)),
        cfg.backend,
        cfg.axis,
        cfg.max_iters,
        cfg.collect_stats,
        cfg.delivery,
        n_parts,
        nv_pad,
        ne_pad,
        nnz_pad,
        shard_len_pad,
        v_attr_sig,
        he_attr_sig,
        e_attr_sig,
        query_sig,
        batch_pad,
        delivery_sig,
    )


# --------------------------------------------------------------------------
# executable builders
# --------------------------------------------------------------------------

def _build_local_executable(spec, cfg, has_query, batch_pad, trace_hook):
    """One jitted callable ``(hgp, delivery, nv_real, ne_real, query) ->
    (v_attr, he_attr, stats, executed)`` over a bucket-padded hypergraph.

    Unbatched requests run ``compute`` (per-run halting ``cond``);
    batches run ``compute_batch`` — the scan sits OUTSIDE the query
    vmap, so halting stays a real branch on ``all(halted)`` and a
    skewed-convergence batch stops at its slowest query instead of
    paying ``max_iters`` (the batch-aware halting design point).
    ``executed`` reports the superstep pairs the batch actually ran
    (``None`` unbatched).
    """
    # Close over only what the trace needs — NOT the whole spec, whose
    # hg0 (full structure + attrs) would otherwise stay pinned in the
    # Engine's executable LRU for the cache entry's lifetime.
    v_program, he_program = spec.v_program, spec.he_program
    initial_msg, bind_query = spec.initial_msg, spec.bind_query
    max_iters, collect_stats = cfg.max_iters, cfg.collect_stats

    def raw(hgp: HyperGraph, delivery, nv_real, ne_real, query):
        trace_hook()
        if has_query:
            hgp = bind_query(hgp, query)
        out = compute(
            hgp,
            max_iters=max_iters,
            initial_msg=initial_msg,
            v_program=v_program,
            he_program=he_program,
            return_stats=collect_stats,
            n_real=(nv_real, ne_real),
            delivery=delivery,
        )
        stats = None
        if collect_stats:
            out, stats = out
        return out.v_attr, out.he_attr, stats, None

    def raw_batch(hgp: HyperGraph, delivery, nv_real, ne_real, queries):
        trace_hook()
        # Bind every query onto the padded structure, keep only the
        # per-query attribute states (the structure itself is shared).
        # NOTE: bind_query may only touch v_attr / he_attr — e_attr and
        # e_mask stay unbatched by the batch-aware halting contract.
        bound = jax.vmap(lambda q: bind_query(hgp, q))(queries)
        v_attr_b, he_attr_b = bound.v_attr, bound.he_attr
        v_b, he_b, stats, executed = compute_batch(
            hgp,
            v_attr_b,
            he_attr_b,
            batch_pad,
            max_iters,
            initial_msg,
            v_program,
            he_program,
            n_real=(nv_real, ne_real),
            delivery=delivery,
        )
        return v_b, he_b, (stats if collect_stats else None), executed

    return jax.jit(raw if batch_pad is None else raw_batch)


def _build_distributed_executable(
    spec, cfg, mesh, n_parts, nv_pad, ne_pad, has_query, batch_pad,
    trace_hook,
):
    """Same contract as the local builder, plus the plan's padded edge
    shards: ``(hgp, shard_src, shard_dst, shard_mask, delivery, nv_real,
    ne_real, query) -> (v_attr, he_attr, stats, executed)``.  Query
    binding happens on the full padded state *before* ``shard_map``
    shards it, so one runner serves both backends' layouts.  Batches run
    the BATCH-AWARE runner (``build_distributed_runner(batch=...)``):
    the scan sits outside the query vmap — inside ``shard_map`` — so
    halting stays a real ``cond`` on ``all(halted)`` and
    ``supersteps_executed`` agrees with the local backend."""
    from repro.core.distributed import DistContext, build_distributed_runner

    ctx = DistContext(
        axis=cfg.axis, n_parts=n_parts, nv_pad=nv_pad, ne_pad=ne_pad
    )
    mapped = build_distributed_runner(
        mesh, ctx, spec.v_program, spec.he_program, cfg.max_iters,
        backend=cfg.backend, batch=batch_pad,
    )
    # As in the local builder: keep the spec's hg0 out of the closure.
    initial_msg, bind_query = spec.initial_msg, spec.bind_query
    collect_stats = cfg.collect_stats

    def raw(hgp: HyperGraph, s_src, s_dst, s_mask, delivery, nv_real,
            ne_real, query):
        trace_hook()
        if has_query:
            hgp = bind_query(hgp, query)
        msg0 = constant_initial_msg(initial_msg, nv_pad)
        v_out, he_out, v_trace, he_trace = mapped(
            hgp.v_attr, hgp.he_attr, msg0,
            hgp.degrees(), hgp.cardinalities(),
            s_src, s_dst, s_mask, nv_real, ne_real, delivery,
        )
        stats = (v_trace, he_trace) if collect_stats else None
        return v_out, he_out, stats, None

    def raw_batch(hgp: HyperGraph, s_src, s_dst, s_mask, delivery,
                  nv_real, ne_real, queries):
        trace_hook()
        # Bind every query onto the padded structure, keep only the
        # per-query attribute states (the structure itself is shared) —
        # same contract as the local batch builder: bind_query may only
        # touch v_attr / he_attr.
        bound = jax.vmap(lambda q: bind_query(hgp, q))(queries)
        msg0 = constant_initial_msg(initial_msg, nv_pad)
        msg0_b = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (batch_pad,) + x.shape), msg0
        )
        v_b, he_b, v_tr, he_tr, executed = mapped(
            bound.v_attr, bound.he_attr, msg0_b,
            hgp.degrees(), hgp.cardinalities(),
            s_src, s_dst, s_mask, nv_real, ne_real, delivery,
        )
        # [max_iters, batch] -> [batch, max_iters]: the layout callers
        # (and the local backend) already consume.
        stats = (v_tr.T, he_tr.T) if collect_stats else None
        return v_b, he_b, stats, executed

    return jax.jit(raw if batch_pad is None else raw_batch)


def _pad_shards(plan, shard_len_pad: int):
    """Zero-pad a plan's ``[n_parts, shard_len]`` edge shards out to the
    bucketed shard length (padding lanes carry mask 0)."""
    pad = shard_len_pad - plan.shard_len
    if pad == 0:
        return (
            jnp.asarray(plan.shard_src),
            jnp.asarray(plan.shard_dst),
            jnp.asarray(plan.shard_mask),
        )

    def padded(x):
        return jnp.asarray(
            np.pad(x, ((0, 0), (0, pad)))
        )

    return (
        padded(plan.shard_src), padded(plan.shard_dst),
        padded(plan.shard_mask),
    )


class AotExecutable:
    """One Engine LRU entry: a jitted builder output, compiled by
    ``warm(args)`` before its first call, so a lowering or compile error
    surfaces in its own phase and never degrades (``_serve``) nor falls
    back to plain jit.  ``source``: ``aot`` (compiled here) or ``disk``
    (deserialized by ``repro.serve.cache``).
    """

    __slots__ = ("jitted", "compiled", "source")

    def __init__(self, jitted):
        self.jitted = jitted
        self.compiled = None
        self.source = None

    def _materialize(self, args: tuple) -> None:
        self.compiled = self.jitted.lower(*args).compile()
        self.source = "aot"

    def warm(self, args: tuple) -> str:
        """Materialize without executing; returns the winning source."""
        if self.compiled is None:
            self._materialize(args)
        return self.source

    def __call__(self, *args):
        self.warm(args)
        return self.compiled(*args)


# --------------------------------------------------------------------------
# the serve-many handle
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledAlgorithm:
    """What ``Engine.compile`` returns: a design point resolved once,
    served many times.

    >>> compiled = engine.compile(shortest_paths_spec(hg, 0))
    >>> compiled.run()                         # hg0, baked-in source
    >>> compiled.run(query=7)                  # same executable, source 7
    >>> compiled.run_batch(np.arange(64))      # one vmapped executable
    >>> compiled.run(other_hg)                 # zero retrace if same bucket

    Executables live in the owning Engine's LRU cache keyed by
    ``serving.signature`` — a second same-bucket hypergraph (or a second
    ``compile`` of the same spec) is a cache hit with zero retracing;
    dtype, bucket, or design-point changes miss and compile fresh.
    ``Engine.cache_stats()`` exposes hits/misses/entries/traces so
    benchmarks can assert amortization.
    """

    engine: Any
    spec: Any
    config: Any                       # fully-resolved ExecutionConfig
    decision: dict
    _plan0: Any = None                # compile-time plan (hg0's structure)
    # Warm-path memo: (source_hg identity, rebind) -> padded state, so a
    # serve loop over one hypergraph pays init + padding once, not per
    # request.  Keyed by object identity like the Engine's plan cache
    # (hypergraphs are treated as immutable); bounded to the last few.
    _pad_cache: list = dataclasses.field(default_factory=list)
    # Memoized _initial_msg_sig: serializing the initial message is
    # host-side work that must not run per request (host-sync lint).
    _init_msg_sig: Any = None
    # Memoized graceful-degradation twin: same spec served with
    # delivery="xla" after a pallas_fused layout/execute failure.
    _xla_twin: Any = None

    # -- public API --------------------------------------------------------

    def run(self, hg: HyperGraph | None = None, query: Any = None):
        """Execute on ``hg`` (default: the spec's own hypergraph).

        ``query`` rebinds the spec's per-request state (requires
        ``spec.bind_query``); ``hg`` may be any hypergraph the spec's
        ``init`` can re-initialize — same shape bucket = zero retraces.
        When no query is given but the spec declares one (``query0``),
        the default query is bound through the same traced path, so
        querying and non-querying calls share one executable.
        """
        spec = self.spec
        if (query is None and spec.bind_query is not None
                and spec.init is not None and spec.query0 is not None):
            query = spec.query0
        if self.config.checkpoint_every is not None:
            return self._run_checkpointed(hg, query)
        q = _canon_query(query) if query is not None else None
        return self._serve(
            hg, q, None, rebind=query is not None,
            retry=lambda twin: twin.run(hg, query=query),
        )

    def run_batch(self, queries: Any, hg: HyperGraph | None = None):
        """Serve a batch: vmap the executable over the spec's query axis.

        ``queries`` is a query pytree with a leading batch dim B (for
        scalar queries: an array of B values).  Returns one ``Result``
        whose value/stats carry a leading B axis, bitwise equal to B
        sequential ``run(query=...)`` calls.  The batch dim is bucketed
        (queries repeat-padded, results sliced back), so varying B hits
        a bounded set of executables.
        """
        if self.spec.bind_query is None:
            raise ValueError(
                f"spec {self.spec.name!r} has no bind_query: declare the "
                "per-request axis to serve batched queries"
            )
        queries_c = _canon_query(queries)
        sizes = {
            int(jnp.shape(leaf)[0])
            for leaf in jax.tree.leaves(queries_c)
        }
        if len(sizes) != 1:
            raise ValueError(
                f"query leaves disagree on batch size: {sorted(sizes)}"
            )
        b = sizes.pop()
        b_pad = bucket_dim(b, floor=BATCH_FLOOR)
        # Repeat-pad with the last query: always a *valid* request,
        # and the padded rows are sliced off the results.
        queries_p = jax.tree.map(
            lambda leaf: jnp.concatenate(
                [leaf] + [leaf[-1:]] * (b_pad - b)
            ) if b_pad > b else leaf,
            queries_c,
        )
        return self._serve(
            hg, queries_p, (b, b_pad), rebind=True,
            retry=lambda twin: twin.run_batch(queries, hg=hg),
        )

    def warmup(
        self,
        *,
        query: Any = None,
        batch_sizes: tuple[int, ...] = (),
        hg: HyperGraph | None = None,
    ) -> dict:
        """Materialize executables WITHOUT serving traffic — the
        replica-boot half of ``repro.serve.cache.warm``.

        Resolves the unbatched path plus one batched path per bucket in
        ``batch_sizes`` (sizes quantize through the normal batch
        buckets).  With a disk cache attached to the Engine, each path
        either deserializes from the store (zero retraces) or
        AOT-compiles and populates it; without one, this is a plain
        eager compile.  ``query``: example request for specs whose
        ``query0`` is unset; required to warm query-bearing paths.

        Returns ``{path: {"source": "disk"|"aot"}}``.
        """
        spec = self.spec
        if query is None:
            query = spec.query0
        has_query = (
            spec.bind_query is not None
            and spec.init is not None
            and query is not None
        )
        prep = self._prepared(hg, rebind=has_query)
        q = _canon_query(query) if has_query else None
        exe, _ = self._materialize(prep, q, None)
        report = {"single": {"source": exe.source}}
        for b in batch_sizes:
            if spec.bind_query is None:
                raise ValueError(
                    f"spec {spec.name!r} has no bind_query: no batched "
                    "path to warm"
                )
            if q is None:
                raise ValueError(
                    "warming a batched path needs an example query "
                    "(spec.query0 is unset — pass query=...)"
                )
            b_pad = bucket_dim(int(b), floor=BATCH_FLOOR)
            queries = jax.tree.map(
                lambda leaf: jnp.broadcast_to(
                    leaf, (b_pad,) + jnp.shape(leaf)
                ),
                q,
            )
            exe, _ = self._materialize(prep, queries, (b_pad, b_pad))
            report[f"batch{b_pad}"] = {"source": exe.source}
        return report

    # -- fault tolerance ---------------------------------------------------

    def _serve(self, hg, query, batch, *, rebind: bool, retry):
        """Prepare, compile, execute.  A layout-build or execute failure
        may degrade to the ``xla`` twin (``retry(twin)``); a lowering or
        compile error never does — a silent switch would hide which
        path the device runs."""
        stage = "prepare"
        try:
            prep = self._prepared(hg, rebind=rebind)
            stage = "compile"
            exe, args = self._materialize(prep, query, batch)
            stage = "execute"
            return self._execute(prep, query, batch, exe, args)
        except Exception as err:
            twin = None
            if (stage != "compile" and not isinstance(err, ValueError)
                    and not is_transient(err)):
                twin = self._degraded_sibling(err)
            if twin is None:
                raise
            return retry(twin)

    def _degraded_sibling(self, err: Exception):
        """Graceful-degradation chain, delivery link: a ``pallas_fused``
        layout-build or execute failure must not fail the request when
        the ``xla`` lowering can still serve it.

        Returns the memoized ``delivery="xla"`` twin of this handle (one
        compile, shared across subsequent degradations), or ``None``
        when degradation does not apply — already on xla, nothing left
        to fall back to.  Non-sticky by design: the next request tries
        the fused path again, so one fused failure does not permanently
        forfeit the faster lowering.

        Callers gate this on ``not is_transient(err)``: transient
        failures propagate so the serve tier retries them on the SAME
        delivery — the two lowerings agree on shapes, not on float
        rounding, so switching deliveries is reserved for faults that
        would otherwise fail the request outright.
        """
        if self.config.delivery != "pallas_fused":
            return None
        engine = self.engine
        if self._xla_twin is None:
            self._xla_twin = CompiledAlgorithm(
                engine=engine,
                spec=self.spec,
                config=dataclasses.replace(self.config, delivery="xla"),
                decision={**self.decision, "degraded_from": "pallas_fused"},
                _plan0=self._plan0,
            )
        metrics = getattr(engine, "metrics", None)
        if metrics is not None:
            metrics.counter("faults.delivery_degraded").inc()
        with maybe_span(
            getattr(engine, "tracer", None), "faults.degrade_delivery",
            cat="faults", algorithm=self.spec.name,
            error=type(err).__name__,
        ):
            pass
        return self._xla_twin

    def _run_checkpointed(self, hg, query):
        """Route through the chunked checkpoint/resume drivers
        (``repro.faults.checkpoint``) instead of the cached executable.

        The chunked drivers run the SAME per-iteration scan body as the
        compiled path (shared ``_halting_body`` / distributed ``_body``)
        on the same padded buffers, snapshotting the carry every
        ``checkpoint_every`` superstep pairs — results are bitwise-equal
        to the uninterrupted executable and a killed run resumes from
        ``checkpoint_dir``'s latest snapshot."""
        from repro.core.executor import Result
        from repro.faults.checkpoint import (
            checkpointed_compute,
            checkpointed_distributed_compute,
        )

        cfg = self.config
        spec = self.spec
        engine = self.engine
        prep = self._prepared(hg, rebind=query is not None)
        q = _canon_query(query) if query is not None else None
        nv, ne = prep["nv"], prep["ne"]
        plan = prep["plan"]
        injector = getattr(engine, "fault_injector", None)
        stats = None
        if cfg.backend == "local":
            hgq = prep["hgp"]
            if q is not None:
                hgq = spec.bind_query(hgq, q)
            out = checkpointed_compute(
                hgq, cfg.max_iters, spec.initial_msg,
                spec.v_program, spec.he_program,
                every=cfg.checkpoint_every, ckpt_dir=cfg.checkpoint_dir,
                return_stats=cfg.collect_stats,
                n_real=(jnp.asarray(nv, jnp.int32),
                        jnp.asarray(ne, jnp.int32)),
                delivery=prep["delivery"], jit=cfg.jit,
                tracer=engine.tracer, metrics=engine.metrics,
                fault_injector=injector,
            )
            if cfg.collect_stats:
                out, stats = out
            # The chunked driver ran on the padded buffers; slice back.
            out = out.with_attrs(
                v_attr=jax.tree.map(lambda x: x[:nv], out.v_attr),
                he_attr=jax.tree.map(lambda x: x[:ne], out.he_attr),
            )
        else:
            base = prep["base"]
            hgq = spec.bind_query(base, q) if q is not None else base
            out = checkpointed_distributed_compute(
                hgq, plan, engine.mesh, cfg.max_iters, spec.initial_msg,
                spec.v_program, spec.he_program,
                every=cfg.checkpoint_every, ckpt_dir=cfg.checkpoint_dir,
                axis=cfg.axis, backend=cfg.backend,
                delivery=cfg.delivery,
                return_stats=cfg.collect_stats,
                tracer=engine.tracer, metrics=engine.metrics,
                fault_injector=injector,
            )
            if cfg.collect_stats:
                out, stats = out
        return Result(
            value=spec.extract(out),
            config=cfg,
            representation=cfg.representation,
            backend=cfg.backend,
            partition=plan.name if plan is not None else None,
            partition_stats=plan.stats if plan is not None else None,
            superstep_stats=stats,
            supersteps_executed=None,
            decision={
                **self.decision,
                "checkpointed": {
                    "every": cfg.checkpoint_every,
                    "dir": cfg.checkpoint_dir,
                },
            },
        )

    # -- internals ---------------------------------------------------------

    def _base_state(self, hg, *, rebind: bool):
        """(initialized state, structure-identity object for plan cache).

        ``rebind=True`` re-initializes even the spec's own hypergraph so
        ``bind_query`` starts from unbound state (hg0 already carries
        ``query0``)."""
        spec = self.spec
        if hg is None and not rebind:
            return spec.hg0, spec.hg0
        if spec.init is None:
            raise ValueError(
                f"spec {self.spec.name!r} has no init: cannot "
                + ("rebind queries" if hg is None else
                   "re-initialize a new hypergraph")
            )
        source = spec.hg0 if hg is None else hg
        return spec.init(source), source

    def _prepared(self, hg, *, rebind: bool):
        """Initialized + bucket-padded inputs for one source hypergraph,
        memoized by (hypergraph identity, rebind): the warm serve loop
        pays init/padding/plan lookup once, not per request."""
        source_probe = self.spec.hg0 if hg is None else hg
        for s, r, prep in self._pad_cache:
            if s is source_probe and r == rebind:
                return prep

        base, source_hg = self._base_state(hg, rebind=rebind)
        cfg = self.config
        nv, ne, nnz = base.n_vertices, base.n_hyperedges, base.nnz
        nv_pad, ne_pad = bucket_dim(nv), bucket_dim(ne)
        nnz_pad = bucket_dim(nnz)
        plan = None
        shards = None
        shard_len_pad = 0
        n_parts = 0
        if cfg.backend != "local":
            plan = self._plan_for(source_hg)
            n_parts = plan.n_parts
            nv_pad = _round_up(nv_pad, n_parts)
            ne_pad = _round_up(ne_pad, n_parts)
            shard_len_pad = bucket_dim(plan.shard_len)
            shards = _pad_shards(plan, shard_len_pad)
        hgp = base.padded(nv_pad, ne_pad, nnz_pad)
        # Fused delivery: the dst-sort + ELL/CSR precompute happens HERE,
        # once per (hypergraph, bucket) — the serve loop never re-sorts.
        # Built from the PADDED structure (padding lanes carry e_mask=0
        # and fold to identity), so the layouts match the executable's
        # shapes; their data-dependent dims enter the cache signature.
        delivery = None
        delivery_sig = None
        if cfg.delivery == "pallas_fused":
            with maybe_span(
                self.engine.tracer, "serve.layout_build", cat="compile",
                algorithm=self.spec.name, nnz_pad=int(nnz_pad),
                nv_pad=int(nv_pad), ne_pad=int(ne_pad),
            ):
                inj = getattr(self.engine, "fault_injector", None)
                if inj is not None:
                    inj.maybe_raise(
                        "layout.build", algorithm=self.spec.name
                    )
                if cfg.backend == "local":
                    delivery = layout_pair(
                        hgp.src, hgp.dst, hgp.e_mask, nv_pad, ne_pad
                    )
                else:
                    from repro.core.distributed import build_shard_delivery

                    delivery = build_shard_delivery(
                        *(np.asarray(s) for s in shards), nv_pad, ne_pad
                    )
            delivery_sig = tuple(l.shape_signature() for l in delivery)
        prep = dict(
            base=base,
            nv=nv, ne=ne,
            nv_pad=nv_pad, ne_pad=ne_pad, nnz_pad=nnz_pad,
            plan=plan, n_parts=n_parts, shard_len_pad=shard_len_pad,
            shards=shards, hgp=hgp,
            delivery=delivery, delivery_sig=delivery_sig,
            attr_sigs=(
                _attr_sig(hgp.v_attr), _attr_sig(hgp.he_attr),
                _attr_sig(hgp.e_attr),
            ),
        )
        self._pad_cache.append((source_probe, rebind, prep))
        del self._pad_cache[:-4]  # bound the strong refs we hold
        return prep

    def _plan_for(self, source_hg):
        if self.config.backend == "local":
            return None
        if source_hg is self.spec.hg0 and self._plan0 is not None:
            return self._plan0
        plan, _ = self.engine._cached_plan(
            source_hg, self.config.n_parts, self.config.partition_strategy
        )
        return plan

    def _materialize(self, prep: dict, query, batch):
        """The compile phase: this request's executable, compiled (or
        loaded from disk) before anything executes, and its args."""
        cfg = self.config
        spec = self.spec
        engine = self.engine
        has_query = query is not None
        b_pad = batch[1] if batch is not None else None

        v_sig, he_sig, e_sig = prep["attr_sigs"]
        one_query = (
            jax.tree.map(lambda leaf: leaf[0], query)
            if batch is not None and has_query
            else query
        )
        if self._init_msg_sig is None:
            self._init_msg_sig = _initial_msg_sig(spec.initial_msg)
        key = signature(
            spec, cfg,
            nv_pad=prep["nv_pad"], ne_pad=prep["ne_pad"],
            nnz_pad=prep["nnz_pad"],
            shard_len_pad=prep["shard_len_pad"], n_parts=prep["n_parts"],
            v_attr_sig=v_sig, he_attr_sig=he_sig, e_attr_sig=e_sig,
            query_sig=_query_sig(one_query),
            batch_pad=b_pad,
            delivery_sig=prep["delivery_sig"],
            initial_msg_sig=self._init_msg_sig,
        )
        meta = {
            "algorithm": spec.name,
            "backend": cfg.backend,
            "delivery": cfg.delivery,
            "nv_pad": prep["nv_pad"],
            "ne_pad": prep["ne_pad"],
            "nnz_pad": prep["nnz_pad"],
            "batch_pad": b_pad,
            "n_parts": prep["n_parts"],
        }
        real = (
            jnp.asarray(prep["nv"], jnp.int32),
            jnp.asarray(prep["ne"], jnp.int32),
        )
        if cfg.backend != "local":
            exe = engine._executable_for(
                key,
                lambda: _build_distributed_executable(
                    spec, cfg, engine.mesh, prep["n_parts"],
                    prep["nv_pad"], prep["ne_pad"],
                    has_query, b_pad, engine._note_trace,
                ),
                meta=meta,
            )
            args = (prep["hgp"], *prep["shards"], prep["delivery"],
                    *real, query)
            with engine.mesh:
                exe.warm(args)
        else:
            exe = engine._executable_for(
                key,
                lambda: _build_local_executable(
                    spec, cfg, has_query, b_pad, engine._note_trace,
                ),
                meta=meta,
            )
            args = (prep["hgp"], prep["delivery"], *real, query)
            exe.warm(args)
        return exe, args

    def _execute(self, prep: dict, query, batch, exe, args):
        from repro.core.executor import Result

        cfg = self.config
        spec = self.spec
        engine = self.engine
        distributed = cfg.backend != "local"
        b = batch[0] if batch is not None else None
        base, plan = prep["base"], prep["plan"]
        nv, ne = prep["nv"], prep["ne"]

        # Fault injection on the execute seam: one attribute load and a
        # None-check when no injector is attached (the same zero-overhead
        # contract as the tracer below).  Warmup never "executes".
        inj = getattr(engine, "fault_injector", None)
        if inj is not None:
            inj.maybe_raise(
                "execute", algorithm=spec.name, backend=cfg.backend,
                delivery=cfg.delivery,
                # analysis: ignore[host-sync] — b is the host-side batch
                # count (Python int or None), never a device value
                batch=int(b) if b is not None else 0,
            )

        # Blocking and measured timing on the serve hot path are
        # strictly opt-in: without an attached tracer this is
        # ``exe(*args)`` under ``maybe_span`` (a span while the JAX
        # profiler records, one check otherwise) — the zero-overhead
        # contract bench_obs asserts.
        tracer = engine.tracer
        timing: dict = {}

        def _call():
            if tracer is None:
                with maybe_span(None, "engine.execute", cat="execute",
                                algorithm=spec.name):
                    out = exe(*args)
                return out
            t0 = time.perf_counter()
            traces0 = engine._trace_count
            with tracer.span(
                "engine.execute", cat="execute", algorithm=spec.name,
                backend=cfg.backend, delivery=cfg.delivery,
                batch=int(b) if b is not None else 0,
            ) as sp:
                out = exe(*args)
                tracer.block(sp, out)
                sp.args["retraces"] = engine._trace_count - traces0
            timing["wall_s"] = time.perf_counter() - t0
            timing["device_wait_s"] = sp.args.get("device_wait_s", 0.0)
            return out

        with engine.mesh if distributed else contextlib.nullcontext():
            v_attr, he_attr, stats, executed = _call()

        # Slice padding (and batch padding) back off; extract on a
        # real-size hypergraph whose attrs may carry a leading batch dim
        # (extracts are field accessors, shape-polymorphic over it).
        if batch is not None:
            unslice_v = lambda x: x[:b, :nv]
            unslice_he = lambda x: x[:b, :ne]
            stats = (
                jax.tree.map(lambda x: x[:b], stats)
                if stats is not None else None
            )
        else:
            unslice_v = lambda x: x[:nv]
            unslice_he = lambda x: x[:ne]
        out = base.with_attrs(
            v_attr=jax.tree.map(unslice_v, v_attr),
            he_attr=jax.tree.map(unslice_he, he_attr),
        )
        decision = self.decision
        if tracer is not None and timing:
            # Measured enrichment is tracer-gated here (unlike
            # Engine.run's one-shot path) so warm serving stays
            # allocation-free by default.
            from repro.core.executor import message_width_bytes
            from repro.obs.calibrate import delivery_traffic_pair

            measured: dict = dict(timing)
            if executed is not None:
                try:
                    measured["supersteps"] = int(np.asarray(executed))
                # analysis: ignore[swallowed-error] — best-effort metric
                # enrichment: losing "supersteps" must not fail a serve
                # that already produced its result
                except Exception:
                    pass
            if prep["delivery"] is not None and not distributed:
                measured["delivery"] = delivery_traffic_pair(
                    prep["delivery"], message_width_bytes(spec.initial_msg)
                )
            decision = {**self.decision, "measured": measured}
        return Result(
            value=spec.extract(out),
            config=cfg,
            representation=cfg.representation,
            backend=cfg.backend,
            partition=plan.name if plan is not None else None,
            partition_stats=plan.stats if plan is not None else None,
            superstep_stats=stats,
            supersteps_executed=executed,
            decision=decision,
        )
