"""One API, many design points: the ``Engine`` facade.

MESH's central claim (§IV) is that representation and partitioning are
*pluggable design choices behind one simple API*, selected per data and
application characteristics.  This module is that API: every algorithm,
benchmark, example and launch script routes through ``Engine.submit``
(dispatching ``AlgorithmSpec`` -> iterative ``run``, ``AnalyticsSpec``
-> batch ``analyze``) or the compile-once serving path ``Engine.compile
-> CompiledAlgorithm`` (``repro.core.serving``); the representation
(bipartite incidence vs clique expansion), partitioning strategy and
execution backend (local / replicated / sharded) are named by an
``ExecutionConfig`` and — when left ``"auto"`` — chosen by small cost
models over the machinery the repo already has:

* clique vs bipartite: ``clique_expansion_size`` against the incidence
  count, gated on the paper's constant-folding precondition (the algorithm
  must never touch hyperedge state — ``AlgorithmSpec.touches_hyperedge_state``);
* replicated vs sharded: ``PartitionStats.sync_bytes_per_dim`` against the
  full-replication sync bound the replicated backend pays by construction;
* partition strategy: min projected sync volume across the strategy
  registry (the selection loop of ``examples/hypergraph_analytics``).

The chosen design point is reported on the returned ``Result`` so callers
(and tests) can see *why* an execution ran the way it did.
"""
from __future__ import annotations

import dataclasses
import time
from collections import Counter, OrderedDict
from typing import Any, Callable, Mapping

import jax
import numpy as np

from repro.core.clique import clique_expansion_size, to_graph
from repro.core.engine import compute, compute_jit
from repro.core.hypergraph import HyperGraph
from repro.core.serving import AotExecutable
from repro.obs.calibrate import (
    delivery_traffic_pair,
    executed_supersteps,
    reference_traffic,
)
from repro.obs.metrics import default_registry, weak_provider
from repro.obs.trace import maybe_span
from repro.kernels.deliver import (
    DELIVERY_MODES,
    layout_pair,
    plan_degree_classes,
    plan_ell_width,
    select_lowering,
)

from repro.motifs.intersect import INTERSECT_KERNELS

REPRESENTATIONS = ("auto", "bipartite", "clique")
BACKENDS = ("auto", "local", "replicated", "sharded")
ANALYTICS_TASKS = ("hmotif_census", "pair_intersections")
ANALYTICS_MODES = ("auto", "exact", "sample")

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """Every design choice from the paper, in one place.

    ``"auto"`` fields are resolved per spec/plan/mesh by ``Engine.run``;
    the resolved copy (no ``"auto"`` left) is returned on ``Result.config``.

    Attributes:
      representation: ``bipartite`` | ``clique`` | ``auto``.  Clique
        expansion is only legal for specs with
        ``touches_hyperedge_state=False`` (paper §IV-A1) and a
        ``clique_program``.
      backend: ``local`` | ``replicated`` | ``sharded`` | ``auto``.
        Distributed backends need a mesh; ``auto`` with no mesh = local.
      partition_strategy: a name from ``repro.partition.STRATEGIES`` or
        ``auto`` (min projected sync volume).  Ignored when an explicit
        plan is passed to ``Engine``.  Resolved configs may carry
        ``"none"``: the execution partitioned nothing (local / clique).
      n_parts: partition count; defaults to ``mesh.shape[axis]``.
      axis: mesh axis carrying edge partitions.
      jit: wrap the local engine in ``jax.jit`` (distributed path is
        always jitted by construction).
      max_iters: overrides ``spec.max_iters`` when set.
      collect_stats: return per-superstep activity counters.  All
        backends: the distributed scan threads its trace out through
        ``shard_map`` out_specs (replicated — counts are psum'd), and
        counts exclude padding slots, so every backend reports the
        same numbers as the local engine.
      clique_edge_budget: clique expansion is auto-picked only when its
        (symmetrized) edge count is within this factor of the bipartite
        incidence count — the build cost and memory are the paper's
        Table I infeasibility argument.
      replicated_bias: sharded wins when the plan's projected sync bytes
        are below ``bias`` x the full-replication sync bound; the bias
        captures replicated's lower constant factor (one fused psum vs
        all_gather + psum_scatter).
      intersect_kernel: ``bitset`` | ``merge`` | ``auto`` — the
        hyperedge-pair intersection kernel the batch analytics mode
        (``Engine.analyze``) runs; iterative ``run`` ignores it.
        ``auto`` = ``repro.motifs.select_intersect_kernel`` (word lanes
        vs sort-merge work per pair).
      delivery: ``xla`` | ``pallas_fused`` | ``auto`` — the
        deliver/combine data path of every half-superstep.  ``xla`` is
        the reference gather -> mask -> segment-reduce;
        ``pallas_fused`` precomputes a dst-sorted degree-class
        (sliced-ELL) layout once per structure
        (``repro.kernels.deliver``) and fuses gather, mask and combine
        so the ``[nnz, D]`` intermediate never hits HBM.  ``auto``
        resolves via ``select_delivery``'s cost model (message width,
        degree skew via the class plan's padding work, nnz, platform
        lowering), falling back to ``xla`` for custom ``reducer``s and
        per-incidence ``edge_transform``s — the non-monoid paths the
        fused kernel cannot legally take.
    """

    representation: str = "auto"
    backend: str = "auto"
    partition_strategy: str = "auto"
    n_parts: int | None = None
    axis: str = "data"
    jit: bool = False
    max_iters: int | None = None
    collect_stats: bool = False
    clique_edge_budget: float = 4.0
    replicated_bias: float = 0.5
    intersect_kernel: str = "auto"
    delivery: str = "auto"
    # Fault tolerance (repro.faults): snapshot the superstep scan carry
    # every N pairs into ``checkpoint_dir`` (train/checkpoint.py format)
    # so a killed run resumes mid-algorithm bitwise-equal to an
    # uninterrupted one.  ``None`` = no checkpointing (the default; the
    # hot path is untouched).
    checkpoint_every: int | None = None
    checkpoint_dir: str | None = None

    def __post_init__(self):
        if self.checkpoint_every is not None and self.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {self.checkpoint_every}"
            )
        if self.checkpoint_every is not None and self.checkpoint_dir is None:
            raise ValueError(
                "checkpoint_every needs checkpoint_dir (where snapshots go)"
            )
        if self.representation not in REPRESENTATIONS:
            raise ValueError(
                f"representation must be one of {REPRESENTATIONS}, "
                f"got {self.representation!r}"
            )
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}"
            )
        if self.intersect_kernel not in INTERSECT_KERNELS:
            raise ValueError(
                f"intersect_kernel must be one of {INTERSECT_KERNELS}, "
                f"got {self.intersect_kernel!r}"
            )
        if self.delivery not in DELIVERY_MODES:
            raise ValueError(
                f"delivery must be one of {DELIVERY_MODES}, "
                f"got {self.delivery!r}"
            )


@dataclasses.dataclass(frozen=True)
class Result:
    """What an execution produced, plus the design point that produced it.

    Attributes:
      value: the spec's extracted output.
      config: the fully-resolved ``ExecutionConfig`` (no ``"auto"``).
      representation / backend: the chosen design point (convenience
        mirrors of ``config``).
      partition: name of the partition strategy used, or ``None`` (local /
        clique executions don't partition).
      partition_stats: the plan's ``PartitionStats``, or ``None``.
      superstep_stats: ``(v_active, he_active)`` int32 arrays of length
        ``max_iters`` when ``collect_stats`` was set (any backend),
        else ``None``.
      supersteps_executed: batched serving only — the superstep pairs
        the batch-aware halting scan actually ran (== the slowest
        query's convergence, <= max_iters); ``None`` elsewhere.
      decision: cost-model numbers behind each ``auto`` choice —
        a dict of dicts, one entry per resolved axis.
    """

    value: Any
    config: ExecutionConfig
    representation: str
    backend: str
    partition: str | None = None
    partition_stats: Any = None
    superstep_stats: Any = None
    supersteps_executed: Any = None
    decision: Mapping[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class AnalyticsSpec:
    """A batch analytics workload — the non-iterative counterpart of
    ``AlgorithmSpec``, consumed by ``Engine.analyze``.

    Attributes:
      hg: the input hypergraph.
      task: ``hmotif_census`` (classify connected 3-hyperedge patterns
        into the 26 h-motif classes) or ``pair_intersections``
        (intersection size per hyperedge pair).
      mode: census only — ``exact`` enumerates every connected triple,
        ``sample`` runs the uniform linked-pair estimator, ``auto``
        picks by the overlap-pair budget below.
      n_samples / seed / confidence: sampling-estimator parameters.
      pairs: ``pair_intersections`` only — optional ``(ea, eb)`` id
        arrays; ``None`` = every overlapping pair.
      exact_pair_budget: ``mode="auto"`` runs exact while the overlap
        graph has at most this many linked pairs.
      tile: pair-batch tile size for the intersection kernel.
    """

    hg: HyperGraph
    task: str = "hmotif_census"
    mode: str = "auto"
    n_samples: int = 4000
    seed: int = 0
    confidence: float = 0.95
    pairs: Any = None
    exact_pair_budget: int = 200_000
    tile: int = 2048
    name: str = "hmotifs"

    def __post_init__(self):
        if self.task not in ANALYTICS_TASKS:
            raise ValueError(
                f"task must be one of {ANALYTICS_TASKS}, got {self.task!r}"
            )
        if self.mode not in ANALYTICS_MODES:
            raise ValueError(
                f"mode must be one of {ANALYTICS_MODES}, got {self.mode!r}"
            )


@dataclasses.dataclass(frozen=True)
class AnalyticsResult:
    """What a batch analytics execution produced, plus its design point.

    Attributes:
      value: ``Census`` (exact) / ``CensusEstimate`` (sampled) for the
        census task; ``(pairs, sizes)`` for ``pair_intersections``.
      representation: ``clique`` = pairwise intersections materialized
        from the dual clique expansion; ``bipartite`` = derived on the
        fly from the incidence by the kernel.
      kernel: ``bitset`` | ``merge`` — the intersection kernel path.
      backend: ``local`` | ``sharded`` (pair blocks tiled across the
        mesh).
      mode: ``exact`` | ``sample`` (census task; ``None`` otherwise).
      decision: cost-model numbers behind each ``auto`` choice.
    """

    value: Any
    config: ExecutionConfig
    representation: str
    kernel: str
    backend: str
    mode: str | None = None
    decision: Mapping[str, Any] = dataclasses.field(default_factory=dict)


def select_representation(
    spec, hg: HyperGraph, *, edge_budget: float = 4.0
) -> tuple[str, dict]:
    """Clique vs bipartite for one spec — the paper's constant-folding
    rule plus a size cost model.

    Clique expansion is chosen only when (a) the algorithm never touches
    hyperedge state and ships a ``clique_program`` (correctness
    precondition, §IV-A1) and (b) the symmetrized expansion stays within
    ``edge_budget`` x the bipartite incidence count (Table I: heavy-tailed
    cardinalities blow the expansion up quadratically).
    """
    touches = getattr(spec, "touches_hyperedge_state", True)
    has_program = getattr(spec, "clique_program", None) is not None
    why: dict[str, Any] = {
        "touches_hyperedge_state": touches,
        "has_clique_program": has_program,
    }
    if touches or not has_program:
        why["reason"] = (
            "algorithm touches hyperedge state"
            if touches
            else "no clique program supplied"
        )
        return "bipartite", why

    n_clique_edges = 2 * clique_expansion_size(hg)  # symmetrized
    budget = edge_budget * max(hg.nnz, 1)
    why.update(
        clique_edges=int(n_clique_edges),
        bipartite_edges=int(hg.nnz),
        edge_budget=float(budget),
    )
    if n_clique_edges <= budget:
        why["reason"] = "expansion within edge budget"
        return "clique", why
    why["reason"] = "expansion exceeds edge budget"
    return "bipartite", why


def state_width_bytes(attr: Pytree, n: int, default: float = 4.0) -> float:
    """Bytes of state per entity in an attribute pytree with leading dim
    ``n`` (one float32 dim when there is no state to measure)."""
    leaves = [leaf for leaf in jax.tree.leaves(attr) if hasattr(leaf, "size")]
    if not leaves or n <= 0:
        return default
    total = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
    return max(float(total) / n, 1.0)


def select_backend(
    plan,
    n_vertices: int,
    n_hyperedges: int,
    *,
    replicated_bias: float = 0.5,
    v_state_bytes: float = 4.0,
    he_state_bytes: float = 4.0,
) -> tuple[str, dict]:
    """Replicated vs sharded for one partition plan.

    The replicated backend syncs a *full-size* state buffer across every
    partition each half-superstep — equivalent to refreshing ``P - 1``
    replicas of every entity:
    ``full_sync = 2 * (P - 1) * (w_v |V| + w_he |E|)`` bytes, where the
    widths are the spec's actual bytes of state per vertex / hyperedge
    (multi-dim attributes count every dim — bytes do NOT cancel out of
    the comparison, because the two sides can be weighted differently).
    The sharded backend's traffic tracks the replicas the edge cut
    actually created, weighted the same way
    (``PartitionStats.sync_bytes``).  Sharded wins when its projected
    sync is below ``replicated_bias`` x the full bound; the bias (< 1)
    favors replicated for well-connected small states where its single
    fused collective is cheaper in practice (the paper's apache/dblp
    regime).
    """
    stats = plan.stats
    p = plan.n_parts
    full_sync = 2.0 * max(p - 1, 0) * (
        v_state_bytes * n_vertices + he_state_bytes * n_hyperedges
    )
    sharded_sync = stats.sync_bytes(v_state_bytes, he_state_bytes)
    why = {
        "n_parts": p,
        "sync_bytes_per_dim": float(stats.sync_bytes_per_dim),
        "sharded_sync_bytes": sharded_sync,
        "full_replication_sync_bytes": full_sync,
        "v_state_bytes": v_state_bytes,
        "he_state_bytes": he_state_bytes,
        "replicated_bias": replicated_bias,
    }
    if p <= 1:
        why["reason"] = "single partition: replication is free"
        return "replicated", why
    if sharded_sync < replicated_bias * full_sync:
        why["reason"] = "plan sync volume beats full replication"
        return "sharded", why
    why["reason"] = "cut replicates most entities anyway"
    return "replicated", why


def select_partition(
    hg: HyperGraph, n_parts: int, strategy: str = "auto"
) -> tuple[Any, dict]:
    """Build a plan; ``auto`` = min projected sync volume over the
    strategy registry (greedy strategies run in chunked/approximate mode
    so selection stays preprocessing-cheap)."""
    from repro.partition import STRATEGIES, partition

    if strategy != "auto":
        if strategy not in STRATEGIES:
            raise ValueError(
                f"unknown partition strategy {strategy!r}; pick one of "
                f"{sorted(STRATEGIES)} or 'auto'"
            )
        kw = {"chunk": 256} if "greedy" in strategy else {}
        return partition(strategy, hg, n_parts, **kw), {
            "strategy": strategy, "reason": "explicitly configured",
        }

    best_name, best_plan = None, None
    costs = {}
    for name in sorted(STRATEGIES):
        kw = {"chunk": 256} if "greedy" in name else {}
        try:
            plan = partition(name, hg, n_parts, **kw)
        except ValueError:
            continue  # e.g. greedy bitmask width on wide meshes
        costs[name] = plan.stats.sync_bytes_per_dim
        if best_plan is None or (
            plan.stats.sync_bytes_per_dim
            < best_plan.stats.sync_bytes_per_dim
        ):
            best_name, best_plan = name, plan
    if best_plan is None:
        raise RuntimeError("no partition strategy produced a plan")
    return best_plan, {
        "strategy": best_name,
        "reason": "min projected sync volume",
        "sync_bytes_by_strategy": costs,
    }


# Fused-delivery cost model constants (ELL lowering; see
# ``select_delivery``).  Calibrated on ``benchmarks/bench_delivery.py``:
# the degree-class (sliced-ELL) dense reduces beat XLA's serialized
# scatter decisively for messages up to ``FUSED_MAX_WIDTH_BYTES``
# regardless of skew — per-class widths keep hubs dense, so zipf skew
# no longer bleeds into an overflow scatter.  The 64-byte zipf point
# (``wide_highskew``) is the regime the class layout flipped: the PR-4
# single-ELL packing measured a ~2x LOSS to the reference there (its
# capped width spilled over half the incidences into the sorted
# scatter); the class layout wins it ~1.3x (2.7x over single-ELL).
# Past the width cap the reference gather/scatter already vectorizes
# and the dense tables' padded row traffic (and cache footprint)
# multiplies with width — measured losses at every skew on XLA hosts.
FUSED_MAX_WIDTH_BYTES = 64.0    # per-entity message bytes
FUSED_ELL_WORK_BUDGET = 4.0     # padded ELL slots per real incidence
# Below this the layout/dispatch overheads swamp any kernel win AND the
# decision would be noise-sensitive (same-bucket graphs flipping design
# points for sub-ms executions); auto stays on the reference path.
FUSED_MIN_NNZ = 4096


def _non_monoid_reason(spec) -> str | None:
    """Why the fused delivery path is illegal for this spec, or None."""
    for side, prog in (("v_program", spec.v_program),
                       ("he_program", spec.he_program)):
        if getattr(prog, "reducer", None) is not None:
            return f"{side} has a custom (Seq) reducer"
        if getattr(prog, "edge_transform", None) is not None:
            return f"{side} has a per-incidence edge_transform"
    return None


def message_width_bytes(initial_msg: Any) -> float:
    """Bytes per entity of one broadcast message (from the spec's
    ``initial_msg`` template — the only static width signal)."""
    total = 0.0
    for leaf in jax.tree.leaves(initial_msg):
        arr = np.asarray(leaf)
        total += float(arr.size * arr.dtype.itemsize)
    return max(total, 1.0)


def select_delivery(spec, hg: HyperGraph) -> tuple[str, dict]:
    """Fused vs reference delivery for one spec — the tentpole's cost
    model over nnz, message width, dtype and degree skew.

    Hard gates first: the fused kernel folds the combine into the
    layout, so custom ``reducer``s / ``edge_transform``s (which consume
    materialized per-incidence rows) and empty structures take ``xla``.

    Then one cost model on every platform; ``why["lowering"]`` names
    the lowering the fused path will run
    (``repro.kernels.deliver.select_lowering``: ``ell``, the XLA
    sliced-ELL form, unless overridden).  The fused win comes from
    replacing the serialized scatter with dense reduces, and dies by
    padding.  The padding term is the degree-class plan's summed work
    (``plan_degree_classes`` over both directions' live-degree
    histograms — dense slots at the builder's pow2 row padding
    (``ClassPlan.built_work``) plus residual; exactly what a layout
    built by the LOCAL builder allocates, so model and builder cannot
    disagree there.  The distributed builder plans from merged
    per-shard histograms and harmonizes pads to shard maxima, so its
    realized padded work can exceed this estimate on shard-skewed cuts
    — the budget is a lower bound in that case).  Pick fused while
    (a) class padding is bounded (``FUSED_ELL_WORK_BUDGET`` slots per
    incidence, both directions) and (b) the message row is within
    ``FUSED_MAX_WIDTH_BYTES`` — a boundary the class layout MOVED: at
    64-byte rows under zipf skew the PR-4 single-ELL packing measured
    a ~2x loss to the reference (overflow scatter), while per-class
    widths keep hubs dense and win the regime.  The reported
    ``skew_gain`` (single-ELL vs class plan, residual-weighted)
    quantifies how much of the decision the degree classes carry.
    """
    reason = _non_monoid_reason(spec)
    why: dict[str, Any] = {}
    if reason is not None:
        why["reason"] = f"non-monoid path: {reason}"
        return "xla", why
    if hg.nnz == 0 or hg.n_vertices == 0 or hg.n_hyperedges == 0:
        why["reason"] = "empty structure"
        return "xla", why

    why["lowering"] = select_lowering()
    live = (
        np.asarray(hg.e_mask) != 0
        if hg.e_mask is not None
        else np.ones(hg.nnz, bool)
    )
    src = np.asarray(hg.src)[live]
    dst = np.asarray(hg.dst)[live]
    nnz = int(live.sum())
    if nnz == 0:
        why["reason"] = "no live incidences"
        return "xla", why
    width = message_width_bytes(spec.initial_msg)
    why["message_width_bytes"] = width
    if nnz < FUSED_MIN_NNZ:
        why["reason"] = (
            f"tiny incidence ({nnz} < {FUSED_MIN_NNZ}): layout and "
            "dispatch overheads dominate"
        )
        return "xla", why

    from repro.kernels.deliver.layout import RESIDUAL_WEIGHT

    class_work = 0.0
    class_weighted = 0.0
    single_weighted = 0.0
    residual = 0
    plans = {}
    for side, n_dst, ids in (
        ("fwd", hg.n_hyperedges, dst), ("bwd", hg.n_vertices, src)
    ):
        deg = np.bincount(ids, minlength=n_dst)
        plan = plan_degree_classes(deg, nnz)
        k1, rem1 = plan_ell_width(deg, nnz)
        # built_work: dense slots at the builder's pow2 row padding —
        # the work the layout will really do, not the DP's tight count.
        class_work += float(plan.built_work)
        class_weighted += float(
            plan.built_work - plan.residual
            + RESIDUAL_WEIGHT * plan.residual
        )
        single_weighted += float(n_dst * k1 + RESIDUAL_WEIGHT * rem1)
        residual = max(residual, plan.residual)
        plans[side] = {
            "widths": plan.widths, "rows": plan.rows,
            "residual": plan.residual,
        }
    # Residual lanes pay the serialized sorted segment reduce, dense
    # slots a vectorized reduce — compare plans on the weighted scale
    # the DP itself optimizes.
    skew_gain = single_weighted / max(class_weighted, 1.0)
    why.update(
        nnz=nnz,
        class_work_slots=class_work,
        class_weighted_work=class_weighted,
        single_ell_weighted_work=single_weighted,
        skew_gain=skew_gain,
        work_budget=FUSED_ELL_WORK_BUDGET * 2 * nnz,
        residual=residual,
        width_budget=FUSED_MAX_WIDTH_BYTES,
        class_plans=plans,
    )
    if class_work > FUSED_ELL_WORK_BUDGET * 2 * nnz:
        why["reason"] = "degree-class padding exceeds the work budget"
        return "xla", why
    if width > FUSED_MAX_WIDTH_BYTES:
        why["reason"] = (
            "wide message rows: the reference gather/scatter already "
            "vectorizes; class-table row traffic multiplies with width"
        )
        return "xla", why
    why["reason"] = (
        "degree-class dense reduces beat the serialized scatter "
        + ("(skewed degrees: per-class widths keep hubs dense)"
           if skew_gain >= 1.4
           else "(bounded class padding)")
    )
    return "pallas_fused", why


class Engine:
    """The single entry point for hypergraph execution.

    >>> eng = Engine()                     # local, auto representation
    >>> res = eng.run(pagerank_spec(hg))
    >>> res.value, res.backend, res.decision

    >>> eng = Engine(mesh=mesh, backend="auto")   # distributed, plan auto
    >>> res = eng.run(label_propagation_spec(hg))

    An ``Engine`` is cheap to construct and stateless apart from its
    config / plan / mesh; algorithms' thin wrappers accept ``engine=`` so
    callers opt any call site into any design point without new APIs.
    """

    def __init__(
        self,
        plan=None,
        mesh=None,
        config: ExecutionConfig | None = None,
        exec_cache_size: int = 32,
        disk_cache=None,
        tracer=None,
        metrics=None,
        fault_injector=None,
        **overrides: Any,
    ):
        cfg = config if config is not None else ExecutionConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        self.plan = plan
        self.mesh = mesh
        self.config = cfg
        # Auto-built plans, keyed by hypergraph identity: repeated
        # run()/resolve() on the same hypergraph must not re-run the
        # full strategy sweep.  [(hg, n_parts, strategy, plan, why)]
        self._plan_cache: list = []
        # Fused-delivery layouts, keyed the same way: the dst-sort +
        # ELL/CSR precompute is paid once per structure.  [(hg, layouts)]
        self._delivery_cache: list = []
        # Compile-once serve-many state: the LRU of shape-bucketed
        # executables behind Engine.compile / CompiledAlgorithm (keyed
        # by repro.core.serving.signature), plus the observability
        # counters cache_stats() reports.
        self.exec_cache_size = int(exec_cache_size)
        self._exec_cache: OrderedDict = OrderedDict()
        self._exec_meta: dict = {}
        self._cache_hits = 0
        self._cache_misses = 0
        self._cache_evictions = 0
        self._trace_count = 0
        # Optional persistent cross-process store (duck-typed:
        # ``repro.serve.cache.DiskExecutableCache``); when set, freshly
        # built executables are wrapped so their first use resolves
        # disk-deserialize vs AOT-compile-and-store.  Core never imports
        # the serve tier — the dependency points the other way.
        self.disk_cache = disk_cache
        # Observability (repro.obs): an optional span recorder
        # (duck-typed like disk_cache: anything with span/block) and
        # the unified metrics registry this Engine's executable-cache
        # counters surface through.  Span sites go through
        # ``maybe_span``: with no tracer attached they record into
        # ``default_tracer()`` while the JAX profiler records and cost
        # one check otherwise; the registry provider is a weakref
        # pulled only at snapshot time.
        self.tracer = tracer
        self.metrics = metrics if metrics is not None else default_registry()
        self.metrics.register_provider(
            "engine.exec_cache", weak_provider(self.cache_stats)
        )
        # Fault injection (repro.faults): duck-typed like tracer /
        # disk_cache — instrumented paths branch on ``is None`` first,
        # so an absent injector costs nothing.  The attached disk cache
        # shares the injector (its read/write/deserialize points).
        self.fault_injector = fault_injector
        if fault_injector is not None and disk_cache is not None:
            disk_cache.fault_injector = fault_injector

    # -- resolution ---------------------------------------------------------

    def _resolve_representation(self, spec, cfg) -> tuple[str, dict]:
        if cfg.representation == "bipartite":
            return "bipartite", {"reason": "explicitly configured"}
        touches = getattr(spec, "touches_hyperedge_state", True)
        has_program = getattr(spec, "clique_program", None) is not None
        if cfg.representation == "clique":
            if touches:
                raise ValueError(
                    "representation='clique' is invalid for "
                    f"{getattr(spec, 'name', 'this spec')!r}: clique "
                    "expansion is only legal for algorithms that never "
                    "touch hyperedge state (MESH §IV-A1)"
                )
            if not has_program:
                raise ValueError(
                    "representation='clique' needs a clique_program on "
                    "the AlgorithmSpec"
                )
            if cfg.backend in ("replicated", "sharded"):
                raise ValueError(
                    "representation='clique' executes locally and cannot "
                    f"honor backend={cfg.backend!r}"
                )
            if cfg.max_iters is not None:
                raise ValueError(
                    "max_iters cannot override a clique_program (its "
                    "iteration count is baked into the spec); rebuild "
                    "the spec with the desired iters instead"
                )
            if self.mesh is not None:
                raise ValueError(
                    "representation='clique' executes locally and "
                    "cannot use the supplied mesh; drop the mesh or "
                    "use representation='bipartite'"
                )
            return "clique", {"reason": "explicitly configured"}
        # auto: explicit requests the clique path cannot honor pin
        # bipartite rather than being silently dropped.
        if cfg.backend in ("replicated", "sharded"):
            return "bipartite", {
                "reason": "distributed backend requested; clique "
                "executes locally"
            }
        if self.mesh is not None:
            return "bipartite", {
                "reason": "mesh supplied (distributed intent); clique "
                "executes locally"
            }
        if cfg.max_iters is not None and has_program and not touches:
            return "bipartite", {
                "reason": "max_iters override cannot apply to a "
                "clique_program"
            }
        return select_representation(
            spec, spec.hg0, edge_budget=cfg.clique_edge_budget
        )

    def _resolve_backend(self, spec, cfg) -> tuple[str, Any, dict, dict]:
        """Returns (backend, plan_or_None, backend_why, partition_why)."""
        if cfg.backend == "local":
            return "local", None, {"reason": "explicitly configured"}, {}

        if self.mesh is None:
            if cfg.backend in ("replicated", "sharded"):
                raise ValueError(
                    f"backend={cfg.backend!r} needs a mesh; construct "
                    "Engine(mesh=...) or use backend='local'"
                )
            return "local", None, {"reason": "no mesh available"}, {}

        n_parts = cfg.n_parts or int(self.mesh.shape[cfg.axis])
        plan = self.plan
        part_why: dict[str, Any] = {}
        if plan is None:
            plan, part_why = self._cached_plan(
                spec.hg0, n_parts, cfg.partition_strategy
            )
        else:
            part_why = {"strategy": plan.name,
                        "reason": "plan supplied by caller"}
        if plan.n_parts != n_parts:
            raise ValueError(
                f"plan has {plan.n_parts} partitions but mesh"
                f"[{cfg.axis!r}] = {n_parts}"
            )
        if cfg.backend in ("replicated", "sharded"):
            return (
                cfg.backend, plan,
                {"reason": "explicitly configured"}, part_why,
            )
        backend, why = select_backend(
            plan,
            spec.hg0.n_vertices,
            spec.hg0.n_hyperedges,
            replicated_bias=cfg.replicated_bias,
            v_state_bytes=state_width_bytes(
                spec.hg0.v_attr, spec.hg0.n_vertices
            ),
            he_state_bytes=state_width_bytes(
                spec.hg0.he_attr, spec.hg0.n_hyperedges
            ),
        )
        return backend, plan, why, part_why

    def _cached_plan(self, hg, n_parts: int, strategy: str):
        for c_hg, c_parts, c_strat, c_plan, c_why in self._plan_cache:
            if c_hg is hg and c_parts == n_parts and c_strat == strategy:
                return c_plan, c_why
        plan, why = select_partition(hg, n_parts, strategy)
        self._plan_cache.append((hg, n_parts, strategy, plan, why))
        del self._plan_cache[:-4]  # bound the strong refs we hold
        return plan, why

    def _resolve_delivery(self, spec, cfg) -> tuple[str, dict]:
        if cfg.delivery == "xla":
            return "xla", {"reason": "explicitly configured"}
        if cfg.delivery == "pallas_fused":
            reason = _non_monoid_reason(spec)
            if reason is not None:
                raise ValueError(
                    "delivery='pallas_fused' is invalid for "
                    f"{getattr(spec, 'name', 'this spec')!r}: {reason}; "
                    "the fused kernel serves monoid combiners only"
                )
            if spec.hg0.nnz == 0:
                raise ValueError(
                    "delivery='pallas_fused' needs a non-empty incidence"
                )
            return "pallas_fused", {"reason": "explicitly configured"}
        return select_delivery(spec, spec.hg0)

    def _delivery_layouts(self, hg):
        """Both directions' fused layouts for one structure, cached by
        hypergraph identity (host-side dst-sort + ELL/CSR precompute)."""
        for c_hg, lay in self._delivery_cache:
            if c_hg is hg:
                return lay
        with maybe_span(
            self.tracer, "engine.layout_build", cat="compile",
            nnz=int(hg.nnz), n_vertices=int(hg.n_vertices),
            n_hyperedges=int(hg.n_hyperedges),
        ):
            lay = layout_pair(
                hg.src, hg.dst, hg.e_mask, hg.n_vertices, hg.n_hyperedges
            )
        self._delivery_cache.append((hg, lay))
        del self._delivery_cache[:-4]  # bound the strong refs we hold
        return lay

    # -- execution ----------------------------------------------------------

    def resolve(
        self, spec, **overrides: Any
    ) -> tuple[ExecutionConfig, Any, dict]:
        """Resolve every ``"auto"`` field for ``spec`` WITHOUT executing.

        Returns ``(resolved_config, plan_or_None, decision)`` — the exact
        design point ``run`` would execute, for dry-run inspection and
        cheap decision tests (no compilation happens here; partition
        construction does run when a plan must be built).
        """
        cfg = (
            dataclasses.replace(self.config, **overrides)
            if overrides
            else self.config
        )
        decision: dict[str, Any] = {}
        representation, rep_why = self._resolve_representation(spec, cfg)
        decision["representation"] = rep_why
        max_iters = (
            cfg.max_iters if cfg.max_iters is not None else spec.max_iters
        )
        if representation == "clique":
            decision["backend"] = {
                "reason": "clique representation executes locally"
            }
            decision["delivery"] = {
                "reason": "clique constant-folding runs a host-side "
                "program; no superstep delivery exists"
            }
            resolved = dataclasses.replace(
                cfg,
                representation="clique",
                backend="local",
                max_iters=max_iters,
                partition_strategy="none",
                delivery="xla",
            )
            return resolved, None, decision

        backend, plan, backend_why, part_why = self._resolve_backend(
            spec, cfg
        )
        decision["backend"] = backend_why
        if part_why:
            decision["partition"] = part_why
        delivery, delivery_why = self._resolve_delivery(spec, cfg)
        decision["delivery"] = delivery_why
        resolved = dataclasses.replace(
            cfg,
            representation="bipartite",
            backend=backend,
            max_iters=max_iters,
            # "none" = this execution partitions nothing (local path);
            # a plan pins its strategy name.
            partition_strategy=(
                plan.name if plan is not None else "none"
            ),
            n_parts=plan.n_parts if plan is not None else cfg.n_parts,
            delivery=delivery,
        )
        return resolved, plan, decision

    def explain(self, spec, hg=None, **overrides: Any) -> dict:
        """The full decision tree for every ``auto`` axis — inputs,
        per-candidate predicted costs, winner, reason — WITHOUT
        executing (no compile, no device work).

        Built directly on ``resolve`` (the same call ``run`` and
        ``compile`` make), so the winners here are BY CONSTRUCTION the
        axes an actual execution of the same inputs resolves — asserted
        axis-for-axis in ``tests/test_obs.py``.  On top of the winner,
        every axis reports the costs of the candidates it did NOT pick,
        which ``resolve`` alone never surfaces for pinned or gated
        axes.

        ``hg``: explain against this hypergraph instead of the spec's
        own (applies ``spec.init`` like ``CompiledAlgorithm.run(hg)``).
        ``AnalyticsSpec`` routes to the batch axes (kernel /
        representation / backend / mode).  Returns::

            {"config": resolved ExecutionConfig,
             "decision": the resolve() decision dict,
             "axes": {axis: {"winner", "reason", "inputs",
                             "candidates": {name: {...costs}}}}}
        """
        if isinstance(spec, AnalyticsSpec):
            return self._explain_analytics(spec, **overrides)
        if hg is not None:
            hg = spec.init(hg) if spec.init is not None else hg
            spec = spec._replace(hg0=hg)
        resolved, plan, decision = self.resolve(spec, **overrides)
        cfg = (
            dataclasses.replace(self.config, **overrides)
            if overrides
            else self.config
        )
        hg0 = spec.hg0
        axes: dict[str, Any] = {}

        # -- representation: bipartite vs clique constant-folding ------
        touches = getattr(spec, "touches_hyperedge_state", True)
        has_program = getattr(spec, "clique_program", None) is not None
        eligible = (not touches) and has_program
        clique_edges = (
            int(2 * clique_expansion_size(hg0)) if eligible else None
        )
        axes["representation"] = {
            "winner": resolved.representation,
            "reason": decision["representation"].get("reason"),
            "inputs": {
                "touches_hyperedge_state": touches,
                "has_clique_program": has_program,
                "nnz": int(hg0.nnz),
            },
            "candidates": {
                "bipartite": {
                    "eligible": True,
                    "predicted_cost_edges": int(hg0.nnz),
                },
                "clique": {
                    "eligible": eligible,
                    "predicted_cost_edges": clique_edges,
                    "edge_budget": float(
                        cfg.clique_edge_budget * max(hg0.nnz, 1)
                    ),
                },
            },
        }

        # -- backend: local vs replicated vs sharded -------------------
        if plan is None:
            axes["backend"] = {
                "winner": resolved.backend,
                "reason": decision["backend"].get("reason"),
                "inputs": {"mesh": self.mesh is not None},
                "candidates": {
                    "local": {"eligible": True, "predicted_sync_bytes": 0.0},
                    "replicated": {"eligible": self.mesh is not None},
                    "sharded": {"eligible": self.mesh is not None},
                },
            }
        else:
            v_w = state_width_bytes(hg0.v_attr, hg0.n_vertices)
            he_w = state_width_bytes(hg0.he_attr, hg0.n_hyperedges)
            _, bwhy = select_backend(
                plan, hg0.n_vertices, hg0.n_hyperedges,
                replicated_bias=cfg.replicated_bias,
                v_state_bytes=v_w, he_state_bytes=he_w,
            )
            axes["backend"] = {
                "winner": resolved.backend,
                "reason": decision["backend"].get("reason"),
                "inputs": {
                    "n_parts": bwhy["n_parts"],
                    "v_state_bytes": v_w,
                    "he_state_bytes": he_w,
                    "replicated_bias": cfg.replicated_bias,
                },
                "candidates": {
                    "replicated": {
                        "eligible": True,
                        "predicted_sync_bytes": bwhy[
                            "full_replication_sync_bytes"
                        ],
                        "bias_adjusted_bytes": (
                            cfg.replicated_bias
                            * bwhy["full_replication_sync_bytes"]
                        ),
                    },
                    "sharded": {
                        "eligible": True,
                        "predicted_sync_bytes": bwhy["sharded_sync_bytes"],
                    },
                },
            }

        # -- partition: projected sync volume per strategy -------------
        if plan is None:
            axes["partition"] = {
                "winner": resolved.partition_strategy,
                "reason": "local execution partitions nothing",
                "inputs": {},
                "candidates": {},
            }
        else:
            part_why = decision.get("partition", {})
            costs = part_why.get("sync_bytes_by_strategy")
            if costs is None:
                # pinned strategy / caller-supplied plan: the sweep was
                # skipped — report the one plan actually in play.
                costs = {plan.name: float(plan.stats.sync_bytes_per_dim)}
            axes["partition"] = {
                "winner": resolved.partition_strategy,
                "reason": part_why.get("reason"),
                "inputs": {"n_parts": plan.n_parts},
                "candidates": {
                    nm: {
                        "eligible": True,
                        "predicted_sync_bytes_per_dim": float(c),
                    }
                    for nm, c in costs.items()
                },
            }

        # -- delivery: reference vs fused HBM-traffic model ------------
        # Run the cost model even when the axis was pinned or gated, so
        # the non-winning candidate's predicted cost is always visible.
        gate = _non_monoid_reason(spec)
        _, dwhy = select_delivery(spec, hg0)
        width = dwhy.get(
            "message_width_bytes", message_width_bytes(spec.initial_msg)
        )
        nnz = dwhy.get("nnz", int(hg0.nnz))
        ref_bytes = reference_traffic(
            nnz, hg0.n_hyperedges, width
        ) + reference_traffic(nnz, hg0.n_vertices, width)
        fused_cand: dict[str, Any] = {
            "eligible": gate is None and nnz > 0,
            "gate": gate,
        }
        for k in (
            "class_work_slots", "class_weighted_work",
            "single_ell_weighted_work", "skew_gain", "work_budget",
            "residual", "class_plans",
        ):
            if k in dwhy:
                fused_cand[k] = dwhy[k]
        if "class_work_slots" in dwhy:
            # Predicted fused HBM bytes from the class plan's work
            # slots — the same (width + id) per slot + output model
            # obs.calibrate prices a BUILT layout with.
            fused_cand["predicted_hbm_bytes"] = (
                dwhy["class_work_slots"] * (width + 4.0)
                + (hg0.n_vertices + hg0.n_hyperedges) * width
            )
        axes["delivery"] = {
            "winner": resolved.delivery,
            "reason": decision["delivery"].get("reason"),
            "inputs": {
                "nnz": nnz,
                "message_width_bytes": width,
                "width_budget": FUSED_MAX_WIDTH_BYTES,
                "min_nnz": FUSED_MIN_NNZ,
                "lowering": dwhy.get("lowering"),
            },
            "candidates": {
                "xla": {
                    "eligible": True,
                    "predicted_hbm_bytes": ref_bytes,
                },
                "pallas_fused": fused_cand,
            },
        }

        return {"config": resolved, "decision": decision, "axes": axes}

    def _explain_analytics(self, spec: "AnalyticsSpec", **overrides) -> dict:
        """``explain`` for the batch axes: intersect kernel,
        (dual) representation, backend, census mode."""
        from repro.motifs import (
            overlap_pairs_with_counts,
            select_intersect_kernel,
        )

        cfg = (
            dataclasses.replace(self.config, **overrides)
            if overrides
            else self.config
        )
        pairs, _ = overlap_pairs_with_counts(spec.hg)
        n_pairs = len(pairs)
        resolved, mode, decision = self._resolve_analytics(
            spec, cfg, n_pairs
        )
        _, kwhy = select_intersect_kernel(spec.hg)
        axes: dict[str, Any] = {
            "kernel": {
                "winner": resolved.intersect_kernel,
                "reason": decision["kernel"].get("reason"),
                "inputs": {
                    "n_hyperedges": int(spec.hg.n_hyperedges),
                    "n_vertices": int(spec.hg.n_vertices),
                },
                "candidates": {
                    "bitset": {
                        "eligible": (
                            kwhy["bitset_index_bytes"]
                            <= kwhy["bitset_budget_bytes"]
                        ),
                        "predicted_ops_per_pair": kwhy[
                            "bitset_words_per_pair"
                        ],
                        "index_bytes": kwhy["bitset_index_bytes"],
                    },
                    "merge": {
                        "eligible": True,
                        "predicted_ops_per_pair": kwhy[
                            "merge_ops_per_pair"
                        ],
                    },
                },
            },
            "representation": {
                "winner": resolved.representation,
                "reason": decision["representation"].get("reason"),
                "inputs": {"n_overlap_pairs": n_pairs},
                "candidates": {
                    "bipartite": {
                        "eligible": True,
                        "predicted_cost_edges": int(spec.hg.nnz),
                    },
                    "clique": {
                        "eligible": True,
                        "predicted_cost_edges": 2 * n_pairs,
                        "edge_budget": float(
                            cfg.clique_edge_budget * max(spec.hg.nnz, 1)
                        ),
                    },
                },
            },
            "backend": {
                "winner": resolved.backend,
                "reason": decision["backend"].get("reason"),
                "inputs": {"mesh": self.mesh is not None},
                "candidates": {
                    "local": {"eligible": True},
                    "sharded": {"eligible": self.mesh is not None},
                },
            },
        }
        if mode is not None:
            axes["mode"] = {
                "winner": mode,
                "reason": decision.get("mode", {}).get("reason"),
                "inputs": {
                    "n_overlap_pairs": n_pairs,
                    "exact_pair_budget": spec.exact_pair_budget,
                },
                "candidates": {
                    "exact": {
                        "eligible": spec.hg.n_hyperedges < (1 << 21),
                        "predicted_pairs": n_pairs,
                    },
                    "sample": {
                        "eligible": True,
                        "predicted_pairs": int(spec.n_samples),
                    },
                },
            }
        return {
            "config": resolved,
            "decision": decision,
            "mode": mode,
            "axes": axes,
        }

    def run(self, spec, **overrides: Any) -> Result:
        """Execute an ``AlgorithmSpec`` at the configured design point.

        ``overrides`` are per-call ``ExecutionConfig`` replacements
        (e.g. ``engine.run(spec, max_iters=8)``).

        Spans: ``engine.run`` (resolve to value ready) over
        ``engine.resolve``, ``engine.layout_build``, ``engine.dispatch``
        (JAX's ``jax.trace`` / ``jax.lower`` / ``jax.compile`` inside)
        and ``engine.device_wait``.
        """
        with maybe_span(self.tracer, "engine.run", cat="execute",
                        algorithm=getattr(spec, "name", "anonymous")):
            return self._run(spec, overrides)

    def _run(self, spec, overrides: dict) -> Result:
        with maybe_span(self.tracer, "engine.resolve", cat="resolve"):
            resolved, plan, decision = self.resolve(spec, **overrides)

        if resolved.representation == "clique":
            t0 = time.perf_counter()
            graph = to_graph(spec.hg0)
            value = spec.clique_program(graph)
            decision = {**decision, "measured": {
                "wall_s": time.perf_counter() - t0,
            }}
            return Result(
                value=value,
                config=resolved,
                representation="clique",
                backend="local",
                decision=decision,
            )

        if resolved.backend == "local":
            fn = compute_jit if resolved.jit else compute
            delivery = (
                self._delivery_layouts(spec.hg0)
                if resolved.delivery == "pallas_fused"
                else None
            )
            t0 = time.perf_counter()
            with maybe_span(self.tracer, "engine.dispatch", cat="execute"):
                if resolved.checkpoint_every is not None:
                    from repro.faults.checkpoint import checkpointed_compute

                    out = checkpointed_compute(
                        spec.hg0,
                        resolved.max_iters,
                        spec.initial_msg,
                        spec.v_program,
                        spec.he_program,
                        every=resolved.checkpoint_every,
                        ckpt_dir=resolved.checkpoint_dir,
                        return_stats=resolved.collect_stats,
                        delivery=delivery,
                        jit=resolved.jit,
                        tracer=self.tracer,
                        metrics=self.metrics,
                        fault_injector=self.fault_injector,
                    )
                else:
                    out = fn(
                        spec.hg0,
                        max_iters=resolved.max_iters,
                        initial_msg=spec.initial_msg,
                        v_program=spec.v_program,
                        he_program=spec.he_program,
                        return_stats=resolved.collect_stats,
                        delivery=delivery,
                    )
            t1 = time.perf_counter()
            with maybe_span(self.tracer, "engine.device_wait",
                            cat="execute"):
                jax.block_until_ready(out)
            t2 = time.perf_counter()
            stats = None
            if resolved.collect_stats:
                out, stats = out
            decision = {**decision, "measured": self._measured(
                spec, resolved, t0, t1, t2, stats, delivery
            )}
            return Result(
                value=spec.extract(out),
                config=resolved,
                representation="bipartite",
                backend="local",
                superstep_stats=stats,
                decision=decision,
            )

        from repro.core.distributed import distributed_compute

        t0 = time.perf_counter()
        with maybe_span(self.tracer, "engine.dispatch", cat="execute"):
            if resolved.checkpoint_every is not None:
                from repro.faults.checkpoint import (
                    checkpointed_distributed_compute,
                )

                out = checkpointed_distributed_compute(
                    spec.hg0,
                    plan,
                    self.mesh,
                    resolved.max_iters,
                    spec.initial_msg,
                    spec.v_program,
                    spec.he_program,
                    every=resolved.checkpoint_every,
                    ckpt_dir=resolved.checkpoint_dir,
                    axis=resolved.axis,
                    backend=resolved.backend,
                    delivery=resolved.delivery,
                    return_stats=resolved.collect_stats,
                    tracer=self.tracer,
                    metrics=self.metrics,
                    fault_injector=self.fault_injector,
                )
            else:
                out = distributed_compute(
                    spec.hg0,
                    plan,
                    self.mesh,
                    max_iters=resolved.max_iters,
                    initial_msg=spec.initial_msg,
                    v_program=spec.v_program,
                    he_program=spec.he_program,
                    axis=resolved.axis,
                    backend=resolved.backend,
                    return_stats=resolved.collect_stats,
                    delivery=resolved.delivery,
                )
        t1 = time.perf_counter()
        with maybe_span(self.tracer, "engine.device_wait", cat="execute"):
            jax.block_until_ready(out)
        t2 = time.perf_counter()
        stats = None
        if resolved.collect_stats:
            out, stats = out
        # No measured delivery bytes here: the distributed builders own
        # their per-shard layouts inside shard_map.
        decision = {**decision, "measured": self._measured(
            spec, resolved, t0, t1, t2, stats, None
        )}
        return Result(
            value=spec.extract(out),
            config=resolved,
            representation="bipartite",
            backend=resolved.backend,
            partition=plan.name,
            partition_stats=plan.stats,
            superstep_stats=stats,
            decision=decision,
        )

    @staticmethod
    def _measured(spec, resolved, t0, t1, t2, stats, delivery) -> dict:
        """The measured counterpart of the predicted ``decision``: wall
        and device time, executed supersteps (when stats were
        collected), and actual per-class delivery bytes for a built
        fused layout — what ``obs.calibrate`` compares against the
        cost models' predictions."""
        measured: dict[str, Any] = {
            "wall_s": t2 - t0,
            "dispatch_s": t1 - t0,
            "device_wait_s": t2 - t1,
            "max_iters": resolved.max_iters,
        }
        if stats is not None:
            measured["supersteps"] = executed_supersteps(
                stats, resolved.max_iters
            )
        if delivery is not None:
            measured["delivery"] = delivery_traffic_pair(
                delivery, message_width_bytes(spec.initial_msg)
            )
        return measured

    # -- compile-once serve-many --------------------------------------------

    def compile(self, spec, **overrides: Any):
        """Resolve the design point ONCE and return a ``CompiledAlgorithm``.

        The serve-many half of the facade: the returned handle's
        ``run(hg)`` executes with zero retracing for any hypergraph in
        the same shape bucket (sizes padded to bounded power-of-two
        buckets; executables cached in this Engine's LRU), and
        ``run_batch(queries)`` vmaps over the spec's query axis
        (``AlgorithmSpec.bind_query``) so one compile serves B requests.

        >>> compiled = engine.compile(shortest_paths_spec(hg, 0))
        >>> compiled.run_batch(np.arange(8))      # 8 sources, 1 compile
        >>> engine.cache_stats()                   # hits/misses/traces

        Compiled execution is always jitted and always bipartite (clique
        constant-folding produces a host-side program with nothing to
        cache); ``overrides`` are per-compile ``ExecutionConfig``
        replacements, as for ``run``.
        """
        from repro.core.serving import CompiledAlgorithm

        if isinstance(spec, AnalyticsSpec):
            raise TypeError(
                "Engine.compile serves iterative AlgorithmSpecs; batch "
                "analytics runs one-shot through Engine.analyze/submit"
            )
        probe = (
            dataclasses.replace(self.config, **overrides)
            if overrides
            else self.config
        )
        if probe.representation == "clique":
            raise ValueError(
                "Engine.compile serves the bipartite representation only: "
                "the clique path runs a host-side clique_program with no "
                "executable to cache; use Engine.run for one-shot clique "
                "execution"
            )
        overrides = {**overrides, "representation": "bipartite"}
        resolved, plan, decision = self.resolve(spec, **overrides)
        return CompiledAlgorithm(
            engine=self,
            spec=spec,
            config=resolved,
            decision=decision,
            _plan0=plan,
        )

    def submit(self, spec, **overrides: Any):
        """THE unified entry point: dispatch on spec type.

        ``AlgorithmSpec`` -> iterative superstep execution (``run``),
        ``AnalyticsSpec`` -> batch analytics (``analyze``).  ``run`` and
        ``analyze`` remain as thin, typed sugar over this dispatch.
        """
        if isinstance(spec, AnalyticsSpec):
            return self.analyze(spec, **overrides)
        from repro.algorithms.spec import AlgorithmSpec

        if isinstance(spec, AlgorithmSpec):
            return self.run(spec, **overrides)
        raise TypeError(
            "Engine.submit takes an AlgorithmSpec or AnalyticsSpec, got "
            f"{type(spec).__name__}"
        )

    def cache_stats(self) -> dict:
        """Executable-cache observability: benchmarks assert amortization.

        ``traces`` counts actual executable tracings (a retrace with a
        warm cache is a bug the serving tests assert against);
        ``hits``/``misses`` count ``CompiledAlgorithm`` lookups in this
        Engine's LRU; ``evictions`` counts LRU capacity drops (an
        eviction storm on a serving fleet means the bucket set outgrew
        ``exec_cache_size``).  ``entry_shapes`` describes each live
        entry's bucket (algorithm, padded dims, batch bucket, design
        point) so an operator can see WHAT the cache holds, not just how
        much; ``sources`` counts live entries by origin (``aot`` |
        ``disk``); ``disk`` mirrors the attached persistent store's counters
        (``None`` without one).
        """
        sources = Counter(getattr(e, "source", None)
                          for e in self._exec_cache.values())
        sources.pop(None, None)
        return {
            "entries": len(self._exec_cache),
            "capacity": self.exec_cache_size,
            "hits": self._cache_hits,
            "misses": self._cache_misses,
            "evictions": self._cache_evictions,
            "traces": self._trace_count,
            "sources": dict(sources),
            "entry_shapes": [
                dict(meta) for meta in self._exec_meta.values()
            ],
            "disk": (
                self.disk_cache.stats()
                if self.disk_cache is not None
                else None
            ),
        }

    def _note_trace(self) -> None:
        """Side-effecting trace probe: runs only while jax traces an
        executable body, so the counter exposes real retraces."""
        self._trace_count += 1

    def _executable_for(self, key, build: Callable[[], Any], meta=None):
        """LRU lookup of a compiled executable by shape signature.

        ``meta``: a small human-readable bucket summary recorded per
        entry for ``cache_stats()["entry_shapes"]``."""
        cache = self._exec_cache
        if key in cache:
            cache.move_to_end(key)
            self._cache_hits += 1
            return cache[key]
        self._cache_misses += 1
        span_args = {
            k: v
            for k, v in (meta or {}).items()
            if isinstance(v, (str, int, float, bool))
        }
        with maybe_span(
            self.tracer, "engine.build_executable", cat="compile",
            **span_args,
        ):
            exe = build()
        if self.disk_cache is not None:
            exe = self.disk_cache.wrap(self, key, exe)
        else:
            exe = AotExecutable(exe)
        cache[key] = exe
        if meta is not None:
            self._exec_meta[key] = meta
        while len(cache) > self.exec_cache_size:
            evicted, _ = cache.popitem(last=False)
            self._exec_meta.pop(evicted, None)
            self._cache_evictions += 1
        return exe

    # -- batch analytics -----------------------------------------------------

    def _resolve_analytics(
        self, spec: "AnalyticsSpec", cfg: ExecutionConfig, n_pairs: int
    ) -> tuple[ExecutionConfig, str | None, dict]:
        """Resolve the batch design point given the overlap-pair count.

        Returns ``(resolved_config, mode, decision)``.  Same cost-model
        seam as ``resolve``: representation weighs the (dual) clique
        expansion against the incidence via ``clique_edge_budget``;
        the kernel axis is ``select_intersect_kernel``; the backend
        tiles pair blocks across the mesh when one is available.
        """
        from repro.motifs import select_intersect_kernel

        decision: dict[str, Any] = {}

        if cfg.intersect_kernel == "auto":
            kernel, kernel_why = select_intersect_kernel(spec.hg)
        else:
            kernel = cfg.intersect_kernel
            kernel_why = {"reason": "explicitly configured"}
        decision["kernel"] = kernel_why

        if cfg.representation == "auto":
            # The paper's §IV-A tradeoff, applied to the *dual*: clique
            # expansion of the dual materializes every pairwise
            # intersection; choose it only while the expansion stays
            # within the same edge budget the iterative path uses.
            dual_edges = 2 * n_pairs
            budget = cfg.clique_edge_budget * max(spec.hg.nnz, 1)
            representation = "clique" if dual_edges <= budget else "bipartite"
            decision["representation"] = {
                "dual_clique_edges": dual_edges,
                "bipartite_edges": int(spec.hg.nnz),
                "edge_budget": float(budget),
                "reason": (
                    "dual expansion within edge budget: materialize "
                    "pair intersections"
                    if representation == "clique"
                    else "dual expansion exceeds edge budget: derive "
                    "intersections from the incidence"
                ),
            }
        else:
            representation = cfg.representation
            decision["representation"] = {"reason": "explicitly configured"}

        if cfg.backend == "replicated":
            raise ValueError(
                "backend='replicated' does not apply to batch analytics "
                "(no replicated superstep state); use 'sharded' to tile "
                "pair blocks across the mesh, or 'local'"
            )
        if cfg.backend == "sharded" and self.mesh is None:
            raise ValueError(
                "backend='sharded' needs a mesh; construct "
                "Engine(mesh=...) or use backend='local'"
            )
        if cfg.backend in ("local", "sharded"):
            backend = cfg.backend
            decision["backend"] = {"reason": "explicitly configured"}
        elif self.mesh is not None:
            backend = "sharded"
            decision["backend"] = {
                "reason": "mesh available: tile hyperedge-pair blocks "
                "across it"
            }
        else:
            backend = "local"
            decision["backend"] = {"reason": "no mesh available"}

        mode: str | None = None
        if spec.task == "hmotif_census":
            enumerable = spec.hg.n_hyperedges < (1 << 21)
            if spec.mode != "auto":
                mode = spec.mode
                decision["mode"] = {"reason": "explicitly configured"}
            else:
                mode = (
                    "exact"
                    if enumerable and n_pairs <= spec.exact_pair_budget
                    else "sample"
                )
                decision["mode"] = {
                    "n_overlap_pairs": n_pairs,
                    "exact_pair_budget": spec.exact_pair_budget,
                    "reason": (
                        "overlap graph within exact budget"
                        if mode == "exact"
                        else "overlap graph too large: sample linked pairs"
                    ),
                }
            if mode == "exact" and not enumerable:
                raise ValueError(
                    "mode='exact' needs n_hyperedges < 2^21; use "
                    "mode='sample'"
                )

        resolved = dataclasses.replace(
            cfg,
            representation=representation,
            backend=backend,
            intersect_kernel=kernel,
            partition_strategy="none",
        )
        return resolved, mode, decision

    def resolve_analytics(
        self, spec: "AnalyticsSpec", **overrides: Any
    ) -> tuple[ExecutionConfig, str | None, dict]:
        """Resolve every ``"auto"`` analytics choice WITHOUT executing.

        Runs the host-side overlap-pair discovery (the quantity every
        cost term turns on) but no intersection kernels.
        """
        from repro.motifs import overlap_pairs_with_counts

        cfg = (
            dataclasses.replace(self.config, **overrides)
            if overrides
            else self.config
        )
        pairs, _ = overlap_pairs_with_counts(spec.hg)
        return self._resolve_analytics(spec, cfg, len(pairs))

    def analyze(self, spec: "AnalyticsSpec", **overrides: Any) -> "AnalyticsResult":
        """Execute a batch ``AnalyticsSpec`` at the configured design
        point — the batch-mode twin of ``run``.

        >>> res = Engine().analyze(AnalyticsSpec(hg))
        >>> res.value.counts, res.kernel, res.decision
        """
        from repro import motifs

        cfg = (
            dataclasses.replace(self.config, **overrides)
            if overrides
            else self.config
        )
        # Overlap-pair discovery is the O(sum deg^2) host-side
        # preprocessing step; skip it when nothing consumes it — an
        # explicit pair batch on a pinned bipartite representation
        # needs only the kernel.
        need_pairs = (
            spec.task == "hmotif_census"
            or spec.pairs is None
            or cfg.representation in ("auto", "clique")
        )
        pairs = n_shared = None
        if need_pairs:
            pairs, n_shared = motifs.overlap_pairs_with_counts(spec.hg)
        resolved, mode, decision = self._resolve_analytics(
            spec, cfg, len(pairs) if pairs is not None else 0
        )
        index = motifs.build_index(spec.hg, resolved.intersect_kernel)
        mesh = self.mesh if resolved.backend == "sharded" else None
        pair_sizes = (
            motifs.materialize_pair_sizes(spec.hg, pairs, n_shared)
            if resolved.representation == "clique"
            else None
        )

        if spec.task == "pair_intersections":
            if spec.pairs is not None:
                ea = np.asarray(spec.pairs[0], np.int64)
                eb = np.asarray(spec.pairs[1], np.int64)
            else:
                ea, eb = pairs[:, 0], pairs[:, 1]
            if pair_sizes is not None:
                e = np.int64(spec.hg.n_hyperedges)
                lo, hi = np.minimum(ea, eb), np.maximum(ea, eb)
                sizes = motifs.pair_sizes_lookup(pair_sizes, lo * e + hi)
                # The materialized table holds overlapping a < b pairs
                # only; |e ∩ e| = |e| must not fall through to 0.
                self_pair = ea == eb
                if self_pair.any():
                    sizes = np.where(
                        self_pair, index.cardinalities()[ea], sizes
                    )
            else:
                sizes = motifs.batch_intersections(
                    index, ea, eb, tile=spec.tile, mesh=mesh,
                    axis=resolved.axis,
                ).astype(np.int64)
            value: Any = (np.stack([ea, eb], axis=1), sizes)
        elif mode == "exact":
            value = motifs.exact_census(
                spec.hg, index=index, tile=spec.tile, mesh=mesh,
                axis=resolved.axis, pair_sizes=pair_sizes,
                og=motifs.build_overlap_graph(spec.hg, pairs),
            )
        else:
            value = motifs.sampled_census(
                spec.hg, spec.n_samples, seed=spec.seed,
                confidence=spec.confidence, index=index, tile=spec.tile,
                mesh=mesh, axis=resolved.axis,
                og=motifs.build_overlap_graph(spec.hg, pairs),
                pair_sizes=pair_sizes,
            )
        return AnalyticsResult(
            value=value,
            config=resolved,
            representation=resolved.representation,
            kernel=resolved.intersect_kernel,
            backend=resolved.backend,
            mode=mode,
            decision=decision,
        )
