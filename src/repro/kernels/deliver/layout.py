"""Dst-sorted degree-class (sliced-ELL) delivery layouts: the precompute
behind fused delivery.

The deliver/combine half-superstep is MESH's hot path.  Its reference
lowering (``repro.core.engine.deliver``) is gather -> mask -> segment
reduce, which materializes a ``[nnz, D]`` rows array in HBM and re-reads
it — roughly 3x the traffic the combine fundamentally needs.  The fused
path removes that intermediate by reorganizing the incidence ONCE, on the
host, into a destination-sorted layout.

Real hypergraphs are heavy-tailed (power-law degrees and cardinalities),
so a single ELL width cannot serve both a mega-hub and the long tail:
capped at ``k``, a hub spills almost all of its incidences into an
overflow scatter; sized for the hub, the tail drowns in padding.  The
layout here is therefore **degree-classed** (SELL-style): destinations
are partitioned into a few contiguous *degree classes*, each with its own
power-of-two ELL width:

* ``plan_degree_classes`` picks 1–``MAX_CLASSES`` class boundaries from
  the live-degree histogram by dynamic programming over candidate
  power-of-two widths, minimizing dense padding plus (weighted) residual
  spill.  The plan is a pure function of the histogram, so the Engine's
  cost model and this builder can never disagree.
* Destinations are permuted class-major (ascending id within a class);
  ``inv_perm`` maps destination id -> its slot in the concatenated
  per-class outputs, so results assemble with one gather — never a
  scatter.  Zero-degree destinations (bucket padding!) own no slot at
  all: they point at an appended identity row.
* Per class, a dense slot-major ``[k_c, rows_c]`` ELL id table: the
  lowering (``repro.kernels.deliver.xla``) gathers it and reduces over
  the slot axis.
* Incidences past a hub's class width land in a small dst-sorted COO
  residual and take one sorted segment reduce.

Statically-dead incidences (``e_mask == 0`` — partition padding, bucket
padding) are dropped from every packing at build time; only dynamic
``active`` vectors cost work at runtime.

Everything here is host-side numpy on concrete arrays; the products are
device arrays registered as one pytree (``DeliveryLayout``) so layouts
flow through jit / scan / vmap / shard_map as ordinary operands.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import default_registry

# Single-ELL planning (the legacy PR-4 packing, kept as the cost model's
# skew baseline): grow k (powers of two) until the COO remainder holds at
# most this fraction of the incidences, then stop at the cap.
ELL_REMAINDER_FRACTION = 0.25
ELL_K_CAP = 64
# Degree-class planning: at most this many classes, widths capped here
# (a power-of-two width at most doubles a row's slots, and the DP only
# widens a class when few rows pay for it, so the cap merely bounds the
# absolute width of a single mega-hub row before it spills).
MAX_CLASSES = 4
CLASS_K_CAP = 65536
# One residual incidence costs a lane of the sorted segment reduce —
# serialized scatter work — vs a dense vectorized ELL slot.  Measured
# on the bench_delivery regimes (CPU XLA): the dense axis reduce moves
# ~125M slots/s vs ~11M lanes/s through the sorted scatter, so the DP
# prices a residual lane at ~12 dense slots and keeps hubs dense.
RESIDUAL_WEIGHT = 12.0
# A class's rows and the residual's lanes are allocated in multiples of
# these, not in powers of two: the scan gathers every allocated lane,
# so padding lanes cost as much as live ones.
_PAD_FLOOR = 8
_ROW_FLOOR = 8


def _pow2_at_least(n: int, floor: int = 1) -> int:
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


def _padded_len(n: int, floor: int) -> int:
    """What a layout allocates for ``n`` real class rows (``floor`` is
    ``_ROW_FLOOR``) or residual lanes (``_PAD_FLOOR``): the smallest
    multiple of ``floor`` at or above ``n``, and at least ``floor``.
    ``ClassPlan.built_work`` and ``build_delivery_layout`` both pad
    through it, so the cost model prices what the layout executes."""
    f = int(floor)
    return max(-(-int(n) // f), 1) * f


def _live_degrees(dst, e_mask, n_dst: int):
    """``(live mask, live degree per destination)`` of an incidence."""
    dst = np.asarray(dst, np.int64)
    live = (
        np.asarray(e_mask) != 0
        if e_mask is not None
        else np.ones(len(dst), bool)
    )
    deg = np.bincount(dst[live], minlength=max(n_dst, 1))[:n_dst]
    return live, deg


def _width_stats(degrees: np.ndarray, k_cap: int):
    """Per-candidate-width overflow stats from ONE cumulative histogram.

    Candidate widths are ``1, 2, 4, ..., min(pow2 >= max_degree, k_cap)``.
    Returns ``(widths, cnt_le, overflow, n_pos)`` where ``cnt_le[j]`` is
    the number of destinations with ``1 <= degree <= widths[j]`` and
    ``overflow[j] = sum(max(degree - widths[j], 0))`` — O(max_degree)
    total instead of rescanning the full degree array per width.
    """
    degrees = np.asarray(degrees, np.int64)
    pos = degrees[degrees > 0]
    n_pos = int(pos.size)
    if n_pos == 0:
        return (np.array([1], np.int64), np.zeros(1, np.int64),
                np.zeros(1, np.int64), 0)
    max_deg = int(pos.max())
    total = int(pos.sum())
    top = min(_pow2_at_least(max_deg), int(k_cap))
    widths = np.asarray(
        [1 << e for e in range(top.bit_length())], np.int64
    )
    hist = np.bincount(pos)
    cnt_cum = np.cumsum(hist)
    deg_cum = np.cumsum(hist * np.arange(hist.size, dtype=np.int64))
    idx = np.minimum(widths, max_deg)
    cnt_le = cnt_cum[idx]
    sum_le = deg_cum[idx]
    overflow = (total - sum_le) - widths * (n_pos - cnt_le)
    return widths, cnt_le, overflow, n_pos


def plan_ell_width(degrees: np.ndarray, nnz: int) -> tuple[int, int]:
    """Pick a SINGLE ELL width ``k`` for a degree distribution.

    Returns ``(k, remainder)``: the smallest power-of-two ``k`` (capped
    at ``ELL_K_CAP``) whose overflow — incidences past each
    destination's first ``k`` — is at most ``ELL_REMAINDER_FRACTION`` of
    ``nnz``, plus the overflow count at that ``k``.  This is the PR-4
    single-class packing, kept as the skew baseline the degree-class
    cost model compares against.  Vectorized over one cumulative degree
    histogram (``_width_stats``); deterministic in the histogram, so the
    Engine's cost model and the layout builder can never disagree.
    """
    if nnz <= 0 or np.asarray(degrees).size == 0:
        return 1, 0
    widths, _, overflow, n_pos = _width_stats(degrees, ELL_K_CAP)
    if n_pos == 0:
        return 1, 0
    ok = overflow <= ELL_REMAINDER_FRACTION * nnz
    ok[-1] = True  # the cap (or a width >= max degree) always stops
    j = int(np.argmax(ok))
    return int(widths[j]), int(overflow[j])


@dataclasses.dataclass(frozen=True)
class ClassPlan:
    """A degree-class partition: the data-dependent half of a layout.

    ``widths`` are ascending power-of-two ELL widths, one per class; a
    destination with live degree ``g > 0`` belongs to the first class
    with ``g <= k_c`` (hubs past the last width stay in the last class,
    spilling ``g - k_C`` incidences to the residual).  ``rows`` counts
    the destinations per class under the histogram the plan was built
    from; ``residual`` their total spill.  Pure data — hashable,
    comparable, derived deterministically from the degree histogram.
    """

    widths: tuple[int, ...]
    rows: tuple[int, ...]
    residual: int

    @property
    def n_classes(self) -> int:
        return len(self.widths)

    @property
    def padded_rows(self) -> int:
        """Dense ELL slots the plan commits to (pre row-padding)."""
        return int(sum(r * k for r, k in zip(self.rows, self.widths)))

    @property
    def work(self) -> int:
        """Total lanes the XLA lowering touches: dense slots + residual."""
        return self.padded_rows + int(self.residual)

    @property
    def built_rows(self) -> tuple:
        """Per-class row counts as ``build_delivery_layout`` pads them
        (``_padded_len``) — what the tables really allocate."""
        return tuple(_padded_len(r, _ROW_FLOOR) for r in self.rows)

    @property
    def built_work(self) -> int:
        """Dense slots + residual lanes at the BUILDER's padding — the
        work a layout built from this plan actually executes, its
        ``ell_slots + rem_len`` (the cost model budgets on this, not
        the tighter DP-count ``work``)."""
        dense = sum(r * k for r, k in zip(self.built_rows, self.widths))
        return int(dense) + _padded_len(self.residual, _PAD_FLOOR)

    @property
    def weighted_work(self) -> float:
        """The DP's objective: dense slots plus residual at
        ``RESIDUAL_WEIGHT`` (a residual lane pays the serialized sorted
        segment reduce; a dense slot is vectorized).  The cost model's
        skew detector compares plans on this scale."""
        return self.padded_rows + RESIDUAL_WEIGHT * self.residual


def plan_degree_classes(
    degrees: np.ndarray,
    nnz: int,
    *,
    max_classes: int = MAX_CLASSES,
    k_cap: int = CLASS_K_CAP,
) -> ClassPlan:
    """Partition a live-degree histogram into 1–``max_classes`` degree
    classes with power-of-two ELL widths.

    Dynamic programming over the candidate widths of ``_width_stats``:
    a class covering degrees ``(k_prev, k]`` costs ``count * k`` dense
    slots; hubs past the last width cost its width dense plus
    ``RESIDUAL_WEIGHT`` per spilled incidence (residual lanes take the
    serialized sorted segment reduce).  With <= 13 candidate widths and
    <= 4 classes the sweep is trivially cheap, and — like
    ``plan_ell_width`` — a pure function of the histogram.
    """
    degrees = np.asarray(degrees)
    if nnz <= 0 or degrees.size == 0 or not (degrees > 0).any():
        return ClassPlan(widths=(1,), rows=(0,), residual=0)
    widths, cnt_le, overflow, n_pos = _width_stats(degrees, k_cap)
    nw = len(widths)
    max_classes = max(int(max_classes), 1)

    INF = float("inf")
    # best[c][j]: min dense slots covering all degrees <= widths[j] with
    # c classes, the last of width widths[j].
    best = np.full((max_classes + 1, nw), INF)
    prev = np.full((max_classes + 1, nw), -1, np.int64)
    best[1, :] = cnt_le * widths
    for c in range(2, max_classes + 1):
        for j in range(c - 1, nw):
            cand = best[c - 1, :j] + (cnt_le[j] - cnt_le[:j]) * widths[j]
            jp = int(np.argmin(cand))
            if cand[jp] < best[c, j]:
                best[c, j] = cand[jp]
                prev[c, j] = jp
    # Close each (c, j) plan: hubs past widths[j] pay widths[j] dense
    # slots each plus weighted residual spill.
    hub_rows = n_pos - cnt_le
    close = hub_rows * widths + RESIDUAL_WEIGHT * overflow
    best_cost, best_c, best_j = INF, 1, nw - 1
    for c in range(1, max_classes + 1):
        for j in range(nw):
            cost = best[c, j] + close[j]
            if cost < best_cost:  # ties: fewer classes, smaller widths
                best_cost, best_c, best_j = cost, c, j
    chain = [best_j]
    for c in range(best_c, 1, -1):
        chain.append(int(prev[c, chain[-1]]))
    chain.reverse()
    plan_widths = [int(widths[j]) for j in chain]

    # Row counts per class; drop classes that own no destinations (the
    # DP can only produce them as no-cost ties).
    bounds = [0] + [cnt_le[j] for j in chain]
    rows = [int(bounds[i + 1] - bounds[i]) for i in range(len(chain))]
    rows[-1] += int(hub_rows[chain[-1]])
    keep = [i for i, r in enumerate(rows) if r > 0]
    if not keep:
        keep = [len(rows) - 1]
    return ClassPlan(
        widths=tuple(plan_widths[i] for i in keep),
        rows=tuple(rows[i] for i in keep),
        residual=int(overflow[chain[-1]]),
    )


def delivery_structure(
    src, dst, e_mask, n_vertices: int, n_hyperedges: int
) -> dict:
    """The structural inputs of the Engine's delivery cost model: the
    live ``nnz`` and, when any incidence is live, both directions'
    degree-class plans (``fwd`` combines by ``dst``, ``bwd`` by
    ``src``) summed three ways — dense slots at the builder's row
    padding plus residual (``class_work_slots``), the same on the DP's
    residual-weighted scale, and the single-ELL baseline on that scale
    — with the larger residual.  A pure function of the incidence, so
    the Engine computes it once per structure."""
    src, dst = np.asarray(src), np.asarray(dst)
    if e_mask is not None:
        live = np.asarray(e_mask) != 0
        src, dst = src[live], dst[live]
    nnz = int(src.shape[0])
    if nnz == 0:
        return {"nnz": 0}
    class_work = class_weighted = single_weighted = 0.0
    residual = 0
    plans = {}
    for side, n_dst, ids in (
        ("fwd", n_hyperedges, dst), ("bwd", n_vertices, src)
    ):
        deg = np.bincount(ids, minlength=n_dst)
        plan = plan_degree_classes(deg, nnz)
        k1, rem1 = plan_ell_width(deg, nnz)
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
    return {
        "nnz": nnz,
        "class_work_slots": class_work,
        "class_weighted_work": class_weighted,
        "single_ell_weighted_work": single_weighted,
        "residual": residual,
        "class_plans": plans,
    }


def classify_degrees(degrees: np.ndarray, widths) -> np.ndarray:
    """Class index per destination under a plan's widths (-1 for
    zero-degree destinations, which own no slot).  Shared by the layout
    builder and the shard harmonizer so assignments always agree."""
    degrees = np.asarray(degrees, np.int64)
    w = np.asarray(widths, np.int64)
    cls = np.minimum(
        np.searchsorted(w, degrees, side="left"), len(w) - 1
    )
    return np.where(degrees > 0, cls, -1).astype(np.int64)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeliveryLayout:
    """One direction's precomputed fused-delivery layout (degree-classed).

    Array children (device arrays; leading dims may gain a partition dim
    under the distributed executor):

      class_ell[c]: ``[k_c, rows_c]`` int32, one per degree class — the
        class's destinations' first-``k_c`` sender ids, slot-major:
        column ``r`` is the destination in slot ``r`` (identity row
        ``n_src`` in empty slots).
      inv_perm: ``[n_dst]`` int32 — destination id -> slot in the
        concatenated per-class outputs; zero-degree destinations point
        at the appended identity slot ``sum(class_rows)``.  Assembly is
        one gather — no scatter.
      rem_src / rem_dst: ``[rem_pad]`` int32 — hub incidences past the
        last class width, in dst-sorted COO (padding lanes: identity
        sender -> last destination).  Statically skipped when
        ``rem_nnz == 0``.

    Static aux: ``n_src``, ``n_dst``, ``nnz`` (real incidences),
    ``rem_nnz`` (real residual), ``class_widths``, ``class_rows``
    (padded row counts — the array dims).
    """

    class_ell: tuple
    inv_perm: jnp.ndarray
    rem_src: jnp.ndarray
    rem_dst: jnp.ndarray
    n_src: int
    n_dst: int
    nnz: int
    rem_nnz: int
    class_widths: tuple
    class_rows: tuple

    def tree_flatten(self):
        children = (self.class_ell, self.inv_perm, self.rem_src, self.rem_dst)
        aux = (
            self.n_src, self.n_dst, self.nnz, self.rem_nnz,
            self.class_widths, self.class_rows,
        )
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def n_classes(self) -> int:
        return len(self.class_widths)

    @property
    def n_slots(self) -> int:
        """Concatenated per-class output rows (the identity slot sits
        one past the end)."""
        return int(sum(self.class_rows))

    @property
    def k(self) -> int:
        """Widest class width (hub class)."""
        return int(max(self.class_widths))

    @property
    def ell_slots(self) -> int:
        """Total dense ELL slots across classes (padding-work metric)."""
        return int(
            sum(r * k for r, k in zip(self.class_rows, self.class_widths))
        )

    @property
    def rem_len(self) -> int:
        return int(self.rem_src.shape[-1])

    def shape_signature(self) -> tuple:
        """Hashable shape tuple for the serving executable cache key —
        covers every class-plan-dependent dim, so a degree-regime shift
        within a shape bucket legitimately recompiles."""
        return (
            tuple(tuple(a.shape) for a in self.class_ell),
            tuple(self.inv_perm.shape),
            tuple(self.rem_src.shape),
            self.class_widths, self.class_rows, self.rem_nnz,
            self.n_src, self.n_dst, self.nnz,
        )


def build_delivery_layout(
    src,
    dst,
    e_mask,
    n_src: int,
    n_dst: int,
    *,
    plan: ClassPlan | None = None,
    class_rows_pad: tuple | None = None,
    rem_pad_to: int | None = None,
) -> DeliveryLayout:
    """Build one direction's degree-class layout — the ELL tables,
    ``inv_perm`` and residual — from a concrete incidence list.

    ``src``/``dst``/``e_mask`` are host-transferable arrays (``e_mask``
    may be None).  ``plan=None`` lets ``plan_degree_classes`` pick the
    class boundaries and widths from the live-degree histogram; the
    distributed builder passes a shared plan so shard layouts agree.
    By default a class's rows and the residual are padded to a multiple
    of 8 (``_padded_len``).  ``class_rows_pad`` / ``rem_pad_to`` force
    the per-class row counts and residual pad (each >= what the
    incidence needs): per-shard layouts stack into one shard_map
    operand, and serving's layouts keep bucketed shapes
    (``layout_pair(bucketed=True)``).
    """
    t_build0 = time.perf_counter()
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    nnz = len(src)
    live, live_deg = _live_degrees(dst, e_mask, n_dst)
    n_live = int(live.sum())
    if plan is None:
        plan = plan_degree_classes(live_deg, n_live)
    widths = np.asarray(plan.widths, np.int64)
    n_classes = len(widths)

    cls = classify_degrees(live_deg, widths)
    rows_real = np.bincount(
        cls[cls >= 0], minlength=n_classes
    )[:n_classes]
    if class_rows_pad is None:
        rows_pad = tuple(_padded_len(r, _ROW_FLOOR) for r in rows_real)
    else:
        rows_pad = tuple(int(r) for r in class_rows_pad)
        assert all(p >= r for p, r in zip(rows_pad, rows_real)), (
            rows_pad, rows_real,
        )

    # Slot assignment: class-major, ascending destination id within a
    # class; zero-degree destinations share the appended identity slot.
    base = np.concatenate([[0], np.cumsum(rows_pad)]).astype(np.int64)
    n_slots = int(base[-1])
    inv_perm = np.full(n_dst, n_slots, np.int64)
    for c in range(n_classes):
        members = np.flatnonzero(cls == c)
        inv_perm[members] = base[c] + np.arange(len(members))

    # One dst-sorted scan feeds every packing.  Stability keeps each
    # segment's rows in original incidence order, so reduction order —
    # and therefore bitwise results for order-sensitive exact sums —
    # matches the reference scatter path.
    order = np.argsort(dst, kind="stable")
    s_src = src[order].astype(np.int32)
    s_dst = dst[order]
    s_live = live[order]
    if nnz:
        counts = np.bincount(s_dst, minlength=max(n_dst, 1))
        seg_starts = np.zeros(counts.size + 1, np.int64)
        np.cumsum(counts, out=seg_starts[1:])
        live_cum = np.cumsum(s_live)
        live_before = np.concatenate([[0], live_cum])[seg_starts[s_dst]]
        live_rank = live_cum - 1 - live_before  # valid on live lanes
        lane_cls = cls[s_dst]
        lane_k = widths[np.maximum(lane_cls, 0)]
        in_ell = s_live & (live_rank < lane_k)
        over = s_live & (live_rank >= lane_k)
    else:
        lane_cls = np.zeros(0, np.int64)
        live_rank = np.zeros(0, np.int64)
        in_ell = over = np.zeros(0, bool)

    # Per-class slot-major ELL tables.
    class_ell = []
    for c in range(n_classes):
        tbl = np.full((int(widths[c]), rows_pad[c]), n_src, np.int32)
        sel = in_ell & (lane_cls == c)
        if sel.any():
            r_local = inv_perm[s_dst[sel]] - base[c]
            tbl[live_rank[sel], r_local] = s_src[sel]
        class_ell.append(tbl)

    # Residual COO (dst-sorted: the scan order preserves it).  Padding
    # lanes keep rem_dst sorted by pointing at the last destination with
    # an identity sender (contributes nothing).
    rem_s = s_src[over]
    rem_d = s_dst[over]
    rem_nnz = len(rem_s)
    if rem_pad_to is not None:
        assert rem_pad_to >= rem_nnz, (rem_pad_to, rem_nnz)
        rem_pad = int(rem_pad_to)
    else:
        rem_pad = _padded_len(rem_nnz, _PAD_FLOOR)
    rem_src = np.full(rem_pad, n_src, np.int32)
    rem_dst = np.full(rem_pad, max(n_dst - 1, 0), np.int32)
    rem_src[:rem_nnz] = rem_s
    rem_dst[:rem_nnz] = rem_d

    layout = DeliveryLayout(
        class_ell=tuple(jnp.asarray(t) for t in class_ell),
        inv_perm=jnp.asarray(inv_perm, jnp.int32),
        rem_src=jnp.asarray(rem_src),
        rem_dst=jnp.asarray(rem_dst),
        n_src=int(n_src),
        n_dst=int(n_dst),
        nnz=int(nnz),
        rem_nnz=int(rem_nnz),
        class_widths=tuple(int(w) for w in widths),
        class_rows=tuple(int(r) for r in rows_pad),
    )
    reg = default_registry()
    reg.counter("delivery.layouts_built").inc()
    reg.counter("delivery.ell_slots").inc(layout.ell_slots)
    reg.counter("delivery.residual_lanes").inc(layout.rem_len)
    reg.histogram("delivery.build_s").record(
        time.perf_counter() - t_build0
    )
    return layout


def _bucketed_pads(dst, e_mask, n_dst: int) -> dict:
    """Forcing arguments that pad each class's rows and the residual to
    powers of two, as ``repro.core.serving.bucket_dim`` pads the
    structure."""
    live, deg = _live_degrees(dst, e_mask, n_dst)
    plan = plan_degree_classes(deg, int(live.sum()))
    return {
        "plan": plan,
        "class_rows_pad": tuple(
            _pow2_at_least(max(r, 1), _ROW_FLOOR) for r in plan.rows
        ),
        "rem_pad_to": _pow2_at_least(max(plan.residual, 1), _PAD_FLOOR),
    }


def layout_pair(
    hg_src, hg_dst, e_mask, n_vertices: int, n_hyperedges: int, *,
    bucketed: bool = False, **kw
) -> tuple[DeliveryLayout, DeliveryLayout]:
    """Both half-superstep directions for one incidence list:
    vertex->hyperedge (combine by ``dst``) and hyperedge->vertex
    (combine by ``src``); ``kw`` goes to ``build_delivery_layout``.
    ``bucketed`` pads rows and residual to powers of two, so that
    hypergraphs of one serving bucket share layout shapes and so one
    executable (``CompiledAlgorithm``)."""
    fwd = build_delivery_layout(
        hg_src, hg_dst, e_mask, n_vertices, n_hyperedges, **kw,
        **(_bucketed_pads(hg_dst, e_mask, n_hyperedges) if bucketed
           else {}),
    )
    bwd = build_delivery_layout(
        hg_dst, hg_src, e_mask, n_hyperedges, n_vertices, **kw,
        **(_bucketed_pads(hg_src, e_mask, n_vertices) if bucketed
           else {}),
    )
    return fwd, bwd


def layout_span_args(layouts, live_nnz: int) -> dict:
    """What a job that delivered through ``layouts`` (a ``layout_pair``)
    records on its ``engine.run`` span: its ``live_nnz`` incidences, the
    ``delivery_lanes`` both directions' scans touch (dense ELL slots
    plus residual lanes, what ``delivery.ell_slots`` and
    ``delivery.residual_lanes`` add at build time) and the pair's device
    ``layout_bytes``."""
    return {
        "live_nnz": int(live_nnz),
        "delivery_lanes": sum(l.ell_slots + l.rem_len for l in layouts),
        "layout_bytes": sum(
            int(a.nbytes) for a in jax.tree.leaves(tuple(layouts))
        ),
    }


Pytree = Any
