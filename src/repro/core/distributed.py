"""Distributed MESH executor: supersteps under ``jax.shard_map``.

Two backends (DESIGN.md §6), both consuming a ``PartitionPlan``'s padded
edge shards over the mesh's ``data`` axis:

* ``replicated`` — entity state replicated on every partition; each
  partition reduces its local edges into a full-size message buffer and a
  single ``psum``/``pmax``/``pmin`` merges.  One collective of O(N·d) per
  half-superstep; best for small states (apache/dblp regime).

* ``sharded`` — entity state sharded by id range over the ``data`` axis;
  per half-superstep: ``all_gather`` of the sender side's outgoing
  messages, local gather + segment-reduce, then ``psum_scatter`` of the
  destination buffer (sum monoid) or ``pmax/pmin`` + slice.  State memory
  scales 1/P; required for the friendster/orkut regime.

Feature-dim (``model`` axis) sharding composes transparently: every array
here is sharded on its *trailing* feature dim by pjit outside the
shard_map, since gathers/reduces act only on the leading entity dim.

Correctness contract (tested): for any plan and any monoid program pair,
both backends equal the single-device engine bit-for-bit in fp32.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.api import Program, constant_initial_msg
from repro.core.engine import _as_out, batch_halting_scan
from repro.core.hypergraph import HyperGraph
from repro.partition.base import PartitionPlan

Pytree = Any
_shard_map = partial(jax.shard_map, check_vma=False)


def _pad_to(n: int, parts: int) -> int:
    return -(-n // parts) * parts


@dataclasses.dataclass(frozen=True)
class DistContext:
    """Static facts the distributed superstep needs.

    Real (unpadded) entity counts are NOT static: they flow through the
    supersteps as traced int32 scalars so one compiled executable serves
    every hypergraph in a shape bucket (activity stats and halting mask
    padding slots dynamically).
    """

    axis: str                  # mesh axis name carrying edge partitions
    n_parts: int
    nv_pad: int
    ne_pad: int


def _local_combine(program: Program, rows, dst_ids, num_dst, live):
    """Per-partition combine of message rows into a full-size buffer."""
    if program.reducer is not None:
        raise NotImplementedError(
            "custom (Seq) reducers are local-engine only; distribute the "
            "sum-decomposed form instead (see pagerank_entropy)."
        )

    def one(leaf):
        monoid = program.monoid_for(leaf)
        if live is not None:
            ident = monoid.identity(leaf.dtype)
            shape = (live.shape[0],) + (1,) * (leaf.ndim - 1)
            leaf = jnp.where(live.reshape(shape), leaf, ident)
        return monoid.segment(leaf, dst_ids, num_segments=num_dst)

    return jax.tree.map(one, rows)


def _cross_combine(program: Program, partials, axis: str):
    """Merge per-partition partial aggregates across the mesh axis with the
    same monoid the local combine used."""

    def one(leaf):
        monoid = program.monoid_for(leaf)
        if monoid.name in ("sum", "or"):
            return jax.lax.psum(leaf, axis)
        if monoid.name == "max":
            return jax.lax.pmax(leaf, axis)
        if monoid.name == "min":
            return jax.lax.pmin(leaf, axis)
        raise NotImplementedError(monoid.name)

    return jax.tree.map(one, partials)


def _cross_combine_scatter(program: Program, partials, axis: str,
                           n_parts: int):
    """Merge partials and keep only this partition's id-range block — a
    true reduce-scatter for every monoid.

    sum -> ``psum_scatter`` (XLA's fused reduce-scatter); max/min have
    no fused collective, so reduce-scatter is built from its definition:
    ``all_to_all`` transposes the per-partition blocks (each device
    receives every device's copy of *its* block — O(n) bytes moved, vs
    the O(n log P) all-reduce a pmax/pmin+slice pays) and a local
    ``max``/``min`` over the received stack finishes the reduction.
    """

    def one(leaf):
        monoid = program.monoid_for(leaf)
        if monoid.name in ("sum", "or"):
            return jax.lax.psum_scatter(
                leaf, axis, scatter_dimension=0, tiled=True
            )
        if monoid.name not in ("max", "min"):
            raise NotImplementedError(monoid.name)
        block = leaf.shape[0] // n_parts
        chunks = leaf.reshape((n_parts, block) + leaf.shape[1:])
        swapped = jax.lax.all_to_all(
            chunks, axis, split_axis=0, concat_axis=0
        )
        reduce = jnp.max if monoid.name == "max" else jnp.min
        return reduce(swapped.reshape((n_parts, block) + leaf.shape[1:]),
                      axis=0)

    return jax.tree.map(one, partials)


def _deliver_local(program, out_msg_full, active_full, src, dst, mask,
                   num_dst, layout=None):
    """gather -> transform -> mask -> local segment combine, over one
    partition's padded edge shard.

    ``layout``: optional per-shard ``DeliveryLayout`` — routes the
    monoid path through fused delivery (degree-class ELL tables over
    THIS shard's edges; the shard mask is folded into the layout), same
    as the local engine's ``delivery='pallas_fused'`` design point.
    """
    if (layout is not None and program.reducer is None
            and program.edge_transform is None):
        from repro.kernels.deliver import fused_deliver

        return fused_deliver(out_msg_full, active_full, layout, program)
    rows = jax.tree.map(
        lambda leaf: jnp.take(leaf, src, axis=0), out_msg_full
    )
    if program.edge_transform is not None:
        rows = program.edge_transform(rows, None)
    live = mask.astype(bool)
    if active_full is not None:
        live = live & jnp.take(active_full, src, axis=0)
    return _local_combine(program, rows, dst, num_dst, live)


# --------------------------------------------------------------------------
# replicated-state backend
# --------------------------------------------------------------------------

def _superstep_replicated(ctx: DistContext, hg_meta, programs, degs,
                          step, v_attr, he_attr, msg_to_v,
                          src, dst, mask, nv_real, ne_real,
                          delivery=(None, None)):
    v_program, he_program = programs
    v_deg, he_card = degs
    fwd_layout, bwd_layout = delivery
    v_ids = jnp.arange(ctx.nv_pad, dtype=jnp.int32)
    he_ids = jnp.arange(ctx.ne_pad, dtype=jnp.int32)

    v_out = _as_out(
        v_program.procedure(step, v_ids, v_attr, msg_to_v, v_deg),
        v_attr, ctx.nv_pad,
    )
    partial_he = _deliver_local(
        v_program, v_out.msg, v_out.active, src, dst, mask, ctx.ne_pad,
        layout=fwd_layout,
    )
    msg_to_he = _cross_combine(v_program, partial_he, ctx.axis)

    he_out = _as_out(
        he_program.procedure(step + 1, he_ids, he_attr, msg_to_he, he_card),
        he_attr, ctx.ne_pad,
    )
    partial_v = _deliver_local(
        he_program, he_out.msg, he_out.active, dst, src, mask, ctx.nv_pad,
        layout=bwd_layout,
    )
    msg_to_v_next = _cross_combine(he_program, partial_v, ctx.axis)

    def count(active, n_pad, n_real):
        # Activity over *real* entities only: padding slots must not
        # leak into the observable stats (or the halting decision).
        # ``n_real`` may be traced, so mask instead of slicing.
        live = jnp.arange(n_pad, dtype=jnp.int32) < n_real
        if active is not None:
            live = live & active
        return live.sum().astype(jnp.int32)

    stats = (
        count(v_out.active, ctx.nv_pad, nv_real),
        count(he_out.active, ctx.ne_pad, ne_real),
    )
    return v_out.attr, he_out.attr, msg_to_v_next, stats


# --------------------------------------------------------------------------
# sharded-state backend
# --------------------------------------------------------------------------

def _superstep_sharded(ctx: DistContext, hg_meta, programs, degs,
                       step, v_attr_sh, he_attr_sh, msg_to_v_sh,
                       src, dst, mask, nv_real, ne_real,
                       delivery=(None, None)):
    """State arrays carry only this partition's id-range block
    (``[n/P, ...]``); ids are globalized with the axis index."""
    v_program, he_program = programs
    v_deg_sh, he_card_sh = degs
    fwd_layout, bwd_layout = delivery
    p = jax.lax.axis_index(ctx.axis)
    v_block = ctx.nv_pad // ctx.n_parts
    he_block = ctx.ne_pad // ctx.n_parts
    v_ids = p * v_block + jnp.arange(v_block, dtype=jnp.int32)
    he_ids = p * he_block + jnp.arange(he_block, dtype=jnp.int32)

    v_out = _as_out(
        v_program.procedure(step, v_ids, v_attr_sh, msg_to_v_sh, v_deg_sh),
        v_attr_sh, v_block,
    )
    # sender messages (and activity) must be visible to every partition
    # whose edges reference them -> all_gather over the partition axis.
    v_msg_full = jax.tree.map(
        lambda leaf: jax.lax.all_gather(
            leaf, ctx.axis, axis=0, tiled=True
        ),
        v_out.msg,
    )
    v_act_full = (
        jax.lax.all_gather(v_out.active, ctx.axis, axis=0, tiled=True)
        if v_out.active is not None
        else None
    )
    partial_he = _deliver_local(
        v_program, v_msg_full, v_act_full, src, dst, mask, ctx.ne_pad,
        layout=fwd_layout,
    )
    msg_to_he_sh = _cross_combine_scatter(
        v_program, partial_he, ctx.axis, ctx.n_parts
    )

    he_out = _as_out(
        he_program.procedure(
            step + 1, he_ids, he_attr_sh, msg_to_he_sh, he_card_sh
        ),
        he_attr_sh, he_block,
    )
    he_msg_full = jax.tree.map(
        lambda leaf: jax.lax.all_gather(
            leaf, ctx.axis, axis=0, tiled=True
        ),
        he_out.msg,
    )
    he_act_full = (
        jax.lax.all_gather(he_out.active, ctx.axis, axis=0, tiled=True)
        if he_out.active is not None
        else None
    )
    partial_v = _deliver_local(
        he_program, he_msg_full, he_act_full, dst, src, mask, ctx.nv_pad,
        layout=bwd_layout,
    )
    msg_to_v_next_sh = _cross_combine_scatter(
        he_program, partial_v, ctx.axis, ctx.n_parts
    )

    def count(active, ids, n_real):
        # Real-entity activity, globalized with one psum so every
        # partition carries the same (replicated) stat.
        real = ids < n_real
        local = (
            real if active is None else (active & real)
        ).sum().astype(jnp.int32)
        return jax.lax.psum(local, ctx.axis)

    stats = (
        count(v_out.active, v_ids, nv_real),
        count(he_out.active, he_ids, ne_real),
    )
    return v_out.attr, he_out.attr, msg_to_v_next_sh, stats


# --------------------------------------------------------------------------
# fused-delivery shard layouts
# --------------------------------------------------------------------------

def _stack_layouts(layouts):
    """Stack per-partition ``DeliveryLayout``s along a new leading axis
    (the shard_map operand form).  Callers guarantee uniform shapes
    (one shared class plan, harmonized per-class row and remainder
    pads); the residual-skip count (``rem_nnz``) takes the max so one
    program serves every shard."""
    rem_nnz = max(l.rem_nnz for l in layouts)
    return jax.tree.map(
        lambda *a: jnp.stack(a),
        *(dataclasses.replace(l, rem_nnz=rem_nnz) for l in layouts),
    )


def build_shard_delivery(shard_src, shard_dst, shard_mask,
                         nv_pad: int, ne_pad: int):
    """Per-shard fused-delivery layouts for both half-superstep
    directions, over a plan's ``[n_parts, shard_len]`` edge shards.

    Each shard gets its own dst-sorted degree-class layout over the
    *full* padded entity range (both backends combine into full-size
    buffers before their cross-partition collective).  Class boundaries
    and widths are planned ONCE per direction from the merged per-shard
    live-degree histograms — every (shard, destination) pair is a row
    the plan must place, so the DP sees the true row population — and
    the remaining data-dependent shapes (per-class row counts,
    remainder pad) are harmonized to per-class maxima across shards.
    Cheap bincounts, no throwaway layout build; the resulting layouts
    stack into one shard_map operand.
    """
    from repro.kernels.deliver import (
        build_delivery_layout,
        classify_degrees,
        plan_degree_classes,
    )
    from repro.kernels.deliver.layout import (
        _PAD_FLOOR, _ROW_FLOOR, _pow2_at_least,
    )

    shard_src = np.asarray(shard_src)
    shard_dst = np.asarray(shard_dst)
    shard_mask = np.asarray(shard_mask)
    n_parts = shard_src.shape[0]

    def direction(srcs, dsts, n_src, n_dst):
        live = shard_mask != 0
        degs = [
            np.bincount(dsts[p][live[p]], minlength=max(n_dst, 1))[:n_dst]
            for p in range(n_parts)
        ]
        plan = plan_degree_classes(
            np.concatenate(degs), int(live.sum())
        )
        widths = np.asarray(plan.widths, np.int64)
        n_classes = len(widths)
        rows_max = np.zeros(n_classes, np.int64)
        rem_max = 0
        for deg in degs:
            cls = classify_degrees(deg, widths)
            pos = cls >= 0
            rows = np.bincount(cls[pos], minlength=n_classes)
            np.maximum(rows_max, rows, out=rows_max)
            spill = int(
                np.maximum(deg[pos] - widths[cls[pos]], 0).sum()
            )
            rem_max = max(rem_max, spill)
        class_rows_pad = tuple(
            _pow2_at_least(max(int(r), 1), _ROW_FLOOR) for r in rows_max
        )
        rem_pad = _pow2_at_least(max(rem_max, 1), _PAD_FLOOR)
        final = [
            build_delivery_layout(
                srcs[p], dsts[p], shard_mask[p], n_src, n_dst,
                plan=plan,
                class_rows_pad=class_rows_pad,
                rem_pad_to=rem_pad,
            )
            for p in range(n_parts)
        ]
        return _stack_layouts(final)

    return (
        direction(shard_src, shard_dst, nv_pad, ne_pad),
        direction(shard_dst, shard_src, ne_pad, nv_pad),
    )


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def _pad_leading(x: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    pad = n_pad - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0
    )


def build_distributed_runner(
    mesh: Mesh,
    ctx: DistContext,
    v_program: Program,
    he_program: Program,
    max_iters: int,
    backend: str = "replicated",
    batch: int | None = None,
    resumable: bool = False,
):
    """Build the ``shard_map``-wrapped superstep scan for one design point.

    Returns a traceable callable
    ``(v_attr, he_attr, msg0, v_deg, he_card, shard_src, shard_dst,
    shard_mask, nv_real, ne_real, delivery) -> (v_attr, he_attr,
    v_trace, he_trace)`` over bucket-padded full-size arrays
    (``[nv_pad, ...]`` state, ``[n_parts, shard_len]`` edge shards).
    ``nv_real`` / ``ne_real`` are traced int32 scalars, so the same
    runner — and therefore the same compiled executable — serves every
    hypergraph whose padded shapes match (the ``Engine.compile`` serving
    path); ``distributed_compute`` is the eager single-shot wrapper.

    ``delivery``: ``None`` (reference path) or the
    ``build_shard_delivery`` pair of stacked per-shard layouts — the
    fused delivery design point, identical on both backends (each
    partition's local combine runs fused over its own edge block).

    ``batch``: when set, state/msg operands carry a leading query batch
    dim ``[batch, ...]`` and the runner is BATCH-AWARE (mirroring the
    local ``compute_batch``): the per-iteration superstep vmaps over the
    query axis INSIDE the ``shard_map`` scan, so halting stays a real
    ``lax.cond`` on ``all(halted)`` across the batch — a
    skewed-convergence batch stops at its slowest query instead of
    paying ``max_iters``.  Returns ``(v_attr_b, he_attr_b, v_trace
    [max_iters, batch], he_trace, supersteps_executed)``; per-query
    results and stats are bitwise those of the unbatched runner (halted
    queries freeze by selection — exactly what the vmapped
    ``cond``-as-``select`` would have computed — and report zero
    activity).
    """
    if backend == "replicated":
        state_spec = P()
        superstep = _superstep_replicated
    elif backend == "sharded":
        state_spec = P(ctx.axis)
        superstep = _superstep_sharded
    else:
        raise ValueError(backend)
    deg_spec = state_spec
    # Batched state shards the ENTITY dim, which sits after the query dim.
    batch_state_spec = (
        state_spec if backend == "replicated" else P(None, ctx.axis)
    )
    edge_spec = P(ctx.axis)  # leading dim = n_parts, one row per partition
    programs = (v_program, he_program)

    def _body(superstep, degs_local, src, dst, mask, nv_real, ne_real,
              delivery_local):
        # The per-iteration scan body — ONE definition shared by the
        # single-shot and resumable runners, so a chunked (checkpointed)
        # distributed run agrees bitwise with an uninterrupted one.
        def body(carry, _):
            step, v_a, he_a, msg, halted = carry

            def go(args):
                step, v_a, he_a, msg = args
                nv_a, nhe_a, nmsg, stats = superstep(
                    ctx, None, programs, degs_local,
                    step, v_a, he_a, msg, src, dst, mask,
                    nv_real, ne_real, delivery_local,
                )
                v_act, he_act = stats
                return nv_a, nhe_a, nmsg, (v_act + he_act) == 0, stats

            def skip(args):
                _, v_a, he_a, msg = args
                zero = jnp.asarray(0, jnp.int32)
                return v_a, he_a, msg, jnp.asarray(True), (zero, zero)

            nv_a, nhe_a, nmsg, halted2, stats = jax.lax.cond(
                halted, skip, go, (step, v_a, he_a, msg)
            )
            return (step + 2, nv_a, nhe_a, nmsg, halted | halted2), stats

        return body

    def run(v_attr, he_attr, msg0, v_deg, he_card, src, dst, mask,
            nv_real, ne_real, delivery):
        # shard_map gives each device its [1, shard_len] edge row; squeeze.
        src, dst, mask = src[0], dst[0], mask[0]
        delivery_local = (
            jax.tree.map(lambda a: a[0], delivery)
            if delivery is not None
            else (None, None)
        )
        body = _body(superstep, (v_deg, he_card), src, dst, mask,
                     nv_real, ne_real, delivery_local)
        init = (
            jnp.asarray(0, jnp.int32), v_attr, he_attr, msg0,
            jnp.asarray(False),
        )
        (_, v_a, he_a, _, _), (v_trace, he_trace) = jax.lax.scan(
            body, init, None, length=max_iters
        )
        return v_a, he_a, v_trace, he_trace

    def run_resumable(v_attr, he_attr, msg, halted, step0, v_deg, he_card,
                      src, dst, mask, nv_real, ne_real, delivery):
        # The checkpoint/resume seam: scan carry in, scan carry out.
        src, dst, mask = src[0], dst[0], mask[0]
        delivery_local = (
            jax.tree.map(lambda a: a[0], delivery)
            if delivery is not None
            else (None, None)
        )
        body = _body(superstep, (v_deg, he_card), src, dst, mask,
                     nv_real, ne_real, delivery_local)
        init = (step0, v_attr, he_attr, msg, halted)
        (step, v_a, he_a, msg, halted), (v_trace, he_trace) = jax.lax.scan(
            body, init, None, length=max_iters
        )
        return v_a, he_a, msg, halted, step, v_trace, he_trace

    def run_batch(v_attr_b, he_attr_b, msg0_b, v_deg, he_card, src, dst,
                  mask, nv_real, ne_real, delivery):
        src, dst, mask = src[0], dst[0], mask[0]
        delivery_local = (
            jax.tree.map(lambda a: a[0], delivery)
            if delivery is not None
            else (None, None)
        )
        degs_local = (v_deg, he_card)

        def one_step(step, v_a, he_a, msg):
            # The superstep reads only shared structure besides the
            # per-query state; collectives batch elementwise under vmap.
            return superstep(
                ctx, None, programs, degs_local,
                step, v_a, he_a, msg, src, dst, mask,
                nv_real, ne_real, delivery_local,
            )

        batched_step = jax.vmap(one_step, in_axes=(None, 0, 0, 0))

        # The halting scaffold (freeze-by-selection, real cond on
        # all(halted), executed counter) is the LOCAL backend's —
        # shared so the executed counts agree by construction.
        v_a, he_a, (v_tr, he_tr), executed = batch_halting_scan(
            batched_step, v_attr_b, he_attr_b, msg0_b, batch, max_iters
        )
        return v_a, he_a, v_tr, he_tr, executed

    # Varying-axes checking off (``_shard_map``): the halt flag is
    # partition-uniform by construction, which the checker cannot
    # prove.  The activity traces are likewise partition-uniform
    # (psum'd / computed on the replicated full-size buffers), so their
    # out_spec is P().
    if resumable:
        if batch is not None:
            raise ValueError("resumable runner is unbatched")
        return _shard_map(
            run_resumable,
            mesh=mesh,
            in_specs=(
                state_spec, state_spec, state_spec, P(), P(),
                deg_spec, deg_spec,
                edge_spec, edge_spec, edge_spec, P(), P(),
                edge_spec,
            ),
            out_specs=(
                state_spec, state_spec, state_spec, P(), P(), P(), P(),
            ),
        )
    if batch is None:
        return _shard_map(
            run,
            mesh=mesh,
            in_specs=(
                state_spec, state_spec, state_spec, deg_spec, deg_spec,
                edge_spec, edge_spec, edge_spec, P(), P(),
                edge_spec,  # delivery layouts: tree prefix, [n_parts, ...]
            ),
            out_specs=(state_spec, state_spec, P(), P()),
        )
    return _shard_map(
        run_batch,
        mesh=mesh,
        in_specs=(
            batch_state_spec, batch_state_spec, batch_state_spec,
            deg_spec, deg_spec,
            edge_spec, edge_spec, edge_spec, P(), P(),
            edge_spec,
        ),
        out_specs=(
            batch_state_spec, batch_state_spec, P(), P(), P(),
        ),
    )


def distributed_compute(
    hg: HyperGraph,
    plan: PartitionPlan,
    mesh: Mesh,
    max_iters: int,
    initial_msg: Pytree,
    v_program: Program,
    he_program: Program,
    *,
    axis: str = "data",
    backend: str = "replicated",
    feature_axis: str | None = None,
    return_stats: bool = False,
    delivery: str = "xla",
) -> HyperGraph:
    """Run ``compute`` distributed over ``mesh[axis]`` per ``plan``.

    ``feature_axis``: optional mesh axis to shard trailing feature dims
    over (2-D hypergraph parallelism; DESIGN.md §6).

    ``return_stats``: also return per-superstep ``(v_active, he_active)``
    activity traces (int32, length ``max_iters``) — the scan trace
    threaded out through ``shard_map`` as replicated outputs, matching
    the local engine's ``return_stats`` bit for bit.

    ``delivery``: ``'xla'`` (reference) or ``'pallas_fused'`` — the
    resolved ``ExecutionConfig.delivery`` axis; fused builds per-shard
    dst-sorted layouts from the plan's edge shards.
    """
    n_parts = plan.n_parts
    assert mesh.shape[axis] == n_parts, (
        f"plan has {n_parts} partitions but mesh[{axis!r}] = "
        f"{mesh.shape[axis]}"
    )
    nv_pad = _pad_to(hg.n_vertices, n_parts)
    ne_pad = _pad_to(hg.n_hyperedges, n_parts)
    ctx = DistContext(
        axis=axis, n_parts=n_parts, nv_pad=nv_pad, ne_pad=ne_pad,
    )

    v_deg = _pad_leading(hg.degrees(), nv_pad)
    he_card = _pad_leading(hg.cardinalities(), ne_pad)
    v_attr = jax.tree.map(lambda x: _pad_leading(x, nv_pad), hg.v_attr)
    he_attr = jax.tree.map(lambda x: _pad_leading(x, ne_pad), hg.he_attr)
    msg0 = constant_initial_msg(initial_msg, nv_pad)

    shard_src = jnp.asarray(plan.shard_src)
    shard_dst = jnp.asarray(plan.shard_dst)
    shard_mask = jnp.asarray(plan.shard_mask)
    layouts = None
    if delivery == "pallas_fused":
        layouts = build_shard_delivery(
            plan.shard_src, plan.shard_dst, plan.shard_mask,
            nv_pad, ne_pad,
        )

    mapped = build_distributed_runner(
        mesh, ctx, v_program, he_program, max_iters, backend=backend
    )
    with mesh:
        v_out, he_out, v_trace, he_trace = jax.jit(mapped)(
            v_attr, he_attr, msg0, v_deg, he_card,
            shard_src, shard_dst, shard_mask,
            jnp.asarray(hg.n_vertices, jnp.int32),
            jnp.asarray(hg.n_hyperedges, jnp.int32),
            layouts,
        )
    unpad_v = jax.tree.map(lambda x: x[: hg.n_vertices], v_out)
    unpad_he = jax.tree.map(lambda x: x[: hg.n_hyperedges], he_out)
    out = hg.with_attrs(v_attr=unpad_v, he_attr=unpad_he)
    if return_stats:
        return out, (v_trace, he_trace)
    return out


def distributed_initial_state(hg: HyperGraph, plan: PartitionPlan,
                              initial_msg: Pytree) -> dict:
    """The explicit (partition-padded) scan carry ``distributed_compute``
    starts from, as a checkpoint-serializable pytree — the distributed
    twin of ``engine.initial_superstep_state``."""
    n_parts = plan.n_parts
    nv_pad = _pad_to(hg.n_vertices, n_parts)
    ne_pad = _pad_to(hg.n_hyperedges, n_parts)
    return {
        "step": jnp.asarray(0, jnp.int32),
        "v_attr": jax.tree.map(
            lambda x: _pad_leading(x, nv_pad), hg.v_attr
        ),
        "he_attr": jax.tree.map(
            lambda x: _pad_leading(x, ne_pad), hg.he_attr
        ),
        "msg": constant_initial_msg(initial_msg, nv_pad),
        "halted": jnp.asarray(False),
    }


def distributed_compute_resumable(
    hg: HyperGraph,
    plan: PartitionPlan,
    mesh: Mesh,
    n_iters: int,
    state: dict,
    v_program: Program,
    he_program: Program,
    *,
    axis: str = "data",
    backend: str = "replicated",
    delivery: str = "xla",
):
    """Run ``n_iters`` superstep pairs from an explicit carry ``state``
    (see ``distributed_initial_state``); returns ``(state', trace)``.

    ``distributed_compute`` with the scan carry lifted to an argument —
    the distributed checkpoint/resume seam.  The per-iteration body is
    shared with the single-shot runner, so chunked runs compose bitwise
    into an uninterrupted run (same contract as the local engine's
    ``compute_resumable``)."""
    n_parts = plan.n_parts
    assert mesh.shape[axis] == n_parts
    nv_pad = _pad_to(hg.n_vertices, n_parts)
    ne_pad = _pad_to(hg.n_hyperedges, n_parts)
    ctx = DistContext(
        axis=axis, n_parts=n_parts, nv_pad=nv_pad, ne_pad=ne_pad,
    )
    v_deg = _pad_leading(hg.degrees(), nv_pad)
    he_card = _pad_leading(hg.cardinalities(), ne_pad)
    layouts = None
    if delivery == "pallas_fused":
        layouts = build_shard_delivery(
            plan.shard_src, plan.shard_dst, plan.shard_mask,
            nv_pad, ne_pad,
        )
    mapped = build_distributed_runner(
        mesh, ctx, v_program, he_program, n_iters, backend=backend,
        resumable=True,
    )
    with mesh:
        v_a, he_a, msg, halted, step, v_tr, he_tr = jax.jit(mapped)(
            state["v_attr"], state["he_attr"], state["msg"],
            state["halted"], state["step"],
            v_deg, he_card,
            jnp.asarray(plan.shard_src), jnp.asarray(plan.shard_dst),
            jnp.asarray(plan.shard_mask),
            jnp.asarray(hg.n_vertices, jnp.int32),
            jnp.asarray(hg.n_hyperedges, jnp.int32),
            layouts,
        )
    out = {
        "step": step, "v_attr": v_a, "he_attr": he_a,
        "msg": msg, "halted": halted,
    }
    return out, (v_tr, he_tr)
