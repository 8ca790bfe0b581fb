"""The MESH superstep engine (single-device reference executor).

``compute`` is the paper's ``HyperGraph.compute``: alternating vertex /
hyperedge supersteps, message delivery along the bipartite incidence with
combiner-merged messages, dynamic termination when every entity goes
inactive (SSSP) inside a static ``lax.scan`` (BSP barrier == one scan
iteration).

The distributed executor (``core.distributed``) reuses ``deliver`` /
``superstep_pair`` verbatim inside ``shard_map`` — the engine is written so
the only distributed delta is *where* the segment reduction's results get
combined (psum / psum_scatter instead of nothing).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.api import Program, ProcedureOut, constant_initial_msg
from repro.core.hypergraph import HyperGraph

Pytree = Any


def deliver(
    out_msg: Pytree,
    active: jnp.ndarray | None,
    src_ids: jnp.ndarray,
    dst_ids: jnp.ndarray,
    num_dst: int,
    program: Program,
    e_attr: Pytree = None,
    e_mask: jnp.ndarray | None = None,
    layout=None,
) -> Pytree:
    """Deliver broadcast messages along incidences and combine by
    destination with the *sender* program's MessageCombiner.

    The reference (``delivery='xla'``) data path is gather (``take``) ->
    optional per-incidence transform -> mask dead rows to the monoid
    identity -> segment-reduce by destination.  This is the entire data
    path of one half-superstep; everything else is pointwise.

    ``layout``: optional precomputed ``DeliveryLayout`` (the
    ``delivery='pallas_fused'`` design point) — routes the monoid fast
    path through ``repro.kernels.deliver`` (dst-sorted CSR; gather,
    mask and combine fused, no ``[nnz, D]`` intermediate).  Custom
    ``reducer``s and per-incidence ``edge_transform``s always take the
    reference path: they consume the materialized rows by contract.
    """
    if (layout is not None and program.reducer is None
            and program.edge_transform is None):
        from repro.kernels.deliver import fused_deliver

        return fused_deliver(out_msg, active, layout, program)

    rows = jax.tree.map(lambda leaf: jnp.take(leaf, src_ids, axis=0), out_msg)
    if program.edge_transform is not None:
        rows = program.edge_transform(rows, e_attr)

    live = None
    if active is not None:
        live = jnp.take(active, src_ids, axis=0)
    if e_mask is not None:
        em = e_mask.astype(bool)
        live = em if live is None else (live & em)

    if program.reducer is not None:
        return program.reducer(rows, dst_ids, num_dst, live)

    def combine_leaf(leaf: jnp.ndarray) -> jnp.ndarray:
        monoid = program.monoid_for(leaf)
        if live is not None:
            ident = monoid.identity(leaf.dtype)
            shape = (live.shape[0],) + (1,) * (leaf.ndim - 1)
            leaf = jnp.where(live.reshape(shape), leaf, ident)
        return monoid.segment(leaf, dst_ids, num_segments=num_dst)

    return jax.tree.map(combine_leaf, rows)


def _as_out(res, attr, n) -> ProcedureOut:
    """Normalize procedure output (allow returning (attr, msg) tuples)."""
    if isinstance(res, ProcedureOut):
        return res
    if isinstance(res, tuple) and len(res) == 2:
        return ProcedureOut(res[0], res[1], None)
    raise TypeError(
        "Procedure must return ProcedureOut or (attr, msg); got "
        f"{type(res)}"
    )


class SuperstepStats(NamedTuple):
    """Per-iteration activity counters (observability hook)."""

    v_active: jnp.ndarray  # [] int32
    he_active: jnp.ndarray  # [] int32


def superstep_pair(
    hg: HyperGraph,
    step: jnp.ndarray,
    v_attr: Pytree,
    he_attr: Pytree,
    msg_to_v: Pytree,
    v_program: Program,
    he_program: Program,
    v_deg: jnp.ndarray,
    he_card: jnp.ndarray,
    n_real: tuple | None = None,
    delivery: tuple | None = None,
):
    """One (vertex, hyperedge) pair of supersteps. Pure; jit/scan-safe.

    ``n_real``: optional ``(nv_real, ne_real)`` — ints or traced int32
    scalars.  When the hypergraph is padded to a shape bucket (the
    compile-once serving path), activity counts mask to the first
    ``n_real`` slots so padding entities never leak into the observable
    stats or the halting decision; traced scalars keep one executable
    serving every real size in the bucket.

    ``delivery``: optional ``(fwd_layout, bwd_layout)`` pair of
    ``DeliveryLayout``s (vertex->hyperedge, hyperedge->vertex) routing
    both half-supersteps through the fused delivery kernel.
    """
    fwd_layout, bwd_layout = delivery if delivery is not None else (None, None)
    v_ids = jnp.arange(hg.n_vertices, dtype=jnp.int32)
    he_ids = jnp.arange(hg.n_hyperedges, dtype=jnp.int32)

    # Named scopes label the four parts in the compiled ops' metadata
    # (a device trace's op names), and change nothing else.
    with jax.named_scope("procedure.vertex"):
        v_out = _as_out(
            v_program.procedure(step, v_ids, v_attr, msg_to_v, v_deg),
            v_attr,
            hg.n_vertices,
        )
    with jax.named_scope("deliver.v_to_he"):
        msg_to_he = deliver(
            v_out.msg, v_out.active, hg.src, hg.dst, hg.n_hyperedges,
            v_program, hg.e_attr, hg.e_mask, layout=fwd_layout,
        )
    with jax.named_scope("procedure.hyperedge"):
        he_out = _as_out(
            he_program.procedure(
                step + 1, he_ids, he_attr, msg_to_he, he_card
            ),
            he_attr,
            hg.n_hyperedges,
        )
    with jax.named_scope("deliver.he_to_v"):
        msg_to_v_next = deliver(
            he_out.msg, he_out.active, hg.dst, hg.src, hg.n_vertices,
            he_program, hg.e_attr, hg.e_mask, layout=bwd_layout,
        )

    def count(active, n, real):
        if real is None:
            if active is None:
                return jnp.asarray(n, jnp.int32)
            return active.sum().astype(jnp.int32)
        live = jnp.arange(n, dtype=jnp.int32) < real
        if active is not None:
            live = live & active
        return live.sum().astype(jnp.int32)

    nv_real, ne_real = n_real if n_real is not None else (None, None)
    stats = SuperstepStats(
        v_active=count(v_out.active, hg.n_vertices, nv_real),
        he_active=count(he_out.active, hg.n_hyperedges, ne_real),
    )
    return v_out.attr, he_out.attr, msg_to_v_next, stats


def _halting_body(hg, v_program, he_program, v_deg, he_card, n_real,
                  delivery):
    """The per-iteration scan body shared by ``compute`` and
    ``compute_resumable`` — ONE definition, so a chunked
    (checkpointed) run and an uninterrupted run execute the same
    per-iteration computation and agree bitwise by construction."""

    def body(carry, _):
        step, v_attr, he_attr, msg_to_v, halted = carry

        def run(args):
            step, v_attr, he_attr, msg_to_v = args
            nv_attr, nhe_attr, nmsg, stats = superstep_pair(
                hg, step, v_attr, he_attr, msg_to_v,
                v_program, he_program, v_deg, he_card, n_real, delivery,
            )
            now_halted = (stats.v_active + stats.he_active) == 0
            return (nv_attr, nhe_attr, nmsg, now_halted, stats)

        def skip(args):
            _, v_attr, he_attr, msg_to_v = args
            stats = SuperstepStats(
                v_active=jnp.asarray(0, jnp.int32),
                he_active=jnp.asarray(0, jnp.int32),
            )
            return (v_attr, he_attr, msg_to_v, jnp.asarray(True), stats)

        nv_attr, nhe_attr, nmsg, halted2, stats = jax.lax.cond(
            halted, skip, run, (step, v_attr, he_attr, msg_to_v)
        )
        return (
            step + 2, nv_attr, nhe_attr, nmsg, halted | halted2,
        ), (stats.v_active, stats.he_active)

    return body


def compute(
    hg: HyperGraph,
    max_iters: int,
    initial_msg: Pytree,
    v_program: Program,
    he_program: Program,
    *,
    return_stats: bool = False,
    n_real: tuple | None = None,
    delivery: tuple | None = None,
):
    """Run the alternating-superstep computation; returns the updated
    HyperGraph (and per-iteration activity stats when requested).

    ``max_iters`` counts (vertex, hyperedge) superstep pairs — the paper's
    "iterations" (30 for its PageRank/LabelProp runs). Dynamic termination:
    once every entity reports inactive the remaining scan iterations are
    no-ops via ``lax.cond`` (compiled once, skipped cheaply at runtime).

    ``n_real``: optional ``(nv_real, ne_real)`` for bucket-padded inputs
    (see ``superstep_pair``); activity/halting then ignore padding slots.

    ``delivery``: optional ``(fwd, bwd)`` ``DeliveryLayout`` pair — the
    fused delivery design point (see ``superstep_pair``).
    """
    v_deg = hg.degrees()
    he_card = hg.cardinalities()
    msg0 = constant_initial_msg(initial_msg, hg.n_vertices)

    body = _halting_body(
        hg, v_program, he_program, v_deg, he_card, n_real, delivery
    )
    init = (
        jnp.asarray(0, jnp.int32),
        hg.v_attr,
        hg.he_attr,
        msg0,
        jnp.asarray(False),
    )
    (_, v_attr, he_attr, _, _), trace = jax.lax.scan(
        body, init, None, length=max_iters
    )
    out = hg.with_attrs(v_attr=v_attr, he_attr=he_attr)
    if return_stats:
        return out, trace
    return out


def initial_superstep_state(hg: HyperGraph, initial_msg: Pytree) -> dict:
    """The explicit scan carry ``compute`` starts from, as a pytree a
    checkpoint can serialize: superstep counter, both attribute trees,
    the in-flight vertex-bound message buffer, and the halt flag."""
    return {
        "step": jnp.asarray(0, jnp.int32),
        "v_attr": hg.v_attr,
        "he_attr": hg.he_attr,
        "msg": constant_initial_msg(initial_msg, hg.n_vertices),
        "halted": jnp.asarray(False),
    }


def compute_resumable(
    hg: HyperGraph,
    n_iters: int,
    state: dict,
    v_program: Program,
    he_program: Program,
    *,
    n_real: tuple | None = None,
    delivery: tuple | None = None,
):
    """Run ``n_iters`` superstep pairs from an explicit carry ``state``
    (see ``initial_superstep_state``); returns ``(state', trace)``.

    This is ``compute`` with the scan carry lifted to an argument — the
    checkpoint/resume seam.  Running k1 pairs, snapshotting, and running
    k2 more from the snapshot executes the identical per-iteration body
    (``_halting_body``) in the identical order as one ``k1 + k2`` run,
    so resumed results are bitwise those of an uninterrupted run.
    """
    body = _halting_body(
        hg, v_program, he_program, hg.degrees(), hg.cardinalities(),
        n_real, delivery,
    )
    init = (
        state["step"], state["v_attr"], state["he_attr"],
        state["msg"], state["halted"],
    )
    (step, v_attr, he_attr, msg, halted), trace = jax.lax.scan(
        body, init, None, length=n_iters
    )
    out = {
        "step": step, "v_attr": v_attr, "he_attr": he_attr,
        "msg": msg, "halted": halted,
    }
    return out, trace


compute_jit = partial(jax.jit, static_argnames=("max_iters", "v_program",
                                                "he_program",
                                                "return_stats"))(compute)

compute_resumable_jit = partial(
    jax.jit, static_argnames=("n_iters", "v_program", "he_program")
)(compute_resumable)


def batch_halting_scan(
    batched_step,
    v_attr_b: Pytree,
    he_attr_b: Pytree,
    msg0_b: Pytree,
    batch: int,
    max_iters: int,
):
    """The batch-aware halting scan shared by the local and distributed
    batched executables.

    ``batched_step(step, v_attr_b, he_attr_b, msg_b) -> (v_attr_b,
    he_attr_b, msg_b, (v_active_b, he_active_b))`` is one vmapped
    superstep pair over the query axis.  The scan wraps it in a REAL
    ``lax.cond`` on ``all(halted)`` — once the last query converges the
    remaining iterations are skipped — while preserving per-query
    semantics bitwise: a halted query's state is frozen by selection
    (exactly what the vmapped ``cond``-as-``select`` would compute) and
    its activity counts report zero.  One definition, two callers
    (``compute_batch`` and ``distributed.build_distributed_runner``),
    so ``Result.supersteps_executed`` agrees across backends by
    construction.

    Returns ``(v_attr_b, he_attr_b, (v_trace, he_trace) [max_iters,
    batch], supersteps_executed)``.
    """

    def select(halted_b, old, new):
        def one(o, n):
            m = halted_b.reshape((batch,) + (1,) * (o.ndim - 1))
            return jnp.where(m, o, n)
        return jax.tree.map(one, old, new)

    def body(carry, _):
        step, v_a, he_a, msg, halted_b, executed = carry
        zero_b = jnp.zeros((batch,), jnp.int32)

        def run(args):
            step, v_a, he_a, msg, halted_b, executed = args
            nv_a, nhe_a, nmsg, stats = batched_step(step, v_a, he_a, msg)
            v_act = jnp.where(halted_b, 0, stats[0])
            he_act = jnp.where(halted_b, 0, stats[1])
            now_halted = halted_b | ((v_act + he_act) == 0)
            return (
                select(halted_b, v_a, nv_a),
                select(halted_b, he_a, nhe_a),
                select(halted_b, msg, nmsg),
                now_halted,
                executed + 1,
                (v_act, he_act),
            )

        def skip(args):
            _, v_a, he_a, msg, halted_b, executed = args
            return v_a, he_a, msg, halted_b, executed, (zero_b, zero_b)

        nv_a, nhe_a, nmsg, halted2, executed, stats = jax.lax.cond(
            halted_b.all(), skip, run,
            (step, v_a, he_a, msg, halted_b, executed),
        )
        return (step + 2, nv_a, nhe_a, nmsg, halted2, executed), stats

    init = (
        jnp.asarray(0, jnp.int32),
        v_attr_b,
        he_attr_b,
        msg0_b,
        jnp.zeros((batch,), bool),
        jnp.asarray(0, jnp.int32),
    )
    (_, v_a, he_a, _, _, executed), traces = jax.lax.scan(
        body, init, None, length=max_iters
    )
    return v_a, he_a, traces, executed


def compute_batch(
    hg: HyperGraph,
    v_attr_b: Pytree,
    he_attr_b: Pytree,
    batch: int,
    max_iters: int,
    initial_msg: Pytree,
    v_program: Program,
    he_program: Program,
    *,
    n_real: tuple | None = None,
    delivery: tuple | None = None,
):
    """Batched superstep computation with BATCH-AWARE halting.

    ``jax.vmap(compute)`` turns the per-query halting ``lax.cond`` into a
    ``select``: both branches execute every iteration, so a batch always
    pays ``max_iters`` supersteps even when every query converged early.
    Here the vmap sits *inside* the scan — one batched superstep per
    iteration — so the halting ``cond`` stays a real branch on
    ``all(halted)`` across the batch: once the LAST query converges the
    remaining iterations are skipped, restoring early exit for
    skewed-convergence batches.

    Per-query semantics are preserved bitwise: a halted query's state is
    frozen by selection (exactly what the vmapped ``cond``-as-``select``
    computed) and its activity counts report zero, so results and stats
    match ``B`` sequential ``compute`` runs.

    ``hg`` carries the (unbatched) structure; ``v_attr_b`` /
    ``he_attr_b`` are the per-query attribute pytrees with a leading
    batch dim ``batch``.  Returns ``(v_attr_b, he_attr_b,
    (v_trace, he_trace) [batch, max_iters], supersteps_executed)`` —
    the executed count is the scan iterations actually run (== the
    slowest query's convergence, <= max_iters).
    """
    v_deg = hg.degrees()
    he_card = hg.cardinalities()
    msg0 = constant_initial_msg(initial_msg, hg.n_vertices)
    msg0_b = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (batch,) + x.shape), msg0
    )

    def one_step(step, v_attr, he_attr, msg_to_v):
        # superstep_pair reads only hg's structure (src/dst/e_attr/
        # e_mask/sizes); the per-query attrs travel as parameters.
        return superstep_pair(
            hg, step, v_attr, he_attr, msg_to_v,
            v_program, he_program, v_deg, he_card, n_real, delivery,
        )

    batched_step = jax.vmap(one_step, in_axes=(None, 0, 0, 0))

    v_a, he_a, (v_tr, he_tr), executed = batch_halting_scan(
        batched_step, v_attr_b, he_attr_b, msg0_b, batch, max_iters
    )
    # [max_iters, batch] -> [batch, max_iters]: match the vmap layout
    # callers already consume.
    return v_a, he_a, (v_tr.T, he_tr.T), executed
