"""Edge-sharded GNN execution: the MESH replicated backend applied to the
GNN family (DESIGN.md §6, §Perf hillclimb #1).

Baseline pjit execution leaves XLA to partition gathers over sharded edge
arrays, and its gather partitioner replicates the [E, hidden] message
tensors per device (measured: TB-scale temps on ogb_products).  This
executor makes the partitioning explicit:

  * edge arrays sharded over every mesh axis (one edge shard per device),
  * node arrays + params replicated,
  * every segment reduction computes a local partial and merges with
    psum/pmax/pmin (via ``repro.sparse.edge_sharded``) — identical
    semantics to the hypergraph engine's replicated-state backend,
  * gradients of replicated params are handled by shard_map's
    replication-checked autodiff (cotangents of replicated inputs are
    psummed exactly once).

Per-device memory: O(E/P * hidden + N * hidden); collectives: one psum of
the [N, hidden] aggregate per layer — the quantity the partitioning
strategies in the paper optimize.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.sparse.segment import edge_sharded
from repro.train.optimizer import AdamWConfig, adamw_update
from repro.train.step import TrainState


def make_edge_sharded_step(mod, cfg, mesh, opt_cfg: AdamWConfig = None):
    """Returns (state, batch) -> (state, metrics).

    Only the *forward loss* runs inside shard_map (edges sharded, nodes +
    params replicated, segment reductions psum-merged); the gradient is
    taken by differentiating THROUGH the shard_map — JAX's shard_map
    transpose inserts the correct psums for replicated-input cotangents,
    so grads are exact without manual bookkeeping."""
    opt_cfg = opt_cfg or AdamWConfig()
    axes = tuple(mesh.axis_names)

    def local_loss(params, batch):
        with edge_sharded(axes):
            return mod.loss_fn(params, cfg, batch)

    # GraphBatch flattens positionally (tree_flatten children tuple):
    # indices 0-2 are the edge arrays; everything else is node-level or
    # scalar and stays replicated.
    _EDGE_CHILD_IDX = {0, 1, 2}

    def batch_spec(batch):
        def per_field(path, leaf):
            # custom pytree nodes yield FlattenedIndexKey(.key: int) or
            # SequenceKey(.idx: int) depending on registration
            idx = getattr(path[0], "idx", getattr(path[0], "key", None))
            if idx in _EDGE_CHILD_IDX:
                return P(axes) if leaf.ndim == 1 else P(axes, None)
            return P(*((None,) * leaf.ndim))

        return jax.tree_util.tree_map_with_path(per_field, batch)

    def step(state, batch):
        params_spec = jax.tree.map(
            lambda x: P(*((None,) * getattr(x, "ndim", 0))), state.params
        )
        sharded_loss = jax.shard_map(
            local_loss,
            mesh=mesh,
            in_specs=(params_spec, batch_spec(batch)),
            out_specs=P(),
        )
        loss, grads = jax.value_and_grad(sharded_loss)(state.params, batch)
        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, grads, state.opt_state, state.params
        )
        return TrainState(new_params, new_opt), {
            "loss": loss, **opt_metrics
        }

    return step
