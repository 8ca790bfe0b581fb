"""Task builders: (arch x shape x mesh) -> lowerable step + shardings.

These build *LM/GNN* tasks (train / decode-serve / dryrun); hypergraph
query serving has its own entry, ``repro.launch.serve_hypergraph``.

``build_task`` is the single entry the dry-run, the roofline harness and
the trainers share.  ``input_specs`` returns ShapeDtypeStruct stand-ins —
weak-type-correct, shardable, zero allocation; abstract parameters come
from ``jax.eval_shape`` over the real initializers, so the dry-run proves
exactly what a real launch would compile.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, ShapeSpec
from repro.launch.mesh import dp_axes, flat_axes, total_devices
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainState, make_train_step


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _path_str(path) -> str:
    """Normalize a tree path to 'a/b/0/c' (DictKey renders as ['a'])."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _pad_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclasses.dataclass
class Task:
    """Everything needed to lower one (arch x shape x mesh) cell."""

    name: str
    fn: Callable                      # closed over static config
    abstract_args: tuple              # ShapeDtypeStructs (pytrees)
    in_shardings: tuple               # matching pytrees of NamedSharding
    out_shardings: Any                # or None to infer
    mesh: Any
    # analysis metadata
    model_flops_per_step: float = 0.0
    notes: str = ""

    def lower(self):
        with self.mesh:
            jitted = jax.jit(
                self.fn,
                in_shardings=self.in_shardings,
                out_shardings=self.out_shardings,
            )
            return jitted.lower(*self.abstract_args)


# ==========================================================================
# LM family
# ==========================================================================

def _lm_param_spec(path_str: str, leaf) -> P:
    """FSDP (d_model over 'data') x TP (heads/ff/vocab over 'model')
    sharding rules; see DESIGN.md §8."""
    nd = leaf.ndim
    if "embed/table" in path_str or "item_embed" in path_str:
        return P("model", "data")
    if "lm_head" in path_str:
        return P("data", "model")
    if any(k in path_str for k in ("wq/", "wk/", "wv/")):
        return P(None, "data", "model") if nd == 3 else P("data", "model")
    if "wo/" in path_str:
        return P(None, "model", "data") if nd == 3 else P("model", "data")
    if "moe/router" in path_str:
        return P(None, "data", None)
    if "moe/w_gate" in path_str or "moe/w_up" in path_str:
        return P(None, "model", "data", None)
    if "moe/w_down" in path_str:
        return P(None, "model", None, "data")
    if "shared/w_gate" in path_str or "shared/w_up" in path_str:
        return P(None, "data", "model")
    if "shared/w_down" in path_str:
        return P(None, "model", "data")
    if "ffn/w_gate" in path_str or "ffn/w_up" in path_str:
        return P(None, "data", "model")
    if "ffn/w_down" in path_str:
        return P(None, "model", "data")
    return P()  # norms, biases, scalars


def _divisible(shape, spec: P, mesh) -> bool:
    for dim, axis in zip(shape, tuple(spec) + (None,) * len(shape)):
        if axis is None:
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        k = math.prod(mesh.shape[a] for a in axes)
        if dim % k != 0:
            return False
    return True


def _named(mesh, spec: P):
    return NamedSharding(mesh, spec)


def lm_param_shardings(params_abs, mesh):
    def per_leaf(path, leaf):
        path_str = _path_str(path)
        spec = _lm_param_spec(path_str, leaf)
        if not _divisible(leaf.shape, spec, mesh):
            spec = P()  # fallback: replicate (guard, not expected)
        return _named(mesh, spec)

    return jax.tree_util.tree_map_with_path(per_leaf, params_abs)


def _abstract_lm_state(cfg) -> tuple:
    from repro.models.transformer import init_params
    from repro.train.optimizer import adamw_init

    params_abs = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    )
    opt_abs = jax.eval_shape(adamw_init, params_abs)
    return params_abs, opt_abs


def build_lm_task(spec: ArchSpec, shape: ShapeSpec, mesh,
                  accum_steps: int = 1) -> Task:
    from repro.models import transformer as tfm

    cfg = spec.model
    dims = shape.dims
    dp = dp_axes(mesh)
    n_dev = total_devices(mesh)
    name = f"{spec.arch_id}:{shape.name}"

    if shape.kind == "train":
        seq, batch = dims["seq_len"], dims["global_batch"]
        accum = dims.get("accum_steps", accum_steps)
        loss = lambda p, b: tfm.loss_fn(p, cfg, b)
        step = make_train_step(loss, AdamWConfig(), accum)
        params_abs, opt_abs = _abstract_lm_state(cfg)
        state_abs = TrainState(params_abs, opt_abs)
        batch_abs = {
            "tokens": _sds((batch, seq), jnp.int32),
            "labels": _sds((batch, seq), jnp.int32),
        }
        p_sh = lm_param_shardings(params_abs, mesh)
        opt_sh = {
            "mu": lm_param_shardings(opt_abs["mu"], mesh),
            "nu": lm_param_shardings(opt_abs["nu"], mesh),
            "step": _named(mesh, P()),
        }
        state_sh = TrainState(p_sh, opt_sh)
        batch_sh = {
            "tokens": _named(mesh, P(dp, None)),
            "labels": _named(mesh, P(dp, None)),
        }
        metrics_sh = _named(mesh, P())
        model_flops = 3 * 2 * tfm.active_param_count(cfg) * batch * seq
        return Task(
            name=name,
            fn=step,
            abstract_args=(state_abs, batch_abs),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, {
                "loss": metrics_sh, "grad_norm": metrics_sh,
                "lr": metrics_sh,
            }),
            mesh=mesh,
            model_flops_per_step=model_flops,
            notes=f"accum_steps={accum}",
        )

    if shape.kind == "prefill":
        seq, batch = dims["seq_len"], dims["global_batch"]
        params_abs, _ = _abstract_lm_state(cfg)
        p_sh = lm_param_shardings(params_abs, mesh)
        tokens_abs = _sds((batch, seq), jnp.int32)
        fn = lambda p, t: tfm.prefill(p, cfg, t)
        logits_sh = _named(mesh, P(dp, "model"))
        # keep the sequence dim sharded over 'model' — the same split-KV
        # layout decode consumes, and no kvh all-gather on the way out.
        cache_sh = {
            "k": _named(mesh, P(None, dp, "model", None, None)),
            "v": _named(mesh, P(None, dp, "model", None, None)),
        }
        model_flops = 2 * tfm.active_param_count(cfg) * batch * seq
        return Task(
            name=name,
            fn=fn,
            abstract_args=(params_abs, tokens_abs),
            in_shardings=(p_sh, _named(mesh, P(dp, None))),
            out_shardings=(logits_sh, cache_sh),
            mesh=mesh,
            model_flops_per_step=model_flops,
        )

    if shape.kind == "decode":
        seq, batch = dims["seq_len"], dims["global_batch"]
        params_abs, _ = _abstract_lm_state(cfg)
        p_sh = lm_param_shardings(params_abs, mesh)
        cache_abs = jax.eval_shape(
            lambda: tfm.init_cache(cfg, batch, seq)
        )
        if batch >= math.prod(mesh.shape[a] for a in dp):
            # batch carries DP; KV sequence split over 'model' (split-KV)
            cache_spec = P(None, dp, "model", None, None)
        else:
            # long-context: batch tiny; sequence-parallel KV over all axes
            cache_spec = P(None, None, tuple(mesh.axis_names), None, None)
        if not _divisible(cache_abs["k"].shape, cache_spec, mesh):
            cache_spec = P(None, dp, None, None, None)
        cache_sh = {
            "k": _named(mesh, cache_spec),
            "v": _named(mesh, cache_spec),
        }
        token_abs = _sds((batch,), jnp.int32)
        token_spec = P(dp) if batch % math.prod(
            mesh.shape[a] for a in dp
        ) == 0 else P()
        pos_abs = _sds((), jnp.int32)
        fn = lambda p, c, t, pos: tfm.serve_step(p, cfg, c, t, pos)
        logits_sh = _named(
            mesh, P(dp, "model") if token_spec != P() else P(None, "model")
        )
        model_flops = 2 * tfm.active_param_count(cfg) * batch
        return Task(
            name=name,
            fn=fn,
            abstract_args=(params_abs, cache_abs, token_abs, pos_abs),
            in_shardings=(
                p_sh, cache_sh, _named(mesh, token_spec), _named(mesh, P())
            ),
            out_shardings=(logits_sh, cache_sh),
            mesh=mesh,
            model_flops_per_step=model_flops,
        )

    raise ValueError(f"unknown LM shape kind {shape.kind}")


# ==========================================================================
# GNN family
# ==========================================================================

def _gnn_model_cfg(spec: ArchSpec, dims: dict):
    """Specialize the model config to the shape's feature/class dims."""
    m = spec.model
    if hasattr(m, "d_in"):
        m = dataclasses.replace(
            m, d_in=dims.get("d_feat", m.d_in),
            n_classes=dims.get("n_classes", m.n_classes),
        )
    return m


def _gnn_sizes(shape: ShapeSpec, n_dev: int) -> tuple[int, int, int]:
    """(n_nodes, n_edges, n_graphs) padded to device multiples."""
    d = shape.dims
    if "batch_nodes" in d:  # sampled minibatch: the device-side block
        seeds = d["batch_nodes"]
        f0, f1 = d["fanout0"], d["fanout1"]
        n_nodes = seeds * (1 + f0 + f0 * f1) + 1
        n_edges = seeds * (f0 + f0 * f1)
        n_graphs = 1
    elif "batch" in d:      # batched molecules
        n_graphs = d["batch"]
        n_nodes = d["n_nodes"] * n_graphs
        n_edges = d["n_edges"] * n_graphs
    else:
        n_nodes, n_edges, n_graphs = d["n_nodes"], d["n_edges"], 1
    return _pad_up(n_nodes, n_dev), _pad_up(n_edges, n_dev), n_graphs


def _gnn_model_flops(spec: ArchSpec, cfg, n_nodes: int,
                     n_edges: int) -> float:
    """Analytic fwd+bwd model FLOPs (~2x matmul-fwd x3 for training).
    Coarse (+-2x) — used only for the useful-ratio / roofline-fraction
    columns, documented as estimates."""
    if hasattr(cfg, "n_heads"):          # GAT family
        per_layer = (
            2 * n_nodes * cfg.d_in * cfg.n_heads * cfg.d_hidden
            + 4 * n_edges * cfg.n_heads * cfg.d_hidden
        )
        fwd = cfg.n_layers * per_layer
    elif hasattr(cfg, "d_in"):           # PNA family
        h = cfg.d_hidden
        per_layer = (
            4 * n_edges * cfg.d_in * h + 2 * n_nodes * (12 * h) * h
        )
        fwd = cfg.n_layers * per_layer
    else:  # equivariant (nequip / mace): has l_max
        from repro.models.gnn.irreps import allowed_paths

        c = cfg.d_hidden
        paths = allowed_paths(cfg.l_max)
        tp = sum(
            2 * c * (2 * l1 + 1) * (2 * l2 + 1) * (2 * l3 + 1)
            for (l1, l2, l3) in paths
        )
        radial = 2 * (cfg.n_rbf * cfg.radial_hidden
                      + cfg.radial_hidden * len(paths) * c)
        mix = 2 * 2 * (cfg.l_max + 1) * c * c * 3
        per_layer = n_edges * (tp + radial) + n_nodes * mix
        if getattr(cfg, "kind", "") == "mace":
            per_layer += (
                (cfg.correlation_order - 1) * n_nodes * c * tp // c
            )
        fwd = cfg.n_layers * per_layer
    return 3.0 * fwd  # fwd+bwd


def build_gnn_task(spec: ArchSpec, shape: ShapeSpec, mesh,
                   exec_mode: str = "pjit") -> Task:
    """exec_mode: 'pjit' (baseline: XLA partitions the gathers) or
    'edge_sharded' (explicit shard_map message passing — the MESH
    replicated backend; §Perf hillclimb H2, sum-aggregation models)."""
    from repro.models.gnn import equivariant, gat, pna
    from repro.models.gnn.graph import GraphBatch

    cfg = _gnn_model_cfg(spec, shape.dims)
    n_dev = total_devices(mesh)
    fa = flat_axes(mesh)
    n_nodes, n_edges, n_graphs = _gnn_sizes(shape, n_dev)
    name = f"{spec.arch_id}:{shape.name}"
    # prefix match: smoke configs carry a "-smoke" suffix
    is_equiv = spec.arch_id.startswith(("mace", "nequip"))

    if is_equiv:
        mod = equivariant
        batch_abs = GraphBatch(
            edge_src=_sds((n_edges,), jnp.int32),
            edge_dst=_sds((n_edges,), jnp.int32),
            edge_mask=_sds((n_edges,), jnp.float32),
            n_nodes=n_nodes,
            positions=_sds((n_nodes, 3), jnp.float32),
            species=_sds((n_nodes,), jnp.int32),
            node_mask=_sds((n_nodes,), jnp.float32),
            graph_ids=_sds((n_nodes,), jnp.int32),
            n_graphs=n_graphs,
            labels=_sds((n_graphs,), jnp.float32),
        )
        node_leaf_specs = {
            "positions": P(fa, None), "species": P(fa),
            "node_mask": P(fa), "graph_ids": P(fa),
        }
        label_spec = P()
    else:
        mod = gat if spec.arch_id.startswith("gat") else pna
        d_feat = shape.dims.get("d_feat", 16)
        batch_abs = GraphBatch(
            edge_src=_sds((n_edges,), jnp.int32),
            edge_dst=_sds((n_edges,), jnp.int32),
            edge_mask=_sds((n_edges,), jnp.float32),
            n_nodes=n_nodes,
            node_feat=_sds((n_nodes, d_feat), jnp.float32),
            node_mask=_sds((n_nodes,), jnp.float32),
            graph_ids=_sds((n_nodes,), jnp.int32),
            n_graphs=n_graphs,
            labels=_sds((n_nodes,), jnp.int32),
        )
        node_leaf_specs = {
            "node_feat": P(fa, None), "node_mask": P(fa),
            "graph_ids": P(fa),
        }
        label_spec = P(fa)

    params_abs = jax.eval_shape(
        lambda: mod.init_params(jax.random.PRNGKey(0), cfg)
    )
    if exec_mode == "edge_sharded":
        from repro.launch.gnn_sharded import make_edge_sharded_step

        step = make_edge_sharded_step(mod, cfg, mesh)
    else:
        loss = lambda p, b: mod.loss_fn(p, cfg, b)
        step = make_train_step(loss, AdamWConfig())
    from repro.train.optimizer import adamw_init

    opt_abs = jax.eval_shape(adamw_init, params_abs)
    state_abs = TrainState(params_abs, opt_abs)
    repl = _named(mesh, P())
    state_sh = jax.tree.map(lambda _: repl, state_abs)

    def batch_sharding(batch):
        def per_path(path, leaf):
            field = _path_str(path[:1])
            if field in ("edge_src", "edge_dst", "edge_mask") or (
                field.isdigit() and int(field) in (0, 1, 2)
            ):
                return _named(mesh, P(fa) if leaf.ndim == 1 else P(fa, None))
            if exec_mode == "edge_sharded":
                return repl  # node arrays replicated (MESH repl. backend)
            if field in node_leaf_specs:
                return _named(mesh, node_leaf_specs[field])
            if field == "labels":
                return _named(mesh, label_spec)
            return repl

        return jax.tree_util.tree_map_with_path(per_path, batch)

    batch_sh = batch_sharding(batch_abs)
    metrics_sh = {"loss": repl, "grad_norm": repl, "lr": repl}
    return Task(
        name=name,
        fn=step,
        abstract_args=(state_abs, batch_abs),
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, metrics_sh),
        mesh=mesh,
        model_flops_per_step=_gnn_model_flops(spec, cfg, n_nodes, n_edges),
        notes=f"padded nodes={n_nodes} edges={n_edges} exec={exec_mode}",
    )


# ==========================================================================
# RecSys family
# ==========================================================================

def build_recsys_task(spec: ArchSpec, shape: ShapeSpec, mesh,
                      n_masked: int = 20, n_neg: int = 8192) -> Task:
    from repro.models.recsys import bert4rec as b4r

    cfg = spec.model
    dims = shape.dims
    dp = dp_axes(mesh)
    name = f"{spec.arch_id}:{shape.name}"
    params_abs = jax.eval_shape(
        lambda: b4r.init_params(jax.random.PRNGKey(0), cfg)
    )

    def param_sharding(path, leaf):
        path_str = _path_str(path)
        if "item_embed" in path_str:
            return _named(mesh, P("model", None))
        return _named(mesh, P())

    p_sh = jax.tree_util.tree_map_with_path(param_sharding, params_abs)
    repl = _named(mesh, P())

    def _b4r_fwd_flops(batch: int) -> float:
        d = cfg.embed_dim
        s_len = cfg.max_seq
        per_block = (
            8 * s_len * d * d          # qkv+o proj
            + 4 * s_len * s_len * d    # scores + AV
            + 4 * s_len * d * cfg.d_ff_mult * d
        )
        return batch * cfg.n_blocks * per_block

    if shape.kind == "recsys_train":
        batch = dims["batch"]
        batch_abs = {
            "items": _sds((batch, cfg.max_seq), jnp.int32),
            "masked_pos": _sds((batch, n_masked), jnp.int32),
            "labels": _sds((batch, n_masked), jnp.int32),
            "negatives": _sds((n_neg,), jnp.int32),
        }
        loss = lambda p, b: b4r.loss_sampled(p, cfg, b)
        step = make_train_step(loss, AdamWConfig())
        from repro.train.optimizer import adamw_init

        opt_abs = jax.eval_shape(adamw_init, params_abs)
        state_abs = TrainState(params_abs, opt_abs)
        opt_sh = jax.tree.map(lambda _: repl, opt_abs)
        opt_sh["mu"] = jax.tree_util.tree_map_with_path(
            param_sharding, opt_abs["mu"]
        )
        opt_sh["nu"] = jax.tree_util.tree_map_with_path(
            param_sharding, opt_abs["nu"]
        )
        state_sh = TrainState(p_sh, opt_sh)
        batch_sh = {
            "items": _named(mesh, P(dp, None)),
            "masked_pos": _named(mesh, P(dp, None)),
            "labels": _named(mesh, P(dp, None)),
            "negatives": repl,
        }
        metrics_sh = {"loss": repl, "grad_norm": repl, "lr": repl}
        sampled_softmax = 2 * batch * n_masked * (1 + n_neg) * cfg.embed_dim
        return Task(
            name=name, fn=step,
            abstract_args=(state_abs, batch_abs),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, metrics_sh),
            mesh=mesh,
            model_flops_per_step=3 * (_b4r_fwd_flops(batch)
                                      + sampled_softmax),
        )

    if shape.kind == "recsys_serve":
        batch = dims["batch"]
        items_abs = _sds((batch, cfg.max_seq), jnp.int32)
        # serving shards the batch over EVERY axis; the 'model' axis then
        # cannot also shard the vocab without forcing XLA to replicate the
        # [B, V] scores (measured: 1 TB/device). Replicate the 0.26 GB
        # table instead.
        p_sh = jax.tree.map(lambda _: _named(mesh, P()), params_abs)

        fa = flat_axes(mesh)

        def fn(p, items):
            from repro.models.sharding import constrain

            # online scoring is embarrassingly batch-parallel: the batch
            # shards over EVERY mesh axis (the embedding table is gathered
            # once — 0.25 GB — instead of 84 TB of attention scores being
            # only 16-way sharded).
            scores = b4r.serve_score(p, cfg, items)      # [B, V]
            scores = constrain(scores, "flat", None)
            # lax.top_k's sort is not batch-partitionable (XLA all-gathers
            # the [B, V] scores; measured 1 TB/device) — shard_map it so
            # each device sorts only its own batch rows.
            vals, idx = jax.shard_map(
                lambda sc: tuple(jax.lax.top_k(sc, 100)),
                mesh=mesh,
                in_specs=P(fa, None),
                out_specs=(P(fa, None), P(fa, None)),
            )(scores)
            return vals, idx

        return Task(
            name=name, fn=fn,
            abstract_args=(params_abs, items_abs),
            in_shardings=(p_sh, _named(mesh, P(fa, None))),
            out_shardings=(
                (_named(mesh, P(fa, None)), _named(mesh, P(fa, None)))
            ),
            mesh=mesh,
            model_flops_per_step=_b4r_fwd_flops(batch)
            + 2 * batch * cfg.vocab * cfg.embed_dim,
        )

    if shape.kind == "recsys_retrieval":
        n_cand = dims["n_candidates"]
        fa = flat_axes(mesh)
        items_abs = _sds((1, cfg.max_seq), jnp.int32)
        cand_abs = _sds((_pad_up(n_cand, total_devices(mesh)),), jnp.int32)

        def fn(p, items, cand):
            scores = b4r.retrieval_score(p, cfg, items, cand)
            vals, idx = jax.lax.top_k(scores, 100)
            return vals, idx

        return Task(
            name=name, fn=fn,
            abstract_args=(params_abs, items_abs, cand_abs),
            in_shardings=(p_sh, repl, _named(mesh, P(fa))),
            out_shardings=(repl, repl),
            mesh=mesh,
        )

    raise ValueError(f"unknown recsys shape kind {shape.kind}")


# ==========================================================================
# dispatch
# ==========================================================================

def build_task(spec: ArchSpec, shape: ShapeSpec, mesh, **kw) -> Task:
    if spec.family == "lm":
        return build_lm_task(spec, shape, mesh, **kw)
    if spec.family == "gnn":
        return build_gnn_task(spec, shape, mesh, **kw)
    if spec.family == "recsys":
        return build_recsys_task(spec, shape, mesh)
    raise ValueError(spec.family)


def input_specs(arch_id: str, shape_name: str, mesh=None, smoke=False):
    """ShapeDtypeStruct stand-ins for every model input of one cell
    (the documented dry-run entry point)."""
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh

    mesh = mesh or make_production_mesh()
    spec = get_config(arch_id, smoke=smoke)
    task = build_task(spec, spec.shape(shape_name), mesh)
    return task.abstract_args
