"""The main path compiled for a described TPU v5e, at full dblp size.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (a
kernel Mosaic cannot lower, a program that overflows HBM).  The shapes
are those of ``make_dataset("dblp", scale=1.0)`` — ~2.8M incidences,
bucket-padded to nnz 4,194,304 — and each compile must fit one chip's
16 GiB (``memory_analysis``).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.  The persistent compilation cache is off
here, since its entries cannot be read back without a chip.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2**30          # one v5e chip
FULL_NNZ_PAD = 4_194_304        # dblp's ~2.8M incidences, bucketed


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def dblp():
    from repro.data import make_dataset

    return make_dataset("dblp", scale=1.0, seed=0)


@pytest.fixture(scope="module")
def padded_layouts(dblp):
    """The compiled path's bucket-padded structure and ELL layouts."""
    from repro.core.serving import bucket_dim
    from repro.kernels.deliver import layout_pair

    dims = (bucket_dim(dblp.n_vertices), bucket_dim(dblp.n_hyperedges),
            bucket_dim(dblp.nnz))
    assert dims[2] == FULL_NNZ_PAD
    hgp = dblp.padded(*dims)
    return dims, layout_pair(hgp.src, hgp.dst, hgp.e_mask, *dims[:2])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.result_type(x), sharding=sharding
        ),
        tree,
    )


def _fits_one_chip(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    return total


def _scalars(sharding, n=2):
    return (jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),) * n


def test_local_pagerank_superstep_program_compiles_for_v5e(
    one_chip, dblp, padded_layouts
):
    """``Engine.run``'s local superstep scan with ``ell`` delivery."""
    from repro import algorithms as alg
    from repro.core.engine import compute

    dims, layouts = padded_layouts
    spec = alg.pagerank_spec(dblp, iters=30)
    hgp = spec.hg0.padded(*dims)

    def program(hgp, layouts, nv, ne):
        out = compute(
            hgp, max_iters=30, initial_msg=spec.initial_msg,
            v_program=spec.v_program, he_program=spec.he_program,
            n_real=(nv, ne), delivery=layouts,
        )
        return out.v_attr, out.he_attr

    compiled = jax.jit(program).lower(
        *_abstract((hgp, layouts), one_chip), *_scalars(one_chip)
    ).compile()
    _fits_one_chip(compiled)
    assert "tpu_custom_call" not in compiled.as_text()  # no Pallas kernel


def test_superstep_program_size_does_not_follow_the_row_padding(
    one_chip, dblp, padded_layouts
):
    """Classes padded to multiples of 8 rows compile to about the
    program that power-of-two rows give.  Row-major ``[rows, k]``
    tables made the v5e compiler emit 16 times the code for row counts
    that were not powers of two, and ``Engine.run`` loads that program
    again on every job."""
    from jax.experimental.serialize_executable import serialize

    from repro import algorithms as alg
    from repro.core.engine import compute
    from repro.kernels.deliver import layout_pair

    dims, tight = padded_layouts
    spec = alg.pagerank_spec(dblp, iters=10)
    hgp = spec.hg0.padded(*dims)
    pow2 = layout_pair(hgp.src, hgp.dst, hgp.e_mask, *dims[:2],
                       bucketed=True)
    assert any(r & (r - 1) for l in tight for r in l.class_rows)
    assert all(not r & (r - 1) for l in pow2 for r in l.class_rows)

    def program(hgp, layouts):
        out = compute(
            hgp, max_iters=10, initial_msg=spec.initial_msg,
            v_program=spec.v_program, he_program=spec.he_program,
            delivery=layouts,
        )
        return out.v_attr, out.he_attr

    size = {}
    for name, layouts in (("tight", tight), ("pow2", pow2)):
        compiled = jax.jit(program).lower(
            *_abstract((hgp, layouts), one_chip)
        ).compile()
        size[name] = len(serialize(compiled)[0])
    assert size["tight"] < 2 * size["pow2"], size


_HLO_DEF = re.compile(r"%(\S+) = (\w+)\[([\d,]*)\]\{([^}]*)\}")


def test_pagerank_pair_gathers_each_he_to_v_table_once(
    one_chip, dblp, padded_layouts
):
    """PageRank's he->v message ``(w, rank / card)`` is one leaf group:
    both leaves sit side by side (``[n_src, 2]``) and gather each he->v
    class table, ``inv_perm`` and the residual once.  At the dblp
    bucket size the scan holds 13 gathers, where delivering leaf by leaf
    held 19: v->he's 4 tables, ``inv_perm`` and residual (6), he->v's
    twice (12), and the hyperedge procedure's weight lookup (1).  The
    stacked operand keeps an index's two leaves in one ``(2, 128)``
    tile, rather than padding the 2-wide minor axis to 128 lanes."""
    from repro import algorithms as alg
    from repro.core.engine import compute

    dims, (fwd, bwd) = padded_layouts
    spec = alg.pagerank_spec(dblp, iters=10)
    hgp = spec.hg0.padded(*dims)

    def program(hgp, layouts):
        out = compute(
            hgp, max_iters=10, initial_msg=spec.initial_msg,
            v_program=spec.v_program, he_program=spec.he_program,
            delivery=layouts,
        )
        return out.v_attr, out.he_attr

    compiled = jax.jit(program).lower(
        *_abstract((hgp, (fwd, bwd)), one_chip)
    ).compile()
    _fits_one_chip(compiled)
    text = compiled.as_text()

    def once(lay):  # the class tables, inv_perm, the residual
        return len(lay.class_ell) + 1 + (lay.rem_nnz > 0)

    per_leaf = once(fwd) + 2 * once(bwd) + 1
    gathers = re.findall(r"= \S+ gather\(%([^,]+),.*slice_sizes=\{([\d,]+)\}",
                         text)
    assert (len(gathers), per_leaf) == (per_leaf - once(bwd), 19)
    defs = {m[0]: m for m in _HLO_DEF.findall(text)}
    stacked = [op for op, sizes in gathers if sizes == "1,2"]
    assert len(stacked) == once(bwd)
    for op in stacked:
        _, dtype, shape, layout = defs[op]
        assert (dtype, shape.split(",")[1]) == ("f32", "2"), defs[op]
        assert layout.startswith("0,1:T(2,128)"), defs[op]


def test_sssp_batch16_executable_compiles_for_v5e(
    one_chip, dblp, padded_layouts
):
    """The ``run_batch`` executable the compiled and serving paths run."""
    from repro import algorithms as alg
    from repro.core.executor import ExecutionConfig
    from repro.core.serving import _build_local_executable

    dims, layouts = padded_layouts
    spec = alg.shortest_paths_spec(dblp, 0, 64)
    cfg = ExecutionConfig(
        backend="local", delivery="pallas_fused", max_iters=64
    )
    exe = _build_local_executable(spec, cfg, True, 16, lambda: None)
    hgq = spec.init(dblp).padded(*dims)
    queries = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    compiled = exe.lower(
        *_abstract((hgq, layouts), one_chip), *_scalars(one_chip), queries
    ).compile()
    _fits_one_chip(compiled)


def test_sharded_pagerank_step_compiles_for_v5e_2x2(topo, no_compile_cache,
                                                    dblp):
    """The ``sharded`` backend's superstep scan over a 4-chip mesh, with
    its collectives in the compiled program."""
    from repro import algorithms as alg
    from repro.core.distributed import (
        DistContext,
        _pad_to,
        build_distributed_runner,
        build_shard_delivery,
    )
    from repro.core.api import constant_initial_msg
    from repro.partition import partition

    mesh = Mesh(np.array(topo.devices[:4]).reshape(4), ("data",))
    replicated = NamedSharding(mesh, P())
    spec = alg.pagerank_spec(dblp, iters=30)
    plan = partition("random_hyperedge_cut", dblp, 4)
    nv_pad = _pad_to(dblp.n_vertices, 4)
    ne_pad = _pad_to(dblp.n_hyperedges, 4)
    layouts = build_shard_delivery(
        plan.shard_src, plan.shard_dst, plan.shard_mask, nv_pad, ne_pad
    )
    state = (
        jnp.zeros((nv_pad,), jnp.float32),
        jnp.zeros((ne_pad,), jnp.float32),
        constant_initial_msg(spec.initial_msg, nv_pad),
        jnp.zeros((nv_pad,), jnp.int32),
        jnp.zeros((ne_pad,), jnp.int32),
        plan.shard_src, plan.shard_dst, plan.shard_mask,
    )
    runner = build_distributed_runner(
        mesh, DistContext(axis="data", n_parts=4, nv_pad=nv_pad,
                          ne_pad=ne_pad),
        spec.v_program, spec.he_program, 30, backend="sharded",
    )
    compiled = jax.jit(runner).lower(
        *_abstract(state, replicated), *_scalars(replicated),
        _abstract(layouts, replicated),
    ).compile()
    _fits_one_chip(compiled)        # per-device bytes
    # The v5e compiler lowers the all_gather / psum_scatter exchange to
    # all-reduces over full-size buffers.
    assert "all-reduce" in compiled.as_text()


def test_tpu_backend_selects_ell_through_cost_model(monkeypatch):
    """On a TPU the fused delivery lowers through XLA (``ell``) and the
    choice goes through the same cost model as every other platform."""
    from repro import algorithms as alg
    from repro.core.executor import select_delivery
    from repro.data import make_dataset

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hg = make_dataset("dblp", scale=0.01, seed=0)
    delivery, why = select_delivery(alg.pagerank_spec(hg), hg)
    assert why["lowering"] == "ell"
    assert "class_work_slots" in why          # the cost model ran
    assert delivery == "pallas_fused"
    assert "native" not in why["reason"]
