"""The Engine facade: one API, every design point agrees.

Three layers of coverage:

* in-process properties (hypothesis): the facade's local backend equals
  the raw single-device engine (``compute``), config resolution reports
  the chosen design point, representation auto-selection enforces the
  paper's constant-folding precondition, ``submit`` dispatches on spec
  type;
* the backend cost model (``select_backend``) picks ``sharded`` when the
  plan's projected sync volume beats full replication and ``replicated``
  when the cut replicates everything anyway — pure decisions, no mesh;
* a subprocess with forced host devices runs the three backends on random
  hypergraphs through ``Engine`` and asserts agreement: bit-for-bit for
  min/max monoids (label propagation), fp32 round-off only (~1 ulp,
  reduction reassociation across partitions) for sum monoids (pagerank),
  plus end-to-end ``backend="auto"`` picks on engineered plans.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.algorithms import (
    label_propagation_spec,
    pagerank_spec,
    vertex_pagerank_spec,
)
from repro.algorithms.graph_pagerank import graph_pagerank
from repro.core import (
    Engine,
    ExecutionConfig,
    compute,
    select_backend,
    select_representation,
    to_graph,
)
from repro.data import powerlaw_hypergraph
from repro.partition import partition
from repro.partition.base import build_plan

settings.register_profile("ci", max_examples=10, deadline=None)
settings.load_profile("ci")


@st.composite
def small_hypergraph(draw):
    nv = draw(st.integers(5, 40))
    ne = draw(st.integers(2, 30))
    seed = draw(st.integers(0, 1000))
    return powerlaw_hypergraph(nv, ne, mean_cardinality=3, seed=seed)


# --------------------------------------------------------------------------
# local backend == the raw single-device engine (facade plumbing)
# --------------------------------------------------------------------------

def _raw_compute(spec):
    """The pre-facade execution: ``compute`` + the spec's extract."""
    out = compute(
        spec.hg0,
        max_iters=spec.max_iters,
        initial_msg=spec.initial_msg,
        v_program=spec.v_program,
        he_program=spec.he_program,
    )
    return spec.extract(out)


@given(small_hypergraph(), st.integers(2, 8))
def test_engine_local_matches_raw_compute(hg, iters):
    spec = pagerank_spec(hg, iters=iters)
    res = Engine(backend="local").run(spec)
    raw = _raw_compute(spec)
    for a, b in zip(res.value, raw):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert res.backend == "local"
    assert res.representation == "bipartite"


@given(small_hypergraph())
def test_engine_jit_matches_eager(hg):
    spec = label_propagation_spec(hg, iters=6)
    eager = Engine(backend="local", jit=False).run(spec).value
    jitted = Engine(backend="local", jit=True).run(spec).value
    for a, b in zip(eager, jitted):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_legacy_entry_points_removed():
    """PR-1 migration is finished: the deprecated shims are gone."""
    with pytest.raises(ImportError):
        from repro.algorithms import run_local  # noqa: F401
    with pytest.raises(ImportError):
        from repro.algorithms import run_distributed  # noqa: F401


def test_submit_dispatches_on_spec_type():
    """Engine.submit is THE entry point: AlgorithmSpec -> iterative run,
    AnalyticsSpec -> batch analytics, anything else -> TypeError."""
    from repro.core import AnalyticsSpec

    hg = powerlaw_hypergraph(20, 12, seed=1)
    run_res = Engine().submit(pagerank_spec(hg, iters=3))
    assert run_res.backend == "local"
    ana_res = Engine().submit(AnalyticsSpec(hg))
    assert ana_res.kernel in ("bitset", "merge")
    with pytest.raises(TypeError, match="AlgorithmSpec or AnalyticsSpec"):
        Engine().submit(hg)


# --------------------------------------------------------------------------
# config resolution / result reporting
# --------------------------------------------------------------------------

def test_result_reports_resolved_config_and_stats():
    hg = powerlaw_hypergraph(20, 12, seed=1)
    res = Engine().run(
        pagerank_spec(hg, iters=9), collect_stats=True, max_iters=4
    )
    assert res.config.representation == "bipartite"
    assert res.config.backend == "local"
    assert res.config.max_iters == 4
    v_act, he_act = res.superstep_stats
    assert v_act.shape == (4,) and he_act.shape == (4,)
    assert int(v_act[0]) == hg.n_vertices  # pagerank never deactivates


def test_invalid_config_rejected():
    with pytest.raises(ValueError, match="representation"):
        ExecutionConfig(representation="adjacency")
    with pytest.raises(ValueError, match="backend"):
        ExecutionConfig(backend="tpu")
    hg = powerlaw_hypergraph(10, 6, seed=0)
    with pytest.raises(ValueError, match="mesh"):
        Engine(backend="sharded").run(pagerank_spec(hg, iters=2))


# --------------------------------------------------------------------------
# representation selection (the paper's constant-folding precondition)
# --------------------------------------------------------------------------

@given(small_hypergraph())
def test_auto_refuses_clique_for_hyperedge_state_specs(hg):
    """Specs that touch hyperedge state must never constant-fold, no
    matter how cheap the expansion is (MESH §IV-A1)."""
    spec = pagerank_spec(hg, iters=4)  # extracts hyperedge ranks
    rep, why = select_representation(spec, hg, edge_budget=1e9)
    assert rep == "bipartite"
    assert why["touches_hyperedge_state"] is True
    res = Engine(representation="auto").run(spec)
    assert res.representation == "bipartite"


@given(small_hypergraph())
def test_explicit_clique_raises_for_hyperedge_state_specs(hg):
    with pytest.raises(ValueError, match="hyperedge state"):
        Engine(representation="clique").run(pagerank_spec(hg, iters=4))


def test_auto_picks_clique_when_cheap_and_legal():
    # Fig. 1's expansion (16 directed edges) is within the default budget
    # of its 11 incidences; powerlaw regimes blow past it (test below).
    from repro.core import HyperGraph

    hg = HyperGraph.from_hyperedge_lists(
        [[0, 1], [0, 1, 2, 3], [0, 3, 4], [2, 3]], n_vertices=5
    )
    spec = vertex_pagerank_spec(hg, iters=8)
    res = Engine(representation="auto").run(spec)
    assert res.representation == "clique"
    expect = graph_pagerank(to_graph(hg), iters=8)
    np.testing.assert_allclose(
        np.asarray(res.value), np.asarray(expect), rtol=1e-6
    )


def test_explicit_bipartite_pins_raw_compute_numbers():
    """representation='bipartite' must reproduce the raw bipartite
    ``compute`` numbers even for specs the auto-selector would
    constant-fold (clique is a *different* design point numerically)."""
    from repro.core import HyperGraph

    hg = HyperGraph.from_hyperedge_lists(
        [[0, 1], [0, 1, 2, 3], [0, 3, 4], [2, 3]], n_vertices=5
    )
    spec = vertex_pagerank_spec(hg, iters=10)
    raw = _raw_compute(spec)
    bipartite = Engine(representation="bipartite").run(spec).value
    assert np.array_equal(np.asarray(raw), np.asarray(bipartite))


def test_explicit_requests_beat_clique_auto_selection():
    """Explicit distributed backend or max_iters override pins bipartite
    (auto) or raises (explicit clique) — never silently dropped."""
    from repro.core import HyperGraph

    hg = HyperGraph.from_hyperedge_lists(
        [[0, 1], [0, 1, 2, 3], [0, 3, 4], [2, 3]], n_vertices=5
    )
    spec = vertex_pagerank_spec(hg, iters=6)
    # auto would pick clique (see test above); an explicit distributed
    # backend forces bipartite resolution first...
    rep, why = Engine(backend="replicated")._resolve_representation(
        spec, ExecutionConfig(backend="replicated")
    )
    assert rep == "bipartite"
    # ...and still fails loudly on the missing mesh, instead of quietly
    # running the clique program locally.
    with pytest.raises(ValueError, match="mesh"):
        Engine(backend="replicated").run(spec)
    with pytest.raises(ValueError, match="cannot honor"):
        Engine(representation="clique", backend="sharded").run(spec)
    with pytest.raises(ValueError, match="max_iters"):
        Engine(representation="clique").run(spec, max_iters=3)
    # explicit clique + a mesh: loud conflict, not a silent local run.
    import jax
    from jax.sharding import Mesh

    mesh1 = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    with pytest.raises(ValueError, match="mesh"):
        Engine(mesh=mesh1, representation="clique").run(spec)
    # auto + mesh: bipartite (distributed intent), never clique.
    rep, why = Engine(mesh=mesh1)._resolve_representation(
        spec, ExecutionConfig()
    )
    assert rep == "bipartite" and "mesh" in why["reason"]
    # max_iters override + auto: honored, on bipartite.
    res = Engine().run(spec, max_iters=3)
    assert res.representation == "bipartite"
    assert res.config.max_iters == 3


def test_auto_falls_back_to_bipartite_when_expansion_blows_up():
    # One giant hyperedge -> quadratic expansion; budget forces bipartite.
    hg = powerlaw_hypergraph(
        200, 40, mean_cardinality=8, max_cardinality=150, seed=2
    )
    spec = vertex_pagerank_spec(hg, iters=4)
    rep, why = select_representation(spec, hg, edge_budget=1.0)
    assert rep == "bipartite"
    assert why["clique_edges"] > why["bipartite_edges"]


# --------------------------------------------------------------------------
# backend cost model: sync_bytes_per_dim decides replicated vs sharded
# --------------------------------------------------------------------------

def _full_replication_plan(n: int = 8, p: int = 8):
    """Complete bipartite incidence spread so every entity is replicated
    on every partition — the cut buys nothing over full replication."""
    src, dst = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    src, dst = src.ravel().astype(np.int32), dst.ravel().astype(np.int32)
    edge_part = ((src + dst) % p).astype(np.int32)
    return build_plan("adversarial", src, dst, n, n, edge_part, p)


def test_auto_backend_picks_sharded_when_sync_favors_it():
    """The acceptance check: a well-cut plan's projected sync volume is
    far below the full-replication bound, so auto picks sharded."""
    hg = powerlaw_hypergraph(60, 40, mean_cardinality=4, seed=3)
    plan = partition("random_hyperedge_cut", hg, 4)  # vertices whole
    backend, why = select_backend(plan, hg.n_vertices, hg.n_hyperedges)
    assert backend == "sharded"
    assert (
        why["sync_bytes_per_dim"]
        < 0.5 * why["full_replication_sync_bytes"]
    )


def test_auto_backend_picks_replicated_when_cut_replicates_everything():
    plan = _full_replication_plan()
    backend, why = select_backend(plan, 8, 8)
    assert backend == "replicated"
    assert (
        why["sync_bytes_per_dim"]
        >= 0.5 * why["full_replication_sync_bytes"]
    )


def test_single_partition_prefers_replicated():
    hg = powerlaw_hypergraph(20, 12, seed=0)
    plan = partition("random_vertex_cut", hg, 1)
    backend, _ = select_backend(plan, hg.n_vertices, hg.n_hyperedges)
    assert backend == "replicated"


def _hyperedge_replicating_plan(nv=80, ne=8, p=4):
    """Every hyperedge spans all partitions (he_extra = (p-1)*ne);
    every vertex lives on exactly one (v_extra = 0)."""
    members_per_he = p
    src = np.arange(ne * members_per_he, dtype=np.int32) % nv
    dst = np.repeat(np.arange(ne, dtype=np.int32), members_per_he)
    edge_part = (np.arange(ne * members_per_he) % p).astype(np.int32)
    return build_plan("he_replicating", src, dst, nv, ne, edge_part, p)


def test_select_backend_folds_state_width_in():
    """ROADMAP open item: bytes/dim must NOT cancel out — a wide
    hyperedge state makes the hyperedge-replicating cut pay for every
    replica, flipping the decision replicated-wards while a scalar
    state stays sharded."""
    plan = _hyperedge_replicating_plan()
    assert plan.stats.v_extra_replicas == 0.0
    assert plan.stats.he_extra_replicas == 3 * 8  # (p-1) * ne

    narrow, why_n = select_backend(plan, 80, 8)
    assert narrow == "sharded"
    wide, why_w = select_backend(plan, 80, 8, he_state_bytes=256.0)
    assert wide == "replicated"
    # the widths are visible in the decision record
    assert why_w["he_state_bytes"] == 256.0
    assert why_w["sharded_sync_bytes"] > why_n["sharded_sync_bytes"]


def test_state_width_bytes_measures_pytrees():
    import jax.numpy as jnp
    from repro.core.executor import state_width_bytes

    assert state_width_bytes(None, 10) == 4.0  # no state: one f32 dim
    assert state_width_bytes(jnp.zeros((10,), jnp.float32), 10) == 4.0
    assert state_width_bytes(jnp.zeros((10, 64), jnp.float32), 10) == 256.0
    tree = {"a": jnp.zeros((10, 2), jnp.float32),
            "b": jnp.zeros((10,), jnp.int32)}
    assert state_width_bytes(tree, 10) == 12.0


def test_engine_passes_state_widths_to_backend_decision():
    """The resolved decision must carry the spec's measured widths (the
    seam select_backend consumes)."""
    hg = powerlaw_hypergraph(60, 40, mean_cardinality=4, seed=3)
    spec = pagerank_spec(hg, iters=2)
    from repro.core.executor import state_width_bytes

    v_w = state_width_bytes(spec.hg0.v_attr, hg.n_vertices)
    he_w = state_width_bytes(spec.hg0.he_attr, hg.n_hyperedges)
    plan = partition("random_hyperedge_cut", hg, 4)
    _, why = select_backend(
        plan, hg.n_vertices, hg.n_hyperedges,
        v_state_bytes=v_w, he_state_bytes=he_w,
    )
    assert why["v_state_bytes"] == v_w
    assert why["he_state_bytes"] == he_w


# --------------------------------------------------------------------------
# three backends agree (subprocess: needs forced host devices)
# --------------------------------------------------------------------------

BACKEND_AGREEMENT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import Engine
    from repro.data import powerlaw_hypergraph
    from repro.partition import partition
    from repro.algorithms import pagerank_spec, label_propagation_spec

    mesh = Mesh(np.array(jax.devices()).reshape(4), ('data',))
    # odd sizes: state padding slots exist, so the activity stats must
    # prove they exclude them.
    hg = powerlaw_hypergraph(47, 33, mean_cardinality=4, seed=0)
    plan = partition('random_vertex_cut', hg, 4)
    from repro.algorithms import shortest_paths_spec
    specs = [(label_propagation_spec(hg, 6), True),
             (pagerank_spec(hg, 6), False),
             # dynamic activation + halting: the stats trace actually
             # varies per superstep (and the min monoid exercises the
             # all_to_all reduce-scatter on the sharded backend).
             (shortest_paths_spec(hg, 0, 8), True)]
    for spec, exact in specs:
        ref = Engine(backend='local').run(spec, collect_stats=True)
        for backend in ('replicated', 'sharded'):
            got = Engine(plan=plan, mesh=mesh, backend=backend).run(
                spec, collect_stats=True)
            for a, b in zip(ref.value, got.value):
                a, b = np.asarray(a), np.asarray(b)
                if exact:
                    assert np.array_equal(a, b), (spec.name, backend)
                else:
                    # sum monoid: partition partials reassociate fp32
                    # adds -> round-off only, everything else exact.
                    np.testing.assert_allclose(
                        a, b, rtol=2e-6, atol=1e-7,
                        err_msg=f'{spec.name} {backend}')
            # distributed superstep stats == local, bit for bit (the
            # shard_map out_specs threading).
            for r, g in zip(ref.superstep_stats, got.superstep_stats):
                assert np.array_equal(np.asarray(r), np.asarray(g)), (
                    spec.name, backend, r, g)

    # batch analytics: the sharded backend (pair blocks tiled across
    # the mesh) equals the local census bitwise.
    from repro.core import AnalyticsSpec
    aspec = AnalyticsSpec(hg)
    a_local = Engine().analyze(aspec)
    a_shard = Engine(mesh=mesh).analyze(aspec)
    assert a_shard.backend == 'sharded', a_shard.backend
    assert np.array_equal(a_local.value.counts, a_shard.value.counts)

    # end-to-end auto decision through Engine.run: same plan + iters as
    # the sharded run above, so the compile cache is warm and the only
    # new work is the decision itself.
    res = Engine(plan=plan, mesh=mesh, backend='auto').run(
        label_propagation_spec(hg, 6))
    assert res.backend == 'sharded', res.backend
    assert res.decision['backend']['sync_bytes_per_dim'] < 0.5 * (
        res.decision['backend']['full_replication_sync_bytes'])

    # the adversarial fully-replicating cut flips the decision; assert
    # via Engine.resolve (no execution needed).
    from repro.partition.base import build_plan
    from repro.core import HyperGraph
    src, dst = np.meshgrid(np.arange(8), np.arange(8), indexing='ij')
    src, dst = src.ravel().astype(np.int32), dst.ravel().astype(np.int32)
    adv = build_plan('adversarial', src, dst, 8, 8,
                     ((src + dst) % 4).astype(np.int32), 4)
    dense = HyperGraph.from_coo(src, dst, 8, 8)
    resolved, _, why = Engine(plan=adv, mesh=mesh, backend='auto').resolve(
        label_propagation_spec(dense, 4))
    assert resolved.backend == 'replicated', resolved.backend
    print('BACKENDS_AGREE')
""")


def test_three_backends_agree_subprocess():
    # Inherit the full environment (dropping JAX_PLATFORMS in particular
    # makes jax probe for accelerator platforms — minutes of stall).
    proc = subprocess.run(
        [sys.executable, "-c", BACKEND_AGREEMENT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "BACKENDS_AGREE" in proc.stdout


def test_make_host_mesh_refuses_more_devices_than_exist():
    import jax

    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    assert make_host_mesh(n).devices.size == n
    with pytest.raises(RuntimeError, match="needs"):
        make_host_mesh(n + 1)
