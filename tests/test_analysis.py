"""The static-analysis suite checks the checkers.

Positive direction: the three passes run clean on the repo as committed
(that is CI's job — here we pin the machinery).  Negative direction
(the acceptance bar): every pass must catch a deliberately injected
violation —

* lint rules on synthetic sources (traced-cond, host-sync with hot-path
  classification, static-arg-array, tracer-gate), plus suppression and
  baseline-diff semantics;
* the retrace sentinel raising ``RetraceError`` on a forced compile
  (and staying quiet on the warm path), including the ``serve.warm``
  runtime guard;
* the digest audit flagging an injected collision and an injected
  identity leak.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (
    Finding,
    RetraceError,
    assert_no_retrace,
    diff_baseline,
    lint_file,
)


def _lint_source(tmp_path, source, rel="pkg/mod.py"):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return lint_file(path, root=tmp_path)


# --------------------------------------------------------------------------
# lint rules on synthetic sources
# --------------------------------------------------------------------------

def test_traced_cond_flags_if_and_while_in_traced_regions(tmp_path):
    found = _lint_source(tmp_path, """
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("flag",))
        def f(x, flag=True):
            y = jnp.abs(x)
            if y > 0:            # traced -> finding
                return y
            if flag:             # static arg -> fine
                return -y
            return x

        def g(x):
            while x < 3:         # traced via the jit call below
                x = x + 1
            return x

        jax.jit(g)(1)

        def cold(x):
            if x > 0:            # not a traced region
                return x
            return -x
    """)
    rules = [(f.rule, f.scope) for f in found
             if f.classification == "finding"]
    assert ("traced-cond", "f") in rules
    assert ("traced-cond", "g") in rules
    assert not any(s == "cold" for _, s in rules)


def test_traced_cond_skips_static_tests(tmp_path):
    found = _lint_source(tmp_path, """
        import jax
        import jax.numpy as jnp

        def body(carry, x):
            a, b = carry
            if a is None:              # identity test: fine
                a = x
            if isinstance(b, tuple):   # static predicate: fine
                b = b[0]
            if x.shape[0] > 2:         # shape read: fine
                pass
            return (a, b), x

        jax.lax.scan(body, (None, 0), jnp.arange(3))
    """)
    assert not [f for f in found if f.rule == "traced-cond"]


def test_host_sync_classifies_hot_guarded_cold(tmp_path):
    # the file's suffix places it on the serve hot-path inventory
    found = _lint_source(tmp_path, """
        import numpy as np

        def _stack(queries, tracer=None):
            rows = [np.asarray(q) for q in queries]     # hot finding
            if tracer is not None:
                tracer.note(float(rows[0].sum()))       # guarded
            return rows

        def boot_helper(x):
            return np.asarray(x)                        # cold path
    """, rel="serve/frontend.py")
    by = {(f.scope, f.classification) for f in found
          if f.rule == "host-sync"}
    assert ("_stack", "finding") in by
    assert ("_stack", "guarded") in by
    assert ("boot_helper", "cold-path") in by


def test_host_sync_early_tracer_return_guards_rest_of_function(tmp_path):
    found = _lint_source(tmp_path, """
        import numpy as np

        def _block(value, tracer):
            if tracer is None:
                return value
            return np.asarray(value)    # only runs traced: guarded
    """, rel="serve/frontend.py")
    syncs = [f for f in found if f.rule == "host-sync"]
    assert [f.classification for f in syncs] == ["guarded"]


def test_static_arg_array_default_and_call_site(tmp_path):
    found = _lint_source(tmp_path, """
        import functools
        import jax
        import numpy as np

        @functools.partial(jax.jit, static_argnames=("w",))
        def f(x, w=np.asarray([1.0])):   # default -> finding
            return x * w

        def g(x, w):
            return x * w

        jax.jit(g, static_argnames=("w",))
        g(w=np.asarray([2.0]))           # call site -> finding
        g(w=1.0)                         # scalar: hashable, fine
    """)
    hits = [f for f in found if f.rule == "static-arg-array"]
    assert len(hits) == 2
    assert {f.scope for f in hits} == {"f", "<module>"}


def test_tracer_gate_requires_none_branch(tmp_path):
    found = _lint_source(tmp_path, """
        def bad(x, tracer=None):
            with tracer.span("a"):
                return x

        def good(x, tracer=None):
            if tracer is None:
                return x
            with tracer.span("a"):
                return x

        def also_good(x, tracer=None):
            from repro.obs import maybe_span
            with maybe_span(tracer, "a"):
                return x
    """)
    gates = [f.scope for f in found if f.rule == "tracer-gate"]
    assert gates == ["bad"]


def test_swallowed_error_hot_routed_narrow_and_cold(tmp_path):
    found = _lint_source(tmp_path, """
        def _stack(reqs):
            try:
                work()
            except Exception:            # hot + discarded -> finding
                pass

        def _unstack(reqs):
            try:
                work()
            except Exception as err:     # routed: error reaches a future
                reqs[0].future.set_exception(err)

        def _block(value):
            try:
                work()
            except ValueError:           # narrow: names the real failure
                pass
            try:
                work()
            except Exception:            # routed: re-raised
                raise

        def boot_helper(x):
            try:
                work()
            except:                      # bare, but off the hot path
                pass
    """, rel="serve/frontend.py")
    hits = {(f.scope, f.classification) for f in found
            if f.rule == "swallowed-error"}
    assert hits == {("_stack", "finding"), ("boot_helper", "cold-path")}


def test_inline_suppression_same_line_and_block_above(tmp_path):
    found = _lint_source(tmp_path, """
        import numpy as np

        def _stack(x):
            a = np.asarray(x)  # analysis: ignore[host-sync]
            # analysis: ignore[host-sync] — rationale text here,
            # continuing onto a second comment line
            b = np.asarray(x)
            c = np.asarray(x)  # analysis: ignore[traced-cond] wrong rule
            return a, b, c
    """, rel="serve/frontend.py")
    syncs = {f.line: f.classification for f in found
             if f.rule == "host-sync"}
    assert sorted(syncs.values()) == ["finding", "suppressed",
                                      "suppressed"]


def test_baseline_diff_budgets_counts_and_reports_stale():
    f1 = Finding("host-sync", "a.py", 3, "f", "m")
    f2 = Finding("host-sync", "a.py", 9, "f", "m2")
    f3 = Finding("traced-cond", "b.py", 1, "g", "m3")
    baseline = {"host-sync:a.py:f": 1, "retrace:gone.py:h": 1}
    fresh, stale = diff_baseline([f1, f2, f3], baseline)
    # one host-sync covered by the budget, the second resurfaces
    assert [f.message for f in fresh] == ["m2", "m3"]
    assert stale == ["retrace:gone.py:h"]


def test_repo_lint_is_clean_and_inventory_classified():
    """The committed tree has NO unsuppressed hot-path findings, and the
    host-sync inventory is fully classified (the ISSUE's ~83+ sites all
    land in a bucket)."""
    from repro.analysis.lint import lint_tree

    root = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    found = lint_tree(root)
    fresh = [f for f in found if f.classification == "finding"]
    assert fresh == [], [f.format(explain=False) for f in fresh]
    sync = [f for f in found if f.rule == "host-sync"]
    assert len(sync) > 80
    assert {f.classification for f in sync} <= {
        "cold-path", "guarded", "suppressed"
    }


# --------------------------------------------------------------------------
# retrace sentinel
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def compiled_pair():
    from repro.algorithms import shortest_paths_spec
    from repro.core import Engine
    from repro.data import powerlaw_hypergraph

    hg = powerlaw_hypergraph(30, 20, mean_cardinality=3, seed=0)
    eng = Engine()
    compiled = eng.compile(shortest_paths_spec(hg, 0, 6))
    compiled.run()
    return eng, compiled


def test_sentinel_quiet_on_warm_path(compiled_pair):
    eng, compiled = compiled_pair
    with assert_no_retrace(eng) as delta:
        compiled.run(query=3)
        assert delta() == 0


def test_sentinel_raises_on_forced_retrace(compiled_pair):
    eng, compiled = compiled_pair
    with pytest.raises(RetraceError, match="design-point change"):
        with assert_no_retrace(eng, label="design-point change"):
            # a new design point misses the cache -> compiles
            eng.compile(compiled.spec, collect_stats=True).run()


def test_sentinel_allow_budget(compiled_pair):
    eng, compiled = compiled_pair
    with assert_no_retrace(eng, allow=1):
        eng.compile(compiled.spec, max_iters=3).run()


def test_warm_runtime_guard_raises_without_disk_store():
    from repro.algorithms import shortest_paths_spec
    from repro.core import Engine
    from repro.data import powerlaw_hypergraph
    from repro.serve import warm

    hg = powerlaw_hypergraph(30, 20, mean_cardinality=3, seed=0)
    with pytest.raises(RetraceError, match="serve.warm"):
        warm(Engine(), [shortest_paths_spec(hg, 0, 6)],
             require_no_retrace=True)


# --------------------------------------------------------------------------
# digest audit
# --------------------------------------------------------------------------

def test_digest_audit_clean_in_process():
    from repro.analysis.digest import audit

    assert audit(cross_process=False) == []


def test_digest_audit_catches_injected_collision():
    from repro.analysis.digest import audit

    found = audit(digest_fn=lambda key: "constant", cross_process=False)
    assert any(f.rule == "digest-collision" for f in found)


def test_digest_audit_catches_identity_leak():
    from repro.analysis.digest import audit
    from repro.serve.cache import stable_digest

    # id() varies between the two in-process grid builds: the exact
    # failure mode of hashing an object by repr/address
    found = audit(digest_fn=lambda key: stable_digest((id(key), )),
                  cross_process=False)
    assert any(f.rule == "digest-identity" for f in found)


@pytest.mark.slow
def test_digest_stable_across_process_boundary():
    """The cross-process half, against a REAL child interpreter with
    randomized hashing — the regression the disk cache depends on."""
    from repro.analysis.digest import grid_digests

    here = grid_digests()
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {**os.environ, "PYTHONPATH": src, "PYTHONHASHSEED": "random"}
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, sys; from repro.analysis.digest import "
         "grid_digests; json.dump(grid_digests(), sys.stdout)"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-500:]
    assert json.loads(out.stdout) == here


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def test_cli_lint_pass_exits_clean_and_explains(capsys):
    from repro.analysis.__main__ import main

    rc = main(["--passes", "lint"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "OK: no new findings vs baseline" in out


def test_cli_reports_new_finding_with_rationale(tmp_path, capsys):
    """A repo-shaped tree with an injected violation exits 1 and prints
    the clickable ``file:line: [rule]`` + rationale format."""
    from repro.analysis.__main__ import main

    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        def f(x):
            y = jnp.abs(x)
            if y > 0:
                return y
            return x

        jax.jit(f)(1.0)
    """))
    (tmp_path / "pyproject.toml").write_text("")
    rc = main(["--passes", "lint", "--root", str(tmp_path),
               "--baseline", "baseline.json"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "bad.py:7: [traced-cond]" in out
    assert "why: " in out
