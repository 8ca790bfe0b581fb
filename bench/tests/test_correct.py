"""``correct`` on a whole run, on the CPU at a small size: true for the
program as it is, false with the timed path broken underneath, and false
for the control (the reference in bfloat16 in the program's place)."""
import dataclasses

import jax.numpy as jnp
import pytest

import run
from harness import check, data, reference

PAGERANK = "dblp.pagerank"


def _run(cfg, seed=2**31 + 17):
    return run.run_cell(PAGERANK, seed, 2.0, False, require_chip=False,
                        config=cfg)


def test_sound_run_is_correct(tiny_config):
    out = _run(tiny_config)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def _unchanged_state(monkeypatch):
    """Every superstep pair returns its state as it found it."""
    from repro.core import engine

    def stuck(hg, step, v_attr, he_attr, msg_to_v, *a, **k):
        one = jnp.asarray(1, jnp.int32)
        return v_attr, he_attr, msg_to_v, engine.SuperstepStats(one, one)

    monkeypatch.setattr(engine, "superstep_pair", stuck)


def _altered(x):
    x = jnp.asarray(x)
    bump = jnp.where(jnp.isfinite(x), x + 1e-2, 0.0)
    return x.at[..., 0].set(bump[..., 0])


def _altered_answers(monkeypatch):
    """One entry of every answer is changed where it is produced."""
    from repro.core import executor

    run_jobs = executor.Engine.run

    def jobs(self, spec, **kw):
        res = run_jobs(self, spec, **kw)
        return dataclasses.replace(res, value=tuple(
            _altered(v) for v in res.value))

    monkeypatch.setattr(executor.Engine, "run", jobs)


@pytest.mark.parametrize("fault", [_unchanged_state, _altered_answers])
def test_broken_path_is_not_correct(fault, tiny_config, monkeypatch):
    fault(monkeypatch)
    out = _run(tiny_config)
    assert not out["correct"], out["checks"]


def test_control_fails_and_float32_passes(tiny_config):
    """The reference in bfloat16 fails the limit; in float32 it passes,
    so the limit sits between the two precisions."""
    for seed in (5, 2**31 + 99):
        inc = data.make_incidence(tiny_config, seed)
        for dtype, ok in ((jnp.bfloat16, False), (jnp.float32, True)):
            cmp = check.Comparison()
            cmp.pagerank([reference.pagerank_lowp(inc, 10, 0.15, dtype)],
                         inc, 10, 0.15)
            assert cmp.judge(check.load_limits(PAGERANK))[0] is ok
