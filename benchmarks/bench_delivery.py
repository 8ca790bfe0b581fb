"""Fused vs reference delivery: rows/sec + modeled HBM traffic across
skew regimes (the delivery tentpoles' perf canary).

The deliver/combine data path dominates every MESH superstep.  This
bench times one half-superstep — combine ``[nnz]`` incidences into
``n_dst`` destinations — through the delivery design points:

* ``xla``: the reference gather -> ``where`` mask -> segment reduce
  (materializes ``[nnz, D]`` in HBM, re-reads it, serialized scatter);
* ``pallas_fused``: the dst-sorted degree-class (sliced-ELL) layout
  (``repro.kernels.deliver``; the layout precompute is paid ONCE, as in
  ``Engine.compile``, and excluded from the steady-state timing);
* ``single_ell`` (skewed regimes): the SAME fused lowering over a
  forced single-class plan — the PR-4 packing, whose capped width
  spills hub incidences into the overflow scatter.  The degree-class
  acceptance floors are measured against THIS, isolating what the
  class planner buys on skewed inputs.

Contenders are timed INTERLEAVED (round-robin, median of per-round
ratios) so the 2-3x load drift of this shared CPU host cancels out of
every ratio instead of landing on whichever contender ran last.

Five regimes probe the cost model's axes (message width, degree skew):

* ``narrow_lowskew`` — scalar messages, bounded degrees: the SSSP /
  components / labelprop shape.  Fused ≥ 1.5x rows/sec over the
  reference AND picked by ``delivery='auto'`` (asserted).
* ``narrow_highskew`` — scalar messages, zipf destination popularity:
  per-class widths keep hubs dense, so the win no longer bleeds into
  an overflow scatter.  ``auto`` must pick fused and the class layout
  must beat the single-ELL packing ≥ 2x (asserted; typ. 3.5-4.6x).
* ``mid_highskew`` — 4-lane (16-byte) rows under zipf: the scatter
  still pays per lane, so the class win persists into multi-lane
  messages.  Same floors as narrow_highskew (typ. 3.5-4.1x).
* ``wide_highskew`` — 16-lane (64-byte, the cost model's width cap)
  rows under zipf: the boundary regime the class layout FLIPPED.  The
  PR-4 single-ELL packing measures a ~2x loss to the reference here —
  so its cost model's fused pick was wrong exactly where skew met
  width.  Per-class widths win the regime back: ``auto`` must keep
  fused, fused must hold parity-or-better with the reference, and the
  class layout must beat single-ELL ≥ 1.2x (asserted; the 64-byte
  scatter amortizes per lane, so the margin is structural, not 2x).
* ``wide_lowskew`` — 64-lane (256-byte) rows, bounded degrees: the
  reference gather/scatter already vectorizes and dense-table row
  traffic multiplies with width; ``auto`` must keep the reference
  path, and the class layout must not regress the single-ELL packing
  (asserted).

Asserts here are calibrated for the XLA (ELL) lowering, fused
delivery's one lowering on every platform.

Writes ``BENCH_delivery.json`` (uploaded by the nightly CI job).
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.algorithms.spec import AlgorithmSpec
from repro.core.api import Program
from repro.core.engine import deliver
from repro.core.executor import select_delivery
from repro.core.hypergraph import HyperGraph
from repro.kernels.deliver import (
    build_delivery_layout,
    fused_deliver,
    plan_ell_width,
)
from repro.kernels.deliver.layout import ClassPlan
from repro.obs import delivery_calibration

from benchmarks.common import SCALE, emit_json, row

REGIMES = {
    # (nnz, n_dst, width, zipf_skew)
    "narrow_lowskew": (200_000, 8192, (), False),
    "narrow_highskew": (200_000, 8192, (), True),
    "mid_highskew": (200_000, 8192, (4,), True),
    "wide_highskew": (200_000, 8192, (16,), True),
    "wide_lowskew": (200_000, 8192, (64,), False),
}
ROUNDS = 7                  # interleaved timing rounds per regime
FUSED_SPEEDUP_FLOOR = 1.5   # fused >= 1.5x reference in its home regime
CLASS_SPEEDUP_FLOOR = 2.0   # class >= 2x single-ELL, narrow/mid skew
# The 64-byte boundary regime: scatter amortizes per lane, so the class
# margin over single-ELL is structural (typ. 1.4-2.1x), and parity with
# the reference is the flip being defended (typ. 1.0-1.45x).
WIDE_CLASS_FLOOR = 1.2
WIDE_PARITY_FLOOR = 0.9


def _make_regime(nnz, n_dst, width, skew, seed=0):
    rng = np.random.default_rng(seed)
    nnz = max(int(nnz * SCALE), 4096)
    n_dst = max(int(n_dst * SCALE), 256)
    n_src = n_dst
    if skew:
        p = 1.0 / np.arange(1, n_dst + 1)
        dst = rng.choice(n_dst, size=nnz, p=p / p.sum()).astype(np.int32)
    else:
        dst = rng.integers(0, n_dst, nnz).astype(np.int32)
    src = rng.integers(0, n_src, nnz).astype(np.int32)
    msg = rng.standard_normal((n_src,) + width).astype(np.float32)
    return src, dst, msg, n_src, n_dst, nnz


def _interleaved_times(fns_args, rounds=ROUNDS):
    """Round-robin timing: per contender, the list of per-round wall
    times (one untimed warmup each).  Ratios between contenders should
    be taken per round and medianed — host load drift then hits every
    contender of a round roughly equally."""
    for fn, args in fns_args:
        jax.block_until_ready(fn(*args))
    times = [[] for _ in fns_args]
    for _ in range(rounds):
        for i, (fn, args) in enumerate(fns_args):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times[i].append(time.perf_counter() - t0)
    return times


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _median_ratio(num, den):
    return _median([n / d for n, d in zip(num, den)])


def _single_ell_plan(dst, n_dst, nnz) -> ClassPlan:
    """The PR-4 packing as a forced plan: ONE class at the capped
    single-ELL width; everything past it overflows."""
    deg = np.bincount(dst, minlength=n_dst)
    k, rem = plan_ell_width(deg, nnz)
    return ClassPlan(
        widths=(k,), rows=(int((deg > 0).sum()),), residual=int(rem)
    )


def _layout_stats(layout, nnz):
    ell_slots = layout.ell_slots
    return {
        "class_widths": list(layout.class_widths),
        "class_rows": list(layout.class_rows),
        "ell_slots": ell_slots,
        "padding_fraction": (ell_slots + layout.rem_nnz) / max(nnz, 1) - 1.0,
        "residual_nnz": layout.rem_nnz,
    }


def _traffic_model(layout, nnz, n_dst, width_bytes):
    """Effective HBM bytes per half-superstep, both paths.

    Reference: read ids, gather+write the [nnz, D] rows array, re-read
    it for the masked scatter, write the output.  Fused: read the
    layout ids once, read each gathered row once, write the output —
    the intermediate never exists.
    """
    ref = nnz * (3 * width_bytes + 2 * 4) + n_dst * width_bytes
    ell_rows = layout.ell_slots + layout.rem_len
    fused = ell_rows * (width_bytes + 4) + n_dst * width_bytes
    return ref, fused


def run() -> None:
    results: dict = {"regimes": {}, "scale": SCALE}
    prog = Program(procedure=lambda *a: None, combiner="sum")

    for name, (nnz0, n_dst0, width, skew) in REGIMES.items():
        src, dst, msg, n_src, n_dst, nnz = _make_regime(
            nnz0, n_dst0, width, skew
        )
        msg_j = jnp.asarray(msg)
        src_j, dst_j = jnp.asarray(src), jnp.asarray(dst)

        ref_fn = jax.jit(
            lambda m, s, d: deliver(m, None, s, d, n_dst, prog)
        )
        layout = build_delivery_layout(src, dst, None, n_src, n_dst)
        # The PR-4 single-ELL packing through the same lowering: the
        # degree-class acceptance baseline (skewed regimes are where
        # they diverge; low-skew plans collapse to ~one class anyway).
        single = build_delivery_layout(
            src, dst, None, n_src, n_dst,
            plan=_single_ell_plan(dst, n_dst, nnz),
        )
        # layouts ride as operands (as in the engine path) — closed
        # over, XLA constant-folds the gathers and skews the timing.
        fused_fn = jax.jit(
            lambda m, lay: fused_deliver(m, None, lay, prog)
        )
        times = _interleaved_times([
            (ref_fn, (msg_j, src_j, dst_j)),
            (fused_fn, (msg_j, layout)),
            (fused_fn, (msg_j, single)),
        ])
        t_ref, t_fused, t_single = map(_median, times)
        speedup = _median_ratio(times[0], times[1])
        class_vs_single = _median_ratio(times[2], times[1])
        width_bytes = float(
            np.prod(width, dtype=np.int64) * 4 if width else 4
        )
        ref_bytes, fused_bytes = _traffic_model(
            layout, nnz, n_dst, width_bytes
        )

        # what would auto do here? (a minimal monoid spec carrying the
        # regime's message width)
        hg = HyperGraph.from_coo(src, dst, n_src, n_dst)
        spec = AlgorithmSpec(
            hg0=hg,
            initial_msg=jnp.zeros(width, jnp.float32),
            v_program=prog,
            he_program=prog,
            max_iters=1,
            extract=lambda out: out,
            name=f"bench_{name}",
        )
        auto_choice, why = select_delivery(spec, hg)

        results["regimes"][name] = {
            "nnz": nnz,
            "n_dst": n_dst,
            "width_bytes": width_bytes,
            "skew": skew,
            "xla_s": t_ref,
            "fused_s": t_fused,
            "single_ell_s": t_single,
            "xla_rows_per_s": nnz / t_ref,
            "fused_rows_per_s": nnz / t_fused,
            "fused_speedup": speedup,
            "class_vs_single_ell": class_vs_single,
            "model_xla_hbm_bytes": ref_bytes,
            "model_fused_hbm_bytes": fused_bytes,
            "model_traffic_ratio": ref_bytes / max(fused_bytes, 1.0),
            "class_layout": _layout_stats(layout, nnz),
            "single_ell_layout": _layout_stats(single, nnz),
            "auto_picks": auto_choice,
            "auto_reason": why.get("reason"),
            "auto_skew_gain": why.get("skew_gain"),
        }
        row(
            f"delivery/{name}/xla", t_ref * 1e6,
            f"rows_per_s={nnz / t_ref:.0f}",
        )
        row(
            f"delivery/{name}/pallas_fused", t_fused * 1e6,
            f"rows_per_s={nnz / t_fused:.0f};speedup={speedup:.2f};"
            f"vs_single_ell={class_vs_single:.2f};auto={auto_choice}",
        )

    r = results["regimes"]
    # The cost model must track the measured winner per regime...
    for regime in (
        "narrow_lowskew", "narrow_highskew", "mid_highskew",
        "wide_highskew",
    ):
        assert r[regime]["auto_picks"] == "pallas_fused", (
            "auto must pick the fused path in its winning regime",
            regime, r[regime],
        )
    assert r["wide_lowskew"]["auto_picks"] == "xla", (
        "wide rows on low-skew degrees must keep auto on the reference "
        "path (ELL lowering)",
        r["wide_lowskew"],
    )
    # ... the fused path must actually deliver where auto sends it
    # (noisy-host tolerance: floors sit below the typical interleaved
    # medians) ...
    measured = r["narrow_lowskew"]["fused_speedup"]
    assert measured >= FUSED_SPEEDUP_FLOOR, (
        f"fused delivery only {measured:.2f}x the XLA path "
        f"(< {FUSED_SPEEDUP_FLOOR}x) in the narrow/low-skew regime"
    )
    for regime in ("narrow_highskew", "mid_highskew"):
        assert r[regime]["fused_speedup"] >= 1.0, (
            "fused delivery lost to the reference where auto sends it",
            regime, r[regime],
        )
        # ... the degree-class acceptance floor: ≥ 2x the PR-4
        # single-ELL packing exactly where skew used to claw it back.
        got = r[regime]["class_vs_single_ell"]
        assert got >= CLASS_SPEEDUP_FLOOR, (
            f"degree-class layout only {got:.2f}x the single-ELL "
            f"packing (< {CLASS_SPEEDUP_FLOOR}x) in {regime}"
        )
    # ... the flipped boundary regime holds its ground ...
    assert r["wide_highskew"]["fused_speedup"] >= WIDE_PARITY_FLOOR, (
        "fused delivery fell below parity in the flipped 64-byte zipf "
        "regime",
        r["wide_highskew"],
    )
    assert r["wide_highskew"]["class_vs_single_ell"] >= WIDE_CLASS_FLOOR, (
        "degree-class layout lost its structural margin over single-ELL "
        "in the 64-byte zipf regime",
        r["wide_highskew"],
    )
    # ... with no regression where classes cannot help (low skew: the
    # plan collapses toward one class, so parity +/- host noise).
    for regime in ("narrow_lowskew", "wide_lowskew"):
        got = r[regime]["class_vs_single_ell"]
        assert got >= 0.75, (
            f"degree-class layout regressed single-ELL ({got:.2f}x) "
            f"in {regime}"
        )
    # Predicted-vs-measured residuals of the traffic model across the
    # regime table — the calibration record the ROADMAP's item asks
    # for, refreshed each nightly run alongside the raw timings.
    results["calibration"] = delivery_calibration(results["regimes"])
    cal = results["calibration"]["summary"]
    row(
        "delivery/calibration", 0.0,
        f"mean_abs_residual_log2={cal['mean_abs_residual_log2']:.3f};"
        f"decision_accuracy={cal['decision_accuracy']:.2f};"
        f"suggested_model_scale={cal['suggested_model_scale']:.3f}",
    )
    emit_json("delivery", results)


if __name__ == "__main__":
    run()
