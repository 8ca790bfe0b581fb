"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.row).
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        bench_delivery,
        bench_loc,
        bench_motifs,
        bench_obs,
        bench_partitioning,
        bench_representation,
        bench_roofline,
        bench_scaling,
        bench_serve_tier,
        bench_serving,
        bench_vs_specialized,
    )

    suites = [
        ("loc (Table II)", bench_loc.run),
        ("representation (Fig 7, Table I)", bench_representation.run),
        ("partitioning (Figs 8-11)", bench_partitioning.run),
        ("scaling (Figs 12-14)", bench_scaling.run),
        ("vs_specialized (Fig 15)", bench_vs_specialized.run),
        ("roofline (EXPERIMENTS §Roofline)", bench_roofline.run),
        ("motifs (batch analytics)", bench_motifs.run),
        ("serving (compile-once serve-many)", bench_serving.run),
        ("serve_tier (front-end + persistent cache)", bench_serve_tier.run),
        ("delivery (fused superstep data path)", bench_delivery.run),
        ("obs (trace coverage + overhead)", bench_obs.run),
    ]
    failures = 0
    print("name,us_per_call,derived")
    for label, fn in suites:
        print(f"# --- {label} ---", flush=True)
        try:
            fn()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
