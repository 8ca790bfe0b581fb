"""The correctness control: the plain reference, in a lower precision, in
the program's place.

    python3 bench/control.py --workload dblp.pagerank --seeds 1,2,3
    python3 bench/control.py --workload dblp.pagerank --seeds 1,2,3 --dtype float32

For each seed it makes the cell's hypergraph as a run does, answers the
cell's job with ``harness.reference.pagerank_lowp`` at ``--dtype``
(bfloat16 by default: the precision below the float32 the
configurations state), and compares that answer with the float64
reference exactly as a run compares the program's.  One JSON line per
seed, with each compared number beside the cell's limit.  A sound limit
passes ``--dtype float32`` and fails ``bfloat16``.  The benchmark's own
runs never run this; it is how the upper readings of the limits were
taken.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def control_numbers(traffic: dict, config: dict, seed: int, dtype) -> dict:
    from harness import check, data, reference

    if traffic["kind"] != "jobs" or traffic["algorithm"] != "pagerank":
        raise ValueError("the control covers pagerank jobs")
    inc = data.make_incidence(config, seed)
    iters, alpha = int(traffic["iters"]), float(traffic.get("alpha", 0.15))
    cmp = check.Comparison()
    cmp.pagerank([reference.pagerank_lowp(inc, iters, alpha, dtype)], inc,
                 iters, alpha)
    return cmp.numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH))
    import jax.numpy as jnp

    import run
    from harness import check

    found = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    limits = check.load_limits(args.workload)
    dtype = getattr(jnp, args.dtype)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(found["traffic"], found["config"], seed,
                                  dtype)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "dtype": args.dtype,
            "numbers": {k: {"value": v, "limit": limits.get(k)}
                        for k, v in numbers.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
