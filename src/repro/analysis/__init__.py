"""Static analysis for the compile-once seam: ``python -m repro.analysis``.

Three passes over the invariants MESH's flexibility bargain rests on:

* ``lint`` — AST rules (traced-cond, host-sync vs the hot-path
  inventory, static-arg-array, tracer-gate) over ``src/repro``;
* ``retrace`` — the compile-once contract, checked live on the warm
  paths (also exported as the ``assert_no_retrace`` guard and the
  ``no_retrace`` pytest fixture);
* ``digest`` — ``stable_digest`` identity / collision / cross-process
  determinism over a spec x config x bucket grid.

Findings diff against ``tools/analysis_baseline.json`` so pre-existing
accepted findings never block CI; new ones do.
"""
from repro.analysis.findings import (
    RULES,
    Finding,
    baseline_counts,
    diff_baseline,
    load_baseline,
    save_baseline,
    summarize,
)
from repro.analysis.lint import HOT_PATHS, lint_file, lint_tree
from repro.analysis.retrace import RetraceError, assert_no_retrace

__all__ = [
    "RULES", "Finding", "baseline_counts", "diff_baseline",
    "load_baseline", "save_baseline", "summarize",
    "HOT_PATHS", "lint_file", "lint_tree",
    "RetraceError", "assert_no_retrace",
]
