"""Abstract shape agreement + static VMEM budgets for delivery.

Two checks, both hardware-free:

* **Shape agreement** — the delivery axis is a pure design choice only
  if both lowerings (`kernels/deliver/xla.py` reference,
  `kernels/deliver/fused.py` Pallas) agree on output shape AND dtype
  for every degree-class layout / monoid / message width.
  ``jax.eval_shape`` proves it abstractly: the Pallas path traces in
  interpret mode without a TPU, so this runs in fast CI.

* **VMEM footprint** — a static byte model of the fused kernel's
  per-grid-step working set, per degree class:

  - the select-reduce tile ``picked [block_n, block_e_c, D]`` (the
    ``_SELECT_MONOIDS`` path materializes it in VMEM),
  - the MXU one-hot ``[block_n, block_e_c] f32`` (the ``sum`` path),
  - the hit/live masks ``[block_n, block_e_c] i32``,
  - the full messages table ``[n_src+1, D]`` (one BlockSpec block) at
    its tiled VMEM size: rows padded to the sublane tile, ``D`` padded
    to 128 lanes,
  - the output tile ``[block_n, D]`` and three ``[block_e_c] i32``
    index blocks.

  ``check_vmem`` errors when any class exceeds the ~16 MiB/core budget
  — the ROADMAP "VMEM-check [block_n, block_e, D] select-reduce tiles
  at D > 8" caveat as a machine-checked constraint.
  ``check_width_gate`` checks the widths within ``FUSED_MAX_WIDTH_BYTES``
  at the builder's worst-case tile geometry and a real ``n_src``: at
  the full-size dblp table (``CELL_N_SRC``) none fits, because the
  lane-padded table alone is 512 MiB.  That is one of the two reasons
  ``repro.kernels.deliver.select_lowering`` never picks the kernel.
"""
from __future__ import annotations

import numpy as np

from repro.analysis.findings import Finding

VMEM_BUDGET_BYTES = 16 * 1024 * 1024   # ~VMEM per TPU core
# the layout builder's per-class tile caps (layout.py: block_n default,
# class_block_e capped at 1024)
_WORST_BLOCK_N = 128
_WORST_BLOCK_E = 1024
# n_src of the smallest full-size Table-I cell: dblp's 899,393 vertices
# bucket-padded to 2^20 (repro.core.serving.bucket_dim).
CELL_N_SRC = 1 << 20
# A table the kernel does hold (the interpret-mode test scale).
_SMALL_N_SRC = 4096
_LANES = 128


def _tiled_bytes(rows: int, d: int, itemsize: int) -> int:
    """VMEM bytes of a 2-D ``[rows, d]`` block under TPU tiling: the
    minor dim pads to 128 lanes, rows to the sublane tile (8 rows of
    32-bit words, so 16 of 16-bit and 32 of 8-bit elements)."""
    sublanes = 8 * max(1, 4 // itemsize)
    rows_p = -(-rows // sublanes) * sublanes
    d_p = -(-d // _LANES) * _LANES
    return rows_p * d_p * itemsize


def vmem_footprint(
    *, block_n: int, block_e: int, d: int, itemsize: int,
    n_src: int, monoid_name: str = "min",
) -> dict[str, int]:
    """Static per-grid-step VMEM bytes of ``_combine_kernel`` for one
    degree class.  ``monoid_name`` picks the combine path; unknown
    names get the worst case (select)."""
    msgs = _tiled_bytes(n_src + 1, d, itemsize)
    out = block_n * d * itemsize
    idx = 3 * block_e * 4
    masks = block_n * block_e * 4
    select = block_n * block_e * d * itemsize
    onehot = block_n * block_e * 4
    if monoid_name == "sum":
        path = onehot
    else:
        path = select
    total = msgs + out + idx + masks + path
    return {
        "msgs_table": msgs, "out_tile": out, "idx_blocks": idx,
        "masks": masks, "combine_path": path, "total": total,
    }


def check_vmem(
    layout, d: int, itemsize: int, *, monoid_name: str = "min",
    budget: int = VMEM_BUDGET_BYTES, where: str = "<vmem>",
) -> list[Finding]:
    """Every class's tile parameters against the budget, for one
    message width.  ``layout`` is a ``DeliveryLayout``."""
    findings = []
    for c, block_e in enumerate(layout.class_block_e):
        fp = vmem_footprint(
            block_n=layout.block_n, block_e=int(block_e), d=d,
            itemsize=itemsize, n_src=int(layout.n_src),
            monoid_name=monoid_name,
        )
        if fp["total"] > budget:
            findings.append(Finding(
                rule="vmem-budget", path=where, line=0,
                scope=f"class{c}[bn={layout.block_n},be={block_e},"
                      f"D={d}x{itemsize}B,{monoid_name}]",
                message=(
                    f"{fp['total'] / 2**20:.1f} MiB working set "
                    f"(select tile {fp['combine_path'] / 2**20:.1f} MiB) "
                    f"> {budget / 2**20:.0f} MiB VMEM budget"
                ),
            ))
    return findings


def check_width_gate(
    *, width_budget_bytes: float | None = None,
    n_src: int = CELL_N_SRC,
    budget: int = VMEM_BUDGET_BYTES,
) -> list[Finding]:
    """Every row width within ``FUSED_MAX_WIDTH_BYTES`` at the layout
    builder's WORST tile geometry and a table of ``n_src`` rows (select
    path, the widest working set): one finding per width that does not
    fit the budget."""
    if width_budget_bytes is None:
        from repro.core.executor import FUSED_MAX_WIDTH_BYTES

        width_budget_bytes = FUSED_MAX_WIDTH_BYTES
    findings = []
    for itemsize in (1, 4, 8):
        max_d = max(1, int(width_budget_bytes // itemsize))
        fp = vmem_footprint(
            block_n=_WORST_BLOCK_N, block_e=_WORST_BLOCK_E, d=max_d,
            itemsize=itemsize, n_src=n_src, monoid_name="min",
        )
        if fp["total"] > budget:
            findings.append(Finding(
                rule="vmem-budget", path="<width-gate>", line=0,
                scope=f"worst[bn={_WORST_BLOCK_N},be={_WORST_BLOCK_E},"
                      f"D={max_d}x{itemsize}B,n_src={n_src}]",
                message=(
                    f"width {max_d}x{itemsize}B at n_src={n_src} needs "
                    f"{fp['total'] / 2**20:.1f} MiB "
                    f"> {budget / 2**20:.0f} MiB"
                ),
            ))
    return findings


# --------------------------------------------------------------------------
# abstract shape agreement between the two lowerings
# --------------------------------------------------------------------------

def _build_layouts():
    """Two small real layouts covering the skew regimes (uniform and a
    hub-heavy draw that forces multiple degree classes)."""
    from repro.kernels.deliver.layout import build_delivery_layout

    rng = np.random.default_rng(0)
    out = []
    # uniform: one narrow class
    nnz, n_src, n_dst = 600, 128, 96
    src = rng.integers(0, n_src, nnz)
    dst = rng.integers(0, n_dst, nnz)
    out.append(("uniform", build_delivery_layout(
        src, dst, None, n_src, n_dst, lowering="pallas",
    )))
    # skewed: a few hubs absorb most edges -> multiple classes
    dst_skew = np.where(
        rng.random(nnz) < 0.6, rng.integers(0, 4, nnz), dst
    )
    out.append(("skewed", build_delivery_layout(
        src, dst_skew, None, n_src, n_dst, lowering="pallas",
    )))
    return out


def check_shapes(
    *, fused_leaf=None, widths=(1, 8), monoids=("sum", "min", "max", "or"),
) -> list[Finding]:
    """``jax.eval_shape`` agreement between ``deliver_ell_leaf`` and the
    fused-Pallas leaf for every layout x monoid x width x dtype.
    ``fused_leaf`` is the mutation hook for the negative tests."""
    import jax

    from repro.kernels.deliver import _pallas_leaf
    from repro.kernels.deliver.xla import deliver_ell_leaf
    from repro.sparse.segment import MONOIDS

    fused = fused_leaf or (
        lambda m, layout, monoid, active: _pallas_leaf(
            m, layout, monoid, active, interpret=True
        )
    )
    findings = []
    for lname, layout in _build_layouts():
        n_src = int(layout.n_src)
        for mname in monoids:
            monoid = MONOIDS[mname]
            dtypes = ("bool",) if mname == "or" else ("float32", "int32")
            for d in widths:
                for dt in dtypes:
                    msgs = jax.ShapeDtypeStruct((n_src, d), np.dtype(dt))
                    ref = jax.eval_shape(
                        lambda m: deliver_ell_leaf(m, layout, monoid),
                        msgs,
                    )
                    got = jax.eval_shape(
                        lambda m: fused(m, layout, monoid, None), msgs,
                    )
                    if (ref.shape, ref.dtype) != (got.shape, got.dtype):
                        findings.append(Finding(
                            rule="shape-mismatch", path="<shape-audit>",
                            line=0,
                            scope=f"{lname}/{mname}/D={d}/{dt}",
                            message=(
                                f"xla {ref.shape}:{ref.dtype} vs fused "
                                f"{got.shape}:{got.dtype}"
                            ),
                        ))
    return findings


def shape_vmem_audit() -> list[Finding]:
    """The CLI pass: shape agreement over the full grid, VMEM budgets
    for every built layout at each width within the gate, and the width
    gate at the small tables the kernel can hold.  The full-size gate
    (``check_width_gate()``) fails by design and is not part of the
    pass: no auto path runs the kernel."""
    findings = check_shapes()
    from repro.core.executor import FUSED_MAX_WIDTH_BYTES

    for lname, layout in _build_layouts():
        for itemsize in (4,):
            max_d = int(FUSED_MAX_WIDTH_BYTES // itemsize)
            for d in (1, 8, max_d):
                findings.extend(check_vmem(
                    layout, d, itemsize,
                    where=f"<vmem:{lname}>",
                ))
    findings.extend(check_width_gate(n_src=_SMALL_N_SRC))
    return findings
