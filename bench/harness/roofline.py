"""Peaks of each chip, and the least work an algorithm's shapes need.

The peaks table (``peaks.json``) is keyed by JAX's ``device_kind``; a
kind missing from it is an error, never a default.

The byte counts are computed from the algorithm's own shapes, not from
any layout the program builds, so they read the same whatever lowering
implements delivery: a faster implementation can only raise the share,
and a share above 100% means the count or the time is wrong.  Per
half-superstep: every live incidence moves its two int32 ids and its
message once, and each entity of the side whose procedure runs reads
and writes its state once.  Layout padding and lookup tables count for
nothing.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")
ID_BYTES = 4

# Message and state widths in bytes of the algorithms the benchmark runs:
# to_he is the vertex -> hyperedge message, to_v the hyperedge -> vertex
# message (PageRank sends (weight, rank / cardinality) back).
WIDTHS = {
    "pagerank": {"to_he": 4, "to_v": 8, "v_state": 4, "he_state": 4},
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def dense_pair_min_bytes(algorithm: str, nnz: int, n_vertices: int,
                         n_hyperedges: int) -> float:
    """Least HBM bytes of one (vertex, hyperedge) superstep pair when
    every incidence is live."""
    w = WIDTHS[algorithm]
    to_he = nnz * (2 * ID_BYTES + w["to_he"]) + 2 * n_vertices * w["v_state"]
    to_v = nnz * (2 * ID_BYTES + w["to_v"]) + 2 * n_hyperedges * w["he_state"]
    return float(to_he + to_v)
