"""The benchmark's own tests: CPU only, small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests -p no:cacheprovider

The repository's test suite does not collect them (its ``testpaths``
is ``tests``).
"""
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


@pytest.fixture
def tiny_config():
    """dblp's regime at 0.3% of its Table-I size."""
    cfg = json.loads((BENCH / "configs" / "dblp.json").read_text())
    cfg.update(n_vertices=2698, n_hyperedges=2347)
    return cfg
