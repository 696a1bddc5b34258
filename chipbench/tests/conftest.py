"""Shared fixtures of the benchmark's own tests: a tiny copy of the cells.

The tiny root holds a ``BENCHMARK.json`` whose configurations point at
copies of the real ones cut to 400 orders (1,600 rows); mixes and metric
readers are the benchmark's own. Widths (page_card, H, D, shards, batch,
top_k) and the key rule are as committed.
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_ORDERS, TINY_ROWS = 400, 1600


def write_tiny_root(root: Path) -> Path:
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "configs").mkdir(parents=True, exist_ok=True)
    for c in spec["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        cfg.update(orders=TINY_ORDERS, rows=TINY_ROWS)
        c["file"] = f"configs/{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return write_tiny_root(tmp_path_factory.mktemp("tiny"))
