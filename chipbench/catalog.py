"""Find a cell and everything that belongs to it, by the names in
``BENCHMARK.json``.

Each piece sits in a file of its own under the benchmark's directory, so a
cell, a mix, a configuration or a metric is added by adding files and
entries, never by editing a file that is there:

  configs/<config>.json     the configuration (the entry's ``file``)
  traffic/<mix>.json        the mix, read by ``loadgen.Streams``
  metrics/<metric>.py       ``read(ctx)`` of one per-layer metric
  peaks.json                published peaks by JAX ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable

BENCH_DIR = Path(__file__).resolve().parent
REPO_ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, Callable]


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = REPO_ROOT,
              bench_dir: Path | None = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its configuration,
    mix and metric readers loaded."""
    bench_dir = bench_dir or root / "chipbench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    per_layer = [m for m in spec["per_layer"] if _applies(m, name)]
    readers = {m["name"]: _load_module(
        bench_dir / "metrics" / f"{m['name']}.py",
        f"chipbench_metric_{m['name']}").read for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=per_layer, readers=readers)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Published peaks of one chip; a kind not in the table is an error."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json")
    return table[device_kind]
