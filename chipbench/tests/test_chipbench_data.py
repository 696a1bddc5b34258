"""The yardstick's pieces that need no device program: the generator, the
traffic, the reference, the byte count, the catalog and the entry point's
refusal without a TPU."""
import datetime
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import catalog, check, costs, datagen, loadgen, reference

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
CONFIG, MIX, CELL = "tpch_sf30_shipdate", "tpch_shipdate_s4", "ship_tpch4"


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def small(name, orders=3000, rows=12000):
    cfg = config(name)
    cfg.update(orders=orders, rows=rows)
    return cfg


# -- generator ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, -3, 2**31 + 5, 2**40 + 1])
def test_load_is_deterministic_per_seed(seed):
    cfg = small(CONFIG)
    a, b = datagen.load_column(cfg, seed), datagen.load_column(cfg, seed)
    c = datagen.load_column(cfg, seed + 1)
    assert a.dtype == np.float32 and a.size == cfg["rows"]
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_line_counts_follow_dbgen_and_the_published_rows():
    g = datagen.rng(7, datagen.LOAD)
    counts = datagen.lines_per_order(g, 20000, 1, 7, rows=81234)
    assert counts.min() >= 1 and counts.max() <= 7
    assert counts.sum() == 81234
    assert set(np.unique(counts)) == set(range(1, 8))
    # the conditioning moves a few orders by one line: still uniform in 1..7
    share = np.bincount(counts, minlength=8)[1:] / counts.size
    assert np.all(np.abs(share - 1 / 7) < 0.02)
    with pytest.raises(ValueError):
        datagen.lines_per_order(g, 10, 1, 7, rows=71)


def test_shipdate_follows_its_orderdate():
    """Lines of one order ship within [orderdate + 1, orderdate + 121]: the
    spread inside an order is at most 120 days, and keys stay in range."""
    cfg = small(CONFIG)
    g = datagen.rng(3, datagen.LOAD)
    counts = datagen.lines_per_order(g, cfg["orders"], 1, 7, cfg["rows"])
    keys = datagen.column_values(g, cfg["key"], counts)
    assert keys.min() >= 1 and keys.max() <= 2405 + 121
    assert datagen.key_range(cfg) == (1, 2526)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    spread = np.maximum.reduceat(keys, starts) - np.minimum.reduceat(keys, starts)
    assert spread.max() <= 120
    assert spread.max() > 100          # lines really vary inside an order
    assert np.array_equal(keys, np.round(keys))


def test_config_is_tpch_sf30_lineitem():
    cfg = config(CONFIG)
    assert cfg["orders"] == cfg["scale_factor"] * 1_500_000
    assert cfg["rows"] == 179_998_372
    assert datagen.key_range(cfg) == (1, 2526)


# -- traffic -----------------------------------------------------------------

def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _day(y, m, d):
    return (datetime.date(y, m, d) - datetime.date(1992, 1, 1)).days


def test_templates_are_the_tpch_shipdate_ranges():
    """Each template's windows are its query's l_shipdate range under every
    substitution parameter of TPC-H 3.0.1 sec. 2.4."""
    t = {q["name"]: [tuple(w) for w in q["windows"]]
         for q in mix(MIX)["templates"]}
    assert sorted(t) == ["Q1", "Q14", "Q15", "Q20", "Q3", "Q6", "Q7"]
    assert t["Q1"] == [(0, _day(1998, 12, 1) - d) for d in range(60, 121)]
    assert t["Q3"] == [(_day(1995, 3, d) + 1, _day(1998, 12, 31))
                       for d in range(1, 32)]
    years = [(_day(y, 1, 1), _day(y + 1, 1, 1) - 1) for y in range(1993, 1998)]
    assert t["Q6"] == t["Q20"] == years
    assert t["Q7"] == [(_day(1995, 1, 1), _day(1996, 12, 31))]
    months = [(y, m) for y in range(1993, 1998) for m in range(1, 13)]
    first = [_day(y, m, 1) for y, m in months] + [_day(1998, 1, 1)]
    assert t["Q14"] == [(a, b - 1) for a, b in zip(first, first[1:])]
    assert t["Q15"] == [(first[i], first[i + 3] - 1) for i in range(58)]
    assert mix(MIX)["streams"] == 4    # sec. 5.3.4's least count at SF 30


def _kind(lo, hi):
    """The template a window comes from, by its ends and length."""
    days = hi - lo + 1
    if lo == 0:
        return "Q1"
    if hi == _day(1998, 12, 31):
        return "Q3"
    return {731: "Q7", 365: "year", 366: "year"}.get(
        days, "Q14" if days <= 31 else "Q15")


def test_streams_run_every_template_once_a_cycle_and_repeat_per_seed():
    m = mix(MIX)
    a, b = loadgen.Streams(m, 42), loadgen.Streams(m, 42)
    k = len(m["templates"])
    rounds = [a.queries() for _ in range(2 * k)]
    assert all(np.array_equal(r, b.queries()) for r in rounds)
    other = loadgen.Streams(m, 43)
    assert not all(np.array_equal(r, other.queries()) for r in rounds)
    assert rounds[0].shape == (m["streams"], 2)
    want = sorted(["Q1", "Q3", "Q7", "Q14", "Q15", "year", "year"])
    for s in range(m["streams"]):
        for cycle in (rounds[:k], rounds[k:]):
            assert sorted(_kind(int(r[s, 0]), int(r[s, 1]))
                          for r in cycle) == want


def test_sample_holds_the_last_round():
    idx = loadgen.sample(5, 640, range(576, 640), 96)
    assert set(range(576, 640)) <= set(idx.tolist())
    assert 96 <= idx.size <= 160 and np.all(np.diff(idx) > 0)
    assert np.array_equal(idx, loadgen.sample(5, 640, range(576, 640), 96))


# -- reference and comparison -------------------------------------------------

@pytest.mark.parametrize("chunk", [reference.CHUNK, 7, 1000])
def test_reference_agrees_with_a_brute_force_scan(monkeypatch, chunk):
    monkeypatch.setattr(reference, "CHUNK", chunk)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 500, 5000).astype(np.float32)
    ref = reference.RangeScan(keys, top_k=32)
    queries = [(float(lo), float(lo + w - 1))
               for lo, w in zip(rng.integers(0, 500, 40),
                                rng.integers(1, 60, 40))]
    for (lo, hi), (count, ids) in zip(queries, ref.answers(queries)):
        rows = [i for i, k in enumerate(keys) if lo <= k <= hi]
        assert count == len(rows)
        assert ids.tolist() == rows[:32]


def test_compare_counts_each_kind_of_mismatch():
    want = [(3, np.array([1, 2, 3])), (0, np.array([], np.int64)),
            (40, np.arange(32))]
    got = [(3, np.array([1, 2, 3])), None, (41, np.arange(32))]
    numbers = check.compare(got, want)
    assert numbers == {"unanswered": 1, "count_mismatches": 1,
                       "rowid_mismatches": 0, "refresh_failures": 0}
    assert not check.is_correct(numbers)
    got[1] = (0, np.array([], np.int64))
    got[2] = (40, np.arange(1, 33))
    assert check.compare(got, want) == {"unanswered": 0, "count_mismatches": 0,
                                        "rowid_mismatches": 1,
                                        "refresh_failures": 0}
    assert check.is_correct(check.compare(want, want))
    assert not check.is_correct(check.compare(want, want, refresh_failures=1))


# -- least bytes of a search batch --------------------------------------------

def test_search_least_bytes_matches_a_hand_count():
    # 20 pages of 50 rows of a 4 B key and a 1 B valid flag, 30 entry
    # bitmaps of ceil(400 / 32) = 13 words of 4 B
    assert costs.search_least_bytes(20, 50, 30, 400) == 20 * 50 * 5 + 30 * 13 * 4
    assert costs.search_least_bytes(1, 10, 1, 32) == 10 * 5 + 1 * 1 * 4
    assert costs.search_least_bytes(1, 10, 1, 33) == 10 * 5 + 1 * 2 * 4
    assert costs.search_least_bytes(0, 50, 0, 400) == 0


# -- catalog -------------------------------------------------------------------

def test_committed_cells_resolve():
    cell = catalog.load_cell(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "ops_s", "index_bytes_per_row"}
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} == {
        "device_ms_per_batch", "search_hbm_share", "selected_page_share",
        "device_idle_share"}


def test_a_config_mix_and_metric_added_as_files_are_found(tmp_path):
    """A later cell needs only new files and entries: nothing committed is
    edited."""
    bench = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    cfg = dict(config(CONFIG), name="new_cfg")
    (bench / "configs" / "new_cfg.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new_mix.json").write_text(
        json.dumps(dict(mix(MIX), streams=1)))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "new_cfg",
                            "file": "chipbench/configs/new_cfg.json"})
    spec["workloads"].append({"name": "new_cell", "config": "new_cfg",
                              "traffic": "new_mix", "chips": 1})
    spec["per_layer"].append({"name": "new_metric", "unit": "%",
                              "workloads": ["new_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = catalog.load_cell("new_cell", root=tmp_path)
    assert cell.config["name"] == "new_cfg" and cell.mix["streams"] == 1
    assert cell.readers["new_metric"](None) == 7.0
    assert "search_hbm_share" not in cell.readers
    with pytest.raises(KeyError):
        catalog.load_cell("no_such_cell", root=tmp_path)


def test_peaks_are_published_and_a_missing_kind_is_an_error():
    p = catalog.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        catalog.peaks("cpu")


# -- the entry point -----------------------------------------------------------

def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--seed", "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    r = _run(REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs 1 TPU chip" in r.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
