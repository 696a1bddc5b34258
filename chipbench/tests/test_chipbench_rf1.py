"""The TPC-H refresh cell ``ship_tpch4_rf``: its configuration and mix as
committed, tiny runs on the CPU that come out correct, and its per-layer
metric ``write_idle_share``."""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import catalog, check, driver, loadgen, tracing

BENCH = Path(__file__).resolve().parents[1]
CELL = "ship_tpch4_rf"
SECONDS = 0.4
SEED = 2**31 + 123
# at the tiny size (1,600 rows, 32 pages): 6 new orders (24 lineitems) a
# round, and room for 5,000 more rows at the tail
TINY_ORDERS_PER_ROUND = 6
TINY_SPARE_PAGES = 100


def committed(tiny_root):
    return catalog.load_cell(CELL, root=tiny_root, bench_dir=catalog.BENCH_DIR)


def tiny_cell(tiny_root):
    """The cell with RF1 and the spare pages cut to the tiny table."""
    cell = committed(tiny_root)
    refresh = dict(cell.mix["refresh"], orders_per_round=TINY_ORDERS_PER_ROUND)
    return dataclasses.replace(
        cell, config=dict(cell.config, spare_pages=TINY_SPARE_PAGES),
        mix=dict(cell.mix, refresh=refresh))


def test_cell_is_the_s4_mix_with_rf1_at_the_specs_rate(tiny_root):
    cell = committed(tiny_root)
    s4 = json.loads((BENCH / "traffic" / "tpch_shipdate_s4.json").read_text())
    assert cell.chips == 1
    assert cell.mix["templates"] == s4["templates"]
    assert cell.mix["streams"] == s4["streams"] == 4
    assert cell.mix["warmup_rounds"] == 3
    # S = 4 refresh pairs of SF x 1,500 orders per 4 x 22 queries, a round
    # being one query of each stream
    sf = cell.config["scale_factor"]
    assert cell.mix["refresh"] == {
        "function": "RF1",
        "orders_per_round": round(4 * sf * 1500 / 88 * 4)} == {
        "function": "RF1", "orders_per_round": 8182}
    assert set(cell.readers) == {"write_idle_share"}
    assert cell.config["engine"]["drain_policy"] == "sync"


def test_configuration_keeps_the_read_only_deployment_and_adds_spare_pages():
    rf1, base = (json.loads((BENCH / "configs" / f"{n}.json").read_text())
                 for n in ("tpch_sf30_shipdate_rf1", "tpch_sf30_shipdate"))
    for k in ("table", "column", "scale_factor", "orders", "rows",
              "lines_per_order", "key", "key_unit", "index"):
        assert rf1[k] == base[k]
    assert rf1["engine"] == dict(base["engine"], drain_policy="sync")
    pages = -(-rf1["rows"] // rf1["index"]["page_card"])
    table, index, engine = driver.engine_args(rf1, pages)
    assert table == {"page_card": 50, "spare_pages": 131072}
    assert index["pages_per_shard"] == 932760
    assert engine == rf1["engine"]
    # the spare pages hold about 200 rounds of RF1
    rows = loadgen.Refresh({"function": "RF1", "orders_per_round": 8182},
                           rf1, 0).rows_per_round
    assert rows == 32728 and 195 < 131072 * 50 / rows < 205


@pytest.mark.parametrize("seed", [SEED, 5, -3])
def test_tiny_refresh_cell_is_correct(tiny_root, seed):
    r = driver.run_cell(tiny_cell(tiny_root), seed, SECONDS, trace=False)
    assert r["correct"] and r["failed"] == 0
    assert {k: v["value"] for k, v in r["checks"].items()} == dict.fromkeys(
        check.LIMITS, 0)
    # the window's operations: each round's 4 queries and its 24 rows
    assert r["attempted"] > 0 and r["attempted"] % 28 == 0
    assert set(r["metrics"]) == {"setup_s", "ops_s", "index_bytes_per_row"}


def test_tiny_traced_run_is_correct_and_reads_nothing_without_a_device(
        tiny_root):
    """On the CPU no device plane exists, so the device-trace metric is
    left out, not zero."""
    r = driver.run_cell(tiny_cell(tiny_root), SEED, SECONDS, trace=True)
    assert r["correct"]
    assert r["metrics"] == {}
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] == 0


def _ctx(window_s, busy_s, gaps):
    return driver.ReaderContext(trace=tracing.Summary(
        window_s=window_s, busy_s=busy_s, devices=1, device_ops=[],
        idle_gaps=gaps))


@pytest.mark.parametrize("window_s,busy_s,gaps,want", [
    (10.0, 9.0, [("write_many", 0.5), ("run_batch", 0.4), ("submit", 0.1)],
     5.0),
    (10.0, 9.9, [("run_batch", 0.1)], 0.0),   # the writes kept it busy
    (10.0, 0.0, [], None),                    # no device ran: nothing to read
    (0.0, 0.0, [], None),
])
def test_write_idle_share_is_the_window_share_idle_inside_write_many(
        window_s, busy_s, gaps, want):
    read = catalog.load_cell(CELL).readers["write_idle_share"]
    got = read(_ctx(window_s, busy_s, gaps))
    if want is None:
        assert got is None
    else:
        assert np.isclose(got, want)


def _reader_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "write_idle_share_under_test", BENCH / "metrics" / "write_idle_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("source,want", [
    (None, True),   # the program as it stands
    ("class QueryEngine:\n    def write(self, v):\n        pass\n", False),
    ("class QueryEngine:\n    pass\n\n"
     "class Other:\n    def write_many(self, v):\n        pass\n", False),
    ("def write_many(values):\n    pass\n", False),
])
def test_a_program_without_batched_writes_cannot_serve_the_cell(
        tmp_path, source, want):
    """Loading the cell's reader refuses a program whose ``QueryEngine`` has
    no ``write_many``: RF1 row by row would outlast any run."""
    mod = _reader_module()
    if source is None:
        assert mod.has_write_many() is want
        return
    path = tmp_path / "engine.py"
    path.write_text(source)
    assert mod.has_write_many(path) is want
