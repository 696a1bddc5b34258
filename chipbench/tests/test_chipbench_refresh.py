"""The refresh stream, windows by width and the configuration pass-through:
tiny runs on the CPU with RF1 inserts beside the query streams, checked by a
reference that tracks every acknowledged insert, and the faults that
reference has to catch."""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import catalog, check, control, datagen, driver, loadgen, reference

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
SECONDS = 0.4
# long enough, after a first compile, for dozens of rounds: the sample then
# holds month windows, whose first 32 rows reach into the inserts
FAULT_SECONDS = 1.5
SEED = 2**31 + 91
CONFIG = "tpch_sf30_shipdate"
# 6 new orders (24 lineitems) a round; room for 5,000 more rows at the tail
REFRESH = {"function": "RF1", "orders_per_round": 6}
SPARE_PAGES = 100


def config():
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def refresh_cell(tiny_root, **engine):
    """The tiny ``ship_tpch4`` with a refresh stream, spare pages, and every
    staged row drained before each batch unless ``engine`` says otherwise."""
    cell = catalog.load_cell("ship_tpch4", root=tiny_root,
                             bench_dir=catalog.BENCH_DIR)
    cfg = dict(cell.config, spare_pages=SPARE_PAGES)
    cfg["engine"] = dict(cfg["engine"], drain_policy="between_batches",
                         drain_units=None, **engine)
    return dataclasses.replace(cell, config=cfg,
                               mix=dict(cell.mix, refresh=REFRESH))


# -- runs with a refresh stream -------------------------------------------------

@pytest.mark.parametrize("seed", [SEED, 8, -21])
def test_refresh_mix_is_correct(tiny_root, seed):
    r = driver.run_cell(refresh_cell(tiny_root), seed, SECONDS, trace=False)
    assert r["correct"] and r["failed"] == 0
    assert {k: v["value"] for k, v in r["checks"].items()} == dict.fromkeys(
        check.LIMITS, 0)
    # the window's operations: each round's 4 queries and its 24 rows
    assert r["attempted"] > 0 and r["attempted"] % 28 == 0
    assert set(r["metrics"]) == {"setup_s", "ops_s", "index_bytes_per_row"}


def _drop_one_insert(monkeypatch):
    """``write_many`` acknowledges a round's rows but leaves the last out."""
    from repro.runtime.engine import QueryEngine

    def write_many(self, values):
        for v in np.asarray(values)[:-1].tolist():
            self.write(v)
    monkeypatch.setattr(QueryEngine, "write_many", write_many, raising=False)


def _count_inserts_only_after_a_drain(monkeypatch):
    """Acknowledged rows stay out of answers until the writer drains them:
    no staging overlay, and the between-batches drain runs after the batch."""
    from repro.runtime.engine import QueryEngine
    from repro.runtime.writer import MaintenanceWriter
    monkeypatch.setattr(MaintenanceWriter, "staged_rows",
                        property(lambda self: 0))
    drain = QueryEngine._maybe_drain_between_batches
    run_batch = QueryEngine.run_batch
    monkeypatch.setattr(QueryEngine, "_maybe_drain_between_batches",
                        lambda self: None)

    def late(self):
        done = run_batch(self)
        drain(self)
        return done
    monkeypatch.setattr(QueryEngine, "run_batch", late)


def _reference_rowid_off_by_one(monkeypatch):
    """The reference places the k-th insert at row ``rows + k + 1``."""
    answer = reference.RangeScan.answer

    def shifted(self, lo, hi, acked=0):
        count, ids = answer(self, lo, hi, acked)
        return count, ids + (ids >= self.keys.size)
    monkeypatch.setattr(reference.RangeScan, "answer", shifted)


# each fault, and the number compared that has to catch it
FAULTS = {
    "insert_dropped_by_write_many": (_drop_one_insert, "count_mismatches"),
    "insert_counted_only_after_a_drain": (_count_inserts_only_after_a_drain,
                                          "count_mismatches"),
    "reference_rowid_off_by_one": (_reference_rowid_off_by_one,
                                   "rowid_mismatches"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_refresh_fault_is_not_correct(tiny_root, monkeypatch, fault):
    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    r = driver.run_cell(refresh_cell(tiny_root), SEED + 1, FAULT_SECONDS,
                        trace=False)
    assert r["correct"] is False
    assert r["checks"][caught_by]["value"] > 0
    assert r["checks"]["refresh_failures"]["value"] == 0


def test_a_write_that_raises_is_a_failed_operation(tiny_root, monkeypatch):
    from repro.runtime.engine import QueryEngine

    def refuse(self, values):
        raise RuntimeError("refused")
    monkeypatch.setattr(QueryEngine, "write_many", refuse, raising=False)
    r = driver.run_cell(refresh_cell(tiny_root), SEED, SECONDS, trace=False)
    assert r["correct"] is False
    assert r["checks"]["refresh_failures"]["value"] == 1
    # the first warm-up refresh raised, and the stream stopped there
    assert r["failed"] == 0


@pytest.mark.parametrize("seed", [SEED, 4])
def test_control_with_refresh_is_not_correct(tiny_root, seed):
    numbers = control.control_numbers(refresh_cell(tiny_root), seed,
                                      rounds=6)
    assert not check.is_correct(numbers)


# -- the write entry point --------------------------------------------------------

@pytest.mark.parametrize("policy", ["sync", "between_batches"])
def test_write_many_equals_repeated_write(policy):
    """The harness's write entry point against ``QueryEngine.write`` row by
    row: equal counts, stats and row ids after ``flush``, without a writer
    (``sync``) and with one."""
    from repro.core.predicate import Predicate
    cfg = dict(config(), orders=300, rows=1200, spare_pages=8)
    keys = datagen.load_column(cfg, 5)
    rows = loadgen.Refresh(REFRESH, cfg, 5).rows()
    cfg["engine"] = dict(cfg["engine"], drain_policy=policy)
    engines = [driver.build_engine(cfg, keys) for _ in range(2)]
    driver.write_many(engines[0], rows)
    for v in rows.tolist():
        engines[1].write(v)
    out = []
    for eng in engines:
        eng.flush()
        tickets = [eng.submit(Predicate.between(lo, lo + w))
                   for lo, w in ((0, 2600), (300, 40), (1000, 3), (2000, 400))]
        eng.drain()
        stats = dataclasses.asdict(eng.stats)
        del stats["drain_us"]          # wall time
        out.append(([(t.count, t.row_ids.tolist()) for t in tickets], stats))
    assert out[0] == out[1]
    assert out[0][1]["writes"] == rows.size


# -- generators -------------------------------------------------------------------

def test_refresh_rounds_are_fixed_in_size_and_repeat_per_seed():
    cfg = config()
    block = {"function": "RF1", "orders_per_round": 8182}
    a, b = (loadgen.Refresh(block, cfg, 2**33 + 1) for _ in range(2))
    rounds = [a.rows() for _ in range(3)]
    assert all(np.array_equal(r, b.rows()) for r in rounds)
    assert all(r.size == 8182 * 4 == a.rows_per_round for r in rounds)
    assert not np.array_equal(rounds[0], rounds[1])
    lo, hi = datagen.key_range(cfg)
    assert all(r.min() >= lo and r.max() <= hi for r in rounds)
    # a stream of its own: not the load's first rows
    load = datagen.column_values(datagen.rng(2**33 + 1, datagen.LOAD),
                                 cfg["key"], np.full(8182, 4))
    assert not np.array_equal(rounds[0], load)
    with pytest.raises(ValueError):
        loadgen.Refresh({"function": "RF2", "orders_per_round": 1}, cfg, 0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_s4_draws_equal_the_parents(seed):
    """Templates given by ``windows`` consume the generator as before: the
    first 20 rounds of ``tpch_shipdate_s4`` equal the stored draws."""
    want = json.loads((DATA / "s4_draws.json").read_text())[str(seed)]
    s = loadgen.Streams(mix("tpch_shipdate_s4"), seed)
    assert [s.queries().astype(int).tolist() for _ in range(20)] == want


@pytest.mark.parametrize("width,first", [(1, [1, 2526]), (3, [1, 2524]),
                                         (26, [1, 2501]), (26, [30, 60])])
def test_width_templates_stay_inside_and_cover_their_range(width, first):
    m = {"streams": 3, "templates": [{"name": "W", "width": width,
                                      "first": first}]}
    s = loadgen.Streams(m, 17)
    w = np.concatenate([s.queries() for _ in range(4000)]).astype(np.int64)
    lo, hi = first
    assert w[:, 0].min() >= lo and w[:, 1].max() <= hi + width - 1
    assert np.all(w[:, 1] - w[:, 0] == width - 1)
    if hi - lo < 100:
        assert set(w[:, 0].tolist()) == set(range(lo, hi + 1))
    else:
        assert w[:, 0].min() < lo + 20 and w[:, 0].max() > hi - 20


def test_fig7_mix_lies_inside_the_keys():
    m = mix("tpch_shipdate_fig7")
    keys = datagen.key_range(config())
    assert m["streams"] == 1 and "refresh" not in m
    assert m["warmup_rounds"] == len(m["templates"]) == 4
    widths = {t["name"]: t["width"] for t in m["templates"]}
    assert widths == {"F0.001": 1, "F0.01": 1, "F0.1": 3, "F1": 26}
    for t in m["templates"]:
        assert t["first"] == [keys[0], keys[1] + 1 - t["width"]]
    with pytest.raises(ValueError):
        loadgen.Streams({"streams": 1, "templates": [
            {"name": "X", "width": 0, "first": [1, 5]}]}, 0)


# -- the reference over a growing table --------------------------------------------

def test_reference_counts_each_querys_acknowledged_inserts():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 300, 900).astype(np.float32)
    inserts = rng.integers(0, 300, 200).astype(np.float32)
    ref = reference.RangeScan(keys, top_k=32, inserts=inserts)
    table = np.concatenate([keys, inserts])
    for lo, w, acked in ((10, 5, 0), (10, 5, 200), (250, 60, 77), (0, 2, 13)):
        rows = [i for i, k in enumerate(table[: keys.size + acked])
                if lo <= k <= lo + w - 1]
        count, ids = ref.answer(lo, lo + w - 1, acked)
        assert count == len(rows) and ids.tolist() == rows[:32]
    with pytest.raises(ValueError):
        ref.answer(0, 1, 201)


# -- configuration pass-through --------------------------------------------------

ENGINE = {"batch": 64, "top_k": 32, "mode": "compact"}
CASES = {
    # the committed configuration: what the parent passed, and nothing more
    "committed": ({}, ({"page_card": 50},
                       {"num_shards": 4, "resolution": 400, "density": 0.2},
                       ENGINE)),
    "spare_pages": ({"spare_pages": 13}, (
        {"page_card": 50, "spare_pages": 13},
        {"num_shards": 4, "resolution": 400, "density": 0.2,
         "pages_per_shard": 29}, ENGINE)),
    "engine_keys": ({"engine": dict(ENGINE, drain_policy="on_depth",
                                    drain_depth=9, drain_units=None)}, (
        {"page_card": 50},
        {"num_shards": 4, "resolution": 400, "density": 0.2},
        dict(ENGINE, drain_policy="on_depth", drain_depth=9,
             drain_units=None))),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_args_pass_only_what_the_config_states(case):
    extra, want = CASES[case]
    assert driver.engine_args(dict(config(), **extra), 100) == want


def test_an_engine_key_outside_the_pass_through_is_refused():
    cfg = config()
    cfg["engine"] = dict(cfg["engine"], storage_dir="x")
    with pytest.raises(ValueError):
        driver.engine_args(cfg, 100)


# -- the Fig. 7 cell -------------------------------------------------------------

def fig7_cell(tiny_root):
    return catalog.load_cell("ship_fig7_1", root=tiny_root,
                             bench_dir=catalog.BENCH_DIR)


@pytest.mark.parametrize("seed", [SEED, 12])
def test_fig7_cell_is_correct_and_compiles_nothing_in_its_window(tiny_root,
                                                                 seed):
    cell = fig7_cell(tiny_root)
    assert set(cell.readers) == {"device_ms_per_batch", "search_hbm_share",
                                 "selected_page_share", "device_idle_share"}
    r = driver.run_cell(cell, seed, SECONDS, trace=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["compiles_in_window"] == 0
    assert {k: v["value"] for k, v in r["checks"].items()} == dict.fromkeys(
        check.LIMITS, 0)


@pytest.mark.parametrize("seed", [SEED, 3, 9])
def test_fig7_control_in_bfloat16_is_not_correct(tiny_root, seed):
    numbers = control.control_numbers(fig7_cell(tiny_root), seed, rounds=24)
    assert numbers["count_mismatches"] > 0
