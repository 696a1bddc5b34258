"""Whole runs of the tiny cells on the CPU, skipping only the harness's look
for a chip: a sound run comes out correct, and the control and each fault
the cells can have, planted under the timed path, come out not correct."""
import numpy as np
import pytest

import jax

from chipbench import catalog, check, control, driver

SECONDS = 0.4
SEED = 2**31 + 77


CELL = "ship_tpch4"


def cell(tiny_root, name=CELL):
    return catalog.load_cell(name, root=tiny_root, bench_dir=catalog.BENCH_DIR)


@pytest.mark.parametrize("seed", [SEED, 3, -11])
def test_sound_run_is_correct_and_reports_its_metrics(tiny_root, seed):
    r = driver.run_cell(cell(tiny_root), seed, SECONDS, trace=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"setup_s", "ops_s", "index_bytes_per_row"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert r["compiles_in_window"] == 0
    assert list(r)[-1] == "checks"
    assert {k: v["value"] for k, v in r["checks"].items()} == dict.fromkeys(
        check.LIMITS, 0)


def test_traced_run_reports_per_layer_metrics_it_can_read(tiny_root):
    """On the CPU no device plane exists, so only the counter metric has
    something to read; the others are left out, not zero."""
    r = driver.run_cell(cell(tiny_root), SEED, SECONDS, trace=True)
    assert r["correct"]
    assert set(r["metrics"]) == {"selected_page_share"}
    assert 0 < r["metrics"]["selected_page_share"]["value"] <= 100
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] == 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("seed", [SEED, 5, 6])
def test_control_in_bfloat16_is_not_correct(tiny_root, seed):
    numbers = control.control_numbers(cell(tiny_root), seed, rounds=6)
    assert not check.is_correct(numbers)
    assert numbers["count_mismatches"] > 0


# -- faults planted under the timed path ----------------------------------------

def _alter_one_answer(monkeypatch):
    from repro.runtime.engine import QueryEngine
    orig = QueryEngine.run_batch

    def run_batch(self):
        done = orig(self)
        if done:
            done[0].count += 1
        return done
    monkeypatch.setattr(QueryEngine, "run_batch", run_batch)


def _drop_half_the_batch(monkeypatch):
    from repro.runtime.engine import QueryEngine
    orig = QueryEngine._execute_compact

    def execute(self, active):
        keep = active[: len(active) // 2]
        counts, inspected, matched, ids = orig(self, keep)
        pad = len(active) - len(keep)
        z = np.zeros(pad, counts.dtype)
        ids = None if ids is None else np.concatenate(
            [ids, np.full((pad, ids.shape[1]), -1, ids.dtype)])
        return (np.concatenate([counts, z]), np.concatenate([inspected, z]),
                np.concatenate([matched, z]), ids)
    monkeypatch.setattr(QueryEngine, "_execute_compact", execute)


def _skip_the_shard_reduction(monkeypatch):
    """Shards stand for chips: only shard 0's part of each answer is kept."""
    from repro.core import index as hix
    orig = hix.search_compact_many_sharded

    def shard0(shards, qbms, keys, valid, los, his, **kw):
        one = jax.tree_util.tree_map(lambda a: a[:1], shards)
        return orig(one, qbms[:1], keys[:1], valid[:1], los, his, **kw)
    monkeypatch.setattr(hix, "search_compact_many_sharded", shard0)


def _repeat_the_last_batch(monkeypatch):
    """A step that returns its state unchanged: each batch hands out the
    answers of the batch before it."""
    from repro.runtime.engine import QueryEngine
    orig = QueryEngine._execute_compact
    last = {}

    def execute(self, active):
        out = orig(self, active)
        prev = last.get(len(active), out)
        last[len(active)] = out
        return prev
    monkeypatch.setattr(QueryEngine, "_execute_compact", execute)


FAULTS = {
    "answer_altered": _alter_one_answer,
    "half_batch_left_out": _drop_half_the_batch,
    "shard_reduction_left_out": _skip_the_shard_reduction,
    "state_unchanged": _repeat_the_last_batch,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_under_the_timed_path_is_not_correct(tiny_root, monkeypatch,
                                                    fault):
    FAULTS[fault](monkeypatch)
    r = driver.run_cell(cell(tiny_root), SEED + 1, SECONDS, trace=False)
    assert r["correct"] is False
    assert any(v["value"] > v["limit"] for v in r["checks"].values())
