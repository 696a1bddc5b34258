"""Batched eager insert (Algorithm 3 for rows appended in heap order) against
its plain reference, a loop of single-tuple ``insert_tuple`` calls: the same
logical entries, coverage and summaries; the append that feeds it; and the
served ``write_many`` entry point against a numpy scan of the grown table."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import histogram as hg
from repro.core import index as hix
from repro.core.partition import ShardedHippoIndex, shard_state, summary_of
from repro.core.predicate import Predicate
from repro.runtime.engine import QueryEngine
from repro.storage.table import PagedTable

C, H, D = 8, 32, 0.25


def logical(st: hix.HippoState):
    """(starts, ends, bitmaps) of the live entries in sorted order, and the
    last summarized page: what a search reads."""
    e = int(st.num_entries)
    order = np.asarray(st.sorted_order)[:e]
    return (np.asarray(st.starts)[order].tolist(),
            np.asarray(st.ends)[order].tolist(),
            np.asarray(st.bitmaps)[order].tolist(),
            int(st.summarized_until))


def build(values, hist=None):
    table = PagedTable.from_values(np.asarray(values, np.float32), page_card=C,
                                   spare_pages=256)
    cfg = hix.HippoConfig(resolution=H, density=D, page_card=C, max_slots=512,
                          relocate_on_update=False)
    hist = hist or hg.build(jnp.asarray(np.asarray(values, np.float32)), H)
    return cfg, table, hix.build(cfg, hist, table.device_keys(),
                                 table.device_valid())


def row_by_row(cfg, table, st, values, live):
    """The reference: append each row, and index the live ones one
    ``insert_tuple`` at a time."""
    for v, alive in zip(values, live):
        page, _ = table.insert(float(v))
        if alive:
            st = hix.insert_tuple(cfg, st, jnp.float32(v), jnp.int32(page))
        else:
            table.valid[page, table.fill - 1] = False
    return st


def batched(cfg, table, st, values, live):
    pages = (table.tail + np.arange(values.size)) // C
    table.append(values, live)
    return hix.insert_appended(cfg, jax.tree.map(jnp.copy, st),
                               *hix.pad_rows(values, pages, live))


# base rows, appended rows, and which appended rows never go live
CASES = {
    "mid_page": (333, 60, ()),            # the tail page is partly full
    "page_boundary": (320, 37, ()),       # the first row opens a page
    "many_pages": (333, 700, ()),         # opens ~88 pages
    "one_row": (333, 1, ()),
    "empty_index": (0, 90, ()),           # no entry yet: the first creates
    "dead_rows": (333, 120, (0, 1, 2, 40, 41, 42, 43, 44, 45, 46, 47, 90)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_insert_appended_equals_the_row_by_row_loop(case):
    base_n, add_n, dead = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    base = rng.uniform(0, 100, base_n).astype(np.float32)
    values = rng.uniform(0, 100, add_n).astype(np.float32)
    live = np.ones(add_n, bool)
    live[list(dead)] = False
    hist = hg.build_uniform(0.0, 100.0, H) if base_n == 0 else None
    cfg, table_a, st = build(base, hist)
    _, table_b, _ = build(base, hist)
    want = row_by_row(cfg, table_a, st, values, live)
    got = batched(cfg, table_b, st, values, live)
    assert logical(got) == logical(want)
    assert int(got.num_entries) == int(want.num_entries) > 0
    np.testing.assert_array_equal(np.asarray(summary_of(got)),
                                  np.asarray(summary_of(want)))
    np.testing.assert_array_equal(table_b.keys, table_a.keys)
    np.testing.assert_array_equal(table_b.valid, table_a.valid)
    assert (table_b.num_pages, table_b.fill) == (table_a.num_pages,
                                                  table_a.fill)


def test_insert_appended_matches_a_relocating_loop_logically():
    """With relocation on, the row-by-row path moves entries to new slots;
    the batch sets bits in place. The entries a search reads agree."""
    rng = np.random.default_rng(11)
    base = rng.uniform(0, 100, 333).astype(np.float32)
    values = rng.uniform(0, 100, 200).astype(np.float32)
    cfg, table_a, st = build(base)
    _, table_b, _ = build(base)
    relocating = dataclasses.replace(cfg, relocate_on_update=True)
    want = row_by_row(relocating, table_a, st, values, np.ones(200, bool))
    got = batched(cfg, table_b, st, values, np.ones(200, bool))
    assert logical(got) == logical(want)
    assert int(want.num_slots) > int(got.num_slots) == int(got.num_entries)


def test_a_last_entry_at_density_d_is_closed():
    """The rule is ``density < D``: an entry whose density equals D exactly
    takes no further page, in the batch as in the loop."""
    hist = hg.build_uniform(0.0, 32.0, H)   # key k + 0.5 falls in bucket k
    cfg, table_a, st = build(np.zeros(0, np.float32), hist)
    _, table_b, _ = build(np.zeros(0, np.float32), hist)
    # page 0: buckets 0-3 (density 4/32 < D); page 1: buckets 4-7, which the
    # open entry takes, reaching 8/32 = D exactly; page 2 must open an entry
    values = np.asarray([0.5, 1.5, 2.5, 3.5, 0.5, 1.5, 2.5, 3.5,
                         4.5, 5.5, 6.5, 7.5, 4.5, 5.5, 6.5, 7.5,
                         20.5, 20.5, 20.5, 20.5, 20.5, 20.5, 20.5, 20.5],
                        np.float32)
    live = np.ones(values.size, bool)
    want = row_by_row(cfg, table_a, st, values, live)
    got = batched(cfg, table_b, st, values, live)
    assert logical(got) == logical(want)
    starts, ends, bitmaps, until = logical(got)
    assert (starts, ends, until) == ([0, 2], [1, 2], 2)


# -- the sharded index --------------------------------------------------------

def sharded(values, num_shards=4, **kw):
    table = PagedTable.from_values(np.asarray(values, np.float32),
                                   page_card=C, spare_pages=256)
    return ShardedHippoIndex.create(table, num_shards=num_shards,
                                    resolution=H, density=D,
                                    relocate_on_update=False, **kw)


def test_an_append_across_two_shards_equals_the_row_by_row_loop():
    rng = np.random.default_rng(21)
    base = rng.uniform(0, 100, 200)
    extra = rng.uniform(0, 100, 150).astype(np.float32)
    a = sharded(base, pages_per_shard=30)
    b = sharded(base, pages_per_shard=30)
    for v in extra:
        a.insert(float(v))
    b.insert_batch(extra)
    assert b.table.num_pages - 1 >= 30 > 200 // C     # crossed into shard 1
    for s in range(4):
        assert logical(shard_state(b.state.shards, s)) == \
            logical(shard_state(a.state.shards, s))
    np.testing.assert_array_equal(np.asarray(b.state.summaries),
                                  np.asarray(a.state.summaries))
    assert b.summarized_until == a.summarized_until == b.table.num_pages - 1
    assert b.counters.pages_opened == a.counters.pages_opened > 0
    assert b.counters.entries_created == a.counters.entries_created > 0


def test_a_capacity_refusal_leaves_table_slabs_and_state_as_they_were():
    idx = sharded(np.linspace(0, 99, 64), num_shards=2, max_slots=12)
    idx.search_batch([Predicate.between(0, 50)])      # builds the slab cache
    t = idx.table
    before = (t.num_pages, t.fill, t.keys.copy(), t.valid.copy(),
              t.slab_upload_bytes)
    cache = t._dev_shard
    slabs = [np.asarray(a) for a in cache[1:]]
    state = [np.asarray(leaf) for leaf in jax.tree.leaves(idx.state)]
    with pytest.raises(RuntimeError, match="slot capacity"):
        idx.insert_batch(np.linspace(0, 99, 300))
    assert (t.num_pages, t.fill) == before[:2]
    np.testing.assert_array_equal(t.keys, before[2])
    np.testing.assert_array_equal(t.valid, before[3])
    assert t.slab_upload_bytes == before[4]
    assert t._dev_shard is cache and not t._dev_shard_stale
    for got, want in zip(cache[1:], slabs):
        np.testing.assert_array_equal(np.asarray(got), want)
    for got, want in zip(jax.tree.leaves(idx.state), state):
        np.testing.assert_array_equal(np.asarray(got), want)


# base rows, appended rows, and the slab bytes the append sends: the pages
# it touched, in 64-page blocks (the last one moved back over the one
# before) or one power-of-two block below that, at 5 B a slot
PATCHES = {
    "8_whole_pages": (200, 64, 8 * C * 5),
    "64_whole_pages": (200, 512, 64 * C * 5),
    "two_part_pages": (200, 10, 2 * C * 5),
    "88_pages": (200, 700, 128 * C * 5),
}


@pytest.mark.parametrize("case", sorted(PATCHES))
def test_an_append_patches_the_slab_cache_in_place(case):
    base_n, n, sent = PATCHES[case]
    rng = np.random.default_rng(sorted(PATCHES).index(case))
    idx = sharded(rng.uniform(0, 100, base_n), num_shards=2,
                  pages_per_shard=200)
    idx.search_batch([Predicate.between(0, 50)])      # builds the slab cache
    t = idx.table
    uploaded = t.slab_upload_bytes
    assert uploaded == 2 * 200 * C * 5                # the whole view, once
    idx.insert_batch(rng.uniform(0, 100, n))
    assert t._dev_shard[0] == (2, 200) and not t._dev_shard_stale
    assert t.slab_upload_bytes - uploaded == sent
    if n % C == 0 and n // C in (8, 64):
        assert sent == n * 5                          # whole pages: the rows
    for dev, host in ((t._dev_shard[1], t.keys), (t._dev_shard[2], t.valid)):
        np.testing.assert_array_equal(
            np.asarray(dev).reshape(-1, C)[: t.num_pages], host[: t.num_pages])
    got = idx.search_batch([Predicate.between(-1e30, 1e30)])
    assert int(got.counts[0]) == base_n + n


def test_a_rollback_after_the_append_patches_the_slabs_back():
    idx = sharded(np.linspace(0, 99, 200), num_shards=2, pages_per_shard=200)
    idx.search_batch([Predicate.between(0, 50)])
    t = idx.table
    snap = (t.num_pages, t.fill)
    t.append(np.full(30, 7.0, np.float32))
    t.truncate_to(*snap)
    keys = np.asarray(t._dev_shard[1]).reshape(-1, C)
    valid = np.asarray(t._dev_shard[2]).reshape(-1, C)
    np.testing.assert_array_equal(keys[: t.capacity_pages], t.keys)
    np.testing.assert_array_equal(valid[: t.capacity_pages], t.valid)
    assert int(idx.search_batch([Predicate.between(6.5, 7.5)]).counts[0]) == \
        int(((t.keys >= 6.5) & (t.keys <= 7.5) & t.valid).sum())


# -- the served entry point ---------------------------------------------------

def scan(table, lo, hi, top_k):
    """Count and first ``top_k`` row ids of [lo, hi] over the whole table."""
    keys = table.keys[: table.num_pages].reshape(-1)
    valid = table.valid[: table.num_pages].reshape(-1)
    rows = np.flatnonzero(valid & (keys >= lo) & (keys <= hi))
    return rows.size, rows[:top_k].tolist()


@pytest.mark.parametrize("policy", ["sync", "between_batches"])
def test_write_many_answers_equal_a_scan_of_the_grown_table(policy):
    rng = np.random.default_rng(31)
    idx = sharded(rng.uniform(0, 100, 300), pages_per_shard=40)
    eng = QueryEngine(idx, batch=8, mode="compact", top_k=16,
                      drain_policy=policy, drain_units=None)
    windows = [(0.0, 100.0), (20.0, 22.5), (55.0, 70.0), (99.0, 99.5)]
    written = 0
    for size in (37, 1, 120, 64):
        eng.write_many(rng.uniform(0, 100, size))
        written += size
        tickets = [eng.submit(Predicate.between(lo, hi)) for lo, hi in windows]
        eng.drain()
        assert idx.table.cardinality == 300 + written
        for t, (lo, hi) in zip(tickets, windows):
            assert (t.count, t.row_ids.tolist()) == scan(idx.table, lo, hi, 16)
    assert eng.stats.writes == written
    assert eng.stats.insert_pages_opened == idx.counters.pages_opened > 0
    assert eng.stats.insert_entries_created == idx.counters.entries_created > 0
    assert eng.stats.slab_upload_bytes == idx.table.slab_upload_bytes > 0


@pytest.mark.parametrize("policy", ["sync", "manual"])
def test_write_many_refuses_a_batch_past_the_layout_whole(policy):
    idx = sharded(np.linspace(0, 99, 64), num_shards=2, pages_per_shard=10)
    eng = QueryEngine(idx, batch=4, drain_policy=policy)
    with pytest.raises(RuntimeError, match="shard layout full"):
        eng.write_many(np.linspace(0, 99, 200))
    assert idx.table.cardinality == 64 and eng.stats.writes == 0
    assert eng.writer is None or eng.writer.queue_depth == 0
