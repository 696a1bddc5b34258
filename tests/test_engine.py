"""Batched query engine: ``search_batch`` / ``QueryEngine`` must agree
bit-for-bit with a Python loop of single-predicate ``index.search`` calls
and with a brute-force scan, including empty-result and full-table
predicates."""
import numpy as np
import pytest

from repro.core import index as hix
from repro.core.hippo import HippoIndex
from repro.core.predicate import (Predicate, to_bucket_bitmap,
                                  to_bucket_bitmaps)
from repro.runtime.engine import QueryEngine
from repro.storage.table import PagedTable


def make_index(values, page_card=8, resolution=32, density=0.25, **kw):
    table = PagedTable.from_values(values, page_card=page_card, spare_pages=64)
    return HippoIndex.create(table, resolution=resolution, density=density, **kw)


def brute_force(table, preds) -> np.ndarray:
    """Per-predicate counts of live keys in range, by a scan."""
    live = table.valid[: table.num_pages]
    keys = table.keys[: table.num_pages]
    out = []
    for p in preds:
        lo, hi = p.selectivity_interval()
        out.append(int((live & (keys >= lo) & (keys <= hi)).sum()))
    return np.asarray(out, np.int64)


def workload(rng, n):
    """Random ranges plus the edge predicates: empty interval, out-of-domain
    range (matches nothing), full table, point, and open-ended."""
    preds = []
    for _ in range(n):
        lo = float(rng.uniform(0, 1000))
        preds.append(Predicate.between(lo, lo + float(rng.uniform(0, 300))))
    preds += [
        Predicate(lo=5.0, hi=1.0),            # empty interval (lo > hi)
        Predicate.between(2000, 3000),        # no key in range
        Predicate.between(-1e30, 1e30),       # full table
        Predicate(),                          # unconstrained (±inf)
        Predicate.equality(float(rng.uniform(0, 1000))),
        Predicate.greater(500.0),
        Predicate.less(100.0),
    ]
    return preds


def test_to_bucket_bitmaps_matches_single():
    rng = np.random.default_rng(0)
    idx = make_index(rng.uniform(0, 1000, 600))
    preds = workload(rng, 25)
    qbms = np.asarray(to_bucket_bitmaps(preds, idx.state.histogram))
    for q, p in enumerate(preds):
        single = np.asarray(to_bucket_bitmap(p, idx.state.histogram))
        np.testing.assert_array_equal(qbms[q], single, err_msg=f"pred {q}")


def test_to_bucket_bitmaps_empty_batch():
    rng = np.random.default_rng(1)
    idx = make_index(rng.uniform(0, 1000, 100))
    assert to_bucket_bitmaps([], idx.state.histogram).shape[0] == 0


@pytest.mark.parametrize("dist", ["uniform", "skewed", "lowcard"])
def test_search_many_matches_search_loop(dist):
    rng = np.random.default_rng({"uniform": 10, "skewed": 11, "lowcard": 12}[dist])
    n = 3000
    if dist == "uniform":
        values = rng.uniform(0, 1000, n)
    elif dist == "skewed":
        values = rng.exponential(50, n)
    else:
        values = rng.integers(0, 12, n).astype(float)
    idx = make_index(values)
    preds = workload(rng, 32)
    assert len(preds) >= 32
    qbms = to_bucket_bitmaps(preds, idx.state.histogram)
    res = idx.search_batch(preds)
    masks, _ = hix._page_match(idx.state, qbms, idx.table.num_pages)
    np.testing.assert_array_equal(np.asarray(res.counts),
                                  brute_force(idx.table, preds))
    np.testing.assert_array_equal(np.asarray(res.pages_inspected),
                                  np.asarray(masks).sum(axis=1))
    for q, p in enumerate(preds):
        single = idx.search(p)
        assert int(res.counts[q]) == int(single.counts[0]), (dist, q)
        assert int(res.pages_inspected[q]) == int(single.pages_inspected[0])
        assert int(res.entries_matched[q]) == int(single.entries_matched[0])


def test_search_many_sees_maintenance():
    """The batched path reads the same state as the scalar path across
    insert and delete+vacuum maintenance."""
    rng = np.random.default_rng(7)
    idx = make_index(rng.uniform(0, 100, 400))
    for v in rng.uniform(0, 100, 10):
        idx.insert(float(v))
    idx.table.delete_where(40, 60)
    idx.vacuum()
    preds = [Predicate.between(0, 100), Predicate.between(45, 55),
             Predicate.between(39, 41)]
    res = idx.search_batch(preds)
    np.testing.assert_array_equal(np.asarray(res.counts),
                                  brute_force(idx.table, preds))
    for q, p in enumerate(preds):
        assert int(res.counts[q]) == idx.count(p)


def test_query_engine_recycles_slots_and_matches_loop():
    rng = np.random.default_rng(3)
    idx = make_index(rng.uniform(0, 1000, 2000))
    preds = workload(rng, 32)
    engine = QueryEngine(idx, batch=8)      # < len(preds): forces recycling
    counts = engine.run_all(preds)
    np.testing.assert_array_equal(counts, brute_force(idx.table, preds))
    assert engine.stats.served == len(preds)
    assert engine.stats.batches == -(-len(preds) // 8)
    assert all(t is None for t in engine.slots)


def test_query_engine_partial_batch_and_tickets():
    rng = np.random.default_rng(4)
    idx = make_index(rng.uniform(0, 1000, 500))
    engine = QueryEngine(idx, batch=16)
    t1 = engine.submit(Predicate.between(0, 1000))
    t2 = engine.submit(Predicate(lo=5.0, hi=1.0))
    assert not t1.done and t1.count is None
    finished = engine.run_batch()
    assert {t.qid for t in finished} == {t1.qid, t2.qid}
    assert t1.done and t1.count == idx.table.cardinality
    assert t2.done and t2.count == 0 and t2.entries_matched == 0
    assert engine.run_batch() == []         # nothing pending -> no-op


def test_query_engine_results_in_submission_order():
    rng = np.random.default_rng(5)
    idx = make_index(rng.uniform(0, 1000, 800))
    engine = QueryEngine(idx, batch=4)
    preds = workload(rng, 10)
    tickets = [engine.submit(p) for p in preds]
    engine.drain()
    for t, want in zip(tickets, brute_force(idx.table, preds)):
        assert t.count == want


def test_admission_is_constant_time_per_query():
    """The queue is a deque and slots come off a free list: admitting from a
    deep backlog must not re-scan the queue (the old list.pop(0) was O(n)
    per admit, O(n^2) per backlog). Guarded structurally — the queue type
    popleft's in O(1) — and behaviorally: FIFO order survives slot recycling
    and an external slot reset (the documented way to discard pending work)."""
    from collections import deque
    rng = np.random.default_rng(8)
    idx = make_index(rng.uniform(0, 1000, 200))
    engine = QueryEngine(idx, batch=4)
    assert isinstance(engine.queue, deque)
    tickets = [engine.submit(Predicate.between(i, i + 1.0)) for i in range(16)]
    engine.run_batch()
    assert [t.done for t in tickets[:4]] == [True] * 4      # FIFO head first
    assert not any(t.done for t in tickets[4:])
    # external slot reset (the writer suite's idiom for dropping admitted
    # work): the free list must resync instead of stranding the slots
    engine._admit()                        # tickets[4:8] occupy the slots
    engine.slots = [None] * engine.batch   # ... and are dropped on the floor
    engine.drain()
    assert not any(t.done for t in tickets[4:8])   # dropped, never served
    assert all(t.done for t in tickets[8:])        # the rest drain FIFO
    assert engine.stats.served == 12


def test_compact_fallback_accounted_in_occupancy_and_gather_stats():
    """Bugfix regression: the truncation fallback is a real extra dispatch,
    but it used to update neither slots_filled/pad_slots nor the gather
    telemetry — so occupancy and gather_occupancy overreported exactly when
    the engine was doing extra work. A compact_bucket of 1 forces every
    page-selecting query through the fallback."""
    rng = np.random.default_rng(21)
    # 400 pages, so a bucket of 1 gathers (``inspects_in_place``)
    idx = make_index(np.sort(rng.uniform(0, 1000, 3200)))
    engine = QueryEngine(idx, batch=4, compact_bucket=1)
    preds = [Predicate.between(0, 1000), Predicate.between(100, 900)]
    counts = engine.run_all(preds)
    np.testing.assert_array_equal(counts, brute_force(idx.table, preds))
    st = engine.stats
    assert st.compact_fallbacks == 2
    # gather telemetry covers both dispatches: the bucket-1 primary slab,
    # and the fallback at the never-truncating cap, which inspects the table
    # in place and so adds no gathered slab
    assert not idx.inspects_in_place(1)
    assert idx.inspects_in_place(idx.gather_cap)
    assert st.gather_slab_pages == 1
    assert st.compact_in_place == 1
    assert st.table_pages_seen == 2 * idx.table.num_pages
    assert st.selected_pages > 0
    assert 0.0 < st.gather_occupancy <= 1.0
    # slot accounting covers the fallback's padded width (pow2 >= 8)
    assert st.slots_filled == 2 + 2                  # primary batch + fallback
    assert st.pad_slots == (4 - 2) + (8 - 2)
    assert st.occupancy == pytest.approx(4 / 12)


@pytest.mark.parametrize("bucket,in_place", [(2, False), (4, True)])
def test_compact_bucket_in_place_is_dispatched_at_the_cap(monkeypatch, bucket,
                                                          in_place):
    """A bucket that ``inspects_in_place`` takes runs at the cap, where the
    slab width changes nothing, so one program serves every such width; a
    narrower bucket gathers at its own width. The TPU's crossover (a slab
    1/128 of its table) stands in for this backend's, so on a 400-page
    table a bucket of 4 goes in place and one of 2 gathers."""
    import jax
    monkeypatch.setitem(hix._GATHER_SHRINK, jax.default_backend(), 128)
    rng = np.random.default_rng(5)
    idx = make_index(np.sort(rng.uniform(0, 1000, 3200)))     # 400 pages
    assert idx.inspects_in_place(bucket) == in_place
    widths, search = [], idx.search_batch

    def spy(preds, max_selected, top_k=0):
        widths.append(max_selected)
        return search(preds, max_selected=max_selected, top_k=top_k)

    monkeypatch.setattr(idx, "search_batch", spy)
    engine = QueryEngine(idx, batch=4, compact_bucket=bucket)
    preds = [Predicate.between(100, 101), Predicate.between(600, 601)]
    counts = engine.run_all(preds)
    np.testing.assert_array_equal(counts, brute_force(idx.table, preds))
    st = engine.stats
    assert widths[0] == (idx.gather_cap if in_place else bucket)
    assert widths[1:] == [idx.gather_cap] * (len(widths) - 1)   # fallbacks
    assert st.compact_in_place == len(widths) - (not in_place)
    assert st.gather_slab_pages == (0 if in_place else bucket)


def test_writerless_noop_delete_skips_vacuum():
    """Bugfix regression: the sync (writerless) delete path always ran
    ``index.vacuum()`` — a dispatch that re-summarizes nothing — even when
    ``delete_where`` removed zero rows."""
    rng = np.random.default_rng(22)
    idx = make_index(rng.uniform(0, 1000, 400))
    engine = QueryEngine(idx, batch=4)
    assert engine.delete(5000.0, 6000.0) == 0        # no key in range
    assert idx.counters.vacuums == 0                 # vacuum skipped
    assert engine.stats.deletes == 0
    n = engine.delete(0.0, 100.0)
    assert n > 0 and idx.counters.vacuums == 1       # real deletes still vacuum
    assert engine.run_all([Predicate.between(0, 1000)])[0] == \
        idx.table.cardinality


def test_table_dirty_page_counter_tracks_lifecycle():
    """``PagedTable.num_dirty`` backs the O(1) on_depth backlog read: it must
    track delete_where (no double count), clear_dirty (idempotent), and
    truncate_to exactly."""
    from repro.storage.table import PagedTable
    t = PagedTable.from_values(np.arange(64, dtype=np.float32), page_card=8)
    assert t.num_dirty == 0
    t.delete_where(0.0, 9.0)                       # dirties pages 0 and 1
    assert t.num_dirty == 2
    t.delete_where(5.0, 11.0)                      # page 1 already dirty
    assert t.num_dirty == 2
    assert t.num_dirty == int(t.dirty.sum())
    t.clear_dirty(np.asarray([0]))
    assert t.num_dirty == 1
    t.clear_dirty(np.asarray([0]))                 # idempotent
    assert t.num_dirty == 1
    t.clear_dirty(np.asarray([1, 1]))              # duplicate ids: one clear
    assert t.num_dirty == 0
    t.delete_where(60.0, 63.0)                     # dirties the last page
    assert t.num_dirty == 1
    t.truncate_to(4, t.page_card)                  # drops the dirty page too
    assert t.num_dirty == int(t.dirty.sum()) == 0


def test_engine_compact_default_matches_explicit_dense():
    """The default engine is the one path: it equals an engine given
    ``mode="compact"`` explicitly and a scan, and every batch and query is
    served by it."""
    rng = np.random.default_rng(9)
    idx = make_index(np.sort(rng.uniform(0, 1000, 1500)))
    preds = workload(rng, 12)
    default = QueryEngine(idx, batch=8)
    assert default.mode == "compact"
    counts = default.run_all(preds)
    np.testing.assert_array_equal(counts, brute_force(idx.table, preds))
    np.testing.assert_array_equal(
        counts, QueryEngine(idx, batch=8, mode="compact").run_all(preds))
    assert default.stats.compact_batches == default.stats.batches
    assert (default.stats.compact_hits + default.stats.compact_fallbacks
            == default.stats.served)
