"""Async maintenance writer: interleaved write/delete/query/vacuum through
the engine must produce counts bit-identical to a fully-synchronous oracle,
drains must stay shard-local and atomic (refusals roll back cleanly), and
queries during a mid-flight shard swap must refuse loudly."""
import numpy as np
import pytest

from repro.core.partition import ShardedHippoIndex, shard_state
from repro.core.predicate import Predicate
from repro.runtime.engine import QueryEngine
from repro.runtime.writer import MaintenanceWriter
from repro.storage.table import PagedTable

pytestmark = pytest.mark.writer


def make_sidx(values, num_shards=4, page_card=8, resolution=32, density=0.25,
              spare_pages=256, **kw):
    table = PagedTable.from_values(np.asarray(values).copy(),
                                   page_card=page_card,
                                   spare_pages=spare_pages)
    return ShardedHippoIndex.create(table, num_shards=num_shards,
                                    resolution=resolution, density=density,
                                    **kw)


def brute_force(table, lo, hi):
    live = table.valid[: table.num_pages]
    keys = table.keys[: table.num_pages]
    return int((live & (keys >= lo) & (keys <= hi)).sum())


def workload(rng, n):
    preds = []
    for _ in range(n):
        lo = float(rng.uniform(0, 100))
        preds.append(Predicate.between(lo, lo + float(rng.uniform(0, 30))))
    preds += [
        Predicate(lo=5.0, hi=1.0),            # empty interval
        Predicate.between(-1e30, 1e30),       # full table
        Predicate.equality(float(rng.uniform(0, 100))),
    ]
    return preds


# ---------------------------------------------------------------------------
# The acceptance invariant: staged == synchronous, at every query point
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["between_batches", "on_depth", "manual"])
def test_interleaved_ops_match_sync_oracle(policy):
    """Random write/delete/query streams: the async engine's counts equal a
    fully-synchronous ShardedHippoIndex oracle after every single query,
    whether staged rows are still queued or already drained."""
    rng = np.random.default_rng({"between_batches": 0, "on_depth": 1,
                                 "manual": 2}[policy])
    base = rng.uniform(0, 100, 300)
    sync = make_sidx(base)
    aidx = make_sidx(base)
    engine = QueryEngine(aidx, batch=8, drain_policy=policy, drain_depth=16)
    preds = workload(rng, 5)
    for step in range(30):
        op = rng.choice(["write", "write", "write", "delete", "query"])
        if op == "write":
            v = float(rng.uniform(0, 100))
            sync.insert(v)
            engine.write(v)
        elif op == "delete":
            lo = float(rng.uniform(0, 90))
            sync.table.delete_where(lo, lo + 3.0)
            sync.vacuum()
            engine.delete(lo, lo + 3.0)
        else:
            got = engine.run_all(preds)
            want = np.asarray(sync.search_batch(preds).counts, np.int64)
            np.testing.assert_array_equal(got, want, err_msg=f"step {step}")
    engine.flush()
    assert engine.writer.queue_depth == 0
    got = engine.run_all(preds)
    want = np.asarray(sync.search_batch(preds).counts, np.int64)
    np.testing.assert_array_equal(got, want)
    truth = [brute_force(aidx.table, *p.selectivity_interval()) for p in preds]
    np.testing.assert_array_equal(got, truth)


def test_write_query_vacuum_query_sequence():
    """The ISSUE's canonical sequence: write -> query -> vacuum -> query,
    staged and synchronous paths bit-identical throughout."""
    rng = np.random.default_rng(7)
    base = rng.uniform(0, 100, 400)
    sync = make_sidx(base)
    aidx = make_sidx(base)
    engine = QueryEngine(aidx, batch=8)        # default: between_batches
    preds = workload(rng, 8)

    for v in rng.uniform(0, 100, 40):
        sync.insert(float(v))
        engine.write(float(v))
    np.testing.assert_array_equal(
        engine.run_all(preds), np.asarray(sync.search_batch(preds).counts))

    sync.table.delete_where(30, 45)
    sync.vacuum()
    engine.delete(30, 45)
    np.testing.assert_array_equal(
        engine.run_all(preds), np.asarray(sync.search_batch(preds).counts))

    engine.flush()                              # drains remaining vacuums too
    assert not aidx.table.dirty[: aidx.table.num_pages].any()
    np.testing.assert_array_equal(
        engine.run_all(preds), np.asarray(sync.search_batch(preds).counts))


def test_counts_exact_while_rows_still_staged():
    """The never-stale contract: queries see staged rows before any drain,
    through the index's search and through the engine."""
    rng = np.random.default_rng(11)
    aidx = make_sidx(rng.uniform(0, 100, 200))
    writer = MaintenanceWriter(aidx)
    card = aidx.table.cardinality
    for v in [10.0, 10.5, 11.0, 95.0]:
        writer.write(v)
    assert writer.queue_depth == 4
    # the one sharded program via the index surface
    assert int(aidx.search_batch([Predicate.between(-1e30, 1e30)]).counts[0]) \
        == card + 4
    assert int(aidx.search_batch([Predicate.between(10, 11)]).counts[0]) \
        == brute_force(aidx.table, 10, 11) + 3
    # the engine's batches
    engine = QueryEngine(aidx, batch=4, drain_policy="manual", writer=writer)
    got = engine.run_all([Predicate.between(10, 11),
                          Predicate.between(-1e30, 1e30)])
    np.testing.assert_array_equal(
        got, [brute_force(aidx.table, 10, 11) + 3, card + 4])
    assert writer.queue_depth == 4              # manual policy: still staged


def test_delete_kills_staged_rows_before_they_land():
    rng = np.random.default_rng(13)
    aidx = make_sidx(rng.uniform(0, 100, 150))
    engine = QueryEngine(aidx, batch=4, drain_policy="manual")
    for v in [25.0, 26.0, 27.0, 95.0]:
        engine.write(v)
    deleted_in_table = brute_force(aidx.table, 20, 30)
    n = engine.delete(20, 30)
    assert n == deleted_in_table + 3                   # n includes staged kills
    assert engine.writer.staged_rows == 1              # only 95.0 survives
    assert engine.writer.queue_depth == 4              # dead rows still queued
    want = brute_force(aidx.table, 0, 100) + 1
    assert engine.run_all([Predicate.between(0, 100)])[0] == want
    engine.flush()
    # dead staged rows reached the table as invalid tuples: counts unchanged
    assert brute_force(aidx.table, 0, 100) == want
    assert engine.run_all([Predicate.between(0, 100)])[0] == want


# ---------------------------------------------------------------------------
# Drain mechanics: policies, locality, atomicity
# ---------------------------------------------------------------------------

def test_between_batches_policy_drains_incrementally():
    rng = np.random.default_rng(17)
    aidx = make_sidx(rng.uniform(0, 100, 200))
    engine = QueryEngine(aidx, batch=4, drain_policy="between_batches",
                         drain_units=1)
    for v in rng.uniform(0, 100, 20):
        engine.write(float(v))
    assert engine.stats.queue_depth == 20
    assert engine.stats.drains == 0             # nothing drained at write time
    engine.run_all(workload(rng, 3))
    assert engine.stats.drains > 0
    assert engine.stats.queue_depth < 20
    while engine.writer.pending_units:
        engine.run_batch()                      # empty batches keep draining
    assert engine.writer.queue_depth == 0
    assert engine.stats.drained_rows + engine.writer.stats.killed == 20
    assert engine.stats.drain_us > 0
    assert engine.stats.peak_queue_depth == 20


def test_on_depth_policy_triggers_at_threshold():
    rng = np.random.default_rng(19)
    aidx = make_sidx(rng.uniform(0, 100, 200))
    engine = QueryEngine(aidx, batch=4, drain_policy="on_depth",
                         drain_depth=8)
    for v in rng.uniform(0, 100, 7):
        engine.write(float(v))
    assert engine.stats.drains == 0 and engine.stats.queue_depth == 7
    engine.write(50.0)                          # depth hits 8: full drain
    assert engine.writer.queue_depth == 0
    assert engine.stats.drained_rows == 8


def test_manual_policy_only_flush_drains():
    rng = np.random.default_rng(23)
    aidx = make_sidx(rng.uniform(0, 100, 200))
    engine = QueryEngine(aidx, batch=4, drain_policy="manual")
    for v in rng.uniform(0, 100, 10):
        engine.write(float(v))
    engine.run_all(workload(rng, 6))
    assert engine.stats.drains == 0 and engine.writer.queue_depth == 10
    assert engine.flush() == 10
    assert engine.writer.queue_depth == 0


def test_drain_swaps_only_the_drained_shard():
    """A drain rebuilds exactly one shard's slice: every other shard's
    bitmaps/entry arrays are bit-identical before and after the swap."""
    rng = np.random.default_rng(29)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 300)))
    writer = MaintenanceWriter(aidx)
    for v in rng.uniform(0, 100, 12):
        writer.write(float(v))
    pending = writer.pending_shards()
    assert len(pending) == 1                    # tail appends: one shard
    s = pending[0]
    before = {t: np.asarray(shard_state(aidx.state.shards, t).bitmaps).copy()
              for t in range(aidx.num_shards)}
    summaries_before = np.asarray(aidx.state.summaries).copy()
    assert writer.drain(max_units=1) == 12
    for t in range(aidx.num_shards):
        after = np.asarray(shard_state(aidx.state.shards, t).bitmaps)
        if t != s:
            np.testing.assert_array_equal(after, before[t], err_msg=f"shard {t}")
            np.testing.assert_array_equal(np.asarray(aidx.state.summaries[t]),
                                          summaries_before[t])


def test_drain_patches_slab_cache_in_place():
    """After a drain the table's sharded device view is patched at the
    appended pages (fresh, same layout) rather than left stale for a full
    (S, PPS, C) rebuild."""
    rng = np.random.default_rng(31)
    aidx = make_sidx(rng.uniform(0, 100, 200))
    engine = QueryEngine(aidx, batch=4, drain_policy="manual")
    engine.run_all([Predicate.between(0, 50)])  # builds the slab cache
    t = aidx.table
    assert t._dev_shard is not None and not t._dev_shard_stale
    uploaded = t.slab_upload_bytes
    for v in rng.uniform(0, 100, 10):
        engine.write(float(v))
    engine.flush()
    assert t._dev_shard is not None
    assert not t._dev_shard_stale               # patched, not invalidated
    assert t._dev_shard[0] == (aidx.spec.num_shards, aidx.spec.pages_per_shard)
    # 200 rows fill 25 pages of 8: the 10 rows open pages 25 and 26, and
    # only those two pages go up (4 B of key and 1 B of validity a slot)
    assert t.slab_upload_bytes - uploaded == 2 * t.page_card * 5
    for dev, host in ((t._dev_shard[1], t.keys), (t._dev_shard[2], t.valid)):
        np.testing.assert_array_equal(
            np.asarray(dev).reshape(-1, t.page_card)[: t.num_pages],
            host[: t.num_pages])
    got = engine.run_all([Predicate.between(-1e30, 1e30)])
    assert got[0] == brute_force(t, -1e30, 1e30)


def test_write_refuses_rows_the_layout_cannot_hold():
    rng = np.random.default_rng(37)
    aidx = make_sidx(rng.uniform(0, 100, 64), num_shards=2,
                     pages_per_shard=5, spare_pages=64)
    engine = QueryEngine(aidx, batch=4, drain_policy="manual")
    with pytest.raises(RuntimeError, match="shard layout full"):
        for v in np.linspace(0, 90, 100):
            engine.write(float(v))
    # whatever was staged before the refusal still serves exactly
    got = engine.run_all([Predicate.between(0, 100)])
    assert got[0] == brute_force(aidx.table, 0, 100) + engine.writer.staged_rows
    engine.flush()
    got = engine.run_all([Predicate.between(0, 100)])
    assert got[0] == brute_force(aidx.table, 0, 100)


def test_drain_slot_capacity_refusal_rolls_back():
    """A drain that hits shard slot capacity restores the table snapshot,
    requeues the staged rows, clears the swap guard, and keeps every count
    exact through the staging overlay."""
    aidx = make_sidx(np.linspace(0, 99, 64), num_shards=2, max_slots=12,
                     relocate_on_update=True)
    engine = QueryEngine(aidx, batch=4, drain_policy="manual")
    for v in np.linspace(0, 99, 300):
        engine.write(float(v))
    t = aidx.table
    snap = (t.num_pages, t.fill, engine.writer.queue_depth)
    want = brute_force(t, 0, 99) + engine.writer.staged_rows
    with pytest.raises(RuntimeError, match="slot capacity"):
        engine.flush()
    assert aidx.swap_in_flight is None
    assert (t.num_pages, t.fill, engine.writer.queue_depth) == snap
    assert engine.run_all([Predicate.between(0, 99)])[0] == want


# ---------------------------------------------------------------------------
# Mid-swap refusal (regression: silent wrong counts -> loud error)
# ---------------------------------------------------------------------------

def test_queries_and_maintenance_refuse_mid_swap():
    """Regression: a query racing a shard swap used to be representable only
    as silent wrong counts; every query/maintenance surface must now refuse
    with a clear error while ``swap_in_flight`` is set."""
    rng = np.random.default_rng(41)
    aidx = make_sidx(rng.uniform(0, 100, 200))
    engine = QueryEngine(aidx, batch=4)
    pred = Predicate.between(0, 50)
    aidx.swap_in_flight = 2
    for attempt in (lambda: aidx.search_batch([pred]),
                    lambda: aidx.search_batch([pred], max_selected=1, top_k=4),
                    lambda: aidx.insert(1.0),
                    lambda: aidx.insert_batch(np.asarray([1.0])),
                    lambda: aidx.vacuum(),
                    lambda: aidx.vacuum_shard(0),
                    lambda: engine.run_all([pred]),
                    lambda: engine.write(1.0),
                    lambda: engine.delete(0.0, 1.0)):
        with pytest.raises(RuntimeError, match="swap in flight"):
            attempt()
    aidx.swap_in_flight = None
    engine.queue.clear()
    engine.slots = [None] * engine.batch
    got = engine.run_all([pred])
    assert got[0] == brute_force(aidx.table, 0, 50)


def test_direct_insert_refused_while_rows_staged():
    """Direct ``ShardedHippoIndex.insert`` under a pending writer queue would
    shift the table tail out from under the staged page routing — it must
    refuse instead."""
    rng = np.random.default_rng(43)
    aidx = make_sidx(rng.uniform(0, 100, 100))
    writer = MaintenanceWriter(aidx)
    writer.write(5.0)
    with pytest.raises(RuntimeError, match="staged rows pending"):
        aidx.insert(1.0)
    with pytest.raises(RuntimeError, match="staged rows pending"):
        aidx.insert_batch(np.asarray([1.0, 2.0]))
    writer.flush()
    aidx.insert(1.0)                            # queue empty: direct is fine


def test_vacuum_drains_only_dirty_shard():
    """Vacuum drain units are shard-local: draining one dirty shard clears
    its dirty notes only, leaving other shards' vacuum work queued."""
    values = np.sort(np.random.default_rng(47).uniform(0, 100, 800))
    aidx = make_sidx(values)
    writer = MaintenanceWriter(aidx)
    pps = aidx.spec.pages_per_shard
    lo_key = float(values[(2 * pps - 2) * 8])
    hi_key = float(values[(2 * pps + 2) * 8])
    writer.delete(lo_key, hi_key)               # dirties two shards
    pending = writer.pending_vacuum_shards()
    assert len(pending) >= 2
    writer.drain(max_units=1)
    assert writer.pending_vacuum_shards() == pending[1:]
    writer.flush()
    assert not writer.pending_vacuum_shards()
    assert not aidx.table.dirty[: aidx.table.num_pages].any()
    assert int(aidx.search_batch([Predicate.between(lo_key, hi_key)]).counts[0]) == 0


def test_second_writer_refused_while_rows_staged():
    """Attaching a new writer would detach the old one's overlay and drop
    its staged rows from every count — refuse while rows are pending, and
    refuse staging through a writer that did get replaced."""
    rng = np.random.default_rng(53)
    aidx = make_sidx(rng.uniform(0, 100, 100))
    w1 = MaintenanceWriter(aidx)
    w2 = MaintenanceWriter(aidx)        # empty: replacement is fine
    assert aidx.staging is w2
    with pytest.raises(RuntimeError, match="detached"):
        w1.write(1.0)                   # stale handle refuses loudly
    w2.write(2.0)
    with pytest.raises(RuntimeError, match="staged rows pending"):
        MaintenanceWriter(aidx)
    with pytest.raises(RuntimeError, match="staged rows pending"):
        QueryEngine(aidx, batch=4)      # implicit writer hits the same guard
    w2.flush()
    engine = QueryEngine(aidx, batch=4)
    assert aidx.staging is engine.writer


def test_noop_delete_keeps_device_caches():
    rng = np.random.default_rng(59)
    aidx = make_sidx(rng.uniform(0, 100, 200))
    engine = QueryEngine(aidx, batch=4, drain_policy="manual")
    engine.run_all([Predicate.between(0, 50)])      # builds the slab cache
    t = aidx.table
    assert not t._dev_shard_stale
    assert engine.delete(500.0, 600.0) == 0         # no key in range
    assert not t._dev_shard_stale                   # cache survived the no-op


@pytest.mark.parametrize("case", ["sync_engine", "superseded_writer"])
def test_overlay_reads_the_attached_writer(case):
    """The overlay comes from ``index.staging`` (the single source of
    truth), not from the engine's writer handle: a sync-policy engine, which
    holds no writer, and an engine whose writer was superseded by a newer
    one both count the rows the attached writer holds staged."""
    rng = np.random.default_rng(61)
    aidx = make_sidx(rng.uniform(0, 100, 200))
    if case == "sync_engine":
        writer = MaintenanceWriter(aidx)
        engine = QueryEngine(aidx, batch=4, drain_policy="sync")
        assert engine.writer is None
    else:
        engine = QueryEngine(aidx, batch=4, drain_policy="manual")
        writer = MaintenanceWriter(aidx)        # supersedes engine.writer
        assert engine.writer is not None and engine.writer is not writer
        assert aidx.staging is writer
    writer.write(42.0)
    writer.write(43.0)
    got = engine.run_all([Predicate.between(-1e30, 1e30),
                          Predicate.between(42.5, 50.0)])
    np.testing.assert_array_equal(
        got, [brute_force(aidx.table, -1e30, 1e30) + 2,
              brute_force(aidx.table, 42.5, 50.0) + 1])
    assert writer.queue_depth == 2              # nothing drained them
    writer.flush()
    assert engine.run_all([Predicate.between(42.5, 50.0)])[0] == \
        brute_force(aidx.table, 42.5, 50.0)


def test_engine_rejects_writer_bound_elsewhere():
    rng = np.random.default_rng(67)
    a = make_sidx(rng.uniform(0, 100, 100))
    b = make_sidx(rng.uniform(0, 100, 100))
    w = MaintenanceWriter(a)
    with pytest.raises(ValueError, match="different index"):
        QueryEngine(b, batch=4, drain_policy="manual", writer=w)


def test_drain_stats_count_partial_progress():
    """Bugfix regression: a drain that applies two units and then refuses on
    the third used to record zero drains and zero drain time (the stats were
    only written after the loop), and ``engine._sync_writer_stats``
    propagated the lie. Units and wall time must land as they apply."""
    aidx = make_sidx(np.linspace(0, 99, 64), num_shards=2, max_slots=12,
                     relocate_on_update=True)
    engine = QueryEngine(aidx, batch=4, drain_policy="manual",
                         auto_resummarize=False)
    writer = engine.writer
    # unit 3: an insert queue that refuses at slot capacity (one shard's
    # worth of distinct relocating values) ...
    for v in np.linspace(0, 99, 100):
        engine.write(float(v))
    # ... units 1+2: one valid remap per shard, drained first
    writer.schedule_resummarize(np.linspace(-1.0, 101.0,
                                            aidx.cfg.resolution + 1))
    assert writer.pending_units == 3
    with pytest.raises(RuntimeError, match="slot capacity"):
        engine.flush()
    assert writer.stats.drains == 2              # the two applied remaps
    assert writer.stats.resummarizes == 2
    assert writer.stats.last_drain_us > 0
    assert writer.stats.total_drain_us > 0
    # the engine saw the partial progress despite the raise
    assert engine.stats.drains == 2
    assert engine.stats.drain_us > 0
    assert engine.stats.resummarizes == 2
    # recovery: counts stay exact through the overlay, discard re-arms
    want = brute_force(aidx.table, 0, 99) + writer.staged_rows
    assert engine.run_all([Predicate.between(0, 99)])[0] == want


def test_on_depth_policy_triggers_on_delete_backlog():
    """Bugfix regression: a delete-heavy stream under on_depth used to
    accumulate vacuum work forever — deletes add no queue depth and
    ``delete()`` never checked the trigger. The trigger now measures staged
    tuples + dirty pages, on writes and deletes alike."""
    values = np.sort(np.random.default_rng(73).uniform(0, 100, 400))
    aidx = make_sidx(values)
    engine = QueryEngine(aidx, batch=4, drain_policy="on_depth",
                         drain_depth=6)
    steps = 0
    for i in range(30):                      # narrow disjoint deletes only
        engine.delete(i * 3.0, i * 3.0 + 1.5)
        steps += 1
        if engine.stats.drains:
            break
    assert engine.stats.drains > 0, \
        "delete-only stream never drained its vacuums"
    assert engine.writer.stats.vacuums > 0
    assert not aidx.table.dirty[: aidx.table.num_pages].any()
    assert steps < 30                        # triggered by backlog, not luck
    got = engine.run_all([Predicate.between(0, 100)])
    assert got[0] == brute_force(aidx.table, 0, 100)


def test_drain_refusal_suspends_auto_drain_and_discard_recovers():
    """A refused between-batches drain raises once, then queries keep
    serving exactly via the overlay instead of re-raising forever;
    ``writer.discard()`` drops the unappliable rows and re-arms."""
    aidx = make_sidx(np.linspace(0, 99, 64), num_shards=2, max_slots=12,
                     relocate_on_update=True)
    engine = QueryEngine(aidx, batch=4, drain_policy="between_batches")
    for v in np.linspace(0, 99, 300):
        engine.write(float(v))
    want = brute_force(aidx.table, 0, 99) + engine.writer.staged_rows
    with pytest.raises(RuntimeError, match="slot capacity"):
        engine.run_all([Predicate.between(0, 99)])
    engine.queue.clear()
    engine.slots = [None] * engine.batch
    got = engine.run_all([Predicate.between(0, 99)])    # no re-raise
    assert got[0] == want
    dropped = engine.writer.discard()
    assert dropped == 300 and engine.writer.queue_depth == 0
    got = engine.run_all([Predicate.between(0, 99)])
    assert got[0] == brute_force(aidx.table, 0, 99)
    engine.write(50.0)                                  # staging works again
    engine.flush()
    assert brute_force(aidx.table, 0, 99) == got[0] + 1


def test_vacuum_counter_consistent_across_entry_points():
    """counters.vacuums counts shard-vacuums that did work, identically
    through vacuum(), vacuum_shard(), and the writer's drain."""
    def dirty_two_shards():
        values = np.sort(np.random.default_rng(71).uniform(0, 100, 800))
        idx = make_sidx(values)
        pps = idx.spec.pages_per_shard
        idx.table.delete_where(float(values[(2 * pps - 2) * 8]),
                               float(values[(2 * pps + 2) * 8]))
        return idx

    a = dirty_two_shards()
    a.vacuum()
    b = dirty_two_shards()
    for s in b.dirty_shards():
        b.vacuum_shard(int(s))
    c = dirty_two_shards()
    MaintenanceWriter(c).flush()
    assert a.counters.vacuums == b.counters.vacuums == c.counters.vacuums >= 2


def test_writer_requires_partition_surface():
    from repro.core.hippo import HippoIndex
    table = PagedTable.from_values(np.linspace(0, 9, 80), page_card=8)
    idx = HippoIndex.create(table, resolution=32, density=0.25)
    with pytest.raises(ValueError, match="ShardedHippoIndex"):
        MaintenanceWriter(idx)
    with pytest.raises(ValueError, match="drain_policy"):
        QueryEngine(idx, drain_policy="bogus")
    with pytest.raises(ValueError, match="sync"):
        QueryEngine(idx, drain_policy="between_batches")
