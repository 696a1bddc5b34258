"""Partition layer: sharded search must be count-identical to the unsharded
index and a scan under arbitrary predicates and maintenance histories, its
per-shard match statistics must sum what each shard's joint-bucket test
selects, and shard-boundary maintenance must stay local and refuse cleanly
at capacity."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import index as hix
from repro.core.hippo import HippoIndex
from repro.core.partition import (ShardedHippoIndex, ShardSpec, shard_state,
                                  summary_of)
from repro.core.predicate import Predicate, intervals, to_bucket_bitmaps
from repro.runtime.engine import QueryEngine
from repro.storage.table import PagedTable

pytestmark = pytest.mark.shard


def make_pair(values, num_shards=4, page_card=8, resolution=32, density=0.25,
              spare_pages=64, **kw):
    """(unsharded, sharded) indexes over identical tables."""
    t1 = PagedTable.from_values(np.asarray(values).copy(), page_card=page_card,
                                spare_pages=spare_pages)
    t2 = PagedTable.from_values(np.asarray(values).copy(), page_card=page_card,
                                spare_pages=spare_pages)
    idx = HippoIndex.create(t1, resolution=resolution, density=density, **kw)
    sidx = ShardedHippoIndex.create(t2, num_shards=num_shards,
                                    resolution=resolution, density=density, **kw)
    return idx, sidx


def brute_force(table, lo, hi):
    live = table.valid[: table.num_pages]
    keys = table.keys[: table.num_pages]
    return int((live & (keys >= lo) & (keys <= hi)).sum())


def workload(rng, n):
    """Random ranges plus the edge predicates (mirrors test_engine)."""
    preds = []
    for _ in range(n):
        lo = float(rng.uniform(0, 1000))
        preds.append(Predicate.between(lo, lo + float(rng.uniform(0, 300))))
    preds += [
        Predicate(lo=5.0, hi=1.0),            # empty interval (lo > hi)
        Predicate.between(2000, 3000),        # no key in range
        Predicate.between(-1e30, 1e30),       # full table
        Predicate(),                          # unconstrained
        Predicate.equality(float(rng.uniform(0, 1000))),
        Predicate.greater(500.0),
        Predicate.less(100.0),
    ]
    return preds


# ---------------------------------------------------------------------------
# Search parity (the acceptance invariant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [1, 3, 4])
def test_sharded_counts_match_unsharded(num_shards):
    rng = np.random.default_rng(num_shards)
    idx, sidx = make_pair(rng.uniform(0, 1000, 2000), num_shards=num_shards)
    preds = workload(rng, 16)
    want = np.asarray(idx.search_batch(preds).counts)
    got = np.asarray(sidx.search_batch(preds).counts)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        want, [brute_force(sidx.table, *p.selectivity_interval())
               for p in preds])


@pytest.mark.parametrize("num_shards", [1, 3, 4])
def test_sharded_match_stats_sum_each_shards_joint_test(num_shards):
    """``pages_inspected``/``entries_matched`` of the one sharded program
    equal, summed over shards, a direct joint-bucket test of every live
    slot of each shard expanded over its slab-local page range — also after
    inserts and a delete + vacuum reshuffled slots."""
    rng = np.random.default_rng(40 + num_shards)
    _, sidx = make_pair(rng.uniform(0, 1000, 1500), num_shards=num_shards,
                        relocate_on_update=True)
    for v in rng.uniform(0, 1000, 40):
        sidx.insert(float(v))
    sidx.table.delete_where(200.0, 260.0)
    sidx.vacuum()
    preds = workload(rng, 12)
    res = sidx.search_batch(preds)
    qbms = np.asarray(sidx._query_bitmaps(preds))          # (S, Q, W)
    pps = sidx.spec.pages_per_shard
    pages = np.zeros(len(preds), np.int64)
    entries = np.zeros(len(preds), np.int64)
    for s in range(num_shards):
        st = shard_state(sidx.state.shards, s)
        slots = np.arange(sidx.cfg.max_slots)
        live = np.asarray(st.slot_live) & (slots < int(st.num_slots))
        starts, ends = np.asarray(st.starts), np.asarray(st.ends)
        for q in range(len(preds)):
            match = np.asarray(bm.any_joint(st.bitmaps,
                                            qbms[s, q][None, :])) & live
            covered = np.zeros(pps, bool)
            for e in np.flatnonzero(match):
                covered[starts[e]:ends[e] + 1] = True
            pages[q] += covered.sum()
            entries[q] += match.sum()
    np.testing.assert_array_equal(np.asarray(res.pages_inspected), pages)
    np.testing.assert_array_equal(np.asarray(res.entries_matched), entries)


@pytest.mark.slow
@pytest.mark.parametrize("dist", ["uniform", "sorted", "skewed", "lowcard"])
def test_sharded_parity_predicate_sweep(dist):
    """Property-style sweep: many random predicates over several data
    distributions, counts bit-identical at every shard count."""
    rng = np.random.default_rng({"uniform": 0, "sorted": 1, "skewed": 2,
                                 "lowcard": 3}[dist])
    n = 3000
    if dist == "uniform":
        values = rng.uniform(0, 1000, n)
    elif dist == "sorted":
        values = np.sort(rng.uniform(0, 1000, n))
    elif dist == "skewed":
        values = rng.exponential(50, n)
    else:
        values = rng.integers(0, 12, n).astype(float)
    preds = workload(rng, 48)
    t0 = PagedTable.from_values(values.copy(), page_card=8, spare_pages=64)
    want = np.asarray(HippoIndex.create(t0, resolution=32,
                                        density=0.25).search_batch(preds).counts)
    truth = [brute_force(t0, *p.selectivity_interval()) for p in preds]
    np.testing.assert_array_equal(want, truth)
    for s in (2, 5):
        t = PagedTable.from_values(values.copy(), page_card=8, spare_pages=64)
        sidx = ShardedHippoIndex.create(t, num_shards=s, resolution=32,
                                        density=0.25)
        got = np.asarray(sidx.search_batch(preds).counts)
        np.testing.assert_array_equal(got, want, err_msg=f"{dist} S={s}")


def test_search_many_sharded_page_mask_global_order():
    """The sharded program returns row ids in global page order and covers
    every truly-qualified row (soundness): with ``top_k`` at least the
    count, the ids are exactly a scan's qualifying rows, ascending."""
    rng = np.random.default_rng(7)
    values = rng.uniform(0, 1000, 1500)
    _, sidx = make_pair(values, num_shards=3)
    pred = Predicate.between(200, 420)
    t = sidx.table
    qual = (t.valid[: t.num_pages] & (t.keys[: t.num_pages] >= 200)
            & (t.keys[: t.num_pages] <= 420)).reshape(-1)
    want = np.flatnonzero(qual)
    top_k = 1 << int(want.size - 1).bit_length()
    res = sidx.search_batch([pred], top_k=top_k)
    assert int(res.counts[0]) == want.size
    ids = np.asarray(res.row_ids[0])
    np.testing.assert_array_equal(ids[: want.size], want)
    assert (ids[want.size:] == -1).all()


def test_empty_and_single_shard_layouts():
    rng = np.random.default_rng(11)
    values = rng.uniform(0, 100, 200)
    idx, sidx = make_pair(values, num_shards=1)
    for lo, hi in [(0, 100), (30, 35)]:
        assert sidx.count(Predicate.between(lo, hi)) == \
            idx.count(Predicate.between(lo, hi))
    # layouts with more shards than pages: trailing shards stay empty
    t = PagedTable.from_values(rng.uniform(0, 100, 20), page_card=8)
    s = ShardedHippoIndex.create(t, num_shards=8, resolution=32, density=0.25)
    assert s.count(Predicate.between(0, 100)) == t.cardinality
    assert (s.shard_entry_counts()[s.spec.owner(t.num_pages - 1) + 1:] == 0).all()


# ---------------------------------------------------------------------------
# Shard-boundary maintenance
# ---------------------------------------------------------------------------

def test_insert_routes_to_owning_shard_and_matches_unsharded():
    rng = np.random.default_rng(21)
    idx, sidx = make_pair(rng.uniform(0, 100, 333), num_shards=3)
    for v in rng.uniform(0, 100, 80):
        idx.insert(float(v))
        sidx.insert(float(v))
    for lo, hi in [(0, 100), (10, 20), (50, 50.5)]:
        want = brute_force(sidx.table, lo, hi)
        assert sidx.count(Predicate.between(lo, hi)) == want
        assert idx.count(Predicate.between(lo, hi)) == want


def test_insert_crossing_shard_boundary_stays_local():
    """Appends that open the first page of a fresh shard must create entries
    in that shard only — earlier shards' arrays stay untouched."""
    values = np.linspace(0, 99, 64)           # 8 pages of 8
    t = PagedTable.from_values(values, page_card=8, spare_pages=64)
    sidx = ShardedHippoIndex.create(t, num_shards=2, pages_per_shard=10,
                                    resolution=32, density=0.25)
    before = np.asarray(shard_state(sidx.state.shards, 0).bitmaps).copy()
    # fill shard 0's slab (pages 8, 9), then cross into shard 1 (page 10+)
    for v in np.linspace(0, 99, 40):
        sidx.insert(float(v))
    assert sidx.table.num_pages > 10          # crossed the boundary
    assert int(sidx.state.shards.num_entries[1]) > 0
    after_s0 = np.asarray(shard_state(sidx.state.shards, 0).bitmaps)
    changed_rows = (before != after_s0).any(axis=1).sum()
    # shard 0 changed only while its own slab filled; shard-1 pages never
    # touched it — and search stays exact throughout
    assert changed_rows <= int(sidx.state.shards.num_slots[0])
    assert sidx.count(Predicate.between(0, 100)) == brute_force(t, 0, 100)


def test_insert_into_full_shard_layout_refuses_cleanly():
    rng = np.random.default_rng(23)
    t = PagedTable.from_values(rng.uniform(0, 100, 64), page_card=8,
                               spare_pages=64)
    sidx = ShardedHippoIndex.create(t, num_shards=2, pages_per_shard=5,
                                    resolution=32, density=0.25)
    with pytest.raises(RuntimeError, match="shard layout full"):
        for v in np.linspace(0, 90, 100):
            sidx.insert(float(v))
    card = t.cardinality
    # the refusing insert left the table untouched and queries exact
    assert sidx.count(Predicate.between(0, 100)) == card
    with pytest.raises(RuntimeError, match="shard layout full"):
        sidx.insert(1.0)
    assert t.cardinality == card
    # batch insert is atomic: rolls the table back to the pre-batch snapshot
    with pytest.raises(RuntimeError, match="shard layout full"):
        sidx.insert_batch(np.linspace(0, 90, 50))
    assert t.cardinality == card
    assert sidx.count(Predicate.between(0, 100)) == card


def test_insert_at_shard_slot_capacity_refuses_cleanly():
    values = np.linspace(0, 99, 64)
    t = PagedTable.from_values(values, page_card=8, spare_pages=256)
    sidx = ShardedHippoIndex.create(t, num_shards=2, max_slots=12,
                                    resolution=32, density=0.25,
                                    relocate_on_update=True)
    with pytest.raises(RuntimeError, match="slot capacity"):
        for v in np.linspace(0, 99, 500):
            sidx.insert(float(v))
    assert (np.asarray(sidx.state.shards.num_slots) <= sidx.cfg.max_slots).all()
    assert sidx.count(Predicate.between(0, 99)) == brute_force(t, 0, 99)


def test_batch_insert_matches_sequential_across_shards():
    rng = np.random.default_rng(25)
    base = rng.uniform(0, 100, 200)
    extra = rng.uniform(0, 100, 150)
    _, sidx_a = make_pair(base, num_shards=3, relocate_on_update=False,
                          spare_pages=256)
    _, sidx_b = make_pair(base, num_shards=3, relocate_on_update=False,
                          spare_pages=256)
    for v in extra:
        sidx_a.insert(float(v))
    sidx_b.insert_batch(extra)
    for lo, hi in [(0, 100), (25, 30), (77, 77.5)]:
        want = brute_force(sidx_b.table, lo, hi)
        assert sidx_a.count(Predicate.between(lo, hi)) == want
        assert sidx_b.count(Predicate.between(lo, hi)) == want


def test_vacuum_spanning_two_shards():
    """A delete band dirtying pages in two different shards re-summarizes
    entries in both, queries stay exact before and after, and untouched
    shards' bitmaps are left alone."""
    values = np.sort(np.random.default_rng(27).uniform(0, 100, 800))
    _, sidx = make_pair(values, num_shards=4)
    pps = sidx.spec.pages_per_shard
    # sorted keys => a mid-domain band hits pages around the shard-1/2 border
    lo_key = float(values[(2 * pps - 2) * 8])
    hi_key = float(values[(2 * pps + 2) * 8])
    sidx.table.delete_where(lo_key, hi_key)
    dirty = np.flatnonzero(sidx.table.dirty[: sidx.table.num_pages])
    touched = np.unique(dirty // pps)
    assert len(touched) >= 2                  # the band spans a shard boundary
    # exact while deletes are lazy (§5.2)
    assert sidx.count(Predicate.between(0, 100)) == brute_force(sidx.table, 0, 100)
    summaries_before = np.asarray(sidx.state.summaries).copy()
    n = sidx.vacuum()
    assert n > 0
    assert not sidx.table.dirty[: sidx.table.num_pages].any()
    assert sidx.count(Predicate.between(lo_key, hi_key)) == 0
    assert sidx.count(Predicate.between(0, 100)) == brute_force(sidx.table, 0, 100)
    # vacuum stayed local: summaries of untouched shards are unchanged
    after = np.asarray(sidx.state.summaries)
    for s in range(sidx.num_shards):
        if s not in touched:
            np.testing.assert_array_equal(after[s], summaries_before[s])


def test_summaries_track_maintenance_as_superset():
    """Shard summaries must stay supersets of their live entry unions (the
    pruning soundness invariant) across inserts and vacuum."""
    rng = np.random.default_rng(29)
    _, sidx = make_pair(rng.uniform(0, 100, 400), num_shards=3)
    for v in rng.uniform(0, 100, 50):
        sidx.insert(float(v))
    sidx.table.delete_where(20, 40)
    sidx.vacuum()
    for s in range(sidx.num_shards):
        st = shard_state(sidx.state.shards, s)
        true_union = np.asarray(summary_of(st))
        cached = np.asarray(sidx.state.summaries[s])
        assert (cached | true_union == cached).all()


# ---------------------------------------------------------------------------
# Engine over a sharded index
# ---------------------------------------------------------------------------

def test_engine_stats_never_count_pads_as_served_work():
    rng = np.random.default_rng(33)
    idx, sidx = make_pair(rng.uniform(0, 1000, 500), num_shards=2)
    # on either index, pads are the free batch slots of the one program
    for target in (idx, sidx):
        engine = QueryEngine(target, batch=16)
        engine.submit(Predicate.between(0, 1000))
        engine.submit(Predicate(lo=5.0, hi=1.0))   # real (empty) query
        finished = engine.run_batch()
        assert [t.count for t in finished] == [target.table.cardinality, 0]
        st = engine.stats
        assert st.slots_filled == 2                # pads excluded
        assert st.pad_slots == 14
        assert st.served == 2
        assert st.occupancy == pytest.approx(2 / 16)
    # a fresh engine with nothing dispatched reports zero occupancy
    assert QueryEngine(idx, batch=4).stats.occupancy == 0.0


# ---------------------------------------------------------------------------
# Device placement (data-axis shardings)
# ---------------------------------------------------------------------------

def test_placed_sharded_state_search_parity():
    from repro.launch.mesh import make_shard_mesh
    from repro.launch.shardings import place_sharded

    rng = np.random.default_rng(41)
    _, sidx = make_pair(rng.uniform(0, 1000, 1000), num_shards=4)
    mesh = make_shard_mesh(sidx.num_shards)
    assert sidx.num_shards % mesh.shape["data"] == 0
    keys, valid = sidx._slabs()
    st, k, v = place_sharded(mesh, sidx.state, keys, valid)
    preds = workload(rng, 8)
    qbms = sidx._query_bitmaps(preds)           # (S, Q, W): per-shard epochs
    los, his = intervals(preds)
    res = hix.search_compact_many_sharded(st.shards, qbms, k, v, los, his,
                                          max_selected=sidx.gather_cap)
    want = np.asarray(sidx.search_batch(preds).counts)
    np.testing.assert_array_equal(np.asarray(res.counts), want)


def test_shard_spec_routing_arithmetic():
    spec = ShardSpec(num_shards=3, pages_per_shard=10)
    assert spec.total_pages == 30
    assert spec.owner(0) == 0 and spec.owner(9) == 0
    assert spec.owner(10) == 1 and spec.owner(29) == 2
    assert spec.owner(30) == 3                 # overflow: past the last slab
    assert spec.to_local(23) == 3
    assert spec.page_lo(2) == 20
