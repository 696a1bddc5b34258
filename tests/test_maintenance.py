import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import histogram as hg
from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate
from repro.storage.table import PagedTable


def make_index(values, page_card=8, resolution=32, density=0.25, **kw):
    table = PagedTable.from_values(values, page_card=page_card, spare_pages=256)
    return HippoIndex.create(table, resolution=resolution, density=density, **kw)


def brute_force(table, lo, hi):
    live = table.valid[: table.num_pages]
    keys = table.keys[: table.num_pages]
    return int((live & (keys >= lo) & (keys <= hi)).sum())


@pytest.mark.parametrize("relocate", [False, True])
def test_eager_insert_existing_and_new_pages(relocate):
    rng = np.random.default_rng(0)
    values = rng.uniform(0, 100, 333)  # last page partially filled
    idx = make_index(values, relocate_on_update=relocate)
    new_vals = rng.uniform(0, 100, 60)
    for v in new_vals:
        idx.insert(float(v))
    # Every subsequent query must see the inserted tuples (§5.1 correctness).
    for lo, hi in [(0, 100), (10, 20), (50, 50.5)]:
        res = idx.search(Predicate.between(lo, hi))
        assert int(res.counts[0]) == brute_force(idx.table, lo, hi)


def test_insert_extends_or_creates_last_entry():
    # Histogram over spread-out data, so bucketization is meaningful.
    values = np.linspace(0, 99, 64)
    idx = make_index(values, page_card=8, resolution=32, density=0.5)
    # Insert identical values: after at most one new entry is opened, its
    # density stays at 1/32 < D, so further new pages extend it (Alg. 3).
    idx.insert(5.0)
    e1 = idx.num_entries
    for _ in range(32):
        idx.insert(5.0)
    assert idx.num_entries <= e1 + 1  # one creation at most (the first new page)
    starts, ends, _ = idx.entries_host()
    assert ends[-1] == idx.table.num_pages - 1
    # Diverse inserts push density over D => new entries get created.
    e2 = idx.num_entries
    for v in list(np.linspace(0, 99, 64)) * 2:
        idx.insert(float(v))
    assert idx.num_entries > e2


def test_sorted_list_stays_sorted_under_relocation():
    rng = np.random.default_rng(1)
    values = rng.uniform(0, 100, 256)
    idx = make_index(values, relocate_on_update=True)
    for v in rng.uniform(0, 100, 64):
        idx.insert(float(v))
    starts, ends, _ = idx.entries_host()
    assert (np.diff(starts) > 0).all()          # logical order ascending
    np.testing.assert_array_equal(starts[1:], ends[:-1] + 1)
    # Relocation happened (num_slots grew past num_entries) yet search is exact.
    assert int(idx.state.num_slots) >= idx.num_entries
    res = idx.search(Predicate.between(0, 100))
    assert int(res.counts[0]) == brute_force(idx.table, 0, 100)


def test_batch_insert_matches_sequential():
    rng = np.random.default_rng(2)
    base = rng.uniform(0, 100, 200)
    extra = rng.uniform(0, 100, 150)

    idx_a = make_index(base.copy(), relocate_on_update=False)
    for v in extra:
        idx_a.insert(float(v))

    idx_b = make_index(base.copy(), relocate_on_update=False)
    idx_b.insert_batch(extra)

    for lo, hi in [(0, 100), (25, 30), (77, 77.5)]:
        ra = idx_a.search(Predicate.between(lo, hi))
        rb = idx_b.search(Predicate.between(lo, hi))
        assert int(ra.counts[0]) == int(rb.counts[0]) == brute_force(idx_b.table, lo, hi)


def test_lazy_delete_correct_before_and_after_vacuum():
    rng = np.random.default_rng(3)
    values = rng.uniform(0, 100, 1000)
    idx = make_index(values)
    # Delete a band; index NOT updated yet — queries must still be exact (§5.2).
    idx.table.delete_where(40, 60)
    for lo, hi in [(0, 100), (45, 55), (39, 41)]:
        res = idx.search(Predicate.between(lo, hi))
        assert int(res.counts[0]) == brute_force(idx.table, lo, hi)
    before_pages = int(idx.search(Predicate.between(45, 55)).pages_inspected[0])
    n = idx.vacuum()
    assert n > 0
    assert not idx.table.dirty[: idx.table.num_pages].any()
    # After vacuum, bitmaps shrink => fewer possible-qualified pages.
    after = idx.search(Predicate.between(45, 55))
    assert int(after.counts[0]) == brute_force(idx.table, 45, 55) == 0
    assert int(after.pages_inspected[0]) <= before_pages


def test_vacuum_only_resummarizes_dirty_entries():
    rng = np.random.default_rng(4)
    values = rng.uniform(0, 100, 800)
    idx = make_index(values)
    bitmaps_before = np.asarray(idx.state.bitmaps).copy()
    idx.table.delete_where(0.0, 1.0)   # touches few pages
    idx.vacuum()
    bitmaps_after = np.asarray(idx.state.bitmaps)
    changed = (bitmaps_before != bitmaps_after).any(axis=1).sum()
    assert 0 < changed < idx.num_entries  # localized maintenance


def test_insert_into_empty_index():
    """A zero-page build must yield a working zero-entry index that grows
    through Algorithm 3 on first insert (the histogram comes from the DBMS,
    not the empty table)."""
    table = PagedTable.from_values(np.zeros(0), page_card=8, spare_pages=64)
    hist = hg.build_uniform(0.0, 100.0, 32)
    idx = HippoIndex.create(table, resolution=32, density=0.25, hist=hist)
    assert idx.num_entries == 0
    assert int(idx.state.summarized_until) == -1
    assert idx.count(Predicate.between(0, 100)) == 0
    vals = [5.0, 50.0, 95.0, 12.0, 13.0]
    for v in vals:
        idx.insert(v)
    assert idx.num_entries >= 1
    assert idx.count(Predicate.between(0, 100)) == len(vals)
    assert idx.count(Predicate.between(40, 60)) == 1
    # batch insert into a fresh empty index agrees too
    t2 = PagedTable.from_values(np.zeros(0), page_card=8, spare_pages=64)
    idx2 = HippoIndex.create(t2, resolution=32, density=0.25, hist=hist)
    idx2.insert_batch(np.asarray(vals))
    assert idx2.count(Predicate.between(0, 100)) == len(vals)


def test_insert_at_max_slots_refuses_cleanly():
    """Relocation/creation at physical capacity must raise before mutating
    anything — not scatter out of bounds and corrupt the sorted list."""
    values = np.linspace(0, 99, 64)
    idx = make_index(values, page_card=8, resolution=32, density=0.25,
                     max_slots=12, relocate_on_update=True)
    with pytest.raises(RuntimeError, match="slot capacity"):
        for v in np.linspace(0, 99, 500):
            idx.insert(float(v))
    # refusal left table and index consistent: every query is still exact
    assert int(idx.state.num_slots) <= idx.cfg.max_slots
    for lo, hi in [(0, 99), (10, 20), (50, 50.5)]:
        assert idx.count(Predicate.between(lo, hi)) == \
            brute_force(idx.table, lo, hi)
    # single insert refuses BEFORE touching the table; batch insert rolls the
    # table back to its pre-batch snapshot (atomic refuse)
    cardinality = idx.table.cardinality
    with pytest.raises(RuntimeError, match="slot capacity"):
        idx.insert(1.0)
    with pytest.raises(RuntimeError, match="slot capacity"):
        idx.insert_batch(np.linspace(0, 99, 300))
    assert idx.table.cardinality == cardinality
    assert idx.count(Predicate.between(0, 99)) == \
        brute_force(idx.table, 0, 99)


def test_large_batch_insert_not_refused_at_low_occupancy():
    """The capacity guard charges slots at actual need, not a worst-case
    up-front bound: a duplicate-heavy batch far larger than the remaining
    slot headroom consumes ~no slots and must succeed."""
    rng = np.random.default_rng(8)
    idx = make_index(rng.uniform(0, 100, 333), relocate_on_update=True)
    assert int(idx.state.num_slots) + 1500 > idx.cfg.max_slots  # worst case "full"
    idx.insert_batch(np.full(1500, 50.0, np.float32))
    assert idx.count(Predicate.between(0, 100)) == \
        brute_force(idx.table, 0, 100) == 333 + 1500


def test_counters_track_maintenance():
    rng = np.random.default_rng(5)
    idx = make_index(rng.uniform(0, 100, 200))
    for v in rng.uniform(0, 100, 10):
        idx.insert(float(v))
    assert idx.counters.inserts == 10
    idx.table.delete_where(0, 50)
    idx.vacuum()
    assert idx.counters.vacuums == 1
    assert idx.counters.entries_resummarized > 0
