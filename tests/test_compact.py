"""Equivalence sweep for the one search program, unsharded and sharded.

The contract under test: ``search_batch`` counts and row ids equal a
brute-force scan wherever ``truncated`` is False — across selectivities,
slab widths, shard counts, and staged-overlay states — its
``pages_inspected``/``entries_matched`` equal step 2 of Algorithm 1
(``core.index._page_match``), and the engine serves exactly what the scan
counts, falling back to the never-truncating cap on truncation but never to
a wrong answer.

Marked ``compact`` (see tests/conftest.py): the sweep compiles many distinct
(max_selected, top_k) trace shapes, so it is split out of the fast inner
loop like the ``shard``/``writer`` suites. Run alone with ``-m compact``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import histogram as hg
from repro.core import index as hix
from repro.core.hippo import HippoIndex
from repro.core.partition import ShardedHippoIndex, shard_state
from repro.core.predicate import Predicate, to_bucket_bitmaps
from repro.runtime.engine import QueryEngine
from repro.runtime.writer import MaintenanceWriter
from repro.storage.table import PagedTable

pytestmark = pytest.mark.compact

PAGE_CARD = 8
TOP_K = 64


def brute_ids(table: PagedTable, lo: float, hi: float) -> np.ndarray:
    """Qualifying global row ids by brute force, ascending."""
    keys = table.keys[: table.num_pages].reshape(-1)
    valid = table.valid[: table.num_pages].reshape(-1)
    lo, hi = max(lo, -3.4e38), min(hi, 3.4e38)
    return np.flatnonzero(valid & (keys >= lo) & (keys <= hi)).astype(np.int64)


def brute_counts(table: PagedTable, preds, staged=()) -> np.ndarray:
    """Per-predicate scan counts of the table, plus the staged values each
    predicate matches (rows waiting in a writer's queues)."""
    staged = np.asarray(staged, np.float32)
    out = []
    for p in preds:
        lo, hi = p.selectivity_interval()
        in_staged = (staged >= max(lo, -3.4e38)) & (staged <= min(hi, 3.4e38))
        out.append(brute_ids(table, lo, hi).size + int(in_staged.sum()))
    return np.asarray(out, np.int64)


def step2(target, preds) -> tuple[np.ndarray, np.ndarray]:
    """Step 2 of Algorithm 1 alone: (Q, pages) possible-qualified pages in
    global page order (every shard's slab, in shard order) and (Q,) matched
    entries, each shard under its own bounds."""
    spec = getattr(target, "spec", None)
    if spec is None:
        qbms = to_bucket_bitmaps(preds, target.state.histogram)
        mask, matched = hix._page_match(target.state, qbms,
                                        target.table.num_pages)
        return np.asarray(mask), np.asarray(matched)
    qbms = target._query_bitmaps(preds)
    per = [hix._page_match(shard_state(target.state.shards, s), qbms[s],
                           spec.pages_per_shard)
           for s in range(spec.num_shards)]
    return (np.concatenate([np.asarray(m) for m, _ in per], axis=1),
            sum(np.asarray(n) for _, n in per))


def workload(rng, widths=(2.0, 20.0, 200.0), per_width=3):
    """Ranges at three selectivity decades plus the edge predicates."""
    preds = []
    for w in widths:
        for _ in range(per_width):
            lo = float(rng.uniform(0, 1000 - w))
            preds.append(Predicate.between(lo, lo + w))
    preds += [
        Predicate(lo=5.0, hi=1.0),          # empty interval
        Predicate.between(2000, 3000),      # out of domain
        Predicate.between(-1e30, 1e30),     # full table
        Predicate.equality(float(rng.uniform(0, 1000))),
    ]
    return preds


def make_pair(values, num_shards):
    t1 = PagedTable.from_values(values.copy(), page_card=PAGE_CARD,
                                spare_pages=64)
    idx = HippoIndex.create(t1, resolution=64, density=0.25)
    t2 = PagedTable.from_values(values.copy(), page_card=PAGE_CARD,
                                spare_pages=256)
    sidx = ShardedHippoIndex.create(t2, num_shards=num_shards, resolution=64,
                                    density=0.25)
    return idx, sidx


# ---------------------------------------------------------------------------
# Core equivalence: unsharded vs sharded vs a scan, swept over slab widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["sorted", "uniform"])
@pytest.mark.parametrize("num_shards", [1, 3])
def test_compact_counts_and_row_ids_match_dense_where_untruncated(dist, num_shards):
    rng = np.random.default_rng({"sorted": 0, "uniform": 1}[dist] * 10
                                + num_shards)
    values = rng.uniform(0, 1000, 2000)
    if dist == "sorted":
        values = np.sort(values)
    idx, sidx = make_pair(values, num_shards)
    preds = workload(rng)
    want_counts = brute_counts(idx.table, preds)
    masks, matched = step2(idx, preds)
    want_ids = [brute_ids(idx.table, *p.selectivity_interval())[:TOP_K]
                for p in preds]
    full = idx.table.num_pages
    # the narrow caps gather (and may truncate), on both indexes
    assert not any(t.inspects_in_place(cap) for t in (idx, sidx)
                   for cap in (4, 32))
    for cap in (4, 32, full):
        res = idx.search_batch(preds, max_selected=cap, top_k=TOP_K)
        trunc = np.asarray(res.truncated)
        counts = np.asarray(res.counts)
        assert trunc.any() or cap != 4           # the narrowest cap truncates
        assert (counts[~trunc] == want_counts[~trunc]).all(), cap
        assert (counts <= want_counts).all()     # truncation only ever loses
        np.testing.assert_array_equal(np.asarray(res.pages_inspected),
                                      masks.sum(axis=1))
        np.testing.assert_array_equal(np.asarray(res.entries_matched),
                                      matched)
        for q in np.flatnonzero(~trunc):
            ids = np.asarray(res.row_ids[q])
            np.testing.assert_array_equal(ids[ids >= 0], want_ids[q], (cap, q))
        # the sharded gather agrees bit-for-bit where neither truncated
        sres = sidx.search_batch(preds, max_selected=cap, top_k=TOP_K)
        strunc = np.asarray(sres.truncated)
        both = ~trunc & ~strunc
        np.testing.assert_array_equal(np.asarray(sres.counts)[both],
                                      want_counts[both])
        for q in np.flatnonzero(both):
            np.testing.assert_array_equal(np.asarray(sres.row_ids[q]),
                                          np.asarray(res.row_ids[q]), (cap, q))
    # at the never-truncating cap nothing may be flagged
    res = idx.search_batch(preds, max_selected=full, top_k=0)
    assert not np.asarray(res.truncated).any()
    sres = sidx.search_batch(
        preds, max_selected=sidx.spec.pages_per_shard, top_k=0)
    assert not np.asarray(sres.truncated).any()
    np.testing.assert_array_equal(np.asarray(sres.counts), want_counts)


def test_compact_through_maintenance_and_staged_overlay():
    """Counts stay equal to a scan through inserts, deletes+vacuum, and —
    on the sharded index — through the writer's staged overlay (rows
    pending in queues count, without ever appearing in row ids)."""
    rng = np.random.default_rng(7)
    values = np.sort(rng.uniform(0, 1000, 1500))
    idx, sidx = make_pair(values, 2)
    preds = workload(rng, widths=(5.0, 100.0), per_width=2)

    # maintenance on the unsharded index: eager inserts + delete/vacuum
    for v in rng.uniform(0, 1000, 20):
        idx.insert(float(v))
    idx.table.delete_where(200, 260)
    idx.vacuum()
    res = idx.search_batch(preds, max_selected=idx.table.num_pages,
                           top_k=TOP_K)
    np.testing.assert_array_equal(np.asarray(res.counts),
                                  brute_counts(idx.table, preds))
    for q, p in enumerate(preds):
        ids = np.asarray(res.row_ids[q])
        np.testing.assert_array_equal(
            ids[ids >= 0], brute_ids(idx.table, *p.selectivity_interval())[:TOP_K])

    # staged overlay on the sharded index
    writer = MaintenanceWriter(sidx)
    staged = rng.uniform(0, 1000, 15)
    for v in staged:
        writer.write(float(v))
    cap = sidx.spec.pages_per_shard
    res = sidx.search_batch(preds, max_selected=cap, top_k=TOP_K)
    np.testing.assert_array_equal(np.asarray(res.counts),
                                  brute_counts(sidx.table, preds, staged))
    # row ids exclude staged rows: they equal the table-only brute force
    for q, p in enumerate(preds):
        ids = np.asarray(res.row_ids[q])
        np.testing.assert_array_equal(
            ids[ids >= 0],
            brute_ids(sidx.table, *p.selectivity_interval())[:TOP_K])
    # after the drain the staged rows land in pages (and so in row ids)
    writer.flush()
    res = sidx.search_batch(preds, max_selected=cap, top_k=TOP_K)
    np.testing.assert_array_equal(np.asarray(res.counts),
                                  brute_counts(sidx.table, preds))
    for q, p in enumerate(preds):
        ids = np.asarray(res.row_ids[q])
        np.testing.assert_array_equal(
            ids[ids >= 0],
            brute_ids(sidx.table, *p.selectivity_interval())[:TOP_K])


# ---------------------------------------------------------------------------
# Inspection in place: a slab too wide for the gather to pay is not built
# ---------------------------------------------------------------------------

def _slab_pages(target) -> int:
    """Pages each compact program inspects: the table's, or a shard's."""
    spec = getattr(target, "spec", None)
    return spec.pages_per_shard if spec is not None else target.table.num_pages


def _in_place_case(name):
    """(index, predicates, expected counts) for one in-place case; expected
    counts come from a scan, plus the staged rows where a writer holds
    them."""
    rng = np.random.default_rng({"unsharded": 23, "sharded_spare": 29,
                                 "staged": 31, "maintained": 37,
                                 "empty": 41}[name])
    # 2,000 pages: wide enough that a bucket below the threshold holds
    # whole queries
    values = np.sort(rng.uniform(0, 1000, 16000))
    preds = workload(rng)
    if name == "empty":
        hist = hg.build(jnp.asarray(rng.uniform(0, 1000, 256)), 64)
        idx = HippoIndex.create(
            PagedTable.from_values(np.zeros(0, np.float32),
                                   page_card=PAGE_CARD),
            resolution=64, density=0.25, hist=hist)
        return idx, preds, brute_counts(idx.table, preds)
    idx, sidx = make_pair(values, 3 if name == "sharded_spare" else 2)
    if name == "unsharded":
        return idx, preds, brute_counts(idx.table, preds)
    if name == "maintained":
        for v in rng.uniform(0, 1000, 20):
            idx.insert(float(v))
        idx.table.delete_where(200, 260)
        idx.vacuum()
        return idx, preds, brute_counts(idx.table, preds)
    staged = rng.uniform(0, 1000, 15) if name == "staged" else ()
    if name == "staged":
        writer = MaintenanceWriter(sidx)
        for v in staged:
            writer.write(float(v))
        assert sidx.staging.staged_rows
    assert sidx.spec.pages_per_shard * 3 > sidx.table.num_pages  # spare slab
    return sidx, preds, brute_counts(sidx.table, preds, staged)


@pytest.mark.parametrize("backend,shrink", [("tpu", 128), ("cpu", 1),
                                            ("gpu", 1)])
def test_inspects_in_place_reads_the_backends_crossover(monkeypatch, backend,
                                                        shrink):
    """The rule takes the crossover of the backend the program runs on: a
    slab ``shrink`` times narrower than its table still goes in place, one
    page narrower gathers. A backend not measured gathers below the cap."""
    monkeypatch.setattr(hix.jax, "default_backend", lambda: backend)
    for pages in (1, 1000, 4096):
        narrowest = -(-pages // shrink)
        assert hix.inspects_in_place(narrowest, pages)
        assert not hix.inspects_in_place(narrowest - 1, pages)
    assert hix.inspects_in_place(0, 0)     # an empty table gathers nothing


@pytest.mark.parametrize("shrink", [None, 128])
@pytest.mark.parametrize("case", ["unsharded", "sharded_spare", "staged",
                                  "maintained", "empty"])
def test_in_place_equals_gather_dense_and_brute_force(case, shrink,
                                                      monkeypatch):
    """In place, the search answers what the gather branch, step 2 and a
    brute-force scan answer: counts,
    ``pages_inspected``, ``entries_matched`` and the first ``TOP_K`` row
    ids. It runs at the first width ``inspects_in_place`` takes and at the
    cap, and the gather branch one width below that threshold; in place
    never flags ``truncated`` and gathers no page. With ``shrink`` 128 the
    TPU's crossover stands in for this backend's, so the in-place program
    also runs at a slab narrower than its table."""
    if shrink is not None:
        monkeypatch.setitem(hix._GATHER_SHRINK, jax.default_backend(), shrink)
    target, preds, want_counts = _in_place_case(case)
    want_ids = [brute_ids(target.table, *p.selectivity_interval())[:TOP_K]
                for p in preds]
    pages = _slab_pages(target)
    first = next(m for m in range(1, pages + 2)
                 if target.inspects_in_place(m))
    assert all(target.inspects_in_place(m)
               for m in (first, first + 1, target.gather_cap))
    masks, matched = step2(target, preds)
    results = {}
    for m in sorted({first, target.gather_cap}):
        res = target.search_batch(preds, max_selected=m, top_k=TOP_K)
        assert not np.asarray(res.truncated).any(), m
        assert int(res.pages_gathered) == 0, m
        np.testing.assert_array_equal(np.asarray(res.counts), want_counts)
        np.testing.assert_array_equal(np.asarray(res.pages_inspected),
                                      masks.sum(axis=1))
        np.testing.assert_array_equal(np.asarray(res.entries_matched),
                                      matched)
        # the batch's union over the table, and its widest part in a shard
        union = masks.any(axis=0)
        per_shard = [union[s:s + pages].sum()
                     for s in range(0, union.size, max(pages, 1))]
        assert int(res.pages_selected) == union.sum()
        assert int(res.bucket_needed) == max(per_shard, default=0)
        for q in range(len(preds)):
            ids = np.asarray(res.row_ids[q])
            np.testing.assert_array_equal(ids[ids >= 0], want_ids[q], (m, q))
        results[m] = res
    if first == 1:          # no width gathers: only the empty table
        assert case == "empty" and pages == 0
        return
    below = first - 1
    assert not target.inspects_in_place(below)
    g = target.search_batch(preds, max_selected=below, top_k=TOP_K)
    trunc = np.asarray(g.truncated)
    assert (~trunc).any() and int(g.pages_gathered) > 0
    res = results[first]
    np.testing.assert_array_equal(np.asarray(g.counts)[~trunc],
                                  np.asarray(res.counts)[~trunc])
    np.testing.assert_array_equal(np.asarray(g.row_ids)[~trunc],
                                  np.asarray(res.row_ids)[~trunc])
    for name in ("pages_inspected", "entries_matched", "pages_selected",
                 "bucket_needed"):
        np.testing.assert_array_equal(np.asarray(getattr(g, name)),
                                      np.asarray(getattr(res, name)), name)


# ---------------------------------------------------------------------------
# Engine: ladder, fallback, row-id payloads
# ---------------------------------------------------------------------------

def test_engine_compact_mode_is_default_and_matches_dense():
    rng = np.random.default_rng(11)
    idx, sidx = make_pair(np.sort(rng.uniform(0, 1000, 2000)), 2)
    preds = workload(rng)
    want = brute_counts(idx.table, preds)
    for target in (idx, sidx):
        engine = QueryEngine(target, batch=8)
        assert engine.mode == "compact"
        np.testing.assert_array_equal(engine.run_all(preds), want)
        assert engine.stats.compact_batches > 0
        assert 0 < engine.stats.selected_page_ratio <= 1.0


def test_engine_compact_fallback_never_wrong():
    """A deliberately tiny initial bucket forces truncation: the per-query
    fallback must keep every count equal to a scan's while the adaptive
    bucket widens for later batches."""
    rng = np.random.default_rng(13)
    idx, sidx = make_pair(np.sort(rng.uniform(0, 1000, 2000)), 2)
    preds = workload(rng)
    dense = brute_counts(idx.table, preds)
    for target in (idx, sidx):
        engine = QueryEngine(target, batch=8, compact_bucket=1, top_k=8)
        first_bucket = engine._compact_bucket
        np.testing.assert_array_equal(engine.run_all(preds), dense)
        assert engine.stats.compact_fallbacks > 0      # the ladder was walked
        assert engine.stats.compact_hits > 0
        assert engine._compact_bucket > first_bucket   # and the bucket adapted
        # a replay is served without fallbacks at the adapted bucket
        before = engine.stats.compact_fallbacks
        np.testing.assert_array_equal(engine.run_all(preds), dense)
        assert engine.stats.compact_fallbacks == before


def test_engine_row_id_payloads_match_brute_force():
    rng = np.random.default_rng(17)
    idx, _ = make_pair(np.sort(rng.uniform(0, 1000, 1200)), 1)
    engine = QueryEngine(idx, batch=4, top_k=16)
    preds = workload(rng, widths=(3.0, 50.0), per_width=2)
    tickets = [engine.submit(p) for p in preds]
    engine.drain()
    for t, p in zip(tickets, preds):
        want = brute_ids(idx.table, *p.selectivity_interval())
        assert t.count == want.size
        np.testing.assert_array_equal(t.row_ids, want[:16])
        # the payload decodes back to in-range key values
        vals = idx.table.row_values(t.row_ids)
        lo, hi = p.selectivity_interval()
        assert ((vals >= lo) & (vals <= hi)).all()


def test_engine_mode_validation():
    rng = np.random.default_rng(19)
    idx, sidx = make_pair(rng.uniform(0, 1000, 300), 2)
    for target in (idx, sidx):
        assert QueryEngine(target, mode="compact").mode == "compact"
    with pytest.raises(ValueError, match="mode"):
        QueryEngine(idx, mode="bogus")
    with pytest.raises(ValueError, match="top_k"):
        QueryEngine(idx, top_k=-1)
    with pytest.raises(ValueError, match="compact_bucket"):
        QueryEngine(idx, compact_bucket=0)


@pytest.mark.parametrize("mode", ["dense", "auto"])
def test_engine_refuses_the_removed_modes(mode):
    """One query path: the full-table and auto-selected modes are gone, and
    asking for one is refused rather than silently served compact."""
    rng = np.random.default_rng(20)
    idx, sidx = make_pair(rng.uniform(0, 1000, 300), 2)
    for target in (idx, sidx):
        with pytest.raises(ValueError, match="mode"):
            QueryEngine(target, mode=mode)
