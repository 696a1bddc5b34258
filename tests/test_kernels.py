"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""
from functools import partial

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.batch_filter import ops as batch_filter_ops
from repro.kernels.batch_filter.ref import (batch_filter_ref,
                                            batch_filter_sharded_ref)
from repro.kernels.bitmap_and import ops as bitmap_and_ops
from repro.kernels.bitmap_and.ref import bitmap_and_any_ref
from repro.kernels.compact_inspect import ops as compact_inspect_ops
from repro.kernels.compact_inspect.ref import compact_inspect_ref
from repro.kernels.bucketize import ops as bucketize_ops
from repro.kernels.bucketize.ref import bucketize_ref
from repro.kernels.page_inspect import ops as page_inspect_ops
from repro.kernels.page_inspect.ref import page_inspect_ref

# The wrappers compile for the TPU unless told otherwise; on this CPU every
# call runs the Pallas interpreter, asked for explicitly.
batch_filter = partial(batch_filter_ops.batch_filter, interpret=True)
batch_filter_sharded = partial(batch_filter_ops.batch_filter_sharded,
                               interpret=True)
bitmap_and_any = partial(bitmap_and_ops.bitmap_and_any, interpret=True)
compact_inspect = partial(compact_inspect_ops.compact_inspect, interpret=True)
bucketize_values = partial(bucketize_ops.bucketize_values, interpret=True)
page_inspect = partial(page_inspect_ops.page_inspect, interpret=True)


# ---------------------------------------------------------------------------
# bitmap_and
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_entries", [1, 7, 512, 513, 2048])
@pytest.mark.parametrize("words", [1, 13, 50, 128])
def test_bitmap_and_shapes(num_entries, words):
    rng = np.random.default_rng(num_entries * 1000 + words)
    entries = rng.integers(0, 2**32, (num_entries, words), dtype=np.uint32)
    # sparse query so matches are non-trivial
    query = (rng.integers(0, 2**32, (words,), dtype=np.uint32)
             & rng.integers(0, 2**32, (words,), dtype=np.uint32))
    got = bitmap_and_any(jnp.asarray(entries), jnp.asarray(query))
    want = bitmap_and_any_ref(jnp.asarray(entries), jnp.asarray(query))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitmap_and_all_zero_query():
    entries = jnp.ones((64, 4), jnp.uint32)
    query = jnp.zeros((4,), jnp.uint32)
    assert int(bitmap_and_any(entries, query).sum()) == 0


# ---------------------------------------------------------------------------
# batch_filter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_queries,num_entries,words", [
    (1, 1, 1),        # all dims below one tile
    (7, 127, 13),     # all dims need padding (H=400 -> 13 words)
    (8, 128, 13),     # exact tile multiples in q and e
    (9, 129, 13),     # one past the tile boundary
    (64, 300, 1),     # single-word bitmaps
    (16, 256, 128),   # multiple tiles on every axis, lane-exact words
])
def test_batch_filter_shapes(num_queries, num_entries, words):
    rng = np.random.default_rng(num_queries * 10000 + num_entries * 10 + words)
    entries = rng.integers(0, 2**32, (num_entries, words), dtype=np.uint32)
    queries = (rng.integers(0, 2**32, (num_queries, words), dtype=np.uint32)
               & rng.integers(0, 2**32, (num_queries, words), dtype=np.uint32))
    got = batch_filter(jnp.asarray(queries), jnp.asarray(entries))
    want = batch_filter_ref(jnp.asarray(queries), jnp.asarray(entries))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batch_filter_rows_match_bitmap_and():
    """Each row of the batched kernel equals the single-query kernel."""
    rng = np.random.default_rng(9)
    entries = jnp.asarray(rng.integers(0, 2**32, (200, 13), dtype=np.uint32))
    queries = jnp.asarray(rng.integers(0, 2**32, (5, 13), dtype=np.uint32))
    batched = np.asarray(batch_filter(queries, entries))
    for q in range(queries.shape[0]):
        row = np.asarray(bitmap_and_any(entries, queries[q]))
        np.testing.assert_array_equal(batched[q], row)


def test_batch_filter_zero_and_dense_queries():
    entries = jnp.ones((64, 4), jnp.uint32)
    queries = jnp.stack([jnp.zeros((4,), jnp.uint32),
                         jnp.full((4,), 0xFFFFFFFF, jnp.uint32)])
    out = np.asarray(batch_filter(queries, entries))
    assert out[0].sum() == 0 and out[1].sum() == 64


@pytest.mark.shard
@pytest.mark.parametrize("num_shards,num_queries,num_entries,words", [
    (1, 1, 1, 1),       # all dims below one tile
    (3, 7, 127, 13),    # every axis needs padding
    (4, 8, 128, 13),    # exact tile multiples in q and e
    (2, 9, 129, 13),    # one past the tile boundary
    (5, 16, 64, 128),   # lane-exact words, several shards
])
def test_batch_filter_sharded_shapes(num_shards, num_queries, num_entries, words):
    rng = np.random.default_rng(
        num_shards * 100000 + num_queries * 1000 + num_entries * 10 + words)
    entries = rng.integers(0, 2**32, (num_shards, num_entries, words),
                           dtype=np.uint32)
    queries = (rng.integers(0, 2**32, (num_queries, words), dtype=np.uint32)
               & rng.integers(0, 2**32, (num_queries, words), dtype=np.uint32))
    got = batch_filter_sharded(jnp.asarray(queries), jnp.asarray(entries))
    want = batch_filter_sharded_ref(jnp.asarray(queries), jnp.asarray(entries))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.shard
def test_batch_filter_sharded_slices_match_unsharded():
    """Each shard's (Q, E) slice equals the unsharded kernel on that shard's
    entry table — the kernel analogue of the count-reduce parity."""
    rng = np.random.default_rng(13)
    entries = jnp.asarray(rng.integers(0, 2**32, (3, 100, 13), dtype=np.uint32))
    queries = jnp.asarray(rng.integers(0, 2**32, (5, 13), dtype=np.uint32))
    out = np.asarray(batch_filter_sharded(queries, entries))
    for s in range(3):
        np.testing.assert_array_equal(out[s],
                                      np.asarray(batch_filter(queries, entries[s])))


# ---------------------------------------------------------------------------
# bucketize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 100, 1024, 1025, 5000])
@pytest.mark.parametrize("resolution", [8, 100, 400, 1600])
def test_bucketize_shapes(n, resolution):
    rng = np.random.default_rng(n * 7 + resolution)
    bounds = np.sort(rng.uniform(0, 1000, resolution + 1)).astype(np.float32)
    bounds += np.arange(resolution + 1, dtype=np.float32) * 1e-3  # strict
    values = rng.uniform(-100, 1100, n).astype(np.float32)
    got = bucketize_values(jnp.asarray(values), jnp.asarray(bounds), resolution)
    want = bucketize_ref(jnp.asarray(values), jnp.asarray(bounds), resolution)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.float64])
def test_bucketize_input_dtypes(dtype):
    rng = np.random.default_rng(3)
    bounds = np.linspace(0, 100, 33).astype(np.float32)
    values = rng.uniform(0, 100, 300).astype(dtype)
    got = bucketize_values(jnp.asarray(values).astype(jnp.float32),
                           jnp.asarray(bounds), 32)
    want = bucketize_ref(jnp.asarray(values).astype(jnp.float32),
                         jnp.asarray(bounds), 32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bucketize_boundary_values():
    bounds = jnp.asarray(np.linspace(0.0, 10.0, 11), jnp.float32)
    values = jnp.asarray([0.0, 1.0, 9.999, 10.0, -1.0, 11.0], jnp.float32)
    got = bucketize_values(values, bounds, 10)
    want = bucketize_ref(values, bounds, 10)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # clamping: below-range -> bucket 0, above-range -> bucket H-1
    assert int(got[4]) == 0 and int(got[5]) == 9


# ---------------------------------------------------------------------------
# page_inspect
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pages,card", [(1, 1), (10, 50), (64, 128), (65, 130), (200, 7)])
def test_page_inspect_shapes(pages, card):
    rng = np.random.default_rng(pages * 31 + card)
    keys = rng.uniform(0, 100, (pages, card)).astype(np.float32)
    valid = rng.random((pages, card)) < 0.9
    mask = rng.random((pages,)) < 0.5
    lo, hi = 25.0, 75.0
    qual, counts = page_inspect(jnp.asarray(keys), jnp.asarray(valid),
                                jnp.asarray(mask), lo, hi)
    qual_ref, counts_ref = page_inspect_ref(jnp.asarray(keys), jnp.asarray(valid),
                                            jnp.asarray(mask), lo, hi)
    np.testing.assert_array_equal(np.asarray(qual), np.asarray(qual_ref))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))


def test_page_inspect_empty_interval():
    keys = jnp.ones((8, 16), jnp.float32)
    valid = jnp.ones((8, 16), bool)
    mask = jnp.ones((8,), bool)
    qual, counts = page_inspect(keys, valid, mask, 5.0, 4.0)
    assert int(counts.sum()) == 0 and not bool(qual.any())


# ---------------------------------------------------------------------------
# compact_inspect
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("queries,pages,card", [
    (1, 1, 1),         # all dims below one tile
    (5, 37, 50),       # all dims need padding
    (8, 64, 128),      # exact tile multiples
    (9, 65, 130),      # one past every tile boundary
    (16, 200, 7),      # multiple tiles, narrow pages
])
def test_compact_inspect_shapes(queries, pages, card):
    rng = np.random.default_rng(queries * 10000 + pages * 10 + card)
    keys = rng.uniform(0, 100, (pages, card)).astype(np.float32)
    valid = rng.random((pages, card)) < 0.9
    sel = rng.random((queries, pages)) < 0.5
    los = rng.uniform(0, 60, queries).astype(np.float32)
    his = (los + rng.uniform(0, 40, queries)).astype(np.float32)
    got = compact_inspect(jnp.asarray(keys), jnp.asarray(valid),
                          jnp.asarray(sel), jnp.asarray(los), jnp.asarray(his))
    want = compact_inspect_ref(jnp.asarray(keys), jnp.asarray(valid),
                               jnp.asarray(sel), los, his)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compact_inspect_empty_interval_and_mask():
    keys = jnp.ones((8, 16), jnp.float32)
    valid = jnp.ones((8, 16), bool)
    sel = jnp.ones((4, 8), bool)
    los = jnp.asarray([5.0, 0.0, 0.0, 2.0], jnp.float32)
    his = jnp.asarray([4.0, 2.0, 2.0, 1.0], jnp.float32)   # rows 0 and 3 empty
    counts = np.asarray(compact_inspect(keys, valid, sel, los, his))
    assert counts[0].sum() == 0 and counts[3].sum() == 0
    assert (counts[1] == 16).all() and (counts[2] == 16).all()
    # an all-false selected mask zeroes everything regardless of interval
    none = np.asarray(compact_inspect(keys, valid, jnp.zeros((4, 8), bool),
                                      los, his))
    assert none.sum() == 0


def test_compact_inspect_matches_search_compact_many():
    """The kernel's per-(query, slab page) counts agree with the gather
    search path when fed the same slab and selected masks."""
    from repro.core import index as hix
    from repro.core.hippo import HippoIndex
    from repro.core.predicate import (Predicate, intervals,
                                      to_bucket_bitmaps)
    from repro.storage.table import PagedTable

    rng = np.random.default_rng(14)
    values = np.sort(rng.uniform(0, 1000, 4000))
    table = PagedTable.from_values(values, page_card=50)
    idx = HippoIndex.create(table, resolution=400, density=0.2)
    preds = [Predicate.between(float(lo), float(lo) + 30.0)
             for lo in rng.uniform(0, 1000, 8)]
    preds.append(Predicate(lo=5.0, hi=1.0))
    qbms = to_bucket_bitmaps(preds, idx.state.histogram)
    los, his = intervals(preds)
    keys, valid = table.device_keys(), table.device_valid()
    res = hix.search_compact_many(idx.state, qbms, keys, valid, los, his,
                                  max_selected=table.num_pages, top_k=0)
    assert not np.asarray(res.truncated).any()
    # rebuild the slab + selected masks exactly as the search does: step 2
    # gives each query's pages, step 3 (``_page_counts``) counts them
    page_mask = np.asarray(hix._page_match(idx.state, qbms,
                                           table.num_pages)[0])
    np.testing.assert_array_equal(
        np.asarray(hix._page_counts(jnp.asarray(page_mask), keys, valid,
                                    los, his)).sum(axis=1),
        np.asarray(res.counts))
    union = page_mask.any(axis=0)
    sel = np.flatnonzero(union)
    slab_keys = np.asarray(keys)[sel]
    slab_valid = np.asarray(valid)[sel]
    sel_mask = page_mask[:, sel]
    counts = compact_inspect(jnp.asarray(slab_keys), jnp.asarray(slab_valid),
                             jnp.asarray(sel_mask), los, his)
    np.testing.assert_array_equal(np.asarray(counts).sum(axis=1),
                                  np.asarray(res.counts))


# ---------------------------------------------------------------------------
# kernels against the index search (end-to-end agreement)
# ---------------------------------------------------------------------------

def test_kernelized_filter_matches_index_search():
    from repro.core import index as hix
    from repro.core.hippo import HippoIndex
    from repro.core.predicate import Predicate, to_bucket_bitmap
    from repro.storage.table import PagedTable

    rng = np.random.default_rng(11)
    values = rng.uniform(0, 1000, 4000)
    table = PagedTable.from_values(values, page_card=50)
    idx = HippoIndex.create(table, resolution=400, density=0.2)
    pred = Predicate.between(100, 105)
    res = idx.search(pred)
    qbm = to_bucket_bitmap(pred, idx.state.histogram)
    s = idx.cfg.max_slots
    live = np.asarray(idx.state.slot_live) & (np.arange(s) < int(idx.state.num_slots))
    match_kernel = np.asarray(bitmap_and_any(idx.state.bitmaps, qbm)).astype(bool) & live
    assert match_kernel.sum() == int(res.entries_matched[0])
    # inspect with the kernel too, over step 2's pages
    page_mask = hix._page_match(idx.state, qbm[None, :], table.num_pages)[0]
    assert int(page_mask.sum()) == int(res.pages_inspected[0])
    qual, counts = page_inspect(table.device_keys(), table.device_valid(),
                                page_mask[0], pred.lo, pred.hi)
    assert int(counts.sum()) == int(res.counts[0])


def test_batch_filter_matches_search_many():
    """The fused kernel's (Q, E) match matrix agrees with the entry-match
    step of the batched search (entries_matched per query)."""
    from repro.core.hippo import HippoIndex
    from repro.core.predicate import Predicate, to_bucket_bitmaps
    from repro.storage.table import PagedTable

    rng = np.random.default_rng(12)
    values = rng.uniform(0, 1000, 4000)
    table = PagedTable.from_values(values, page_card=50)
    idx = HippoIndex.create(table, resolution=400, density=0.2)
    preds = [Predicate.between(float(lo), float(lo) + 40.0)
             for lo in rng.uniform(0, 1000, 16)]
    preds.append(Predicate(lo=5.0, hi=1.0))        # all-zero query row
    qbms = to_bucket_bitmaps(preds, idx.state.histogram)
    res = idx.search_batch(preds)
    s = idx.cfg.max_slots
    live = np.asarray(idx.state.slot_live) & (np.arange(s) < int(idx.state.num_slots))
    match = np.asarray(batch_filter(qbms, idx.state.bitmaps)).astype(bool) & live[None, :]
    np.testing.assert_array_equal(match.sum(axis=1),
                                  np.asarray(res.entries_matched))
