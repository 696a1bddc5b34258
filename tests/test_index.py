import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import histogram as hg
from repro.core import index as hix
from repro.core.hippo import HippoIndex
from repro.core.partition import ShardedHippoIndex
from repro.core.predicate import Predicate, to_bucket_bitmap, to_bucket_bitmaps
from repro.storage.table import PagedTable


def make_index(values, page_card=8, resolution=32, density=0.25, **kw):
    table = PagedTable.from_values(values, page_card=page_card, spare_pages=64)
    return HippoIndex.create(table, resolution=resolution, density=density, **kw)


def brute_force(table, lo, hi):
    live = table.valid[: table.num_pages]
    keys = table.keys[: table.num_pages]
    return int((live & (keys >= lo) & (keys <= hi)).sum())


def page_masks(idx, preds):
    """Step 2 of Algorithm 1 alone: (Q, pages) possible-qualified pages."""
    qbms = to_bucket_bitmaps(preds, idx.state.histogram)
    return np.asarray(hix._page_match(idx.state, qbms,
                                      idx.table.num_pages)[0])


def test_build_structure_invariants():
    rng = np.random.default_rng(0)
    values = rng.uniform(0, 1000, size=2000)
    idx = make_index(values)
    starts, ends, bitmaps = idx.entries_host()
    # Entries partition [0, num_pages-1] contiguously and in order.
    assert starts[0] == 0
    assert ends[-1] == idx.table.num_pages - 1
    np.testing.assert_array_equal(starts[1:], ends[:-1] + 1)
    assert (ends >= starts).all()
    # Each entry bitmap is non-empty; all but the trailing entry exceeded D.
    pops = np.asarray(bm.popcount(jnp.asarray(bitmaps)))
    assert (pops > 0).all()
    dens = pops / idx.cfg.resolution
    assert (dens[:-1] > idx.cfg.density).all()


def test_locate_slot_takes_an_array_of_pages():
    """One sorted-list search over many pages equals one search per page."""
    rng = np.random.default_rng(3)
    idx = make_index(rng.uniform(0, 100, 400))
    pages = np.arange(idx.table.num_pages, dtype=np.int32)
    slots, pos = hix.locate_slot(idx.state, jnp.asarray(pages))
    for p in pages:
        s, q = hix.locate_slot(idx.state, jnp.int32(p))
        assert (int(slots[p]), int(pos[p])) == (int(s), int(q))
        assert int(idx.state.starts[s]) <= p <= int(idx.state.ends[s])


@pytest.mark.parametrize("n", [1, 3, 4, 5, 8, 9])
def test_owning_slots_pads_to_a_power_of_two(n):
    """The host search over a subset of pages equals one search per page,
    and every count in (2^k, 2^(k+1)] reuses one compiled program."""
    rng = np.random.default_rng(4)
    idx = make_index(rng.uniform(0, 100, 400))
    pages = np.sort(rng.choice(idx.table.num_pages, n, replace=False))
    slots = hix.owning_slots(idx.state, pages)
    assert slots.shape == (n,)
    for p, s in zip(pages, slots):
        assert int(s) == int(hix.locate_slot(idx.state, jnp.int32(p))[0])
    before = hix._locate_slot._cache_size()
    width = 1 << (n - 1).bit_length()
    for m in range(width // 2 + 1, width + 1):
        hix.owning_slots(idx.state, pages[:1].repeat(m))
    assert hix._locate_slot._cache_size() - before <= 1


def test_entries_matched_counts_live_entries_after_maintenance():
    """entries_matched and the page mask equal a direct joint-bucket test of
    every live slot expanded over its page range — also after relocating
    inserts and a delete + vacuum have reshuffled slots and the sorted list,
    and for a batch wider than one 32-query word of the packed lookup."""
    rng = np.random.default_rng(5)
    idx = make_index(rng.uniform(0, 100, 800), relocate_on_update=True)
    for v in rng.uniform(0, 100, 60):
        idx.insert(float(v))
    idx.table.delete_where(20.0, 30.0)
    idx.vacuum()
    preds = [Predicate.between(float(lo), float(lo) + w)
             for lo, w in zip(rng.uniform(0, 100, 40), rng.uniform(0, 20, 40))]
    res = idx.search_batch(preds)
    masks = page_masks(idx, preds)
    st = idx.state
    s = idx.cfg.max_slots
    live = np.asarray(st.slot_live) & (np.arange(s) < int(st.num_slots))
    assert live.sum() < int(st.num_slots)       # relocation left dead slots
    starts, ends = np.asarray(st.starts), np.asarray(st.ends)
    for q, p in enumerate(preds):
        qbm = to_bucket_bitmap(p, st.histogram)
        match = np.asarray(bm.any_joint(st.bitmaps, qbm[None, :])) & live
        pages = np.zeros(idx.table.num_pages, bool)
        for e in np.flatnonzero(match):
            pages[starts[e]:ends[e] + 1] = True
        assert int(res.entries_matched[q]) == int(match.sum())
        assert int(res.pages_inspected[q]) == int(pages.sum())
        assert (masks[q] == pages).all()
        single = idx.search(p)
        assert int(single.entries_matched[0]) == int(match.sum())
        assert (page_masks(idx, [p])[0] == pages).all()


def _expansion_case(name):
    """(stacked state, (shards, Q, W) query bitmaps, pages) for one case of
    the page-expansion checks; an unsharded state gains a shard axis of 1."""
    rng = np.random.default_rng(11)
    preds = [Predicate.between(float(lo), float(lo) + w)
             for lo, w in zip(rng.uniform(0, 100, 40), rng.uniform(0, 20, 40))]
    if name == "sharded":
        table = PagedTable.from_values(
            rng.uniform(0, 100, 8000).astype(np.float32), page_card=8)
        idx = ShardedHippoIndex.create(table, num_shards=4, resolution=32,
                                       density=0.25)
        keys, _ = idx._slabs()
        return idx.state.shards, idx._query_bitmaps(preds), keys.shape[1]
    if name == "empty":
        cfg = hix.HippoConfig(resolution=32, density=0.25, page_card=8,
                              max_slots=64)
        hist = hg.build(jnp.asarray(rng.uniform(0, 100, 256)), 32)
        state = hix.build(cfg, hist, jnp.zeros((0, 8), jnp.float32),
                          jnp.zeros((0, 8), bool))
        pages = 300
    else:
        # "dense": every page its own entry, so every page starts one
        idx = make_index(rng.uniform(0, 100, 4000),
                         density=0.0 if name == "dense" else 0.25,
                         relocate_on_update=name == "relocated")
        if name == "relocated":
            for v in rng.uniform(0, 100, 60):
                idx.insert(float(v))
            idx.table.delete_where(20.0, 30.0)
            idx.vacuum()
        if name == "gap":
            # three live pages, each of 8 values in 8 of the 32 buckets (an
            # entry that holds one reaches D), a page whose rows never go
            # live, then two live pages: the next entry opens after the
            # dead page, which no entry owns
            live = np.repeat([True] * 3 + [False] + [True] * 2, 8)
            values = np.tile(np.linspace(1, 99, 8, dtype=np.float32), 6)
            page_of = (idx.table.tail + np.arange(live.size)) // 8
            idx.table.append(values, live)
            idx.state = hix.insert_appended(
                idx.cfg, idx.state, *hix.pad_rows(values, page_of, live))
        state = idx.state
        pages = idx.table.num_pages
        if name == "slack":
            # the slab's pages past the last entry's end, and barely more
            # slots than entries (build order is page order)
            pages += 17
            k = int(state.num_entries) + 5
            state = state._replace(**{f: getattr(state, f)[:k] for f in (
                "bitmaps", "starts", "ends", "sorted_order", "slot_live")},
                num_slots=jnp.int32(k))
    qbms = jnp.stack([to_bucket_bitmap(p, state.histogram) for p in preds])
    return (jax.tree.map(lambda a: jnp.asarray(a)[None], state), qbms[None],
            pages)


def _owner_reference(state, qbms, pages):
    """Per-query page masks and match counts of one shard, in numpy: every
    live logical entry's joint-bucket test, spread over its page range."""
    starts, ends = np.asarray(state.starts), np.asarray(state.ends)
    order, bits = np.asarray(state.sorted_order), np.asarray(state.bitmaps)
    slots = np.arange(bits.shape[0])
    live = np.asarray(state.slot_live) & (slots < int(state.num_slots))
    match = ((bits[None] & np.asarray(qbms)[:, None]) != 0).any(-1) & live
    mask = np.zeros((match.shape[0], pages), bool)
    for e in order[: int(state.num_entries)]:
        mask[:, starts[e]:ends[e] + 1] = match[:, e, None]
    return mask, match.sum(axis=1)


@pytest.mark.parametrize("case", ["fresh", "relocated", "empty", "slack",
                                  "sharded", "dense", "gap"])
def test_page_expansion_equals_the_binary_search(case):
    """The block-wise expansion of the sorted starts gives every page the
    logical position a binary search gives it and that entry's row, and
    ``_page_match``'s masks equal each live entry's match bits spread over
    its own pages."""
    shards, qbms, pages = _expansion_case(case)
    ls = jax.vmap(hix._logical_starts)(shards)
    assert pages > hix._BLOCK            # more than one block of pages
    every = jnp.arange(pages, dtype=jnp.int32)
    want = jax.vmap(lambda l: jnp.searchsorted(l, every, side="right") - 1)(ls)
    table = jnp.stack([jnp.arange(ls.shape[1], dtype=jnp.int32) + 1] * 2)
    pos, cols = jax.vmap(hix._page_owners, in_axes=(0, None, None))(
        ls, table, pages)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(want))
    col = np.asarray(want) + 1                    # 0 where there is no entry
    np.testing.assert_array_equal(np.asarray(cols), np.stack([col] * 2, 1))
    masks, matched = jax.vmap(hix._page_match, in_axes=(0, 0, None))(
        shards, qbms, pages)
    for k in range(qbms.shape[0]):
        shard = jax.tree.map(lambda a: a[k], shards)
        mask, count = _owner_reference(shard, qbms[k], pages)
        np.testing.assert_array_equal(np.asarray(masks[k]), mask)
        np.testing.assert_array_equal(np.asarray(matched[k]), count)
    if case == "relocated":
        order = np.asarray(shards.sorted_order[0])
        assert (order != np.arange(order.size)).any()
        live = np.asarray(shards.slot_live[0])
        assert live.sum() < int(shards.num_slots[0])  # dead slots exist
    if case in ("empty", "slack"):
        last = int(shards.summarized_until[0])
        assert not np.asarray(masks[:, :, last + 1:]).any()
    if case == "slack":
        assert shards.starts.shape[1] < int(shards.num_entries[0]) + hix._BLOCK
    if case == "dense":
        assert int(shards.num_entries[0]) == pages
    if case == "gap":
        dead = pages - 3                  # the appended page with no live row
        e = np.asarray(shards.sorted_order[0])[:int(shards.num_entries[0])]
        starts = np.asarray(shards.starts[0])[e]
        ends = np.asarray(shards.ends[0])[e]
        assert dead + 1 in starts and dead - 1 in ends    # a gap of one page
        assert not ((starts <= dead) & (ends >= dead)).any()
        assert not np.asarray(masks[0, :, dead]).any()
        assert np.asarray(pos[0, dead]) >= 0    # past an entry, not before all


def test_entry_bitmap_matches_page_contents():
    rng = np.random.default_rng(1)
    values = rng.uniform(0, 100, size=600)
    idx = make_index(values)
    hist = idx.state.histogram
    starts, ends, bitmaps = idx.entries_host()
    keys = idx.table.keys[: idx.table.num_pages]
    valid = idx.table.valid[: idx.table.num_pages]
    ids = np.asarray(hg.bucketize(hist, jnp.asarray(keys.ravel()))).reshape(keys.shape)
    for s, e, packed in zip(starts, ends, bitmaps):
        expect = np.zeros(idx.cfg.resolution, bool)
        blk = ids[s : e + 1][valid[s : e + 1]]
        expect[blk] = True
        got = np.asarray(bm.to_bool(jnp.asarray(packed), idx.cfg.resolution))
        np.testing.assert_array_equal(got, expect)


@pytest.mark.parametrize("dist", ["uniform", "skewed", "sorted", "lowcard"])
def test_search_exact_vs_bruteforce(dist):
    rng = np.random.default_rng(2)
    n = 3000
    if dist == "uniform":
        values = rng.uniform(0, 1000, n)
    elif dist == "skewed":
        values = rng.exponential(50, n)
    elif dist == "sorted":
        values = np.sort(rng.uniform(0, 1000, n))
    else:
        values = rng.integers(0, 12, n).astype(float)
    idx = make_index(values)
    for lo, hi in [(0, 1000), (100, 110), (500, 500), (-5, -1), (900, 2000)]:
        got = idx.count(Predicate.between(lo, hi))
        assert got == brute_force(idx.table, lo, hi), (dist, lo, hi)


def test_search_compact_matches_dense():
    """The search at the cap counts what a scan counts and inspects what
    step 2 selects; an undersized slab flags truncation rather than
    silently undercounting."""
    rng = np.random.default_rng(3)
    values = rng.uniform(0, 100, 1500)
    idx = make_index(values)
    pred = Predicate.between(10, 20)
    res = idx.search(pred)
    assert int(res.counts[0]) == brute_force(idx.table, 10, 20)
    assert int(res.pages_inspected[0]) == int(page_masks(idx, [pred]).sum())
    assert not bool(res.truncated[0])
    trunc2 = idx.search_batch([pred], max_selected=1).truncated
    assert bool(trunc2[0])


def test_search_compact_truncation_flag_parity():
    """Sweep max_selected across the truncation boundary: whenever the flag
    is clear the count must equal a scan's, and the flag must be set
    exactly when capacity fell short of the pages selected."""
    rng = np.random.default_rng(6)
    values = rng.uniform(0, 100, 1200)
    idx = make_index(values)
    pred = Predicate.between(30, 45)
    want = brute_force(idx.table, 30, 45)
    n_sel = int(page_masks(idx, [pred]).sum())
    assert n_sel > 1  # the sweep below must cross the boundary
    for cap in [n_sel - 1, n_sel, idx.table.num_pages]:
        res = idx.search_batch([pred], max_selected=cap)
        assert int(res.pages_inspected[0]) == n_sel
        assert bool(res.truncated[0]) == (n_sel > cap)
        if not res.truncated[0]:
            assert int(res.counts[0]) == want


def test_search_compact_fill_value_never_undercounts_silently():
    """Regression for the gather fill-value hazard: selection pads with
    ``fill_value=num_pages`` and gathers with ``mode="fill"``. A full-table
    match that overflows ``max_selected`` must set ``truncated`` (so callers
    fall back) — the pads themselves must never masquerade as real pages or
    push the count below what the gathered slab actually holds."""
    rng = np.random.default_rng(21)
    values = rng.uniform(0, 100, 800)
    idx = make_index(values)
    full = Predicate.between(-1e30, 1e30)
    n_sel = int(idx.search(full).pages_inspected[0])
    assert n_sel == idx.table.num_pages          # full-table match
    for cap in (1, 7, n_sel - 1):
        assert not idx.inspects_in_place(cap), cap
        res = idx.search_batch([full], max_selected=cap)
        assert bool(res.truncated[0]), cap
        assert int(res.pages_inspected[0]) == n_sel, cap
        # the slab holds exactly cap real pages => their tuples and no more
        assert int(res.counts[0]) == int(np.sum(
            idx.table.valid[:cap]
            & (idx.table.keys[:cap] >= -3.4e38)
            & (idx.table.keys[:cap] <= 3.4e38))), cap
    # at exactly n_sel the flag clears and the count is exact
    res = idx.search_batch([full], max_selected=n_sel)
    assert not bool(res.truncated[0])
    assert int(res.counts[0]) == idx.table.cardinality


def test_search_compact_rejects_zero_capacity():
    """max_selected=0 would turn every slab row into a pad and silently
    count 0 — the search must refuse it outright, and a negative top_k."""
    rng = np.random.default_rng(22)
    idx = make_index(rng.uniform(0, 100, 200))
    pred = Predicate.between(0, 50)
    with pytest.raises(ValueError, match="max_selected"):
        idx.search_batch([pred], max_selected=0)
    with pytest.raises(ValueError, match="max_selected"):
        idx.search_batch([pred, pred], max_selected=0, top_k=4)
    with pytest.raises(ValueError, match="top_k"):
        idx.search_batch([pred], max_selected=4, top_k=-1)


def test_search_compact_many_matches_search_many():
    """Quick (unmarked) batched parity check against a scan and step 2; the
    full selectivity x shards x staged sweep lives in tests/test_compact.py
    (-m compact)."""
    rng = np.random.default_rng(23)
    idx = make_index(np.sort(rng.uniform(0, 100, 1000)))
    preds = [Predicate.between(10, 12), Predicate.between(40, 80),
             Predicate(lo=5.0, hi=1.0), Predicate.between(-1e30, 1e30)]
    res = idx.search_batch(preds, top_k=8)
    assert not np.asarray(res.truncated).any()
    np.testing.assert_array_equal(
        np.asarray(res.counts),
        [brute_force(idx.table, *p.selectivity_interval()) for p in preds])
    np.testing.assert_array_equal(np.asarray(res.pages_inspected),
                                  page_masks(idx, preds).sum(axis=1))
    # row ids: first 8 qualifying rows of each predicate, ascending
    keys = idx.table.keys[: idx.table.num_pages].reshape(-1)
    valid = idx.table.valid[: idx.table.num_pages].reshape(-1)
    for q, p in enumerate(preds):
        lo, hi = max(p.lo, -3.4e38), min(p.hi, 3.4e38)
        want = np.flatnonzero(valid & (keys >= lo) & (keys <= hi))[:8]
        ids = np.asarray(res.row_ids[q])
        np.testing.assert_array_equal(ids[ids >= 0], want, q)


def test_false_positive_filtering_is_effective():
    # Sorted data => contiguous buckets per entry => small range predicates
    # should prune most pages (the paper's headline search behaviour).
    values = np.linspace(0, 1000, 4000)
    idx = make_index(values, resolution=64, density=0.2)
    res = idx.search(Predicate.between(10, 20))
    assert int(res.counts[0]) == brute_force(idx.table, 10, 20)
    assert int(res.pages_inspected[0]) < idx.table.num_pages * 0.2


def test_equality_and_open_predicates():
    rng = np.random.default_rng(4)
    values = rng.uniform(0, 100, 1000)
    idx = make_index(values)
    v = float(values[123])
    assert idx.count(Predicate.equality(v)) == brute_force(idx.table, v, v)
    assert idx.count(Predicate.greater(50.0)) == int((values > 50.0).sum())
    assert idx.count(Predicate.less(50.0).and_(Predicate.greater(25.0))) == \
        int(((values < 50.0) & (values > 25.0)).sum())


def test_density_threshold_controls_entry_count():
    rng = np.random.default_rng(5)
    values = rng.uniform(0, 1000, 8000)
    sizes = {}
    for d in (0.2, 0.4, 0.8):
        idx = make_index(values, resolution=400, density=d, page_card=50)
        sizes[d] = idx.num_entries
    # §6.2 Observation 1: higher density => fewer entries.
    assert sizes[0.2] > sizes[0.4] > sizes[0.8]
