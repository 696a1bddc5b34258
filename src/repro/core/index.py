"""Hippo index — structure, build, search, and maintenance (§2–§5).

State layout (fixed-shape device arrays, functional updates):

  bitmaps   (S, W) uint32  partial histograms in packed bitmap form (physical slots)
  starts    (S,)   int32   first page summarized by each slot
  ends      (S,)   int32   last page summarized by each slot (inclusive)
  sorted_order (S,) int32  logical (page-ascending) position -> physical slot;
                           this is the paper's *index entries sorted list* (§5.3)
  slot_live (S,)   bool    false for slots abandoned by out-of-place updates
  num_entries      int32   logical entry count
  num_slots        int32   physical slots in use (>= num_entries with relocation)
  summarized_until int32   last page id covered by the index (-1 if empty)

Static config (``HippoConfig``) carries H (resolution), D (density threshold),
page_card, and capacity; it is hashable and passed as a static argument.

Search (Algorithm 1) is one program, ``search_compact_many``: step 2
(``_page_match``) gives each query's possible-qualified pages, and step 3
(``_page_counts``) inspects them, either where the table lies or, for a
slab narrow enough to pay (``inspects_in_place``), in a gathered slab of the
batch's union. ``search_compact_many_sharded`` runs it over a stacked shard
axis and reduces across it; ``search_compact_many_sharded_staged`` adds the
writer's staged rows. All three return a ``CompactBatchResult``.

Out-of-place updates: the paper relocates an updated entry to the end of the
index when its compressed bitmap no longer fits (§5.1). Fixed-width device
slots always fit, so relocation is **optional** here (``relocate_on_update``);
enabling it exercises the sorted-list indirection exactly as in Fig. 4.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import grouping
from repro.core.histogram import Histogram, bucketize

_INT32_MAX = np.iinfo(np.int32).max


@dataclass(frozen=True)
class HippoConfig:
    resolution: int = 400          # H — complete histogram resolution (default, §7)
    density: float = 0.2           # D — partial histogram density threshold (default, §7)
    page_card: int = 50            # tuples per page (paper's running example)
    max_slots: int = 1 << 14       # physical entry capacity
    relocate_on_update: bool = True  # model §5.1 out-of-place updates

    @property
    def words(self) -> int:
        return bm.num_words(self.resolution)


class HippoState(NamedTuple):
    bounds: jnp.ndarray        # (H+1,) f32 — complete histogram boundaries
    bitmaps: jnp.ndarray       # (S, W) u32
    starts: jnp.ndarray        # (S,) i32
    ends: jnp.ndarray          # (S,) i32
    sorted_order: jnp.ndarray  # (S,) i32
    slot_live: jnp.ndarray     # (S,) bool
    num_entries: jnp.ndarray   # i32 scalar
    num_slots: jnp.ndarray     # i32 scalar
    summarized_until: jnp.ndarray  # i32 scalar

    @property
    def histogram(self) -> Histogram:
        return Histogram(self.bounds)


class CompactBatchResult(NamedTuple):
    """Per-query results of Algorithm 1 over a batch (``search_compact_many``
    and its sharded forms, the one search program).

    Work after the bitmap filter is proportional to ``max_selected`` gathered
    pages, not to the table — the paper's "read only possible qualified
    pages" cost model on an accelerator. ``truncated`` is exact per query: it
    fires iff one of *that query's* selected pages fell outside the gathered
    slab, in which case ``counts[q]``/``row_ids[q]`` are lower bounds and the
    caller must fall back to a wider slab (at the cap the table is
    inspected in place, which never truncates).
    ``pages_inspected``/``entries_matched`` are computed before the gather,
    so they are exact even for truncated rows.
    """
    counts: jnp.ndarray           # (Q,) i32
    pages_inspected: jnp.ndarray  # (Q,) i32 — possible qualified pages (exact)
    entries_matched: jnp.ndarray  # (Q,) i32
    truncated: jnp.ndarray        # (Q,) bool — slab missed >=1 of q's pages
    bucket_needed: jnp.ndarray    # i32 scalar — slab size that avoids any
    #                               truncation (max per-shard union of the
    #                               batch's page masks); drives adaptive
    #                               max_selected bucketing upstream
    pages_selected: jnp.ndarray   # i32 scalar — distinct pages selected by
    #                               the whole batch (summed over shards)
    pages_gathered: jnp.ndarray   # i32 scalar — selected pages that fit the
    #                               slab, min(union, max_selected) per shard
    #                               summed (gather-occupancy numerator); 0
    #                               when inspected in place
    row_ids: jnp.ndarray          # (Q, top_k) i32 global row ids in ascending
    #                               order, -1 padded; (Q, 0) when top_k == 0


# ---------------------------------------------------------------------------
# Build (§4, Algorithm 2)
# ---------------------------------------------------------------------------

def build(cfg: HippoConfig, hist: Histogram, keys: jnp.ndarray,
          valid: jnp.ndarray) -> HippoState:
    """Initialize Hippo over a paged key column.

    Device work: bucketize + grouping scan (jit). Entry extraction is a cheap
    host finalize. Returns a fixed-capacity ``HippoState``.
    """
    num_pages = keys.shape[0]
    if num_pages == 0:
        # Empty table: zero-entry index; Algorithm 3 grows it on first insert.
        starts = ends = np.zeros((0,), np.int32)
        packed = np.zeros((0, cfg.words), np.uint32)
    else:
        page_bits = grouping.page_bucket_bits(hist, keys, valid, cfg.resolution)
        flags, merged = grouping.group_pages(page_bits, cfg.resolution, cfg.density)
        starts, ends, packed = grouping.finalize_entries(np.asarray(flags), np.asarray(merged))
    e = starts.shape[0]
    if e > cfg.max_slots:
        raise ValueError(f"built {e} entries > max_slots {cfg.max_slots}; raise capacity")
    s, w = cfg.max_slots, cfg.words

    bitmaps = np.zeros((s, w), np.uint32)
    bitmaps[:e] = packed
    st = np.full((s,), _INT32_MAX, np.int32)
    st[:e] = starts
    en = np.full((s,), _INT32_MAX, np.int32)
    en[:e] = ends
    order = np.arange(s, dtype=np.int32)   # build order is page order (§5.3 init)
    live = np.zeros((s,), bool)
    live[:e] = True
    return HippoState(
        bounds=hist.bounds,
        bitmaps=jnp.asarray(bitmaps),
        starts=jnp.asarray(st),
        ends=jnp.asarray(en),
        sorted_order=jnp.asarray(order),
        slot_live=jnp.asarray(live),
        num_entries=jnp.int32(e),
        num_slots=jnp.int32(e),
        summarized_until=jnp.int32(num_pages - 1 if e else -1),
    )


# ---------------------------------------------------------------------------
# Search (§3, Algorithm 1)
# ---------------------------------------------------------------------------

def _logical_starts(state: HippoState) -> jnp.ndarray:
    """starts in logical (sorted-list) order, padded with INT32_MAX."""
    s = state.sorted_order.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    starts = state.starts[state.sorted_order]
    return jnp.where(pos < state.num_entries, starts, _INT32_MAX)


def locate_slot(state: HippoState, page_id) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Binary search the sorted list for the entry owning ``page_id`` (§5.3).

    Returns (physical_slot, logical_pos). Caller guarantees the page is
    summarized (page_id <= summarized_until). ``page_id`` may be a scalar or
    an array of page ids; the results take its shape.
    """
    ls = _logical_starts(state)
    pos = jnp.searchsorted(ls, page_id, side="right").astype(jnp.int32) - 1
    pos = jnp.clip(pos, 0, None)
    return state.sorted_order[pos], pos


_locate_slot = jax.jit(locate_slot)


def owning_slots(state: HippoState, page_ids: np.ndarray) -> np.ndarray:
    """Physical slots owning each of the (summarized) host ``page_ids``, in
    one device search. The ids are padded with their first id to a power-of-
    two length, so maintenance calls with varying numbers of dirty pages
    reuse a few compiled programs instead of compiling one per count."""
    ids = np.asarray(page_ids, np.int32).reshape(-1)
    if ids.size == 0:
        return np.zeros((0,), np.int32)
    padded = np.full((1 << (ids.size - 1).bit_length(),), ids[0], np.int32)
    padded[:ids.size] = ids
    slots, _ = _locate_slot(state, jnp.asarray(padded))
    return np.asarray(slots)[:ids.size]


_BLOCK = 128   # pages per block of the page expansion; the TPU's lane width
# how many times narrower than its table a compact slab must be before
# gathering it pays, by backend (PERF.md, section 6). On a TPU v5e a
# gathered page costs ~21 times a page inspected where it lies, and the
# union's selection a fixed ~25 ms more at SF 30: the two cross at ~1/100
# of a shard. On a CPU a gathered page costs less than inspecting it where
# it lies, and the two cross at 0.7-0.85 of a shard. A backend not measured
# takes 1: only a slab as wide as its table is not gathered.
_GATHER_SHRINK = {"tpu": 128, "cpu": 1}


def inspects_in_place(max_selected: int, num_pages: int) -> bool:
    """Whether a compact search of slab width ``max_selected`` over a table
    (or shard) of ``num_pages`` pages inspects the table where it lies
    instead of gathering the batch union into a slab: true unless the slab
    is at least ``_GATHER_SHRINK`` times narrower than the table on the
    default backend, where the program runs. Both are static shapes, so
    the choice is made at trace time."""
    shrink = _GATHER_SHRINK.get(jax.default_backend(), 1)
    return max_selected * shrink >= num_pages


def _page_owners(ls: jnp.ndarray, table: jnp.ndarray, num_pages: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Each page's owning logical position and that entry's column of
    ``table``.

    ``ls`` (S,) holds the logical starts, INT32_MAX-padded, strictly
    ascending where live (each entry summarizes at least one page);
    ``table`` (C, S) holds one column per logical entry, in int32 or
    uint32: either holds every start exactly. Returns ``pos`` =
    ``searchsorted(ls, arange(num_pages), side="right") - 1`` (-1: no entry)
    and ``table[:, pos]`` (zeros where ``pos`` is -1), with no search and no
    gather of every page.

    The pages split into blocks of ``_BLOCK``. A block's first page finds
    its owner by counting starts at or before it, first over every
    ``_BLOCK``-th start, then within that group. The block's pages hold at
    most ``_BLOCK - 1`` further starts, so all its owners lie in the
    ``_BLOCK`` entries from there. The starts, their successors and
    ``table`` are stacked into one (2 + C, S) array, so each block takes
    one contiguous slice of it (a single gather of windows, where a slice
    of each 1-D row compiles to a loop over the blocks on a TPU), and a
    dense compare of each page against the window's starts.
    """
    s, c = ls.shape[0], table.shape[0]
    b = min(_BLOCK, s)
    nb = -(-num_pages // b)
    first = jnp.arange(nb, dtype=jnp.int32) * b                # (NB,)
    groups = jnp.pad(ls, (0, -s % b),
                     constant_values=_INT32_MAX).reshape(-1, b)
    g = jnp.maximum((groups[:, 0] <= first[:, None]).sum(1, dtype=jnp.int32)
                    - 1, 0)
    head = g * b + (groups[g] <= first[:, None]).sum(1, dtype=jnp.int32) - 1
    base = jnp.clip(head, 0, s - b)                            # (NB,)
    stacked = jnp.concatenate(
        [jnp.stack([ls, jnp.append(ls[1:], _INT32_MAX)]).astype(table.dtype),
         table])                                               # (2+C, S)
    win = jax.vmap(lambda o: jax.lax.dynamic_slice_in_dim(stacked, o, b,
                                                          axis=1))(base)
    here = win[:, None, 0].astype(jnp.int32)                   # (NB, 1, B)
    after = win[:, None, 1].astype(jnp.int32)                  # successors
    pages = (first[:, None] + jnp.arange(b, dtype=jnp.int32))[:, :, None]
    pos = base[:, None] + (here <= pages).sum(2, dtype=jnp.int32) - 1
    owner = (here <= pages) & (after > pages)                  # (NB, B, B)
    rows = jnp.where(owner[:, None], win[:, 2:, None, :], 0
                     ).sum(3, dtype=table.dtype)               # (NB, C, B)
    rows = rows.transpose(1, 0, 2).reshape(c, nb * b)
    return pos.reshape(-1)[:num_pages], rows[:, :num_pages]


def _page_match(state: HippoState, query_bitmaps: jnp.ndarray,
                num_pages: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Step 2 of Algorithm 1: possible-qualified pages (Bitmap b) and the
    number of matched entries, per query.

    One bit-level joint-bucket test of every query against every live entry
    (Fig. 3) gives the (Q, S) match matrix. Live entries partition the
    summarized page space contiguously in logical (sorted-list) order — the
    §5.3 invariant ``locate_slot``'s binary search also relies on — so each
    page belongs to at most one entry. One gather puts every entry's start,
    end and match bits in logical order, and ``_page_owners`` expands them
    to the pages block by block, with no search of every page. The match
    bits travel packed, the query axis in uint32 words: a first full-cap
    batch at SF 10 shard shapes that gathered a (Q, P) slice of the bool
    matrix instead took about two minutes on a v5e, nearly all of it
    compiling, against 8-25 s with packed words.
    Pages past the last entry's ``end`` — and everything in an empty index —
    resolve to no entry and stay False. ``query_bitmaps`` is (W,) or
    (Q, W); the results are (num_pages,) and a scalar, or (Q, num_pages)
    and (Q,).
    """
    squeeze = query_bitmaps.ndim == 1
    qbm = query_bitmaps[None] if squeeze else query_bitmaps
    q = qbm.shape[0]
    s = state.bitmaps.shape[0]
    with jax.named_scope("hippo.entry_filter"):
        live = state.slot_live & (jnp.arange(s) < state.num_slots)
        match = bm.any_joint(qbm[:, None, :], state.bitmaps[None]) & live[None]
        matched = match.sum(axis=1, dtype=jnp.int32)                # (Q,)
        # bit j of words[w, e]: entry e matched query 32 w + j
        nw = bm.num_words(q)
        padded = jnp.zeros((nw * bm.WORD_BITS, s), bool).at[:q].set(match)
        shifts = jnp.arange(bm.WORD_BITS, dtype=jnp.uint32)
        words = (padded.reshape(nw, bm.WORD_BITS, s).astype(jnp.uint32)
                 << shifts[None, :, None]).sum(axis=1, dtype=jnp.uint32)
    with jax.named_scope("hippo.page_expand"):
        # each entry's start, end and match words, in logical order
        table = jnp.concatenate(
            [state.starts[None].astype(jnp.uint32),
             state.ends[None].astype(jnp.uint32), words],
            axis=0)[:, state.sorted_order]                          # (2+nw, S)
        ls = jnp.where(jnp.arange(s) < state.num_entries,
                       table[0].astype(jnp.int32), _INT32_MAX)
        pos, cols = _page_owners(ls, table[1:], num_pages)
        pages = jnp.arange(num_pages, dtype=jnp.int32)
        owned = (pos >= 0) & (pages <= cols[0].astype(jnp.int32))
        bits = (cols[1:, None, :] >> shifts[None, :, None]) & 1  # (nw, 32, P)
        bits = bits.reshape(nw * bm.WORD_BITS, num_pages)[:q].astype(bool)
        page_mask = bits & owned[None, :]                           # (Q, P)
    if squeeze:
        return page_mask[0], matched[0]
    return page_mask, matched


def _page_counts(page_mask: jnp.ndarray, keys: jnp.ndarray,
                 valid: jnp.ndarray, los: jnp.ndarray, his: jnp.ndarray
                 ) -> jnp.ndarray:
    """Step 3 of Algorithm 1 for Q intervals: (Q, M) rows of each page that
    are valid and inside query q's interval, counted only on the pages
    ``page_mask`` (Q, M) selects for q. keys/valid: (M, C)."""
    v = keys.astype(jnp.float32)[None]
    qual = (page_mask[:, :, None] & valid[None]
            & (v >= los[:, None, None]) & (v <= his[:, None, None]))
    return qual.sum(axis=2, dtype=jnp.int32)


# Per-shard vmap axes for a stacked ``HippoState``: every array gains a
# leading shard axis, *including* ``bounds`` — each shard carries its own
# complete-histogram boundary set so a drift re-summarization can remap one
# shard at a time while the others keep serving under their old bounds.
# Query bitmaps are converted per shard epoch (``core.partition``) and fed
# with a matching leading shard axis.
SHARD_AXES = HippoState(
    bounds=0, bitmaps=0, starts=0, ends=0, sorted_order=0, slot_live=0,
    num_entries=0, num_slots=0, summarized_until=0)


@partial(jax.jit, static_argnames=())
def staged_overlay_counts(staged_vals: jnp.ndarray, staged_live: jnp.ndarray,
                          los: jnp.ndarray, his: jnp.ndarray) -> jnp.ndarray:
    """Exact counts of staged-but-undrained rows per query.

    staged_vals: (S, B) f32 pending insert values per shard, padded to a
    bucketed width B; staged_live: (S, B) bool (False for pads and for staged
    rows killed by a later delete); los/his: (Q,) f32 predicate intervals.
    Returns (Q,) i32. Staged rows live in no page yet, so this is a plain
    interval test — the writer's staging-buffer overlay
    (``runtime.writer.MaintenanceWriter``).
    """
    with jax.named_scope("hippo.staged_overlay"):
        v = staged_vals[None]                                   # (1, S, B)
        hit = (staged_live[None] & (v >= los[:, None, None])
               & (v <= his[:, None, None]))
        return hit.sum(axis=(1, 2), dtype=jnp.int32)


@partial(jax.jit, static_argnames=("max_selected", "top_k"))
def search_compact_many(state: HippoState, query_bitmaps: jnp.ndarray,
                        keys: jnp.ndarray, valid: jnp.ndarray,
                        los: jnp.ndarray, his: jnp.ndarray, *,
                        max_selected: int, top_k: int = 0
                        ) -> CompactBatchResult:
    """Batched gather-then-inspect: Q predicates over one shared page slab.

    The per-query page masks of Algorithm 1 step 2 are unioned, the union's
    pages are gathered **once** into a ``(max_selected, C)`` slab, and every
    query's interval test runs against that shared slab — so inspect cost is
    O(Q x max_selected x C) instead of the table's O(Q x P x C),
    i.e. proportional to the batch's selectivity, not the table.

    A slab too wide for the gather to pay (``inspects_in_place``) is not
    built: every query's interval test runs on ``keys``/``valid`` where
    they lie, masked by its own page mask. That branch never truncates and
    gathers nothing (``pages_gathered`` 0); ``bucket_needed`` and
    ``pages_selected`` still report the union.

    Row q's ``counts`` is bit-identical to the in-place branch whenever
    ``truncated[q]`` is False (pages are gathered in ascending page order and
    inspection is exact). With ``top_k > 0``, ``row_ids[q]`` carries the
    first ``top_k`` qualifying global row ids (``page_id * C + slot``) in
    ascending order, -1 padded; when ``counts[q] > top_k`` the id list is a
    prefix (callers see the shortfall from the count itself).

    Fill-value contract: selection pads with ``num_pages`` and gathers
    with ``mode="fill"``, so pad rows can never qualify; a ``max_selected``
    of zero would make every row a pad and silently count 0, so it is
    rejected (static arg => plain raise at trace time).
    """
    if max_selected < 1:
        raise ValueError(f"max_selected must be >= 1, got {max_selected}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    num_pages = keys.shape[0]
    # Step 2, batched: joint-bucket test + page-range expansion per query.
    page_mask, matched = _page_match(state, query_bitmaps, num_pages)
    with jax.named_scope("hippo.inspect"):
        pages_inspected = page_mask.sum(axis=1, dtype=jnp.int32)
    if inspects_in_place(max_selected, num_pages):
        sel, slab_keys, slab_valid = None, keys, valid
        with jax.named_scope("hippo.inspect"):
            n_union = jnp.any(page_mask, axis=0).sum(dtype=jnp.int32)
            page_counts = _page_counts(page_mask, keys, valid, los, his)
        truncated = jnp.zeros_like(pages_inspected, bool)
        gathered = jnp.int32(0)
    else:
        # Union across the batch: one gather serves every query's inspection.
        with jax.named_scope("hippo.select"):
            union = jnp.any(page_mask, axis=0)                      # (P,)
            n_union = union.sum(dtype=jnp.int32)
            sel = jnp.nonzero(union, size=max_selected,
                              fill_value=num_pages)[0]
            in_range = sel < num_pages                              # (M,)
        with jax.named_scope("hippo.gather"):
            slab_keys = jnp.where(in_range[:, None],
                                  keys.at[sel].get(mode="fill",
                                                   fill_value=0.0), 0.0)
            slab_valid = (valid.at[sel].get(mode="fill", fill_value=False)
                          & in_range[:, None])
            # Each query's mask restricted to the gathered slab (filter-
            # match half of the fused inspect; kernels/compact_inspect is
            # the Pallas twin).
            sel_mask = (page_mask.at[:, sel].get(mode="fill",
                                                 fill_value=False)
                        & in_range[None, :])                        # (Q, M)
        with jax.named_scope("hippo.inspect"):
            page_counts = _page_counts(sel_mask, slab_keys, slab_valid,
                                       los, his)
            covered = sel_mask.sum(axis=1, dtype=jnp.int32)
        truncated = covered < pages_inspected
        gathered = jnp.minimum(n_union, max_selected)
    with jax.named_scope("hippo.inspect"):
        counts = page_counts.sum(axis=1, dtype=jnp.int32)
    if top_k:
        with jax.named_scope("hippo.row_ids"):
            row_ids = _first_row_ids(page_counts, slab_keys, slab_valid,
                                     los, his, top_k, sel)
    else:
        row_ids = jnp.zeros((page_counts.shape[0], 0), jnp.int32)
    return CompactBatchResult(
        counts=counts,
        pages_inspected=pages_inspected,
        entries_matched=matched,
        truncated=truncated,
        bucket_needed=n_union,
        pages_selected=n_union,
        pages_gathered=gathered,
        row_ids=row_ids,
    )


def _first_row_ids(page_counts, keys, valid, los, his, top_k: int,
                   sel=None) -> jnp.ndarray:
    """First ``top_k`` qualifying global row ids per query, ascending, -1
    padded. ``keys``/``valid`` (M, C) are the gathered slab, whose page m
    is table page ``sel[m]``, or with ``sel`` None the table itself; either
    way page order is ascending global row order (``sel`` ascends and slots
    are row-ordered).

    A prefix sum of the per-page qualifying counts finds, for each k, the
    page holding the query's k-th row and its rank inside that page; only
    those (Q, top_k) pages are read again and re-inspected. Memory is
    O(Q x M) plus O(Q x top_k x C) — a (Q, M*C) position array is never
    built. A page holding a qualifying row is one the query selected, so
    the re-inspection needs no selection mask; its invalid slots read as
    NaN, which fails every interval test.
    """
    m, card = keys.shape
    q = page_counts.shape[0]
    if m == 0:                        # an empty table holds no row
        return jnp.full((q, top_k), -1, jnp.int32)
    ks = jnp.arange(top_k, dtype=jnp.int32)
    incl = jnp.cumsum(page_counts, axis=1, dtype=jnp.int32)         # (Q, M)
    # the first page whose inclusive count exceeds k holds the k-th row
    page = jax.vmap(lambda c: jnp.searchsorted(c, ks, side="right"))(incl)
    found = ks[None, :] < incl[:, -1:]                              # (Q, K)
    page = jnp.minimum(page, m - 1).astype(jnp.int32)
    rank = ks[None, :] - jnp.take_along_axis(incl - page_counts, page, axis=1)
    if sel is None:
        # in place: the NaN rule on the (Q, K) pages read again, not on the
        # whole table
        pk = jnp.where(valid[page], keys[page].astype(jnp.float32), jnp.nan)
        first = page
    else:
        # a gathered slab is narrow: one pass over it, then one f32 gather
        pk = jnp.where(valid, keys.astype(jnp.float32), jnp.nan)[page]
        first = sel[page]                                           # (Q, K)
    hit = (pk >= los[:, None, None]) & (pk <= his[:, None, None])
    # slot of the rank-th hit: the number of prefix counts not past rank
    seen = jnp.cumsum(hit, axis=2, dtype=jnp.int32)
    slot = (seen <= rank[:, :, None]).sum(axis=2, dtype=jnp.int32)
    return jnp.where(found, first * card + slot, -1)


_I32_PAD = jnp.int32(_INT32_MAX)


@partial(jax.jit, static_argnames=("max_selected", "top_k"))
def search_compact_many_sharded(shards: HippoState, query_bitmaps: jnp.ndarray,
                                keys: jnp.ndarray, valid: jnp.ndarray,
                                los: jnp.ndarray, his: jnp.ndarray, *,
                                max_selected: int, top_k: int = 0
                                ) -> CompactBatchResult:
    """``search_compact_many`` over S shards in one device program,
    count-reduced.

    ``shards`` is a stacked ``HippoState`` (leading shard axis per
    ``SHARD_AXES``); keys/valid are (S, PPS, page_card) slabs where shard s
    owns global pages [s*PPS, (s+1)*PPS) and its entry page ids are local to
    the slab. ``query_bitmaps`` is (S, Q, W): row s holds the Q predicates
    converted under shard s's histogram bounds — identical rows while every
    shard shares one bounds epoch, distinct rows mid-drift-resummarization
    (a shard's page bitmaps and its query bitmaps always share one bucket
    space). The reductions over the shard axis are the ``jax.lax.psum`` of
    a ``shard_map`` placement, expressed as array-axis sums so they are
    identical under vmap on one device and lower to an AllReduce when the
    shard axis is sharded over a mesh ``data`` axis
    (``launch.shardings.sharded_hippo_shardings``). Shards partition the
    page space, so per-shard exact counts sum to exactly the unsharded
    count. ``max_selected`` is the *per-shard* slab
    size (each shard gathers its own union). Counts/pages_inspected/
    entries_matched sum over the shard axis — bit-identical to the unsharded
    gather over the same pages wherever no shard truncated; ``truncated``
    ORs over shards per query, and ``bucket_needed`` is the max per-shard
    union (the slab size that would clear every flag). Shard-local row ids
    globalize by the slab offset (shard s's local row r is global
    ``s * PPS * C + r``) and merge by an ascending sort, so ``row_ids``
    equals the unsharded result's.
    """
    fn = partial(search_compact_many, max_selected=max_selected, top_k=top_k)
    per = jax.vmap(fn, in_axes=(SHARD_AXES, 0, 0, 0, None, None))(
        shards, query_bitmaps, keys, valid, los, his)
    if top_k:
        with jax.named_scope("hippo.row_ids"):
            s, _, card = keys.shape
            offs = (jnp.arange(s, dtype=jnp.int32) * keys.shape[1] * card)
            gids = jnp.where(per.row_ids >= 0,
                             per.row_ids + offs[:, None, None], _I32_PAD)
            q = gids.shape[1]
            merged = jnp.moveaxis(gids, 0, 1).reshape(q, -1)  # (Q, S*K)
            merged = jax.lax.sort(merged, dimension=1)[:, :top_k]
            row_ids = jnp.where(merged < _I32_PAD, merged, -1)
    else:
        row_ids = per.row_ids[0]
    return CompactBatchResult(
        counts=per.counts.sum(axis=0),                 # psum over shards
        pages_inspected=per.pages_inspected.sum(axis=0),
        entries_matched=per.entries_matched.sum(axis=0),
        truncated=jnp.any(per.truncated, axis=0),
        bucket_needed=per.bucket_needed.max(),
        pages_selected=per.pages_selected.sum(),
        pages_gathered=per.pages_gathered.sum(),
        row_ids=row_ids,
    )


def search_compact_many_sharded_staged(shards: HippoState,
                                       query_bitmaps: jnp.ndarray,
                                       keys: jnp.ndarray, valid: jnp.ndarray,
                                       los: jnp.ndarray, his: jnp.ndarray,
                                       staged_vals: jnp.ndarray,
                                       staged_live: jnp.ndarray, *,
                                       max_selected: int, top_k: int = 0
                                       ) -> CompactBatchResult:
    """``search_compact_many_sharded`` plus the staging-buffer overlay.

    Counts gain the staged rows matching each predicate, so results never
    go stale while inserts wait in the writer's queues: row q equals what
    the search would count *after* every staged row drained. Staged rows
    occupy no page until their drain, so they appear in ``counts`` only —
    never in ``row_ids``/``pages_inspected``/``entries_matched`` — and they
    cannot cause truncation.
    """
    res = search_compact_many_sharded(shards, query_bitmaps, keys, valid,
                                      los, his, max_selected=max_selected,
                                      top_k=top_k)
    return res._replace(
        counts=res.counts + staged_overlay_counts(staged_vals, staged_live,
                                                  los, his))


# ---------------------------------------------------------------------------
# Maintenance — eager insert (§5.1, Algorithm 3)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def insert_tuple(cfg: HippoConfig, state: HippoState, value: jnp.ndarray,
                 page_id: jnp.ndarray) -> HippoState:
    """Algorithm 3: eager single-tuple index update.

    Steps: (1) bucketize the new value; (2) locate the owning entry via the
    sorted list; (3) set the bucket bit / extend the last entry / open a new
    entry, per the density rule.
    """
    hist = Histogram(state.bounds)
    b = bucketize(hist, value[None])[0]
    word = b // 32
    bit = jnp.uint32(1) << jnp.uint32(b % 32)
    is_new_page = page_id > state.summarized_until

    def existing_page(st: HippoState) -> HippoState:
        slot, pos = locate_slot(st, page_id)
        old_word = st.bitmaps[slot, word]
        new_word = old_word | bit
        changed = new_word != old_word

        def in_place(st: HippoState) -> HippoState:
            return st._replace(bitmaps=st.bitmaps.at[slot, word].set(new_word))

        def relocate(st: HippoState) -> HippoState:
            # §5.1: updated entry may not fit its old slot -> append a new
            # physical entry at the end, fix the sorted list pointer (Fig. 4).
            new_slot = st.num_slots
            bitmaps = st.bitmaps.at[new_slot].set(st.bitmaps[slot]).at[new_slot, word].set(new_word)
            return st._replace(
                bitmaps=bitmaps,
                starts=st.starts.at[new_slot].set(st.starts[slot]),
                ends=st.ends.at[new_slot].set(st.ends[slot]),
                slot_live=st.slot_live.at[slot].set(False).at[new_slot].set(True),
                sorted_order=st.sorted_order.at[pos].set(new_slot),
                num_slots=st.num_slots + 1,
            )

        if cfg.relocate_on_update:
            return jax.lax.cond(changed, relocate, lambda s: s, st)
        return jax.lax.cond(changed, in_place, lambda s: s, st)

    def new_page(st: HippoState) -> HippoState:
        last_slot = st.sorted_order[jnp.maximum(st.num_entries - 1, 0)]
        last_density = jnp.where(
            st.num_entries > 0,
            bm.density(st.bitmaps[last_slot], cfg.resolution),
            jnp.float32(2.0),  # empty index -> always create
        )

        def extend(st: HippoState) -> HippoState:
            return st._replace(
                bitmaps=st.bitmaps.at[last_slot, word].set(st.bitmaps[last_slot, word] | bit),
                ends=st.ends.at[last_slot].set(page_id),
                summarized_until=page_id,
            )

        def create(st: HippoState) -> HippoState:
            slot = st.num_slots
            zero = jnp.zeros((cfg.words,), jnp.uint32).at[word].set(bit)
            return st._replace(
                bitmaps=st.bitmaps.at[slot].set(zero),
                starts=st.starts.at[slot].set(page_id),
                ends=st.ends.at[slot].set(page_id),
                slot_live=st.slot_live.at[slot].set(True),
                sorted_order=st.sorted_order.at[st.num_entries].set(slot),
                num_entries=st.num_entries + 1,
                num_slots=st.num_slots + 1,
                summarized_until=page_id,
            )

        return jax.lax.cond(last_density < cfg.density, extend, create, st)

    return jax.lax.cond(is_new_page, new_page, existing_page, state)


def value_bits(cfg: HippoConfig, bounds: jnp.ndarray, values: jnp.ndarray,
               rows: jnp.ndarray, live: jnp.ndarray, num_rows: int
               ) -> jnp.ndarray:
    """(num_rows, W) packed bucket bits: row r is the OR of the bits of the
    live ``values`` whose ``rows`` entry is r (rows out of range are
    dropped)."""
    ids = bucketize(Histogram(bounds), values)
    rows = jnp.where(live, rows, num_rows)
    bits = jnp.zeros((num_rows, cfg.resolution), bool)
    return bm.from_bool(bits.at[rows, ids].set(True, mode="drop"))


def pad_rows(values: np.ndarray, local_pages: np.ndarray,
             live: np.ndarray | None = None) -> tuple:
    """(values, local_pages, live) of an append padded to a power of two of
    at least 8 rows with rows that never go live, so appends of similar
    size share one compiled ``insert_appended``."""
    n = values.shape[0]
    b = 1 << max(n - 1, 7).bit_length()
    pv = np.zeros((b,), np.float32)
    pv[:n] = values
    pl = np.full((b,), local_pages[-1], np.int32)
    pl[:n] = local_pages
    pm = np.zeros((b,), bool)
    pm[:n] = True if live is None else live
    return pv, pl, pm


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def insert_appended(cfg: HippoConfig, state: HippoState, values: jnp.ndarray,
                    local_pages: jnp.ndarray, live: jnp.ndarray) -> HippoState:
    """Algorithm 3 for a batch of rows appended to the heap, in heap order:
    the state repeated ``insert_tuple`` calls reach, in one program.

    ``values``/``local_pages``/``live``: (N,) rows in append order, their
    pages (non-decreasing, as appends fill the tail) and which of them go
    live; padding rows have ``live`` False. Only the tail page can already
    be summarized, and the last entry owns it: its live rows OR their bits
    into that entry (``hippo.insert_existing``). Every later page's bitmap
    is the OR of its rows' bits; a loop over those pages then applies the
    paper's rule in order — extend the last entry while its density is
    under D, else create an entry — which is what the row-by-row path
    decides too, since a page's choice depends only on the last entry after
    all rows of earlier pages (``hippo.insert_pages``). A page with no live
    row opens nothing. Pages of N rows span at most N // page_card + 2
    pages, so the page bitmaps' shape follows N.

    Unlike ``insert_tuple`` with ``relocate_on_update``, it never relocates
    an entry: bits are set in place. Logical entries (starts, ends and
    bitmaps in sorted order) equal the row-by-row result; physical slots
    may differ. The caller makes sure there is a free slot for every page
    the batch opens. ``state`` is donated.
    """
    num_pages = values.shape[0] // cfg.page_card + 2
    first = local_pages[0]
    new = local_pages > state.summarized_until
    with jax.named_scope("hippo.insert_existing"):
        tail_bits = value_bits(cfg, state.bounds, values,
                               jnp.zeros_like(local_pages), live & ~new, 1)[0]
        last = state.sorted_order[jnp.maximum(state.num_entries - 1, 0)]
        state = state._replace(bitmaps=state.bitmaps.at[last].set(
            state.bitmaps[last] | tail_bits))
    with jax.named_scope("hippo.insert_pages"):
        page_bits = value_bits(cfg, state.bounds, values, local_pages - first,
                               live & new, num_pages)
        count = jnp.max(jnp.where(live & new, local_pages - first + 1, 0))

        def open_page(j, st: HippoState) -> HippoState:
            bits = page_bits[j]
            has = jnp.any(bits != 0)
            page = first + j
            last = st.sorted_order[jnp.maximum(st.num_entries - 1, 0)]
            last_density = jnp.where(
                st.num_entries > 0,
                bm.density(st.bitmaps[last], cfg.resolution),
                jnp.float32(2.0),  # empty index -> always create
            )
            create = has & ~(last_density < cfg.density)
            slot = jnp.where(create, st.num_slots, last)
            pos = jnp.minimum(st.num_entries, st.sorted_order.shape[0] - 1)
            return st._replace(
                bitmaps=st.bitmaps.at[slot].set(
                    jnp.where(create, bits, st.bitmaps[slot] | bits)),
                starts=st.starts.at[slot].set(
                    jnp.where(create, page, st.starts[slot])),
                ends=st.ends.at[slot].set(
                    jnp.where(has, page, st.ends[slot])),
                slot_live=st.slot_live.at[slot].set(
                    st.slot_live[slot] | create),
                sorted_order=st.sorted_order.at[pos].set(
                    jnp.where(create, slot, st.sorted_order[pos])),
                num_entries=st.num_entries + create,
                num_slots=st.num_slots + create,
                summarized_until=jnp.where(has, page, st.summarized_until),
            )

        return jax.lax.fori_loop(0, count, open_page, state)


# ---------------------------------------------------------------------------
# Maintenance — lazy delete / vacuum (§5.2)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def resummarize_slots(cfg: HippoConfig, state: HippoState, keys: jnp.ndarray,
                      valid: jnp.ndarray, affected: jnp.ndarray) -> HippoState:
    """Re-summarize the page ranges of ``affected`` slots (vacuum, §5.2).

    The refreshed bitmap can only lose bits, so the update is in place and the
    sorted list is untouched (paper's observation). ``affected``: (S,) bool.
    """
    num_pages = keys.shape[0]
    hist = Histogram(state.bounds)
    page_bits = grouping.page_bucket_bits(hist, keys, valid, cfg.resolution)  # (P, H)
    # entry-of-page for affected slots via boundary deltas over live slots.
    s = state.bitmaps.shape[0]
    live = state.slot_live & (jnp.arange(s) < state.num_slots) & affected
    # Map each page to its owning affected slot (or S = "none").
    seg = jnp.full((num_pages,), s, jnp.int32)
    # scatter slot id at starts, then forward-fill within [start, end].
    slot_ids = jnp.arange(s, dtype=jnp.int32)
    start_marks = jnp.full((num_pages,), -1, jnp.int32)
    start_marks = start_marks.at[jnp.clip(state.starts, 0, num_pages - 1)].max(
        jnp.where(live, slot_ids, -1), mode="drop")
    filled = jax.lax.associative_scan(jnp.maximum, start_marks)
    ends_of = jnp.where(filled >= 0, state.ends[jnp.clip(filled, 0, s - 1)], -1)
    in_range = (filled >= 0) & (jnp.arange(num_pages) <= ends_of)
    seg = jnp.where(in_range, filled, s)
    agg = jax.ops.segment_max(page_bits.astype(jnp.int32), seg,
                              num_segments=s + 1) > 0          # (S+1, H)
    fresh = bm.from_bool(agg[:s])
    new_bitmaps = jnp.where(affected[:, None], fresh, state.bitmaps)
    return state._replace(bitmaps=new_bitmaps)


@partial(jax.jit, static_argnames=("cfg",))
def resummarize_shard(cfg: HippoConfig, state: HippoState, keys: jnp.ndarray,
                      valid: jnp.ndarray, new_bounds: jnp.ndarray) -> HippoState:
    """Remap a shard's partial histograms onto new complete-histogram bounds.

    The drift-adaptation unit of work (``runtime.writer``): every live
    entry's packed bitmap is rebuilt from its pages' tuples bucketized under
    ``new_bounds``, and the state's ``bounds`` swap to the new boundary set
    in the same functional update. Entry page ranges, the sorted list, and
    every count are untouched — the remap changes which buckets a page's
    tuples land in, never which pages an entry covers — so counts stay
    bit-identical as long as query bitmaps convert under the same bounds the
    shard serves (the per-shard epoch contract in ``core.partition``).

    Built on ``resummarize_slots`` with every live slot affected: one jit
    trace per slab shape serves every shard and every remap, and the whole
    remap is plain jnp (kernel-free — no Pallas path to revalidate on TPU).
    """
    s = state.bitmaps.shape[0]
    live = state.slot_live & (jnp.arange(s) < state.num_slots)
    st = state._replace(bounds=new_bounds)
    return resummarize_slots(cfg, st, keys, valid, live)


# ---------------------------------------------------------------------------
# Storage accounting (paper's index-size metric)
# ---------------------------------------------------------------------------

def index_nbytes(cfg: HippoConfig, state: HippoState, compressed: bool = False) -> int:
    """Bytes of live index storage: entries (bitmap + 2 page ids) + sorted list.

    ``compressed=True`` reports the serialized RLE form (paper's on-disk
    compressed bitmaps); the device-resident form is fixed-width words.
    """
    e = int(state.num_entries)
    live = np.asarray(state.slot_live)
    words = np.asarray(state.bitmaps)[live]
    if compressed:
        bitmap_bytes = sum(bm.compressed_nbytes(wrow) for wrow in words)
    else:
        bitmap_bytes = words.nbytes
    page_range_bytes = e * 8          # two int32 page ids per entry
    sorted_list_bytes = e * 4         # one pointer per entry (§5.3)
    histogram_bytes = state.bounds.shape[0] * 4
    return bitmap_bytes + page_range_bytes + sorted_list_bytes + histogram_bytes
