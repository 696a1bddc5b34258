"""Sharded partition layer — device-parallel Hippo over contiguous page slabs.

The paper scales Hippo by keeping the index tiny while the *table* grows
(§6's storage model, §7's TPC-H experiments); this layer scales it across
*devices*. The page space is split into S contiguous slabs ("shards") of
``pages_per_shard`` pages each, and every shard carries a full, independent
Hippo structure over its slab:

  shard           a contiguous page extent [s*PPS, (s+1)*PPS) with its own
                  entry table — the paper's index over one table fragment,
                  so every per-shard quantity (§6 index size, §6.1 query
                  cost SF*H, §7 maintenance I/O) applies per shard unchanged
  routing map     ``ShardSpec``: pure page-id arithmetic mapping any page to
                  its owning shard (the thin analogue of a partition catalog)
  summary bitmap  the union of a shard's live partial-histogram bitmaps —
                  one (W,) packed bitmap per shard, kept up to date by
                  inserts and vacuum and stored in snapshots (§3.2's test
                  lifted from entries to shards); no query reads it

Search runs Algorithm 1 per shard in one device program and reduces counts/
match-stats across the shard axis (``core.index.search_compact_many_sharded``,
``ShardedHippoIndex.search_batch``); because shards partition the page space
and page inspection is exact, per-shard counts sum bit-identically to the
unsharded count. Maintenance (Algorithm 3 inserts, §5.2 vacuum) routes
through ``ShardSpec`` and touches exactly one shard's arrays per page — the
locality that lets shards live on different devices (``launch.shardings``)
and lets the async writer (``runtime.writer.MaintenanceWriter``) rebuild and
swap shard s's slice between query batches while every other shard keeps
serving. The writer attaches as ``staging`` (its pending rows overlay into
``search_batch`` counts) and raises ``swap_in_flight`` while a slice is
mid-swap, which every query/maintenance surface checks.

Entry page ids inside each shard are *local* to its slab; global page order
is recovered by construction since slabs are contiguous and append-ordered.

Bounds epochs (drift adaptation): every shard carries its *own* complete-
histogram boundary set (``SHARD_AXES.bounds = 0``), initially identical
across shards. A drift re-summarization (``runtime.writer``) remaps shards
onto new bounds one at a time, bumping that shard's entry in
``bounds_epochs``; predicates are converted once per distinct epoch and fed
to the search program as (S, Q, W) per-shard query bitmaps, so every
shard's query bitmaps and page bitmaps always share one bucket space —
counts stay exact before, during, and after a partial re-summarization.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import bitmap as bm
from repro.core import histogram as hg
from repro.core import index as hix
from repro.core import learned as ln
from repro.core.hippo import MaintenanceCounters, sample_histogram, sample_keys
from repro.core.predicate import (Predicate, intervals,
                                  interval_bitmaps_sharded)
from repro.storage.table import PagedTable

# Summary-policy ladder: how a boundary set is produced, at build time and at
# every drift refit. "equal_mass" is the paper's equi-depth quantile summary
# (``histogram.build``/``rebuild``) and the fallback/oracle; "learned" fits an
# error-bounded piecewise-linear CDF (``core.learned``) and materializes its
# boundaries — same Histogram type, same downstream stack, better placement on
# skewed/drifting keys. The policy is a property of the *index* (it governs
# every shard's bounds), consumed by ``runtime.writer.schedule_resummarize``.
SUMMARY_POLICIES = ("equal_mass", "learned")


@dataclass(frozen=True)
class ShardSpec:
    """The routing map: shard s owns global pages [s*PPS, (s+1)*PPS)."""
    num_shards: int
    pages_per_shard: int

    @property
    def total_pages(self) -> int:
        return self.num_shards * self.pages_per_shard

    def owner(self, page_id: int) -> int:
        """Owning shard of a global page id (may be >= num_shards: overflow)."""
        return page_id // self.pages_per_shard

    def page_lo(self, s: int) -> int:
        return s * self.pages_per_shard

    def to_local(self, page_id: int) -> int:
        return page_id - self.page_lo(self.owner(page_id))


def default_pages_per_shard(num_pages: int, num_shards: int) -> int:
    """Slab width ``ShardedHippoIndex.create`` picks for a table: 25% growth
    room plus a fixed floor so tiny tables can still insert (mirroring
    ``HippoIndex.create``'s slot headroom), split over the shards."""
    return -(-(int(num_pages * 1.25) + 64) // num_shards)


def default_max_slots(pages_per_shard: int) -> int:
    """Per-shard entry capacity ``create`` picks: worst case one entry per
    slab page, plus ``HippoIndex.create``'s fixed update budget."""
    return int(pages_per_shard * 1.25) + 1024


class ShardedHippoState(NamedTuple):
    shards: hix.HippoState     # stacked per hix.SHARD_AXES (incl. per-shard bounds)
    summaries: jnp.ndarray     # (S, W) u32 — OR of live entry bitmaps per shard


# ---------------------------------------------------------------------------
# Stacked-state plumbing
# ---------------------------------------------------------------------------

def shard_state(shards: hix.HippoState, s: int) -> hix.HippoState:
    """Slice one shard's ``HippoState`` out of the stacked arrays."""
    return hix.HippoState(*(
        leaf if ax is None else leaf[s]
        for leaf, ax in zip(shards, hix.SHARD_AXES)))


@jax.jit
def set_shard(shards: hix.HippoState, s, st: hix.HippoState) -> hix.HippoState:
    """Write one shard's ``HippoState`` back into the stacked arrays.

    Jitted with ``s`` traced, so every shard (and every writer swap) reuses
    one compiled scatter program instead of nine eager dispatches.
    """
    return hix.HippoState(*(
        stacked if ax is None else stacked.at[s].set(new)
        for stacked, new, ax in zip(shards, st, hix.SHARD_AXES)))


@jax.jit
def summary_of(st: hix.HippoState) -> jnp.ndarray:
    """(W,) packed union of a shard's live entry bitmaps (the summary).

    After deletes+vacuum the union can only lose bits, so a cached summary is
    always a superset of the true union.
    """
    s = st.bitmaps.shape[0]
    live = st.slot_live & (jnp.arange(s) < st.num_slots)
    masked = jnp.where(live[:, None], st.bitmaps, jnp.uint32(0))
    return jax.lax.reduce(masked, jnp.uint32(0), jax.lax.bitwise_or, (0,))


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def apply_appended(cfg: hix.HippoConfig, state: ShardedHippoState, s,
                   values: jnp.ndarray, local_pages: jnp.ndarray,
                   live: jnp.ndarray) -> ShardedHippoState:
    """Shard ``s``'s eager Algorithm 3 for rows appended to its slab
    (``core.index.insert_appended``, page ids local to the slab), with its
    summary ORed with the rows' bits: one program per shard an append
    touches, in place on the donated stacked state."""
    st = shard_state(state.shards, s)
    bits = hix.value_bits(cfg, st.bounds, values, jnp.zeros_like(local_pages),
                          live, 1)[0]
    st = hix.insert_appended(cfg, st, values, local_pages, live)
    return ShardedHippoState(
        shards=set_shard(state.shards, s, st),
        summaries=state.summaries.at[s].set(state.summaries[s] | bits))


class AppendPiece(NamedTuple):
    """The rows ``[lo, hi)`` of an append that land in shard ``shard``, on
    slab-local ``pages``, opening ``opened`` pages the shard has not
    summarized; the shard held ``entries`` entries before."""
    shard: int
    lo: int
    hi: int
    pages: np.ndarray
    opened: int
    entries: int


def build_sharded(cfg: hix.HippoConfig, spec: ShardSpec, hist: hg.Histogram,
                  table: PagedTable) -> ShardedHippoState:
    """Algorithm 2 per shard: the grouping scan restarts at every slab
    boundary, so no entry ever spans two shards (maintenance stays local)."""
    states = []
    for s in range(spec.num_shards):
        lo = spec.page_lo(s)
        hi = min(lo + spec.pages_per_shard, table.num_pages)
        n = max(hi - lo, 0)
        keys = jnp.asarray(table.keys[lo:hi]) if n else jnp.zeros(
            (0, table.page_card), jnp.float32)
        valid = jnp.asarray(table.valid[lo:hi]) if n else jnp.zeros(
            (0, table.page_card), bool)
        states.append(hix.build(cfg, hist, keys, valid))
    shards = hix.HippoState(*(
        states[0][i] if ax is None else jnp.stack([st[i] for st in states])
        for i, ax in enumerate(hix.SHARD_AXES)))
    summaries = jnp.stack([summary_of(st) for st in states])
    return ShardedHippoState(shards=shards, summaries=summaries)


# ---------------------------------------------------------------------------
# High-level sharded index (CREATE INDEX ... PARTITION BY page range)
# ---------------------------------------------------------------------------

@dataclass
class ShardedHippoIndex:
    """Shard-parallel counterpart of ``core.hippo.HippoIndex``.

    ``cfg.max_slots`` is *per shard*. ``search_batch`` matches
    ``HippoIndex.search_batch`` in signature and in counts, so
    ``runtime.engine.QueryEngine`` serves either transparently; only this
    index takes a ``runtime.writer.MaintenanceWriter``.
    """
    cfg: hix.HippoConfig
    spec: ShardSpec
    state: ShardedHippoState
    table: PagedTable
    counters: MaintenanceCounters = field(default_factory=MaintenanceCounters)
    # Attached ``runtime.writer.MaintenanceWriter`` (None when maintenance is
    # synchronous). When present, ``search_batch`` folds its staging-buffer
    # overlay into counts so queries never go stale while inserts wait in the
    # per-shard queues.
    staging: object | None = field(default=None, repr=False, compare=False)
    # Shard id currently being rebuilt by a writer drain (None otherwise).
    # Queries and maintenance refuse while set: mid-swap the stacked state
    # and the table disagree about that shard, and serving from it would
    # return silently wrong counts.
    swap_in_flight: int | None = field(default=None, repr=False, compare=False)
    # Per-shard bounds epoch: bumped when a drift re-summarization remaps a
    # shard onto new histogram bounds. Shards sharing an epoch share one
    # predicate conversion (``_query_bitmaps``); epochs diverge only while a
    # re-summarization is partially drained.
    bounds_epochs: np.ndarray = field(default=None, repr=False, compare=False)
    # Summary policy (see SUMMARY_POLICIES): consulted by the writer at every
    # ``schedule_resummarize`` to pick the boundary builder for the refit.
    summary: str = "equal_mass"
    # Per-shard learned model (``learned.PiecewiseLinearModel``) whose
    # boundaries shard s currently serves; None under equal-mass bounds or
    # after a degenerate-sample fallback. Recorded by the writer drain at the
    # same moment it bumps ``bounds_epochs[s]``.
    summary_models: list = field(default=None, repr=False, compare=False)

    def __post_init__(self):
        if self.bounds_epochs is None:
            self.bounds_epochs = np.zeros((self.spec.num_shards,), np.int64)
        if self.summary not in SUMMARY_POLICIES:
            raise ValueError(f"summary must be one of {SUMMARY_POLICIES}, "
                             f"got {self.summary!r}")
        if self.summary_models is None:
            self.summary_models = [None] * self.spec.num_shards

    # -- creation ------------------------------------------------------------

    @staticmethod
    def create(table: PagedTable, num_shards: int = 4, resolution: int = 400,
               density: float = 0.2, pages_per_shard: int | None = None,
               max_slots: int | None = None, sample_size: int = 65536,
               relocate_on_update: bool = True,
               hist: hg.Histogram | None = None,
               summary: str = "equal_mass") -> "ShardedHippoIndex":
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if summary not in SUMMARY_POLICIES:
            raise ValueError(f"summary must be one of {SUMMARY_POLICIES}, "
                             f"got {summary!r}")
        if pages_per_shard is None:
            pages_per_shard = default_pages_per_shard(table.num_pages,
                                                      num_shards)
        spec = ShardSpec(num_shards=num_shards, pages_per_shard=pages_per_shard)
        if spec.total_pages < table.num_pages:
            raise ValueError(
                f"shard layout {num_shards}x{pages_per_shard} covers "
                f"{spec.total_pages} pages < table's {table.num_pages}")
        if max_slots is None:
            max_slots = default_max_slots(pages_per_shard)
        cfg = hix.HippoConfig(resolution=resolution, density=density,
                              page_card=table.page_card, max_slots=max_slots,
                              relocate_on_update=relocate_on_update)
        model = None
        if hist is None:
            if summary == "learned":
                # same build sample as the equal-mass path, fit instead of
                # quantiled; a degenerate sample falls back inside
                hist, model = ln.build_histogram(
                    sample_keys(table, sample_size), resolution)
            else:
                hist = sample_histogram(table, resolution, sample_size)
        state = build_sharded(cfg, spec, hist, table)
        return ShardedHippoIndex(cfg=cfg, spec=spec, state=state, table=table,
                                 summary=summary,
                                 summary_models=[model] * num_shards)

    # -- device views --------------------------------------------------------

    def _slabs(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        return (self.table.device_keys_sharded(self.spec.num_shards,
                                               self.spec.pages_per_shard),
                self.table.device_valid_sharded(self.spec.num_shards,
                                                self.spec.pages_per_shard))

    # -- mid-swap refusal ----------------------------------------------------

    def _check_swap_guard(self) -> None:
        """Refuse queries/maintenance while a writer drain is swapping a shard.

        Between a drain's table appends and its state swap, shard
        ``swap_in_flight``'s slice of ``ShardedHippoState`` describes a table
        that no longer exists; any result computed from it would be silently
        wrong. Single-threaded callers only hit this via re-entrancy (e.g. a
        query issued from inside a drain hook), but the refusal must be loud
        either way.
        """
        if self.swap_in_flight is not None:
            raise RuntimeError(
                f"shard {self.swap_in_flight} swap in flight: queries and "
                f"maintenance are refused until the writer drain completes "
                f"(state and table disagree about that shard mid-swap)")

    def _check_no_staged(self) -> None:
        """Refuse direct inserts while a writer holds staged rows: staged
        page routing was predicted from the table tail, and a direct append
        would shift it under the queues."""
        if self.staging is not None and self.staging.queue_depth:
            raise RuntimeError(
                f"writer has {self.staging.queue_depth} staged rows pending: "
                f"route writes through the writer (or flush() it first) — a "
                f"direct insert would shift the table tail and break the "
                f"staged rows' page routing")

    # -- query ---------------------------------------------------------------

    def _query_bitmaps(self, preds: list[Predicate]) -> jnp.ndarray:
        """(S, Q, W) packed query bitmaps, row s converted under shard s's
        histogram bounds. One fused dispatch over the stacked (S, H+1)
        bounds (``predicate.interval_bitmaps_sharded``) serves every epoch
        mix: identical rows while all shards share one bounds epoch,
        distinct rows while a drift re-summarization is partially drained —
        same trace either way."""
        if not preds:
            return bm.zeros(self.cfg.resolution, self.spec.num_shards, 0)
        los, his = intervals(preds)
        return interval_bitmaps_sharded(
            self.state.shards.bounds, los, his,
            jnp.asarray([not p.empty for p in preds]))

    def search_batch(self, preds: list[Predicate], *,
                     max_selected: int | None = None, top_k: int = 0
                     ) -> hix.CompactBatchResult:
        """Algorithm 1 for a batch of predicates over every shard in one
        device program (``core.index.search_compact_many_sharded``), counts
        reduced across the shard axis. ``max_selected`` is the per-shard
        slab width; ``None`` means ``gather_cap``, the program that never
        truncates. Each shard inspects its slab where it lies, or gathers
        its own (``max_selected``, C) slab of the batch union where that
        pays (``inspects_in_place``). With a writer attached, the
        staging-buffer overlay folds into counts (never-stale contract);
        staged rows occupy no page yet, so they appear in counts only,
        never in row ids, and cannot truncate. Row ids are global
        (``page_id * page_card + slot``). The conversion and search
        dispatches are the profiler span ``hippo.dispatch`` (args
        ``bucket``, the slab width ``max_selected``, and ``in_place``, whether
        the shards are inspected where they lie, ``inspects_in_place``)."""
        if max_selected is None:
            max_selected = self.gather_cap
        self._check_swap_guard()
        with TraceAnnotation("hippo.dispatch", bucket=max_selected,
                             in_place=self.inspects_in_place(max_selected)):
            qbms = self._query_bitmaps(preds)
            los, his = intervals(preds)
            keys, valid = self._slabs()
            if self.staging is not None and self.staging.staged_rows:
                vals, live = self.staging.device_buffers()
                return hix.search_compact_many_sharded_staged(
                    self.state.shards, qbms, keys, valid, los, his, vals,
                    live, max_selected=max_selected, top_k=top_k)
            return hix.search_compact_many_sharded(
                self.state.shards, qbms, keys, valid, los, his,
                max_selected=max_selected, top_k=top_k)

    @property
    def gather_cap(self) -> int:
        """Per-shard slab width at which the gather path can never truncate
        (a shard's union is at most its ``pages_per_shard`` slab pages)."""
        return self.spec.pages_per_shard

    def inspects_in_place(self, max_selected: int) -> bool:
        """Whether ``search_batch`` at this per-shard slab width
        inspects each shard's slab where it lies instead of gathering
        (``core.index.inspects_in_place``)."""
        return hix.inspects_in_place(max_selected, self.spec.pages_per_shard)

    def search(self, pred: Predicate) -> hix.CompactBatchResult:
        """Single-predicate convenience: a batch of one at the cap."""
        return self.search_batch([pred])

    def count(self, pred: Predicate) -> int:
        return int(self.search_batch([pred]).counts[0])

    # -- maintenance ---------------------------------------------------------

    def _require_capacity(self, s: int, page_id: int, opens_page: bool) -> None:
        """Refuse, before any mutation, inserts the shard layout cannot hold:
        a page past the last slab, or slot exhaustion inside shard s."""
        if s >= self.spec.num_shards:
            raise RuntimeError(
                f"shard layout full: page {page_id} falls past shard "
                f"{self.spec.num_shards - 1}'s slab "
                f"(pages_per_shard={self.spec.pages_per_shard}); rebuild with "
                f"more shards or larger slabs")
        if opens_page or self.cfg.relocate_on_update:
            if int(self.state.shards.num_slots[s]) + 1 > self.cfg.max_slots:
                raise RuntimeError(
                    f"shard {s} at slot capacity "
                    f"({int(self.state.shards.num_slots[s])}/"
                    f"{self.cfg.max_slots}); rebuild with a larger max_slots")

    def _apply_shard(self, s: int, st: hix.HippoState) -> None:
        self.state = ShardedHippoState(
            shards=set_shard(self.state.shards, s, st),
            summaries=self.state.summaries.at[s].set(summary_of(st)))

    def insert(self, value: float) -> None:
        """Eager insert routed to the owning shard (Algorithm 3, shard-local)."""
        self._check_swap_guard()
        self._check_no_staged()
        page_id, opens_page = self.table.next_page_id()
        s = self.spec.owner(page_id)
        self._require_capacity(s, page_id, opens_page)
        self.table.insert(value)
        st = shard_state(self.state.shards, s)
        before = int(st.num_entries)
        st = hix.insert_tuple(self.cfg, st, jnp.float32(value),
                              jnp.int32(self.spec.to_local(page_id)))
        self._apply_shard(s, st)
        self.counters.inserts += 1
        self.counters.entries_touched += 1
        self.counters.pages_opened += int(opens_page)
        self.counters.entries_created += int(st.num_entries) - before

    def insert_batch(self, values: np.ndarray) -> None:
        """Atomic batched insert at the table tail: one eager Algorithm 3
        program per shard the rows land in (``apply_appended``). What the
        layout cannot hold is refused before the table or any shard
        changes."""
        self._check_swap_guard()
        self._check_no_staged()
        values = np.asarray(values, np.float32).ravel()
        if values.size == 0:
            return
        pieces = self.route_append(values)
        self.table.append(values)
        self.apply_append(pieces, values)
        self.counters.inserts += len(values)

    def route_append(self, values: np.ndarray, live: np.ndarray | None = None
                     ) -> list[AppendPiece]:
        """Route rows about to be appended at the table tail to their
        shards, before anything changes. Refuses rows past the last slab,
        and a shard without a free slot for every page the rows would open
        there (each opened page creates at most one entry; nothing
        relocates)."""
        pps = self.spec.pages_per_shard
        pages = (self.table.tail + np.arange(values.size)) // \
            self.table.page_card
        last = int(pages[-1])
        if self.spec.owner(last) >= self.spec.num_shards:
            raise RuntimeError(
                f"shard layout full: page {last} falls past shard "
                f"{self.spec.num_shards - 1}'s slab (pages_per_shard={pps}); "
                f"rebuild with more shards or larger slabs")
        live = np.ones(values.size, bool) if live is None else live
        su, slots, entries = jax.device_get((
            self.state.shards.summarized_until, self.state.shards.num_slots,
            self.state.shards.num_entries))
        owners = pages // pps
        pieces = []
        for s in range(int(owners[0]), int(owners[-1]) + 1):
            lo, hi = (int(i) for i in np.searchsorted(owners, [s, s + 1]))
            local = pages[lo:hi] - s * pps
            opened = np.unique(local[live[lo:hi] & (local > su[s])]).size
            if int(slots[s]) + opened > self.cfg.max_slots:
                raise RuntimeError(
                    f"shard {s} at slot capacity ({int(slots[s])}/"
                    f"{self.cfg.max_slots}, {opened} pages to open); "
                    f"rebuild with a larger max_slots")
            pieces.append(AppendPiece(s, lo, hi, local, opened,
                                      int(entries[s])))
        return pieces

    def apply_append(self, pieces: list[AppendPiece], values: np.ndarray,
                     live: np.ndarray | None = None) -> None:
        """Index rows the table has just appended, as ``route_append``
        routed them: each piece padded (``core.index.pad_rows``) and applied
        by ``apply_appended``."""
        for p in pieces:
            rows = hix.pad_rows(values[p.lo:p.hi], p.pages,
                                None if live is None else live[p.lo:p.hi])
            self.state = apply_appended(self.cfg, self.state, np.int32(p.shard),
                                        *rows)
            self.counters.pages_opened += p.opened
        entries = np.asarray(self.state.shards.num_entries)
        self.counters.entries_created += sum(
            int(entries[p.shard]) - p.entries for p in pieces)

    def dirty_shards(self) -> np.ndarray:
        """Shard ids owning at least one dirty page (pending vacuum work)."""
        dirty_pages = np.flatnonzero(self.table.dirty[: self.table.num_pages])
        return np.unique(dirty_pages // self.spec.pages_per_shard)

    def vacuum(self) -> int:
        """§5.2 lazy maintenance, shard-grouped: dirty pages re-summarize
        entries inside their owning shards only (dirty spans touch each shard
        independently). Returns total entries re-summarized."""
        self._check_swap_guard()
        shards = self.dirty_shards()
        if shards.size == 0:
            return 0
        total = 0
        for s in shards:
            total += self._vacuum_shard_locked(int(s))
        return total

    def vacuum_shard(self, s: int) -> int:
        """Vacuum one shard: re-summarize its entries covering dirty pages
        and clear *only that shard's* dirty notes. The per-shard unit of work
        the async writer drains between query batches — other shards' dirty
        pages stay queued, and their state/summaries are untouched. Returns
        entries re-summarized (0 if the shard has no dirty pages)."""
        self._check_swap_guard()
        return self._vacuum_shard_locked(s)

    def _vacuum_shard_locked(self, s: int) -> int:
        """``vacuum_shard`` body without the swap guard — for the writer,
        which holds ``swap_in_flight`` itself while draining a vacuum."""
        dirty_pages = np.flatnonzero(self.table.dirty[: self.table.num_pages])
        dirty_pages = dirty_pages[dirty_pages // self.spec.pages_per_shard == s]
        if dirty_pages.size == 0:
            return 0
        keys, valid = self._slabs()
        st = shard_state(self.state.shards, s)
        affected = np.zeros((self.cfg.max_slots,), bool)
        # one sorted-list search for every dirty page of the shard
        affected[hix.owning_slots(
            st, dirty_pages - self.spec.page_lo(s))] = True
        st = hix.resummarize_slots(self.cfg, st, keys[s], valid[s],
                                   jnp.asarray(affected))
        self._apply_shard(s, st)
        self.table.clear_dirty(dirty_pages)
        n = int(affected.sum())
        # one counted vacuum per shard that actually did work, on every
        # entry point (vacuum / vacuum_shard / writer drain) alike
        self.counters.vacuums += 1
        self.counters.entries_resummarized += n
        return n

    # -- introspection -------------------------------------------------------

    def shard_histogram(self, s: int) -> hg.Histogram:
        """Shard s's complete histogram (its current bounds epoch)."""
        return hg.Histogram(self.state.shards.bounds[s])

    @property
    def histogram(self) -> hg.Histogram:
        """The histogram shared by every shard — valid only while all shards
        sit on one bounds epoch (always true outside a partially-drained
        re-summarization); prefer ``shard_histogram`` in epoch-aware code."""
        return self.shard_histogram(0)

    @property
    def num_shards(self) -> int:
        return self.spec.num_shards

    @property
    def num_entries(self) -> int:
        return int(np.asarray(self.state.shards.num_entries).sum())

    @property
    def summarized_until(self) -> int:
        """Last globally-summarized page id (-1 if the index is empty)."""
        su = np.asarray(self.state.shards.summarized_until)
        glob = np.where(su >= 0,
                        su + np.arange(self.spec.num_shards) *
                        self.spec.pages_per_shard, -1)
        return int(glob.max())

    def shard_entry_counts(self) -> np.ndarray:
        return np.asarray(self.state.shards.num_entries)

    def nbytes(self, compressed: bool = False) -> int:
        """Live index bytes summed over shards, plus the routing map and the
        per-shard summary bitmaps (the layer's only additions)."""
        total = 0
        for s in range(self.spec.num_shards):
            total += hix.index_nbytes(self.cfg, shard_state(self.state.shards, s),
                                      compressed=compressed)
        total += self.spec.num_shards * 8        # routing map: page range per shard
        total += int(np.asarray(self.state.summaries).nbytes)
        return total

    # -- persistence (checkpointing.snapshot) --------------------------------

    def save(self, root, *, wal_seqno: int = 0, keep: int = 3, **kw):
        """Durably snapshot this index (table, shards, bounds/epochs, models,
        and any attached writer's staged state) under ``<root>/snap_<N>/``.
        Returns the committed snapshot directory. Extra keywords (``epoch``,
        ``compact``) pass through to
        ``repro.checkpointing.snapshot.save_index``."""
        from repro.checkpointing.snapshot import save_index
        return save_index(root, self, wal_seqno=wal_seqno, keep=keep, **kw)

    def save_delta(self, root, *, shards, wal_seqno: int = 0, **kw):
        """Durably commit an incremental delta — the given shards' index
        sections and table slab rows — against the last full snapshot under
        ``root``. See ``repro.checkpointing.snapshot.save_delta``."""
        from repro.checkpointing.snapshot import save_delta
        return save_delta(root, self, shards=shards, wal_seqno=wal_seqno,
                          **kw)

    @staticmethod
    def load(root, *, epoch: int | None = None) -> "ShardedHippoIndex":
        """Reconstruct the latest (or a given) committed snapshot. Counts,
        row ids, bounds, epochs, and learned models round-trip exactly; use
        ``checkpointing.snapshot.recover_index`` (or
        ``runtime.engine.QueryEngine.recover``) to also replay a write-ahead
        journal after a crash."""
        from repro.checkpointing.snapshot import load_index
        return load_index(root, epoch=epoch)[0]
