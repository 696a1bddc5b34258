"""Async maintenance writer — insert/vacuum off the query path (§5, Alg. 3).

The paper's headline maintenance claim (up to three orders of magnitude less
insert overhead than a B+-Tree, §5/Fig. 6c) assumes maintenance does not sit
on the query path. In this repro it did: every ``insert`` ran Algorithm 3
synchronously — one jit dispatch plus a full slab-view invalidation — before
the next query batch could run. ``MaintenanceWriter`` moves that work between
engine batches, exploiting the partition layer's locality (PR 2): a write
touches exactly one shard's arrays, so shard s can be rebuilt while every
other shard keeps serving.

Lifecycle (per shard):

  stage    ``write(v)`` routes v by ``ShardSpec`` page arithmetic into the
           owning shard's pending queue — a small staging buffer kept in
           table-append order, with a sorted view for overlay counting.
           Nothing touches the device index; staging is a host list append.
  overlay  queries stay exact while rows wait: the index's
           ``search_batch`` hands the staged rows to the search program
           (``device_buffers``; ``core.index.staged_overlay_counts``), which
           adds those matching each predicate on top of the index counts —
           the never-stale contract. Staged rows occupy no page until their
           drain, so they appear in counts only, never in row ids.
           ``delete(lo, hi)`` marks table tuples invalid immediately (queries
           read the validity mask, §5.2 lazy deletes) and kills staged rows
           in range before they ever reach the table.
  drain    between engine batches the writer takes one shard's whole queue,
           appends its tuples to the table (which patches the touched pages
           into the cached device slabs) and applies Algorithm 3 to them in
           one program, the same apply as ``insert_batch``
           (``ShardedHippoIndex.route_append``/``apply_append``); dirty
           shards get their §5.2 ``vacuum_shard`` the same way. Queues drain
           in ascending shard order so staged page ids land exactly where
           stage-time routing predicted.
  swap     the apply updates the shard's slice of ``ShardedHippoState`` and
           its summary bitmap in place. While a drain is in flight the index
           refuses queries and maintenance (``swap_in_flight``) instead of
           serving a shard whose state and table disagree.

Failure atomicity: a drain that refuses (slot capacity) does so before the
table or the index changes, and requeues the shard's staged rows; a failure
after the append rolls the table back. The overlay keeps counts exact, and
the error surfaces from ``drain``/``flush``, not from a query.

Drift re-summarization (beyond paper): every staged insert feeds a
``histogram.DriftTracker`` (per-bucket hit counters + reservoir sample), so
the writer knows when the complete histogram's bucket space has drifted out
from under the workload — the paper never rebuilds it on local updates
(§4.1), which under sustained drift clamps every new tuple into an edge
bucket and erodes pruning. ``schedule_resummarize`` queues a third drain-unit
kind: one per shard, each remapping that shard's bitmaps onto a fresh
boundary set (``histogram.rebuild`` from the reservoir;
``core.index.resummarize_shard``) under the same swap discipline as insert
drains. Resummarize units drain *before* insert queues so rows staged under
the drifted bounds land under the new ones and group well from their first
page; each remapped shard bumps its ``bounds_epochs`` entry, and queries stay
exact throughout because predicate conversion is per shard epoch
(``core.partition``).

``runtime.engine.QueryEngine`` owns the interleave policy (drain-between-
batches, drain-on-queue-depth, explicit ``flush``) and the drift policy knobs
(``drift_threshold``, auto vs. manual resummarize); the writer itself is
policy-free mechanism.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

import jax.numpy as jnp

from repro.core import histogram as hg
from repro.core import index as hix
from repro.core import learned as ln
from repro.core.partition import (SUMMARY_POLICIES, ShardedHippoIndex,
                                  ShardedHippoState, set_shard, shard_state,
                                  summary_of)
from repro.runtime.faultinject import crashpoint

_STAGE_BUCKET_MIN = 8   # smallest device overlay width (trace bucketing)


class _ShardQueue:
    """Pending inserts for one shard, kept in table-append order.

    ``live`` marks rows not yet killed by a staged delete; the sorted view of
    live values fills the device overlay's buffers.
    """
    __slots__ = ("values", "live", "n_live", "_sorted")

    def __init__(self):
        self.values: list[float] = []
        self.live: list[bool] = []
        self.n_live = 0
        self._sorted: np.ndarray | None = None

    def append(self, v: float) -> None:
        self.values.append(v)
        self.live.append(True)
        self.n_live += 1
        self._sorted = None

    def extend(self, values: np.ndarray) -> None:
        self.values.extend(values.tolist())
        self.live.extend([True] * values.size)
        self.n_live += values.size
        self._sorted = None

    def kill_range(self, lo: float, hi: float) -> int:
        """Mark live staged values in [lo, hi] dead (a delete overtaking a
        staged insert); they never reach the index's bitmaps."""
        n = 0
        for i, (v, alive) in enumerate(zip(self.values, self.live)):
            if alive and lo <= v <= hi:
                self.live[i] = False
                n += 1
        if n:
            self.n_live -= n
            self._sorted = None
        return n

    @property
    def sorted_live(self) -> np.ndarray:
        if self._sorted is None:
            self._sorted = np.sort(np.asarray(
                [v for v, alive in zip(self.values, self.live) if alive],
                np.float32))
        return self._sorted


@dataclass
class WriterStats:
    staged: int = 0           # tuples ever staged
    killed: int = 0           # staged tuples overtaken by a delete
    drains: int = 0           # drain units applied (inserts + vacuums + resummarizes)
    drained_rows: int = 0     # live tuples applied to the index by drains
    vacuums: int = 0          # shard vacuums drained
    resummarizes: int = 0     # shard remaps drained (drift re-summarization)
    learned_refits: int = 0   # resummarize schedules served by a learned fit
    learned_fallbacks: int = 0  # learned schedules that fell back to equal-mass
    last_drain_us: float = 0.0
    total_drain_us: float = 0.0


def takes_writer(index) -> bool:
    """Whether ``index`` can carry a ``MaintenanceWriter``: only a
    ``ShardedHippoIndex`` routes rows into per-shard queues by its
    ``ShardSpec``."""
    return isinstance(index, ShardedHippoIndex)


class MaintenanceWriter:
    """Per-shard staged maintenance over a ``ShardedHippoIndex``.

    Constructing the writer attaches it to the index (``index.staging``), so
    every search path folds the staging overlay into counts from then on.
    """

    def __init__(self, index, journal=None):
        if not takes_writer(index):
            raise ValueError(
                "MaintenanceWriter needs a ShardedHippoIndex (ShardSpec "
                f"routing + stacked per-shard state); got "
                f"{type(index).__name__}")
        prior = getattr(index, "staging", None)
        if prior is not None and prior.queue_depth:
            # Replacing the attached writer would detach its overlay and
            # silently drop its staged rows from every count.
            raise RuntimeError(
                f"index already has a writer with {prior.queue_depth} staged "
                f"rows pending: flush() it before attaching a new one")
        self.index = index
        index.staging = self
        # Write-ahead journal (checkpointing.wal.Journal, or None): when
        # attached, every acknowledged operation appends one fsynced record
        # *before* any in-memory state changes — append before admission —
        # so crash recovery (checkpointing.snapshot.recover_index) can
        # replay exactly the acknowledged stream past the last snapshot.
        self.journal = journal
        self._queues: dict[int, _ShardQueue] = {}
        self._staged_total = 0       # pending tuples, dead rows included
        self._version = 0            # bumps on any staging change
        self._dev_cache: tuple | None = None
        self.stats = WriterStats()
        # Drift telemetry: armed with the bounds serving the table tail
        # (where appends route); rearmed when a re-summarization completes.
        s_tail = min(index.spec.owner(max(index.table.num_pages - 1, 0)),
                     index.spec.num_shards - 1)
        self.drift = hg.DriftTracker(index.shard_histogram(s_tail))
        self._pending_resummarize: list[int] = []
        self._pending_bounds: np.ndarray | None = None
        self._pending_model = None   # learned model behind the pending bounds
        self._resum_epoch = 0
        # Shards whose published state/table slab changed since the last
        # durable commit — exactly what an incremental delta must capture
        # (checkpointing.snapshot.save_delta). Fed by every mutation that
        # survives: drain swaps, vacuums, resummarize remaps, and deletes
        # (which flip validity bits across arbitrary shards' slabs).
        self._dirty_since_checkpoint: set[int] = set()

    # -- staging (the off-query-path write surface) --------------------------

    def _check_attached(self) -> None:
        """Refuse staging through a writer the index no longer consults —
        its rows would never be overlaid into counts."""
        if self.index.staging is not self:
            raise RuntimeError(
                "writer is detached: the index has a different (newer) "
                "staging writer attached; stage through that one")

    def _tail_pos(self) -> int:
        """Absolute tuple position of the table's append tail."""
        t = self.index.table
        if t.num_pages == 0:
            return 0
        return t.num_pages * t.page_card - (t.page_card - t.fill)

    def write(self, value: float) -> int:
        """Stage one insert; returns the owning shard.

        Routing is pure ``ShardSpec`` arithmetic on the page the tuple *will*
        occupy once every earlier staged row has drained — appends are
        sequential, so the k-th staged tuple's page is fully determined by
        the table tail. Refuses (before staging) writes the shard layout
        cannot ever hold, mirroring the synchronous path's refusal.
        """
        self.index._check_swap_guard()
        self._check_attached()
        spec = self.index.spec
        pos = self._tail_pos() + self._staged_total
        page = pos // self.index.table.page_card
        s = spec.owner(page)
        if s >= spec.num_shards:
            raise RuntimeError(
                f"shard layout full: staged tuple would land on page {page}, "
                f"past shard {spec.num_shards - 1}'s slab "
                f"(pages_per_shard={spec.pages_per_shard}); rebuild with more "
                f"shards or larger slabs")
        if self.journal is not None:
            # durable before acknowledged: if this append fails, the write
            # raises with nothing staged and nothing to lose
            self.journal.append_insert(s, float(value))
        self._queues.setdefault(s, _ShardQueue()).append(float(value))
        self._staged_total += 1
        self._version += 1
        self._dev_cache = None
        self.stats.staged += 1
        self.drift.observe(value)
        return s

    def write_many(self, values: np.ndarray) -> None:
        """Stage rows in order, as ``write`` row by row would: each row's
        shard follows from the page it will occupy, so the array splits
        into one run per shard. The whole array is refused, before anything
        is staged, if its last row falls past the last slab."""
        self.index._check_swap_guard()
        self._check_attached()
        values = np.asarray(values, np.float32).ravel()
        if values.size == 0:
            return
        spec = self.index.spec
        pos = self._tail_pos() + self._staged_total + np.arange(values.size)
        owners = pos // self.index.table.page_card // spec.pages_per_shard
        if int(owners[-1]) >= spec.num_shards:
            raise RuntimeError(
                f"shard layout full: staged tuple would land on page "
                f"{int(pos[-1]) // self.index.table.page_card}, past shard "
                f"{spec.num_shards - 1}'s slab "
                f"(pages_per_shard={spec.pages_per_shard}); rebuild with more "
                f"shards or larger slabs")
        if self.journal is not None:
            for s, v in zip(owners.tolist(), values.tolist()):
                self.journal.append_insert(s, v)
        for s in range(int(owners[0]), int(owners[-1]) + 1):
            lo, hi = np.searchsorted(owners, [s, s + 1])
            self._queues.setdefault(s, _ShardQueue()).extend(values[lo:hi])
        self._staged_total += values.size
        self._version += 1
        self._dev_cache = None
        self.stats.staged += values.size
        self.drift.observe(values)

    def delete(self, lo: float, hi: float) -> int:
        """Apply a delete: table tuples in range go invalid now (queries read
        the validity mask, so results stay exact with zero index work), staged
        rows in range die before ever reaching the table, and the dirtied
        shards queue for an async ``vacuum_shard`` drain. Returns tuples
        deleted (table + staged)."""
        self.index._check_swap_guard()
        self._check_attached()
        if self.journal is not None:
            self.journal.append_delete(float(lo), float(hi))
        table = self.index.table
        spec = self.index.spec
        was_fresh = table._dev_shard is not None and not table._dev_shard_stale
        n = table.delete_where(lo, hi)
        if n:
            self._dirty_since_checkpoint.update(
                int(s) for s in self.index.dirty_shards())
        if n and was_fresh:
            # every mutated page carries a dirty note until its vacuum, so
            # the dirty owners are exactly the slabs to patch
            table.refresh_shard_slabs(self.index.dirty_shards(),
                                      spec.num_shards, spec.pages_per_shard)
        killed = 0
        for q in self._queues.values():
            killed += q.kill_range(lo, hi)
        if killed:
            self._version += 1
            self._dev_cache = None
            self.stats.killed += killed
        return n + killed

    # -- introspection -------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        """Staged tuples pending a drain (dead rows included: they still
        occupy a staged table position)."""
        return self._staged_total

    @property
    def staged_rows(self) -> int:
        """Live staged rows currently overlaid into query counts."""
        return sum(q.n_live for q in self._queues.values())

    def pending_shards(self) -> list[int]:
        """Shards with queued inserts, in the mandatory drain order."""
        return sorted(s for s, q in self._queues.items() if q.values)

    def pending_vacuum_shards(self) -> list[int]:
        return [int(s) for s in self.index.dirty_shards()]

    def pending_resummarize_shards(self) -> list[int]:
        """Shards still awaiting their remap onto the pending bounds."""
        return list(self._pending_resummarize)

    @property
    def pending_units(self) -> int:
        """Drain units outstanding (resummarizes + insert queues + vacuums)."""
        return (len(self._pending_resummarize) + len(self.pending_shards())
                + len(self.pending_vacuum_shards()))

    def dirty_checkpoint_shards(self) -> list[int]:
        """Shards changed since the last durable commit (delta capture set)."""
        return sorted(self._dirty_since_checkpoint)

    def clear_checkpoint_dirty(self) -> None:
        """Mark the current state durably captured (commit just happened)."""
        self._dirty_since_checkpoint.clear()

    # -- drift re-summarization (the third drain-unit kind) ------------------

    def schedule_resummarize(self, bounds=None, policy=None) -> hg.Histogram:
        """Queue a remap of every shard onto new histogram bounds.

        With ``bounds=None`` the new boundary set comes from the summary
        policy — ``policy`` if given, else the index's ``summary`` attribute
        (``core.partition.SUMMARY_POLICIES``):

        - ``"equal_mass"``: ``histogram.rebuild`` — the armed bounds' own
          boundary summary blended *equal-mass* with the drift reservoir.
          Equal mass (rather than weighting by tuple counts) is a deliberate
          policy: the reservoir region is where the workload is writing —
          and, under drift, where it is querying — so it gets half the
          boundary budget however few rows it holds yet, while the old
          data's resolution loss is bounded at 2x.
        - ``"learned"``: ``learned.learned_rebuild`` — an error-bounded
          piecewise-linear fit of the same {old summary, reservoir} blend,
          with the reservoir carrying the dominant mass share and per-key
          mass clamped at one bucket's worth. A degenerate sample falls back
          to the equal-mass path (``stats.learned_fallbacks``); the fitted
          model is recorded per shard (``index.summary_models``) as each
          shard's remap drains.

        An explicit ``bounds`` array schedules a manual remap (callers
        wanting count-weighted blending can call ``histogram.rebuild`` with
        ``old_count``/``new_count`` themselves). Rescheduling before the
        previous remap finished replaces the pending bounds and re-queues
        every shard. The bounds are validated at *drain* time — the
        refusal-and-rollback point of every drain-unit kind — not here.

        Returns the histogram the shards will serve once all units drain.
        """
        self.index._check_swap_guard()
        self._check_attached()
        if policy is None:
            policy = getattr(self.index, "summary", "equal_mass")
        if policy not in SUMMARY_POLICIES:
            raise ValueError(f"policy must be one of {SUMMARY_POLICIES}, "
                             f"got {policy!r}")
        pending_model = None
        refit = fallback = False
        if bounds is None:
            sample = self.drift.sample()
            if sample.size == 0:
                raise RuntimeError(
                    "no drift sample: stage inserts through write() before "
                    "scheduling a reservoir-based resummarize, or pass "
                    "explicit bounds")
            if policy == "learned":
                hist, model = ln.learned_rebuild(self.drift.armed_histogram,
                                                 sample)
                pending_model = model
                fallback = model is None
                refit = not fallback
            else:
                hist = hg.rebuild(self.drift.armed_histogram, sample)
            bounds = hg.host_bounds(hist)
        bounds = np.asarray(bounds, np.float32)
        if self.journal is not None:
            # the *materialized* bounds are journaled (not the reservoir
            # they came from), so replay schedules the identical remap.
            # Everything above computed into locals only: append-before-
            # admission means no writer state may change until this record
            # is durable — a crash before here loses an operation that was
            # never acknowledged, a crash after replays it exactly.
            self.journal.append_resummarize(bounds, policy)
        self._pending_model = pending_model
        if fallback:
            self.stats.learned_fallbacks += 1
        elif refit:
            self.stats.learned_refits += 1
        self._pending_bounds = bounds
        self._pending_resummarize = list(range(self.index.spec.num_shards))
        self._resum_epoch = int(self.index.bounds_epochs.max()) + 1
        return hg.Histogram(jnp.asarray(bounds))

    def queue_depths(self) -> dict[int, int]:
        """Per-shard staged tuple counts (engine stats surface)."""
        return {s: len(q.values) for s, q in self._queues.items() if q.values}

    # -- overlay (queries never go stale) ------------------------------------

    def device_buffers(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(vals (S, B) f32, live (S, B) bool) staged rows for the device
        overlay (``core.index.search_compact_many_sharded_staged``). B is
        the max per-shard live depth rounded to a power of two (min 8) so
        the overlay re-traces only when the queue outgrows its bucket."""
        if self._dev_cache is not None and self._dev_cache[0] == self._version:
            return self._dev_cache[1], self._dev_cache[2]
        s_n = self.index.spec.num_shards
        depth = max((q.n_live for q in self._queues.values()), default=0)
        b = _STAGE_BUCKET_MIN
        while b < depth:
            b *= 2
        vals = np.zeros((s_n, b), np.float32)
        live = np.zeros((s_n, b), bool)
        for s, q in self._queues.items():
            a = q.sorted_live
            vals[s, : a.size] = a
            live[s, : a.size] = True
        out = (jnp.asarray(vals), jnp.asarray(live))
        self._dev_cache = (self._version, *out)
        return out

    # -- drain / swap --------------------------------------------------------

    def drain(self, max_units: int | None = None) -> int:
        """Apply up to ``max_units`` drain units (default: everything).

        A unit is one shard's resummarize remap, one shard's whole insert
        queue, or one shard's vacuum. Resummarize units go first so staged
        rows land under the new bounds (their pages group well from the
        start); then insert queues in ascending shard order — the order
        their staged page ids were predicted in — then dirty shards vacuum.
        Returns live rows applied to the index.

        Stats account per applied unit: a unit that refuses partway through
        the drain still leaves the units (and wall time) already applied in
        ``stats.drains``/``last_drain_us``/``total_drain_us`` — a 2-of-3
        drain records 2 drains, not 0.
        """
        t0 = time.perf_counter()
        units = rows = 0
        try:
            for s in self.pending_resummarize_shards():
                if max_units is not None and units >= max_units:
                    break
                self._drain_resummarize(s)
                units += 1
            for s in self.pending_shards():
                if max_units is not None and units >= max_units:
                    break
                rows += self._drain_shard(s)
                units += 1
            for s in self.pending_vacuum_shards():
                if max_units is not None and units >= max_units:
                    break
                self._drain_vacuum(s)
                units += 1
        finally:
            if units:
                us = (time.perf_counter() - t0) * 1e6
                self.stats.drains += units
                self.stats.last_drain_us = us
                self.stats.total_drain_us += us
        return rows

    def flush(self) -> int:
        """Drain every pending queue and vacuum; returns rows applied."""
        return self.drain(max_units=None)

    def discard(self) -> int:
        """Drop every staged row without applying it; returns rows dropped.

        The recovery path for a drain that keeps refusing (shard slot
        capacity): the staged rows never reach the table or the index, and
        counts simply stop including them. All-or-nothing by design — later
        queues' page routing was predicted assuming earlier queues land, so
        a single shard's queue cannot be dropped in isolation.
        """
        dropped = self._staged_total
        self._queues.clear()
        self._staged_total = 0
        self._version += 1
        self._dev_cache = None
        return dropped

    def _drain_shard(self, s: int) -> int:
        """Drain shard s's queue: append it to the table and index it with
        the same apply as ``insert_batch``, under the swap guard."""
        idx = self.index
        table = idx.table
        q = self._queues.pop(s)
        values = np.asarray(q.values, np.float32)
        # dead staged rows occupy their predicted slots but never go live —
        # they keep later queues' page routing exact
        live = np.asarray(q.live, bool)
        snap_pages, snap_fill = table.num_pages, table.fill
        idx.swap_in_flight = s
        appended = False
        try:
            pieces = idx.route_append(values, live)  # refusals: nothing changed
            if [p.shard for p in pieces] != [s]:
                raise RuntimeError(
                    f"writer invariant violated: shard {s} drain appended "
                    f"pages outside its slab (was the table mutated behind "
                    f"the staged queues?)")
            table.append(values, live)
            appended = True
            crashpoint("drain.pre_swap")
            idx.apply_append(pieces, values, live)
        except Exception:
            if appended:
                table.truncate_to(snap_pages, snap_fill)
            self._queues[s] = q      # rows stay staged; overlay stays exact
            raise
        finally:
            idx.swap_in_flight = None
        self._staged_total -= len(q.values)
        self._version += 1
        self._dev_cache = None
        self._dirty_since_checkpoint.add(s)
        applied = int(live.sum())
        idx.counters.inserts += applied
        self.stats.drained_rows += applied
        return applied

    def _drain_vacuum(self, s: int) -> int:
        """Drain one shard's §5.2 vacuum under the swap guard."""
        idx = self.index
        idx.swap_in_flight = s
        try:
            n = idx._vacuum_shard_locked(s)
        finally:
            idx.swap_in_flight = None
        if n:
            self._dirty_since_checkpoint.add(s)
        self.stats.vacuums += 1
        return n

    def _drain_resummarize(self, s: int) -> None:
        """Drain one shard's drift remap: rebuild its bitmaps onto the
        pending bounds against a copy of the shard's state slice, swap it in
        atomically, bump the shard's bounds epoch.

        Same discipline as an insert drain: the swap guard refuses queries
        mid-swap, and a refusal (invalid pending bounds) releases the guard
        with the old state — and the old bounds — still serving; the unit
        stays pending so a corrected ``schedule_resummarize`` can retry.
        No table mutation happens here, so there is no snapshot to restore
        and no slab to patch (the remap changes bitmaps, not pages).
        """
        idx = self.index
        b = self._pending_bounds
        idx.swap_in_flight = s
        try:
            if b is None or b.ndim != 1 or b.shape[0] != idx.cfg.resolution + 1:
                raise RuntimeError(
                    f"resummarize refused: pending bounds must be a "
                    f"({idx.cfg.resolution + 1},) boundary array, got "
                    f"{None if b is None else b.shape}")
            if not bool((np.diff(b) > 0).all()):
                raise RuntimeError(
                    "resummarize refused: pending bounds are not strictly "
                    "increasing (tied or decreasing boundaries would make "
                    "bucketize and the remap disagree)")
            keys, valid = idx._slabs()
            st = shard_state(idx.state.shards, s)   # working copy (functional)
            st = hix.resummarize_shard(idx.cfg, st, keys[s], valid[s],
                                       jnp.asarray(b))
            idx.state = ShardedHippoState(
                shards=set_shard(idx.state.shards, s, st),
                summaries=idx.state.summaries.at[s].set(summary_of(st)))
        finally:
            idx.swap_in_flight = None
        idx.bounds_epochs[s] = self._resum_epoch
        self._dirty_since_checkpoint.add(s)
        models = getattr(idx, "summary_models", None)
        if models is not None:
            # shard s now serves the pending bounds: its model (None under
            # equal-mass or a fallback) swaps in at the same moment
            models[s] = self._pending_model
        self._pending_resummarize.remove(s)
        self.stats.resummarizes += 1
        if not self._pending_resummarize:
            # every shard serves the new bounds: measure drift against them
            self.drift.rearm(hg.Histogram(jnp.asarray(b)))
            self._pending_bounds = None
            self._pending_model = None
