"""Batched multi-predicate query engine — the serving front for Hippo search.

Mirrors ``launch/serve.py``'s lock-step batch server: queries arrive as
``Predicate``s, get admitted into a fixed number of slots, execute together in
one device program (``core.index.search_many``), and finished queries free
their slot for the next queued request. The fixed slot count keeps every
``run_batch`` at one stable jit shape — (batch, W) bitmaps, (batch,) interval
bounds — so the trace is compiled once and recycled for the life of the engine.

    engine = QueryEngine(idx, batch=64)
    tickets = [engine.submit(p) for p in preds]
    engine.drain()
    counts = [t.count for t in tickets]

Free slots in a partially-filled batch are padded with the empty predicate
(lo > hi), which converts to an all-zero query bitmap and matches nothing —
the query analogue of a recycled decode slot idling on a pad token. Pads are
tracked separately (``EngineStats.pad_slots``) and never counted as served
work; ``EngineStats.occupancy`` is real queries over dispatched slots.

Execution modes (``mode``): the default ``compact`` mode runs the batch
through the gather path (``search_compact_batch``): the per-query page masks
are unioned, the union's pages gathered once into a shared slab of
``max_selected`` pages, and every query inspected against that slab — so
inspect cost tracks the batch's selectivity, not the table size. A slab
too wide for the gather to pay is not built: the index inspects the table
where it lies (``core.index.inspects_in_place``; counted in
``EngineStats.compact_in_place``), and such a bucket is dispatched at the
cap, so one program serves every width inspected in place. The mode
ladder keeps it exact and trace-stable:

  compact    run at the current slab bucket (a power of two, adapted from
             the batches seen so far, so traces are reused)
  widen      a batch whose union overflows the bucket raises the bucket to
             the next power of two (capped at the width that can never
             truncate) for subsequent batches
  fallback   queries whose own pages overflowed *this* batch's slab
             (per-query ``truncated`` flag) re-run at the never-truncating
             cap — dense-cost, still row-id-capable — so results are always
             bit-identical to dense mode, never silently short

Compact serving stats land in ``EngineStats``: ``compact_hits`` /
``compact_fallbacks``, ``compact_in_place``, ``gather_occupancy`` (union
pages over gathered slab capacity), and ``selected_page_ratio`` (union
pages over table pages — the fraction of the table the batch actually
touched). With ``top_k`` set, tickets additionally carry the first
``top_k`` qualifying global row ids (``row_ids``; decode via
``PagedTable.row_values``).

``mode="dense"`` is the previous full-table behavior: one (Q, P, C) program
(or, with ``sharded=True``, the summary-routed per-shard dispatch below).

Sharded routed dispatch (``mode="dense"`` + ``sharded=True`` on a
``core.partition.ShardedHippoIndex``): the admitted batch is
routed through the per-shard summary bitmaps — a (batch, S) joint-bucket
test — and each shard receives one dispatch carrying only the queries whose
summaries match it, padded to a small bucket width so every shard reuses the
same compiled traces. Shards no admitted query can match are skipped
entirely (partition pruning), and per-query counts are reduced across the
dispatched shards on the way out. Per-shard occupancy lands in
``EngineStats.shard_queries`` / ``shard_slots``. In compact mode a sharded
index instead runs the fused sharded gather (every shard gathers its own
slab of the batch union; counts reduce across the shard axis), and the
writer's staging overlay folds into counts on either path.

Shapes/dtypes on the dispatch boundary: predicates convert once per batch to
(Q, W) uint32 packed bucket bitmaps plus (Q,) float32 interval bounds; dense
mode runs one (Q=batch)-wide program, sharded mode runs per-shard programs at
bucketed widths, compact mode one (Q=batch, max_selected)-slab program.
Equivalence contract: for the same predicate stream, dense mode on
``HippoIndex``, dense mode on ``ShardedHippoIndex`` (fused (Q, S)
count-reduce), the summary-routed sharded dispatch, and compact mode on
either index all return bit-identical counts.

Writes (``runtime.writer.MaintenanceWriter``): ``write()``/``delete()``
stage maintenance instead of running Algorithm 3 on the query path; staged
rows are overlaid into counts so results never go stale, and the engine
drains shard queues between batches under one of three interleave policies:

  sync             no writer — write() runs Algorithm 3 immediately and
                   delete() vacuums immediately (the baseline the async
                   benchmark contrasts)
  between_batches  after each ``run_batch``, drain up to ``drain_units``
                   shard queues/vacuums (default for sharded indexes)
  on_depth         drain everything once the maintenance backlog — staged
                   tuples plus table pages dirtied by deletes and awaiting
                   vacuum — reaches ``drain_depth`` (checked by ``write()``
                   *and* ``delete()``: a delete-heavy stream adds no queue
                   depth but still accumulates vacuum work)
  manual           drain only on explicit ``flush()``

``write_many(values)`` takes a batch of rows in order: under ``sync`` the
index applies them as one eager Algorithm 3 program per shard touched
(``insert_batch``) before it returns; under a writer policy they are staged
in one routed append and the policy's trigger runs once. The call is the
profiler span ``hippo.write_many`` (arg ``rows``).

Queue depth, staged rows, and drain latency land in ``EngineStats``, with
the index's insert counters (pages opened, entries created) and the bytes
sent to the device slabs.

Drift re-summarization (``drift_threshold`` / ``auto_resummarize``): the
writer's drift telemetry (``core.histogram.DriftTracker``) watches the
staged insert stream; when the edge-bucket overflow ratio crosses
``drift_threshold`` (after ``drift_min_observed`` inserts), the engine
schedules a re-summarization — one remap drain unit per shard onto a
boundary set rebuilt from the drift reservoir — and the normal drain policy
applies it off the query path. ``auto_resummarize=False`` leaves scheduling
to explicit ``resummarize()`` calls. ``EngineStats`` reports
``resummarizes``, the live ``edge_overflow_ratio``, and the pruning-quality
window around the last re-summarization (``pruning_before_resummarize`` vs.
``pruning_after_resummarize`` — selected-page ratios of the compact batches
before and since).
"""
from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.partition import SUMMARY_POLICIES
from repro.core.predicate import Predicate
from repro.runtime.writer import MaintenanceWriter

_EMPTY = Predicate(lo=1.0, hi=0.0)   # lo > hi: matches nothing


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p

_SHARD_BUCKET_MIN = 8     # smallest per-shard dispatch width (trace bucketing)
_COMPACT_BUCKET_MIN = 64  # smallest gather-slab width (trace bucketing)
_FALLBACK_Q_MIN = 8       # smallest dense-fallback query width


@dataclass
class QueryTicket:
    """One submitted predicate and, once its batch ran, its results.

    ``row_ids`` is filled only by the compact mode with ``top_k`` set: the
    first ``top_k`` qualifying global row ids in ascending order (pads
    stripped; ``count`` tells the caller whether the list is a prefix).
    """
    qid: int
    pred: Predicate
    count: int | None = None
    pages_inspected: int | None = None
    entries_matched: int | None = None
    row_ids: np.ndarray | None = None
    done: bool = False


@dataclass
class EngineStats:
    submitted: int = 0
    served: int = 0
    batches: int = 0
    slots_filled: int = 0    # real query-slots dispatched (never _EMPTY pads)
    pad_slots: int = 0       # _EMPTY pads dispatched alongside them
    shard_dispatches: int = 0          # per-shard programs run (sharded mode)
    shards_pruned: int = 0             # shard dispatches skipped via summaries
    shard_queries: dict = field(default_factory=dict)  # shard -> real queries
    shard_slots: dict = field(default_factory=dict)    # shard -> slots incl. pads
    # -- compact mode (gather path) ------------------------------------------
    compact_batches: int = 0     # batches executed through the gather path
    compact_hits: int = 0        # queries served off the gathered slab
    compact_fallbacks: int = 0   # truncated queries re-run at the dense cap
    compact_in_place: int = 0    # dispatches inspected in place, fallbacks too
    gather_union_pages: int = 0  # batch-union pages gathered into slabs, cum.
    gather_slab_pages: int = 0   # gathered slab capacity dispatched, cum.
    selected_pages: int = 0      # batch-union pages selected (unclamped), cum.
    table_pages_seen: int = 0    # table pages visible per compact batch, cum.
    # -- async maintenance (runtime.writer) ----------------------------------
    writes: int = 0          # tuples written through the engine
    deletes: int = 0         # tuples deleted through the engine (incl. staged kills)
    drains: int = 0          # drain units applied (inserts + vacuums + resummarizes)
    drained_rows: int = 0    # staged rows applied to the index by drains
    drain_us: float = 0.0    # cumulative wall time spent inside writer drains
    queue_depth: int = 0     # staged tuples pending after the last engine op
    peak_queue_depth: int = 0
    staged_rows: int = 0     # live staged rows currently overlaid into counts
    insert_pages_opened: int = 0     # pages inserts summarized first
    insert_entries_created: int = 0  # index entries those pages created
    slab_upload_bytes: int = 0       # bytes sent to the table's device slabs
    # -- durable persistence (checkpointing + runtime.persister) -------------
    persists: int = 0          # durable commits (full snapshots + deltas)
    persist_pending: int = 0   # background commits queued or in flight
    persist_lag: int = 0       # journal records not yet covered by a commit
    # -- drift re-summarization ----------------------------------------------
    resummarizes: int = 0            # shard remap units drained
    edge_overflow_ratio: float = 0.0  # writer drift telemetry, live value
    learned_refits: int = 0          # resummarize schedules served by a learned fit
    learned_fallbacks: int = 0       # learned schedules that fell back to equal-mass
    # selected-page ratio of the compact batches before the last resummarize
    # was scheduled; the matching "after" window accumulates below
    pruning_before_resummarize: float = 0.0
    window_selected_pages: int = 0   # compact window since the last resummarize
    window_table_pages: int = 0

    @property
    def occupancy(self) -> float:
        """Fraction of *dispatched* slots that carried a real query.

        Dense mode dispatches the full batch width, so pads are the free
        batch slots; sharded mode dispatches per-shard bucketed widths, so
        pads are the bucket roundings (a query dispatched to several shards
        fills one slot in each)."""
        total = self.slots_filled + self.pad_slots
        return self.slots_filled / total if total else 0.0

    def shard_occupancy(self) -> dict[int, float]:
        """Per-shard occupancy of the sharded dispatch path."""
        return {s: self.shard_queries[s] / self.shard_slots[s]
                for s in sorted(self.shard_slots) if self.shard_slots[s]}

    @property
    def gather_occupancy(self) -> float:
        """Fraction of dispatched gather-slab capacity holding a selected
        page (compact mode). Low occupancy means the adaptive bucket is
        oversized for the workload; 1.0 means batches run at the edge of
        their bucket."""
        return (self.gather_union_pages / self.gather_slab_pages
                if self.gather_slab_pages else 0.0)

    @property
    def selected_page_ratio(self) -> float:
        """Batch-union pages over table pages across compact batches — the
        fraction of the table the batches selected (the dense path's
        denominator is always 1.0). Uses the unclamped union, so a
        truncating batch reports what it *selected*, not the slab-capped
        subset it managed to gather (that is ``gather_occupancy``'s job)."""
        return (self.selected_pages / self.table_pages_seen
                if self.table_pages_seen else 0.0)

    @property
    def pruning_after_resummarize(self) -> float:
        """Selected-page ratio of the compact batches since the last
        re-summarization was scheduled (the whole run, if none was) — the
        "after" half of the pruning-quality pair; lower is better pruning."""
        return (self.window_selected_pages / self.window_table_pages
                if self.window_table_pages else 0.0)


_DRAIN_POLICIES = ("sync", "between_batches", "on_depth", "manual")

_MODES = ("auto", "compact", "dense")


class QueryEngine:
    """Lock-step batched query executor with slot recycling.

    ``mode`` selects the execution path (see module docstring): ``compact``
    (the default via ``auto``) serves batches off the gathered
    union-of-selected-pages slab with adaptive power-of-two bucketing and a
    per-query dense fallback on truncation; ``dense`` is the full-table
    path. ``auto`` resolves to ``dense`` when ``sharded=True`` is requested
    explicitly (routed dispatch is a dense-mode feature) and to ``compact``
    otherwise.

    ``sharded`` selects the summary-routed per-shard dispatch of dense mode;
    under ``mode="dense"`` it defaults on whenever the index exposes the
    partition-layer routing surface (``plan_batch`` /
    ``search_batch_shard_arrays``). Compact mode on a sharded index runs the
    fused sharded gather instead.

    ``top_k`` (compact mode only) makes every ticket carry up to ``top_k``
    qualifying global row ids; ``compact_bucket`` seeds the adaptive slab
    bucket (rounded up to a power of two, adapted upward as batches reveal
    their union sizes).

    ``drain_policy`` selects the maintenance interleave (see module
    docstring); the default is ``between_batches`` when the index supports a
    writer and ``sync`` otherwise. ``drain_units`` bounds the shard
    queues/vacuums applied per batch under ``between_batches``;
    ``drain_depth`` is the ``on_depth`` trigger (staged tuples + dirty
    pages, checked on writes and deletes alike).

    ``drift_threshold`` / ``auto_resummarize`` / ``drift_min_observed``
    drive drift adaptation (writer-backed engines only): once at least
    ``drift_min_observed`` inserts have been staged since the last
    re-summarization and their edge-bucket overflow ratio reaches
    ``drift_threshold``, a re-summarization is scheduled automatically (one
    remap unit per shard, drained by the normal policy).
    ``drift_threshold=None`` or ``auto_resummarize=False`` disables the
    automatic trigger; ``resummarize()`` stays available either way.

    ``summary`` overrides the boundary policy every re-summarization this
    engine schedules uses (``core.partition.SUMMARY_POLICIES``:
    ``"equal_mass"`` quantiles or the ``"learned"`` piecewise-linear CDF
    fit, which falls back to equal-mass on degenerate samples); ``None``
    (default) defers to the index's own ``summary`` attribute, so an index
    created with ``summary="learned"`` keeps learned bounds across refits
    with no engine configuration. ``EngineStats.learned_refits`` /
    ``learned_fallbacks`` report which path the schedules actually took.
    """

    def __init__(self, index, batch: int = 64, sharded: bool | None = None,
                 drain_policy: str | None = None, drain_units: int = 1,
                 drain_depth: int = 256,
                 writer: MaintenanceWriter | None = None,
                 mode: str = "auto", top_k: int = 0,
                 compact_bucket: int | None = None,
                 drift_threshold: float | None = 0.25,
                 auto_resummarize: bool = True,
                 drift_min_observed: int = 256,
                 summary: str | None = None,
                 storage_dir=None, snapshot_on_drain: bool = True,
                 wal_sync: bool = True, snapshot_mode: str = "incremental",
                 background_save: bool = False, compact_every: int = 8,
                 compact_ratio: float = 0.5, snapshot_keep: int = 3,
                 persist_queue: int = 4):
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        self.index = index
        self.batch = batch
        if mode not in _MODES:
            raise ValueError(f"mode must be one of {_MODES}, got {mode!r}")
        if mode == "auto":
            mode = "dense" if sharded is True else "compact"
        if mode == "compact":
            if sharded is True:
                raise ValueError(
                    "sharded=True selects dense mode's routed dispatch; "
                    "compact mode runs the fused sharded gather — pass "
                    "mode='dense' for routing or drop sharded=True")
            if not hasattr(index, "search_compact_batch"):
                raise ValueError(
                    "mode='compact' needs an index with the gather surface "
                    "(search_compact_batch/gather_cap); got "
                    f"{type(index).__name__}")
            sharded = False
        else:
            if sharded is None:
                sharded = hasattr(index, "plan_batch")
            if sharded and not hasattr(index, "plan_batch"):
                raise ValueError("sharded=True needs a ShardedHippoIndex-style "
                                 "index (plan_batch/search_batch_shard_arrays)")
        self.mode = mode
        self.sharded = sharded
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if top_k and mode != "compact":
            raise ValueError("row-id payloads (top_k > 0) ride the gather "
                             "path; they need mode='compact'")
        self.top_k = top_k
        if compact_bucket is not None and compact_bucket < 1:
            raise ValueError(f"compact_bucket must be >= 1, got {compact_bucket}")
        self._compact_bucket = _pow2_at_least(compact_bucket
                                              or _COMPACT_BUCKET_MIN)
        supports_writer = hasattr(index, "plan_batch")
        if drain_policy is None:
            drain_policy = "between_batches" if supports_writer else "sync"
        if drain_policy not in _DRAIN_POLICIES:
            raise ValueError(f"drain_policy must be one of {_DRAIN_POLICIES}, "
                             f"got {drain_policy!r}")
        if drain_policy != "sync" and not supports_writer:
            raise ValueError(
                "async drain policies need a ShardedHippoIndex-style index "
                "(per-shard queues route by ShardSpec); use "
                "drain_policy='sync' for an unsharded index")
        self.drain_policy = drain_policy
        self.drain_units = drain_units
        self.drain_depth = drain_depth
        if writer is not None and writer.index is not index:
            raise ValueError("writer is bound to a different index than the "
                             "engine's — its staged rows and drains would "
                             "target the wrong index")
        if writer is None and drain_policy != "sync":
            writer = MaintenanceWriter(index)
        self.writer = writer
        if drift_threshold is not None and not 0.0 < drift_threshold <= 1.0:
            raise ValueError(f"drift_threshold must be in (0, 1] or None, "
                             f"got {drift_threshold}")
        self.drift_threshold = drift_threshold
        self.auto_resummarize = auto_resummarize
        self.drift_min_observed = drift_min_observed
        if summary is not None and summary not in SUMMARY_POLICIES:
            raise ValueError(f"summary must be one of {SUMMARY_POLICIES} or "
                             f"None (the index's policy), got {summary!r}")
        self.summary = summary
        self.slots: list[QueryTicket | None] = [None] * batch
        self.queue: deque[QueryTicket] = deque()
        self.stats = EngineStats()
        self._next_qid = 0
        self._auto_drain_suspended = False
        # -- durable storage (checkpointing.snapshot + checkpointing.wal) ----
        # With ``storage_dir`` set, every acknowledged write()/delete()/
        # resummarize journals before it stages (append before admission),
        # and each successful drain commits a snapshot then truncates the
        # journal — so QueryEngine.recover() restores the acknowledged state
        # after a crash at any instant. The directory must be fresh; an
        # existing snapshot/journal means a previous engine's durable state,
        # which recover() (not a new engine) must adopt.
        from pathlib import Path as _Path
        self.storage_dir = _Path(storage_dir) if storage_dir is not None \
            else None
        self.snapshot_on_drain = snapshot_on_drain
        self.journal = None
        if snapshot_mode not in ("full", "incremental"):
            raise ValueError(f"snapshot_mode must be 'full' or "
                             f"'incremental', got {snapshot_mode!r}")
        if compact_every < 1:
            raise ValueError(f"compact_every must be >= 1, got "
                             f"{compact_every}")
        if compact_ratio <= 0:
            raise ValueError(f"compact_ratio must be > 0, got "
                             f"{compact_ratio}")
        self.snapshot_mode = snapshot_mode
        self.background_save = background_save
        self.compact_every = compact_every
        self.compact_ratio = compact_ratio
        self.snapshot_keep = snapshot_keep
        self.persist_queue = persist_queue
        self._persister = None
        self._base_epoch = None        # epoch of the current full base
        self._delta_seq = 0            # committed deltas against it
        self._full_bytes = 0           # base snapshot payload size
        self._delta_bytes = 0          # cumulative chain payload size
        # the persister's commit callback (_commit_job, worker thread)
        # advances the durable watermark while the foreground reads it for
        # persist_lag; both sides go through this lock
        self._durable_lock = threading.Lock()
        self._durable_watermark = 0    # guarded-by: _durable_lock
        #                                (highest seqno covered by a commit)
        if self.storage_dir is not None:
            if self.writer is None:
                raise ValueError(
                    "storage_dir needs a writer-backed engine (an async "
                    "drain_policy on a ShardedHippoIndex); a writer-less "
                    "index persists directly via index.save()")
            from repro.checkpointing.snapshot import latest_epoch
            from repro.checkpointing.wal import Journal
            journal = Journal(self.storage_dir, index.spec.num_shards,
                              sync=wal_sync)
            if latest_epoch(self.storage_dir) is not None \
                    or journal.last_seqno > 0:
                raise ValueError(
                    f"storage_dir {self.storage_dir} already holds a "
                    f"snapshot or journal — use QueryEngine.recover() to "
                    f"adopt existing durable state")
            self.journal = journal
            if self.writer.journal is None:
                self.writer.journal = journal
            # initial durable base: recovery needs a committed snapshot to
            # replay the journal against, even before the first drain
            self.save()
            self._start_persister()

    # -- admission (mirrors BatchServer.admit) -------------------------------

    def submit(self, pred: Predicate) -> QueryTicket:
        """Enqueue a predicate; returns its ticket (filled in by run_batch).

        The queue is a deque and admission pops from its head while slot ids
        come off a free list, so a deep backlog admits in O(1) per query —
        a 100k-query burst no longer pays the O(n^2) of ``list.pop(0)``."""
        t = QueryTicket(qid=self._next_qid, pred=pred)
        self._next_qid += 1
        self.stats.submitted += 1
        self.queue.append(t)
        return t

    def _admit(self) -> None:
        if not self.queue:
            return
        # the free-slot list is rebuilt from the slots each round (O(batch),
        # paid once per batch, and immune to external slot resets — the
        # documented way to discard admitted work); each admission is then
        # one O(1) popleft, so a deep backlog admits in O(1) per query
        for i in (i for i, t in enumerate(self.slots) if t is None):
            if not self.queue:
                break
            self.slots[i] = self.queue.popleft()

    # -- writes (async maintenance surface) ----------------------------------

    def write(self, value: float) -> None:
        """Insert one tuple. Sync policy runs Algorithm 3 immediately; async
        policies stage the row into its shard's queue (a host list append)
        and let the interleave policy drain it off the query path. Counts
        include the staged row either way."""
        self.stats.writes += 1
        if self.writer is None:
            self.index.insert(float(value))
            self._sync_index_stats()
            return
        self.writer.write(float(value))
        self._maybe_schedule_resummarize()
        if (self.drain_policy == "on_depth"
                and self._maintenance_backlog() >= self.drain_depth):
            self._drain(None)
        self._sync_writer_stats()

    def write_many(self, values) -> None:
        """Insert rows, in order, as ``write`` row by row would. Sync policy
        applies them through ``index.insert_batch`` before returning; async
        policies stage them in one routed append to the shard queues, then
        run the policy's trigger once. Counts include the rows either
        way."""
        values = np.asarray(values, np.float32).ravel()
        with TraceAnnotation("hippo.write_many", rows=values.size):
            if self.writer is None:
                self.index.insert_batch(values)
                self.stats.writes += values.size
                self._sync_index_stats()
                return
            self.writer.write_many(values)
            self.stats.writes += values.size
            self._maybe_schedule_resummarize()
            if (self.drain_policy == "on_depth"
                    and self._maintenance_backlog() >= self.drain_depth):
                self._drain(None)
            self._sync_writer_stats()

    def delete(self, lo: float, hi: float) -> int:
        """Delete tuples with key in [lo, hi]. The validity-mask update is
        immediate on every policy (queries stay exact, §5.2 lazy deletes);
        sync policy then vacuums on the spot, async policies queue the dirty
        shards for drained ``vacuum_shard`` calls. Returns tuples deleted."""
        if self.writer is None:
            n = self.index.table.delete_where(lo, hi)
            if n:   # a no-op delete dirtied nothing: skip the vacuum dispatch
                self.index.vacuum()
            self.stats.deletes += n
            return n
        n = self.writer.delete(lo, hi)
        self.stats.deletes += n
        # deletes add vacuum work, not queue depth — the on_depth trigger
        # must fire here too or a delete-heavy stream never drains
        if (self.drain_policy == "on_depth"
                and self._maintenance_backlog() >= self.drain_depth):
            self._drain(None)
        self._sync_writer_stats()
        return n

    def flush(self) -> int:
        """Drain every pending resummarize, shard queue, and vacuum now
        (explicit policy). Returns staged rows applied to the index."""
        if self.writer is None:
            return 0
        rows = self._drain(None)
        return rows

    def resummarize(self, bounds=None) -> int:
        """Schedule a re-summarization of every shard (bounds rebuilt from
        the drift reservoir unless given) and drain it now, along with any
        other pending maintenance. Returns remap units applied."""
        if self.writer is None:
            raise RuntimeError(
                "resummarize needs a writer-backed engine (an async "
                "drain_policy on a ShardedHippoIndex)")
        before = self.writer.stats.resummarizes
        # may refuse (no sample): then stats stay intact
        self.writer.schedule_resummarize(bounds, policy=self.summary)
        self._mark_resummarize_window()
        self._drain(None)
        return self.writer.stats.resummarizes - before

    def _maintenance_backlog(self) -> int:
        """What the ``on_depth`` trigger measures: staged tuples plus table
        pages dirtied by deletes and still awaiting their vacuum. Both terms
        are O(1) reads (``PagedTable.num_dirty`` is kept incrementally) —
        this runs on every write under the on_depth policy."""
        return self.writer.queue_depth + self.index.table.num_dirty

    def _maybe_schedule_resummarize(self) -> None:
        """Auto drift trigger: schedule a remap of every shard once enough
        inserts have been observed and their edge-bucket overflow ratio
        crosses the threshold. Scheduling is idempotent while a remap is
        pending; the drain policy applies the units off the query path."""
        w = self.writer
        if (not self.auto_resummarize or self.drift_threshold is None
                or w is None or w.pending_resummarize_shards()):
            return
        d = w.drift
        if (d.observed >= self.drift_min_observed
                and d.edge_overflow_ratio >= self.drift_threshold):
            # observed > 0: the reservoir holds at least one value
            w.schedule_resummarize(policy=self.summary)
            self._mark_resummarize_window()

    def _mark_resummarize_window(self) -> None:
        """Close the pruning-quality window: the ratio accumulated so far
        becomes the "before" figure, and the window restarts to measure the
        batches served after the re-summarization."""
        st = self.stats
        st.pruning_before_resummarize = st.pruning_after_resummarize
        st.window_selected_pages = 0
        st.window_table_pages = 0

    def _drain(self, max_units: int | None) -> int:
        before = self.writer.stats.drains
        try:
            rows = self.writer.drain(max_units)
        finally:
            # even a refused drain applied some units: propagate the partial
            # progress instead of letting EngineStats claim nothing happened
            self._sync_writer_stats()
        self._auto_drain_suspended = False      # a successful drain re-arms
        if (self.storage_dir is not None and self.snapshot_on_drain
                and self.writer.stats.drains > before):
            # drain-swap commit point: persist what the drain changed (the
            # watermark is recorded before the commit and the journal only
            # truncated through it after, so a crash anywhere between
            # replays nothing twice and loses nothing acknowledged)
            self._commit_snapshot()
            self._sync_writer_stats()
        return rows

    # -- durable commits (incremental deltas, background persistence) --------

    def _commit_snapshot(self) -> None:
        """The per-drain durable commit: a delta of the shards this drain
        round changed, or a full snapshot when one is due — first commit,
        ``snapshot_mode='full'``, or the compaction policy firing (K deltas
        accumulated, or the chain outweighing ``compact_ratio`` of the
        base). Runs synchronously unless ``background_save`` handed commits
        to the persister thread."""
        wm = self.journal.last_seqno
        dirty = self.writer.dirty_checkpoint_shards()
        full_due = (self.snapshot_mode == "full"
                    or self._base_epoch is None
                    or self._delta_seq >= self.compact_every
                    or (self._full_bytes > 0 and self._delta_bytes
                        >= self.compact_ratio * self._full_bytes))
        if self._persister is not None:
            self._submit_background(full_due, dirty, wm)
            return
        if full_due:
            self.save()
            return
        path = self.index.save_delta(
            self.storage_dir, shards=dirty, wal_seqno=wm,
            base_epoch=self._base_epoch, delta_seq=self._delta_seq + 1)
        self._note_delta(path, self._delta_seq + 1)
        self.writer.clear_checkpoint_dirty()
        self._truncate_journal(wm)
        self.stats.persists += 1

    def _submit_background(self, full: bool, dirty, wm: int) -> None:
        """Collect sections foreground (the index is mutable again the
        moment this returns), hand the file I/O to the persister. The
        epoch/sequence is reserved here so jobs commit in submission order
        with no allocation race; the dirty set clears at submit — safe
        because a later job failure poisons the persister, and the only
        way out of poison is a synchronous full save that captures
        everything regardless."""
        from repro.checkpointing.snapshot import (collect_delta_sections,
                                                  collect_full_sections)
        from repro.runtime.persister import PersisterPoisoned
        try:
            if full:
                epoch = (self._base_epoch or 0) + 1
                sections = collect_full_sections(self.index, wm)
                self._persister.submit(
                    {"kind": "full", "sections": sections, "epoch": epoch,
                     "compact": self._delta_seq > 0, "watermark": wm})
                self._base_epoch = epoch
                self._delta_seq = 0
                self._full_bytes = sum(a.nbytes for a in sections.values())
                self._delta_bytes = 0
            else:
                seq = self._delta_seq + 1
                sections = collect_delta_sections(self.index, wm, dirty,
                                                  self._base_epoch, seq)
                self._persister.submit(
                    {"kind": "delta", "sections": sections,
                     "base_epoch": self._base_epoch, "seq": seq,
                     "watermark": wm})
                self._delta_seq = seq
                self._delta_bytes += sum(a.nbytes
                                         for a in sections.values())
            self.writer.clear_checkpoint_dirty()
            self.stats.persists += 1
        except PersisterPoisoned:
            # a background commit failed: supersede the broken chain with
            # a synchronous full snapshot (clears the poison) rather than
            # let acknowledged state ride on the WAL alone indefinitely
            self.save()

    def _commit_job(self, job: dict) -> None:  # thread: worker
        """The persister worker's half: durable file I/O, then — and only
        then — WAL truncation through the job's watermark. Truncating here
        (the commit callback) rather than at submit is what keeps a slow
        background save from widening the crash window: records appended
        while the job was in flight survive to the next commit.

        Runs on the ``BackgroundPersister`` thread. It reads only
        attributes fixed before ``_start_persister()`` spawned the worker
        (``storage_dir``/``journal``/``snapshot_keep``) plus the job dict,
        and publishes exactly one thing back: the durable watermark, under
        ``_durable_lock``."""
        from repro.checkpointing.snapshot import (write_delta_snapshot,
                                                  write_full_snapshot)
        if job["kind"] == "full":
            # hippolint: disable=locks -- storage_dir is rebound only by
            # _adopt_storage, which runs before _start_persister spawns
            # this worker; it is immutable for the persister's lifetime
            write_full_snapshot(self.storage_dir, job["sections"],
                                keep=self.snapshot_keep,
                                epoch=job["epoch"], compact=job["compact"])
        else:
            write_delta_snapshot(self.storage_dir, job["sections"],
                                 job["base_epoch"], job["seq"])
        from repro.runtime.faultinject import crashpoint
        crashpoint("truncate.pre")
        # hippolint: disable=locks -- journal is rebound only by
        # _adopt_storage before _start_persister spawns this worker; the
        # Journal object itself is internally locked (wal.py)
        self.journal.truncate_through(job["watermark"])
        with self._durable_lock:
            self._durable_watermark = job["watermark"]

    def _truncate_journal(self, wm: int) -> None:
        """Post-commit journal GC: a quiet journal (nothing appended past
        the watermark) resets outright; otherwise only records at or below
        the watermark are dropped."""
        from repro.runtime.faultinject import crashpoint
        crashpoint("truncate.pre")
        if self.journal.last_seqno == wm:
            self.journal.reset()
        else:
            self.journal.truncate_through(wm)
        with self._durable_lock:
            self._durable_watermark = wm

    def _note_full(self, path, epoch: int) -> None:
        self._base_epoch = epoch
        self._delta_seq = 0
        self._full_bytes = (path / "index.bin").stat().st_size
        self._delta_bytes = 0

    def _note_delta(self, path, seq: int) -> None:
        self._delta_seq = seq
        self._delta_bytes += (path / "index.bin").stat().st_size

    def _start_persister(self) -> None:
        if self.background_save and self.storage_dir is not None \
                and self._persister is None:
            from repro.runtime.persister import BackgroundPersister
            self._persister = BackgroundPersister(
                self._commit_job, max_queue=self.persist_queue)

    def save(self):
        """Synchronous *full* durable commit: snapshot the whole index
        (staged queues included), fold any delta chain into the new base,
        truncate the journal. Returns the committed snapshot directory.
        Requires ``storage_dir``. This is also the poison-recovery escape:
        after a failed background commit it supersedes the broken chain and
        re-enables background persistence."""
        if self.storage_dir is None:
            raise RuntimeError("save() needs storage_dir (durable mode); "
                               "writer-less indexes persist via index.save()")
        if self._persister is not None:
            # settle in-flight commits first; if one failed, this full
            # snapshot is about to supersede the whole chain anyway
            self._persister.flush(raise_on_poison=False)
        wm = self.journal.last_seqno
        epoch = (self._base_epoch or 0) + 1
        path = self.index.save(self.storage_dir, wal_seqno=wm,
                               keep=self.snapshot_keep, epoch=epoch,
                               compact=self._delta_seq > 0)
        self._note_full(path, epoch)
        self.writer.clear_checkpoint_dirty()
        if self._persister is not None:
            self._persister.clear_poison()
        self._truncate_journal(wm)
        self.stats.persists += 1
        return path

    def flush_durable(self) -> None:
        """Barrier: return once every submitted background commit is
        durably on disk (no-op without ``background_save``). Raises
        ``PersisterPoisoned`` if a background commit failed — call
        ``save()`` to supersede the broken chain."""
        if self._persister is not None:
            self._persister.flush()

    def close(self) -> None:
        """Stop the background persister (flush + join) and close the
        journal's file handles. Safe to call more than once; the engine
        remains queryable, but durable commits stop."""
        if self._persister is not None:
            try:
                self._persister.flush(raise_on_poison=False)
            finally:
                self._persister.close()
            self._persister = None
        if self.journal is not None:
            self.journal.close()

    @classmethod
    def recover(cls, storage_dir, *, wal_sync: bool = True,
                snapshot_on_recover: bool = True, **kwargs) -> "QueryEngine":
        """Rebuild an engine from a durable directory after a crash: load
        the latest committed snapshot plus its delta chain (uncommitted
        partials are ignored, a gapped chain is refused), replay the
        journal suffix through a fresh writer, and re-attach the journal so
        subsequent writes stay durable. ``snapshot_on_recover`` immediately
        collapses base + deltas + replayed journal into a fresh committed
        full base. Extra ``kwargs`` configure the engine as usual
        (``storage_dir`` comes from the first argument; ``background_save``
        et al. apply to the recovered engine too)."""
        if "storage_dir" in kwargs or "writer" in kwargs:
            raise ValueError("recover() derives storage_dir and writer from "
                             "the durable directory itself")
        from pathlib import Path as _Path
        from repro.checkpointing.snapshot import recover_index
        idx, writer, journal = recover_index(storage_dir, wal_sync=wal_sync)
        if writer is None:
            writer = MaintenanceWriter(idx)
            writer.journal = journal
        eng = cls(idx, writer=writer, **kwargs)
        eng._adopt_storage(_Path(storage_dir), journal)
        eng._sync_writer_stats()
        if snapshot_on_recover:
            eng.save()
        return eng

    def _adopt_storage(self, root, journal) -> None:
        """Attach existing durable state (the recover() path): pick up the
        on-disk base epoch, delta chain position, and byte counters so the
        compaction policy resumes where the crashed process left off."""
        from repro.checkpointing.snapshot import latest_delta_seq, latest_epoch
        self.storage_dir = root
        self.journal = journal
        if self.writer.journal is None:
            self.writer.journal = journal
        self._base_epoch = latest_epoch(root)
        self._delta_seq = (latest_delta_seq(root, self._base_epoch)
                           if self._base_epoch is not None else 0)
        if self._base_epoch is not None:
            self._full_bytes = (root / f"snap_{self._base_epoch}"
                                / "index.bin").stat().st_size
            self._delta_bytes = sum(
                (root / f"delta_{self._base_epoch}_{k}"
                 / "index.bin").stat().st_size
                for k in range(1, self._delta_seq + 1))
        # until the next commit records a watermark, persist_lag honestly
        # reports the whole surviving journal as not-yet-snapshotted
        with self._durable_lock:
            self._durable_watermark = 0
        self._start_persister()

    def _sync_index_stats(self) -> None:
        st = self.stats
        counters = self.index.counters
        st.insert_pages_opened = counters.pages_opened
        st.insert_entries_created = counters.entries_created
        st.slab_upload_bytes = self.index.table.slab_upload_bytes

    def _sync_writer_stats(self) -> None:
        self._sync_index_stats()
        w = self.writer
        st = self.stats
        if self.journal is not None:
            with self._durable_lock:
                wm = self._durable_watermark
            st.persist_lag = max(0, self.journal.last_seqno - wm)
        if self._persister is not None:
            st.persist_pending = self._persister.pending
        st.drains = w.stats.drains
        st.drained_rows = w.stats.drained_rows
        st.drain_us = w.stats.total_drain_us
        st.queue_depth = w.queue_depth
        st.staged_rows = w.staged_rows
        st.peak_queue_depth = max(st.peak_queue_depth, w.queue_depth)
        st.resummarizes = w.stats.resummarizes
        st.edge_overflow_ratio = w.drift.edge_overflow_ratio
        st.learned_refits = w.stats.learned_refits
        st.learned_fallbacks = w.stats.learned_fallbacks

    # -- execution ------------------------------------------------------------

    def run_batch(self) -> list[QueryTicket]:
        """Admit queued queries into free slots and execute one device program
        (or, in sharded mode, one summary-routed dispatch per matched shard).

        Returns the tickets retired by this batch (empty if nothing pending).
        The call is the profiler span ``hippo.run_batch`` (args ``batch``,
        the batch's number, and ``active``, its real queries); the drain,
        dispatch, readback and fallback spans nest inside it.
        """
        with TraceAnnotation("hippo.run_batch",
                             batch=self.stats.batches) as span:
            # Drain *before* executing: the drain sits between the previous
            # batch and this one either way, and a drain refusal (slot
            # capacity) then raises before any query work instead of
            # discarding a fully computed batch on the way out.
            self._maybe_drain_between_batches()
            self._admit()
            active = [i for i, t in enumerate(self.slots) if t is not None]
            span.set_metadata(active=len(active))
            if not active:
                return []
            row_ids = None
            if self.mode == "compact":
                counts, inspected, matched, row_ids = \
                    self._execute_compact(active)
            elif self.sharded:
                counts, inspected, matched = self._execute_sharded(active)
            else:
                counts, inspected, matched = self._execute_dense(active)
            finished = []
            for k, i in enumerate(active):
                t = self.slots[i]
                t.count = int(counts[k])
                t.pages_inspected = int(inspected[k])
                t.entries_matched = int(matched[k])
                if row_ids is not None:
                    ids = row_ids[k]
                    t.row_ids = ids[ids >= 0].copy()   # strip the -1 pads
                t.done = True
                finished.append(t)
                self.slots[i] = None          # recycle the slot
            self.stats.batches += 1
            if not self.sharded:
                # compact and dense modes dispatch the full batch width;
                # routed dispatch accounting happens per shard inside
                # _execute_sharded
                self.stats.slots_filled += len(active)
                self.stats.pad_slots += self.batch - len(active)
            self.stats.served += len(finished)
            self._sync_index_stats()     # a search may upload the slabs
            return finished

    def _maybe_drain_between_batches(self) -> None:
        """Between-batches drain. A drain refusal (e.g. shard slot capacity)
        raises once, loudly, then suspends auto-draining so queries keep
        serving exactly through the staging overlay; an explicit ``flush()``
        (after fixing capacity) or ``writer.discard()`` re-arms it."""
        if (self.writer is None or self.drain_policy != "between_batches"
                or self._auto_drain_suspended
                or not self.writer.pending_units):
            return
        try:
            with TraceAnnotation("hippo.drain",
                                 units=self.writer.pending_units):
                self._drain(self.drain_units)
        except RuntimeError:
            self._auto_drain_suspended = True
            raise

    def _execute_dense(self, active: list[int]) -> tuple:
        """One full-width device program; pads fill the free slots."""
        preds = [t.pred if t is not None else _EMPTY for t in self.slots]
        res = self.index.search_batch(preds)
        with TraceAnnotation("hippo.readback"):
            counts = np.asarray(res.counts)[active]
            inspected = np.asarray(res.pages_inspected)[active]
            matched = np.asarray(res.entries_matched)[active]
        return counts, inspected, matched

    def _execute_compact(self, active: list[int]) -> tuple:
        """The compact mode ladder: gather-path batch at the current slab
        bucket, widen the bucket for future batches when the union overflows
        it, and re-run this batch's truncated queries at the never-truncating
        cap (dense cost, still exact and row-id-capable).

        ``pages_inspected``/``entries_matched`` come from the first run even
        for truncated rows (they are computed before the gather and exact
        regardless); only counts and row ids are patched from the fallback.
        Each dispatch's result comes to the host in one ``hippo.readback``
        span; the re-run is the span ``hippo.fallback`` (arg ``width``).
        """
        preds = [t.pred if t is not None else _EMPTY for t in self.slots]
        cap = self.index.gather_cap
        bucket = min(self._compact_bucket, cap)   # never gather past the slab
        if self.index.inspects_in_place(bucket):
            bucket = cap        # in place, every width runs the cap's program
        res = self.index.search_compact_batch(preds, max_selected=bucket,
                                              top_k=self.top_k)
        with TraceAnnotation("hippo.readback"):
            res = jax.device_get(res)
        counts = res.counts.copy()
        inspected = res.pages_inspected
        matched = res.entries_matched
        trunc = res.truncated
        row_ids = res.row_ids.copy() if self.top_k else None
        st = self.stats
        st.compact_batches += 1
        self._account_compact_dispatch(res, bucket)
        needed = int(res.bucket_needed)
        if needed > bucket:
            # adapt: the next batch starts at a slab the last union fits
            self._compact_bucket = min(_pow2_at_least(needed), cap)
        bad = [i for i in active if trunc[i]]
        if bad:
            st.compact_fallbacks += len(bad)
            width = _pow2_at_least(max(len(bad), _FALLBACK_Q_MIN))
            fb_preds = [self.slots[i].pred for i in bad]
            fb_preds += [_EMPTY] * (width - len(bad))
            with TraceAnnotation("hippo.fallback", width=width):
                fb = self.index.search_compact_batch(
                    fb_preds, max_selected=cap, top_k=self.top_k)
                with TraceAnnotation("hippo.readback"):
                    fb = jax.device_get(fb)
            # the fallback is a real extra dispatch: its slot width and its
            # slab capacity must land in occupancy/gather accounting, or the
            # stats overreport exactly when the engine is doing extra work
            st.slots_filled += len(bad)
            st.pad_slots += width - len(bad)
            self._account_compact_dispatch(fb, cap)
            if bool(fb.truncated[: len(bad)].any()):
                raise RuntimeError(
                    "compact fallback truncated at the full gather cap — "
                    "the slab no longer covers the table (was the index "
                    "mutated mid-batch?)")
            for k, i in enumerate(bad):
                counts[i] = fb.counts[k]
                if row_ids is not None:
                    row_ids[i] = fb.row_ids[k]
        st.compact_hits += len(active) - len(bad)
        return (counts[active], inspected[active], matched[active],
                row_ids[active] if row_ids is not None else None)

    def _account_compact_dispatch(self, res, max_selected: int) -> None:
        """Fold one compact dispatch (primary batch or truncation fallback)
        at slab width ``max_selected`` into the gather telemetry and the
        pruning-quality window. A dispatch inspected in place gathers no
        slab, so it adds no slab capacity."""
        st = self.stats
        if self.index.inspects_in_place(max_selected):
            st.compact_in_place += 1
        else:
            shards = getattr(self.index, "num_shards", 1)
            st.gather_slab_pages += max_selected * shards
        st.gather_union_pages += int(res.pages_gathered)
        st.selected_pages += int(res.pages_selected)
        st.table_pages_seen += self.index.table.num_pages
        st.window_selected_pages += int(res.pages_selected)
        st.window_table_pages += self.index.table.num_pages

    def _execute_sharded(self, active: list[int]) -> tuple:
        """Per-shard dispatch with summary pruning and count-reduce.

        Each shard runs a program over only the active queries whose bucket
        bitmaps share a joint bucket with its summary — padded up to a bucket
        width so all shards share compiled traces — and per-query results sum
        across shards (shards partition the page space, so the reduction is
        exact; a pruned (query, shard) pair is provably count-zero). The
        predicates are converted to bucket bitmaps once per shard bounds
        epoch (``plan_batch`` returns (S, Q, W)); per-shard dispatches slice
        and pad shard s's converted rows, with zero bitmaps + (lo=1, hi=0)
        intervals as the pads.
        """
        preds = [self.slots[i].pred for i in active]
        qbms, los, his, match = self.index.plan_batch(preds)
        a = len(active)
        counts = np.zeros((a,), np.int64)
        inspected = np.zeros((a,), np.int64)
        matched = np.zeros((a,), np.int64)
        for s in range(self.index.num_shards):
            hit = np.flatnonzero(match[:, s])
            if hit.size == 0:
                self.stats.shards_pruned += 1
                continue
            width = _pow2_at_least(max(int(hit.size), _SHARD_BUCKET_MIN))
            qb = np.zeros((width, qbms.shape[2]), qbms.dtype)
            qb[: hit.size] = qbms[s, hit]       # shard s's epoch conversion
            lo = np.full((width,), _EMPTY.lo, np.float32)
            hi = np.full((width,), _EMPTY.hi, np.float32)
            lo[: hit.size] = los[hit]
            hi[: hit.size] = his[hit]
            res = self.index.search_batch_shard_arrays(s, qb, lo, hi)
            with TraceAnnotation("hippo.readback"):
                counts[hit] += np.asarray(res.counts)[: hit.size]
                inspected[hit] += np.asarray(res.pages_inspected)[: hit.size]
                matched[hit] += np.asarray(res.entries_matched)[: hit.size]
            self.stats.shard_dispatches += 1
            self.stats.slots_filled += int(hit.size)
            self.stats.pad_slots += width - int(hit.size)
            self.stats.shard_queries[s] = (
                self.stats.shard_queries.get(s, 0) + int(hit.size))
            self.stats.shard_slots[s] = (
                self.stats.shard_slots.get(s, 0) + width)
        # Staging overlay: rows waiting in a writer's queues belong to no
        # index entry yet, so summary routing can't see them — their counts
        # add on top, independent of which shards were dispatched or pruned.
        # Read the overlay from the index's *attached* writer (the single
        # source of truth), not this engine's handle: a sync-policy engine,
        # or one whose writer was superseded, must still see staged rows.
        staging = getattr(self.index, "staging", None)
        if staging is not None and staging.staged_rows:
            counts += staging.staged_counts(los, his).sum(axis=1)
        return counts, inspected, matched

    def drain(self) -> list[QueryTicket]:
        """Run batches until the queue and all slots are empty."""
        finished = []
        while self.queue or any(t is not None for t in self.slots):
            finished.extend(self.run_batch())
        return finished

    def run_all(self, preds: list[Predicate]) -> np.ndarray:
        """Submit + drain convenience; counts in submission order."""
        tickets = [self.submit(p) for p in preds]
        self.drain()
        return np.asarray([t.count for t in tickets], np.int64)
