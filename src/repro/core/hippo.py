"""High-level Hippo index API — the paper's CREATE INDEX / SELECT / INSERT /
DELETE / VACUUM surface (§7.1), wrapping the functional core.

    table = PagedTable.from_values(values, page_card=50)
    idx = HippoIndex.create(table, resolution=400, density=0.2)
    n = idx.count(Predicate.between(1000, 2000))
    idx.insert(1234.0)                  # eager (Algorithm 3)
    table.delete_where(500, 600)        # marks pages dirty
    idx.vacuum()                        # lazy re-summarize (§5.2)

The wrapper owns the host-side table handle plus the device ``HippoState`` and
keeps simple maintenance counters (entries touched, bytes written) used by the
maintenance benchmarks as the I/O-cost metric.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import jax.numpy as jnp

from repro.core import histogram as hg
from repro.core import index as hix
from repro.core.predicate import Predicate, intervals, to_bucket_bitmaps
from repro.storage.table import PagedTable


def sample_keys(table: PagedTable, sample_size: int = 65536) -> np.ndarray:
    """The CREATE INDEX build sample: live tuples, capped at ``sample_size``
    by a fixed-seed uniform draw. One definition of the sampling policy so
    every summary policy (equal-mass quantiles, learned CDF) fits the same
    sample."""
    if table.num_pages == 0:
        raise ValueError(
            "empty table: pass an explicit hist (the complete histogram "
            "is DBMS-maintained and cannot be sampled from zero tuples)")
    live = table.keys[: table.num_pages][table.valid[: table.num_pages]]
    if live.size > sample_size:
        rng = np.random.default_rng(0)
        live = rng.choice(live, size=sample_size, replace=False)
    return live


def sample_histogram(table: PagedTable, resolution: int,
                     sample_size: int = 65536) -> hg.Histogram:
    """The DBMS-maintained complete histogram, sampled from the table (§4.1).

    Shared by the unsharded and sharded CREATE INDEX paths so the sampling
    policy (live-tuple mask, fixed seed, cap) has one definition.
    """
    return hg.build(jnp.asarray(sample_keys(table, sample_size)), resolution)


@dataclass
class MaintenanceCounters:
    inserts: int = 0
    entries_touched: int = 0
    pages_opened: int = 0      # pages an insert summarized first
    entries_created: int = 0
    vacuums: int = 0
    entries_resummarized: int = 0


@dataclass
class HippoIndex:
    cfg: hix.HippoConfig
    state: hix.HippoState
    table: PagedTable
    counters: MaintenanceCounters = field(default_factory=MaintenanceCounters)

    # -- creation ------------------------------------------------------------

    @staticmethod
    def create(table: PagedTable, resolution: int = 400, density: float = 0.2,
               max_slots: int | None = None, sample_size: int = 65536,
               relocate_on_update: bool = True, hist: hg.Histogram | None = None,
               ) -> "HippoIndex":
        """CREATE INDEX ... USING hippo(attr). Builds the complete histogram
        from a table sample (the DBMS-maintained histogram, §4.1), then runs
        Algorithm 2."""
        if max_slots is None:
            # worst case one entry per page, plus an update budget
            max_slots = int(table.num_pages * 1.25) + 1024
        cfg = hix.HippoConfig(resolution=resolution, density=density,
                              page_card=table.page_card, max_slots=max_slots,
                              relocate_on_update=relocate_on_update)
        if hist is None:
            hist = sample_histogram(table, resolution, sample_size)
        state = hix.build(cfg, hist, table.device_keys(), table.device_valid())
        return HippoIndex(cfg=cfg, state=state, table=table)

    # -- query (Algorithm 1) ---------------------------------------------------

    def search_batch(self, preds: list[Predicate], *,
                     max_selected: int | None = None, top_k: int = 0
                     ) -> hix.CompactBatchResult:
        """Batched Algorithm 1: Q predicates in one device program
        (``core.index.search_compact_many``). ``max_selected`` is the slab
        width; ``None`` means ``gather_cap``, the program that never
        truncates. With ``top_k`` set, rows carry qualifying global row ids
        (``page_id * page_card + slot``, decode via
        ``PagedTable.row_values``). See ``runtime.engine.QueryEngine`` for
        the queued/slotted serving front over this path."""
        if max_selected is None:
            max_selected = self.gather_cap
        qbms = to_bucket_bitmaps(preds, self.state.histogram)
        los, his = intervals(preds)
        return hix.search_compact_many(
            self.state, qbms, self.table.device_keys(),
            self.table.device_valid(), los, his,
            max_selected=max_selected, top_k=top_k)

    def search(self, pred: Predicate) -> hix.CompactBatchResult:
        """Single-predicate convenience: a batch of one at the cap."""
        return self.search_batch([pred])

    def count(self, pred: Predicate) -> int:
        return int(self.search_batch([pred]).counts[0])

    @property
    def gather_cap(self) -> int:
        """Slab width at which the search can never truncate (the engine's
        fallback ``max_selected``)."""
        return max(self.table.num_pages, 1)

    def inspects_in_place(self, max_selected: int) -> bool:
        """Whether ``search_batch`` at this slab width inspects the
        table where it lies instead of gathering
        (``core.index.inspects_in_place``)."""
        return hix.inspects_in_place(max_selected, self.table.num_pages)

    # -- maintenance -----------------------------------------------------------

    def _require_slot_capacity(self, needed: int = 1) -> None:
        """Refuse maintenance that would overflow the physical slot array.

        The jit'd update paths cannot raise; an out-of-capacity scatter would
        silently drop writes and corrupt the sorted list. Checked here, before
        any table or index state changes.
        """
        if int(self.state.num_slots) + needed > self.cfg.max_slots:
            raise RuntimeError(
                f"index at slot capacity ({int(self.state.num_slots)}/"
                f"{self.cfg.max_slots}); rebuild with a larger max_slots")

    def insert(self, value: float) -> None:
        """Eager single-tuple insert: table append + Algorithm 3 update."""
        _, opens_page = self.table.next_page_id()
        if opens_page or self.cfg.relocate_on_update:
            # Only the new-entry and relocation paths consume a slot;
            # in-place bit updates never do.
            self._require_slot_capacity()
        page_id, _ = self.table.insert(value)
        before = int(self.state.num_entries)
        self.state = hix.insert_tuple(self.cfg, self.state, jnp.float32(value),
                                      jnp.int32(page_id))
        self.counters.inserts += 1
        self.counters.entries_touched += 1
        self.counters.pages_opened += int(opens_page)
        self.counters.entries_created += int(self.state.num_entries) - before

    def insert_batch(self, values: np.ndarray) -> None:
        """Batched insert at the table tail: one eager Algorithm 3 program
        for the whole batch (``core.index.insert_appended``). Atomic: a
        batch that could open more pages than there are free slots is
        refused before the table or the index changes."""
        values = np.asarray(values, np.float32).ravel()
        if values.size == 0:
            return
        pages = (self.table.tail + np.arange(values.size)) // \
            self.table.page_card
        opened = np.unique(pages[pages > int(self.state.summarized_until)]).size
        self._require_slot_capacity(opened)
        self.table.append(values)
        before = int(self.state.num_entries)
        self.state = hix.insert_appended(self.cfg, self.state,
                                         *hix.pad_rows(values, pages))
        self.counters.inserts += values.size
        self.counters.pages_opened += opened
        self.counters.entries_created += int(self.state.num_entries) - before

    def vacuum(self) -> int:
        """Lazy maintenance after deletes (§5.2): re-summarize entries whose
        ranges contain dirty pages. Returns entries re-summarized."""
        dirty_pages = np.flatnonzero(self.table.dirty[: self.table.num_pages])
        if dirty_pages.size == 0:
            return 0
        s = self.cfg.max_slots
        affected = np.zeros((s,), bool)
        affected[hix.owning_slots(self.state, dirty_pages)] = True
        self.state = hix.resummarize_slots(
            self.cfg, self.state, self.table.device_keys(),
            self.table.device_valid(), jnp.asarray(affected))
        self.table.clear_dirty(dirty_pages)
        n = int(affected.sum())
        self.counters.vacuums += 1
        self.counters.entries_resummarized += n
        return n

    # -- introspection ----------------------------------------------------------

    @property
    def num_entries(self) -> int:
        return int(self.state.num_entries)

    def nbytes(self, compressed: bool = False) -> int:
        return hix.index_nbytes(self.cfg, self.state, compressed=compressed)

    def entries_host(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(starts, ends, bitmaps) of live entries in logical order."""
        order = np.asarray(self.state.sorted_order)[: self.num_entries]
        return (np.asarray(self.state.starts)[order],
                np.asarray(self.state.ends)[order],
                np.asarray(self.state.bitmaps)[order])
