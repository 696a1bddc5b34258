"""PagedTable — the heap-file analogue backing a Hippo index.

PostgreSQL stores tuples in fixed-size heap pages; Hippo summarizes *pages*.
Here a page is a fixed-width row block of ``page_card`` tuples. The key
attribute is a float32 column of shape (num_pages, page_card); additional
payload columns ride along untouched. Mutations (insert/delete) are host-side
numpy — the buffer-manager role — while queries operate on jnp device views.

Deletions mark a validity bit and a per-page ``dirty`` flag, which is exactly
the "note in the page header" PostgreSQL leaves for VACUUM (§5.2 / §7.1);
``HippoIndex.vacuum`` consumes the dirty flags.

Sharded views: ``device_keys_sharded``/``device_valid_sharded`` reshape the
page space into S contiguous slabs of ``pages_per_shard`` pages each — the
storage-layout half of the partition layer (``core.partition``). Each shard
owns the page range [s*PPS, (s+1)*PPS); slab pages past ``num_pages`` are
zero-key/invalid padding, so per-shard programs are shape-stable while the
table grows into its slabs. An append patches the cached slabs in place at
the pages it touched (``hippo.slab_append``); the whole view is uploaded
again only for a new layout or after a delete.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

# Pages per slab patch: an append's page range goes up in blocks of this
# many pages (the last block overlapping the one before), and a shorter
# range in one block of the next power of two, so appends of any length
# reuse a handful of compiled patch programs.
_PATCH_PAGES = 64


@partial(jax.jit, donate_argnums=(0, 1))
def _patch_slab(keys, valid, s, page, block_keys, block_valid):
    """Write a (k, C) block of pages into shard ``s``'s slab at ``page``, in
    place: the slabs are donated."""
    with jax.named_scope("hippo.slab_append"):
        at = (s, page, jnp.int32(0))
        return (jax.lax.dynamic_update_slice(keys, block_keys[None], at),
                jax.lax.dynamic_update_slice(valid, block_valid[None], at))


def _patch_blocks(lo: int, hi: int, limit: int) -> list[tuple[int, int]]:
    """(first page, pages) blocks that cover pages [lo, hi) and lie inside
    [0, limit): ``_PATCH_PAGES``-page blocks, the last one moved back to end
    at ``hi``; a range shorter than that is one block of the next power of
    two, moved back where it would pass ``limit``."""
    n = hi - lo
    if n <= 0:
        return []
    if n < _PATCH_PAGES:
        size = min(1 << (n - 1).bit_length(), limit)
        return [(min(lo, limit - size), size)]
    size = min(_PATCH_PAGES, limit)
    starts = list(range(lo, hi - size, size)) + [hi - size]
    return [(a, size) for a in starts]


@dataclass
class PagedTable:
    page_card: int
    capacity_pages: int
    keys: np.ndarray = field(default=None)      # (capacity_pages, page_card) f32
    valid: np.ndarray = field(default=None)     # (capacity_pages, page_card) bool
    dirty: np.ndarray = field(default=None)     # (capacity_pages,) bool — VACUUM notes
    num_pages: int = 0                          # pages in use (last may be partial)
    fill: int = 0                               # tuples in the last page
    num_dirty: int = 0                          # pages with a pending VACUUM note
    #                                             (kept incrementally: the engine's
    #                                             on_depth backlog reads it per write)
    payload: dict = field(default_factory=dict)  # name -> (capacity, page_card) array
    # bytes sent to the sharded device slabs: whole views, shard slabs and
    # the pages an append patched
    slab_upload_bytes: int = field(default=0, compare=False)
    _dev: tuple | None = field(default=None, repr=False, compare=False)  # device-view cache
    # ((S, PPS), keys, valid): the slab-view cache. Appends patch it in
    # place; deletes mark it stale, so a shard-local writer can patch just
    # the touched slabs back in (``refresh_shard_slabs``) instead of
    # re-uploading every shard.
    _dev_shard: tuple | None = field(default=None, repr=False, compare=False)
    _dev_shard_stale: bool = field(default=False, repr=False, compare=False)

    def __post_init__(self):
        if self.keys is None:
            self.keys = np.zeros((self.capacity_pages, self.page_card), np.float32)
        if self.valid is None:
            self.valid = np.zeros((self.capacity_pages, self.page_card), bool)
        if self.dirty is None:
            self.dirty = np.zeros((self.capacity_pages,), bool)
        # appends write through flat views of the row-major page arrays
        self.keys = np.ascontiguousarray(self.keys)
        self.valid = np.ascontiguousarray(self.valid)

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_values(values: np.ndarray, page_card: int, spare_pages: int = 0,
                    payload: dict | None = None) -> "PagedTable":
        values = np.asarray(values, np.float32).ravel()
        n = values.size
        num_pages = (n + page_card - 1) // page_card
        cap = num_pages + spare_pages
        t = PagedTable(page_card=page_card, capacity_pages=cap)
        flat = t.keys.reshape(-1)
        flat[:n] = values
        vflat = t.valid.reshape(-1)
        vflat[:n] = True
        t.num_pages = num_pages
        t.fill = n - (num_pages - 1) * page_card if n else 0
        for name, col in (payload or {}).items():
            buf = np.zeros((cap, page_card), np.asarray(col).dtype)
            buf.reshape(-1)[:n] = np.asarray(col).ravel()
            t.payload[name] = buf
        return t

    # -- properties ----------------------------------------------------------

    @property
    def cardinality(self) -> int:
        return int(self.valid[: self.num_pages].sum())

    def heap_nbytes(self) -> int:
        """Bytes of live table storage (key column only, paper's table size)."""
        return self.num_pages * self.page_card * 4

    @property
    def tail(self) -> int:
        """Tuple position (``page * page_card + slot``) the next append
        takes: the row id it will have."""
        if self.num_pages == 0:
            return 0
        return (self.num_pages - 1) * self.page_card + self.fill

    # -- row-id decoding (compact-path result payloads) ----------------------

    def row_values(self, row_ids: np.ndarray, payload: str | None = None
                   ) -> np.ndarray:
        """Fetch key (or payload-column) values for global row ids.

        A global row id is ``page_id * page_card + slot`` — the coordinate
        the gather path's ``row_ids`` results use
        (``core.index.search_compact_many``). Negative ids (the -1 pads of a
        ``top_k`` result) are skipped, so a padded id row can be passed
        straight through. Raises on ids past the table's tuple capacity.
        """
        ids = np.asarray(row_ids).ravel()
        ids = ids[ids >= 0]
        if ids.size and int(ids.max()) >= self.num_pages * self.page_card:
            raise IndexError(
                f"row id {int(ids.max())} past the table's "
                f"{self.num_pages * self.page_card} tuple slots")
        col = self.keys if payload is None else self.payload[payload]
        return col.reshape(-1)[ids]

    # -- device views --------------------------------------------------------

    def _device_views(self, n: int) -> tuple:
        """(keys, valid) device arrays for the first ``n`` pages, cached until
        the next host-side mutation — a query-heavy loop (the batched engine)
        pays one H2D transfer per mutation, not per batch."""
        if self._dev is None or self._dev[0] != n:
            self._dev = (n, jnp.asarray(self.keys[:n]), jnp.asarray(self.valid[:n]))
        return self._dev

    def device_keys(self, num_pages: int | None = None) -> jnp.ndarray:
        n = self.num_pages if num_pages is None else num_pages
        return self._device_views(n)[1]

    def device_valid(self, num_pages: int | None = None) -> jnp.ndarray:
        n = self.num_pages if num_pages is None else num_pages
        return self._device_views(n)[2]

    # -- sharded device views (core.partition slab layout) -------------------

    def _shard_views(self, num_shards: int, pages_per_shard: int) -> tuple:
        """(keys, valid) slabs of shape (S, PPS, page_card), cached per
        layout. Slab pages beyond ``num_pages`` are invalid padding;
        per-shard entries never cover them, so they cost inspection FLOPs
        only inside their shard's fixed-shape program. Appends inside the
        layout patch the cache (``append``); a new layout or a stale cache
        uploads the whole view."""
        key = (num_shards, pages_per_shard)
        if (self._dev_shard is None or self._dev_shard_stale
                or self._dev_shard[0] != key):
            total = num_shards * pages_per_shard
            if total < self.num_pages:
                raise ValueError(
                    f"slab layout {num_shards}x{pages_per_shard} covers {total} "
                    f"pages < table's {self.num_pages}")
            keys = np.zeros((total, self.page_card), np.float32)
            valid = np.zeros((total, self.page_card), bool)
            keys[: self.num_pages] = self.keys[: self.num_pages]
            valid[: self.num_pages] = self.valid[: self.num_pages]
            shape = (num_shards, pages_per_shard, self.page_card)
            self._dev_shard = None          # let the old view go first
            self._dev_shard = (key, jnp.asarray(keys.reshape(shape)),
                               jnp.asarray(valid.reshape(shape)))
            self._dev_shard_stale = False
            self.slab_upload_bytes += keys.nbytes + valid.nbytes
        return self._dev_shard

    def _host_pages(self, lo: int, n: int) -> tuple:
        """(keys, valid) host copies of global pages [lo, lo + n), zero/
        invalid past the table's capacity."""
        keys = np.zeros((n, self.page_card), np.float32)
        valid = np.zeros((n, self.page_card), bool)
        hi = min(lo + n, self.capacity_pages)
        if hi > lo:
            keys[: hi - lo] = self.keys[lo:hi]
            valid[: hi - lo] = self.valid[lo:hi]
        return keys, valid

    def _patch_pages(self, lo: int, hi: int) -> None:
        """Upload global pages [lo, hi) from host into the cached slabs, in
        place (``_patch_slab``), block by block within each shard."""
        layout, keys_dev, valid_dev = self._dev_shard
        pps = layout[1]
        self._dev_shard = None        # the slabs are donated below
        for s in range(lo // pps, (hi - 1) // pps + 1):
            base = s * pps
            for a, n in _patch_blocks(max(lo, base) - base,
                                      min(hi, base + pps) - base, pps):
                hk, hv = self._host_pages(base + a, n)
                keys_dev, valid_dev = _patch_slab(
                    keys_dev, valid_dev, np.int32(s), np.int32(a), hk, hv)
                self.slab_upload_bytes += hk.nbytes + hv.nbytes
        self._dev_shard = (layout, keys_dev, valid_dev)

    def _slabs_follow(self, lo: int, hi: int) -> None:
        """Bring a fresh slab cache up to date after pages [lo, hi) changed
        on the host: patch them in place, or drop the cache when they fall
        outside its layout. A stale cache is rebuilt whole on its next
        use anyway."""
        self._dev = None
        if self._dev_shard is None or self._dev_shard_stale or hi <= lo:
            return
        shards, pps = self._dev_shard[0]
        if hi > shards * pps:
            self._dev_shard = None
            return
        self._patch_pages(lo, hi)

    def refresh_shard_slabs(self, shard_ids, num_shards: int,
                            pages_per_shard: int) -> bool:
        """Patch a stale slab cache in place after shard-local mutations.

        Contract: every mutation since the cache went stale must be confined
        to the slabs in ``shard_ids`` (``delete_where`` callers pass the
        owners of the pages they dirtied). Each touched slab is re-uploaded
        with one (PPS, C) H2D instead of rebuilding the whole (S, PPS, C)
        view. Returns True if the cache was patched; False if there was no
        compatible cache (the next ``device_*_sharded`` call rebuilds fully —
        always correct).
        """
        if self._dev_shard is None:
            return False
        if self._dev_shard[0] != (num_shards, pages_per_shard):
            return False
        if num_shards * pages_per_shard < self.num_pages:
            return False                     # table outgrew the layout
        (_, pps), keys_dev, valid_dev = self._dev_shard
        self._dev_shard = None        # the slabs are donated below
        for s in sorted(set(int(s) for s in shard_ids)):
            hk, hv = self._host_pages(s * pps, pps)
            keys_dev, valid_dev = _patch_slab(
                keys_dev, valid_dev, np.int32(s), np.int32(0), hk, hv)
            self.slab_upload_bytes += hk.nbytes + hv.nbytes
        self._dev_shard = ((num_shards, pages_per_shard), keys_dev, valid_dev)
        self._dev_shard_stale = False
        return True

    def device_keys_sharded(self, num_shards: int, pages_per_shard: int) -> jnp.ndarray:
        return self._shard_views(num_shards, pages_per_shard)[1]

    def device_valid_sharded(self, num_shards: int, pages_per_shard: int) -> jnp.ndarray:
        return self._shard_views(num_shards, pages_per_shard)[2]

    # -- mutations (host side = buffer manager) ------------------------------

    def next_page_id(self) -> tuple[int, bool]:
        """(page the next append lands on, whether it opens a new page).

        The single statement of the append policy — index layers that must
        route or capacity-check *before* mutating (``HippoIndex.insert``,
        shard routing in ``core.partition``) predict through this instead of
        re-deriving the fill rule."""
        new_page = self.fill == self.page_card or self.num_pages == 0
        return (self.num_pages if new_page else self.num_pages - 1), new_page

    def append(self, values: np.ndarray, valid: np.ndarray | None = None
               ) -> tuple[int, int]:
        """Append tuples at the heap tail, in order; returns the first and
        last page they touched (an empty range for no values).

        Each value's page and slot follow from the tail by arithmetic: the
        last partially-filled page fills first, then new pages open — the
        heap-file append Algorithm 3 assumes. ``valid`` (default all True)
        lets a writer place rows that never go live. A fresh slab cache is
        patched in place at the touched pages."""
        values = np.asarray(values, np.float32).ravel()
        if values.size == 0:
            return self.num_pages, self.num_pages - 1
        lo = self.tail
        hi = lo + values.size
        pages = -(-hi // self.page_card)
        while pages > self.capacity_pages:
            self._grow()
        self.keys.reshape(-1)[lo:hi] = values
        self.valid.reshape(-1)[lo:hi] = True if valid is None else valid
        self.num_pages = pages
        self.fill = hi - (pages - 1) * self.page_card
        first = lo // self.page_card
        self._slabs_follow(first, pages)
        return first, pages - 1

    def insert(self, value: float) -> tuple[int, bool]:
        """Append one tuple; returns (page_id, is_new_page)."""
        _, new_page = self.next_page_id()
        _, page = self.append(np.asarray([value], np.float32))
        return page, new_page

    def delete_where(self, lo: float, hi: float) -> int:
        """Mark tuples with key in [lo, hi] deleted; set page dirty notes."""
        live = self.valid[: self.num_pages]
        hit = live & (self.keys[: self.num_pages] >= lo) & (self.keys[: self.num_pages] <= hi)
        if not hit.any():
            return 0                      # nothing changed: keep device caches
        npages = hit.any(axis=1)
        self.num_dirty += int((npages & ~self.dirty[: self.num_pages]).sum())
        self.valid[: self.num_pages] &= ~hit
        self.dirty[: self.num_pages] |= npages
        self._dev = None
        self._dev_shard_stale = True
        return int(hit.sum())

    def clear_dirty(self, page_ids: np.ndarray) -> None:
        # dedup: repeated ids must not decrement num_dirty twice
        ids = np.unique(np.asarray(page_ids, np.int64))
        self.num_dirty -= int(self.dirty[ids].sum())
        self.dirty[ids] = False

    def truncate_to(self, num_pages: int, fill: int) -> None:
        """Drop tuples appended past a (num_pages, fill) snapshot.

        Rollback primitive for atomic batch inserts: appends only ever write
        forward of the snapshot position, so clearing that region restores
        the pre-batch table exactly.
        """
        dropped = self.num_pages
        self.valid[num_pages:] = False
        self.keys[num_pages:] = 0.0
        self.num_dirty -= int(self.dirty[num_pages:].sum())
        self.dirty[num_pages:] = False
        if num_pages:
            self.valid[num_pages - 1, fill:] = False
            self.keys[num_pages - 1, fill:] = 0.0
        self.num_pages = num_pages
        self.fill = fill
        self._slabs_follow(max(num_pages - 1, 0), dropped)

    def _grow(self) -> None:
        add = max(self.capacity_pages // 2, 64)
        self.keys = np.concatenate([self.keys, np.zeros((add, self.page_card), np.float32)])
        self.valid = np.concatenate([self.valid, np.zeros((add, self.page_card), bool)])
        self.dirty = np.concatenate([self.dirty, np.zeros((add,), bool)])
        for name, buf in self.payload.items():
            self.payload[name] = np.concatenate([buf, np.zeros((add, self.page_card), buf.dtype)])
        self.capacity_pages += add
