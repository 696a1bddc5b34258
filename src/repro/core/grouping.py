"""Density-driven page grouping (§4.3, Algorithm 2).

The build scans pages in storage order, OR-ing each page's bucket bitmap into
a *working partial histogram*; when the working histogram's density exceeds
the user threshold D, the current entry is cut (the triggering page is the
entry's last page) and a fresh working histogram starts at the next page.

``group_pages`` is the jit-compiled device scan; it emits one boolean cut-flag
per page. ``page_bucket_bits`` produces per-page bucket membership (the unpacked
partial histogram of a single page). Entry extraction from flags is a cheap
host step (``finalize_entries``) since it only runs at build time.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core.histogram import Histogram, bucketize


@partial(jax.jit, static_argnames=("resolution",))
def page_bucket_bits(hist: Histogram, keys: jnp.ndarray, valid: jnp.ndarray,
                     resolution: int) -> jnp.ndarray:
    """Per-page bucket membership: (num_pages, H) bool.

    keys/valid: (num_pages, page_card). Invalid tuples hit no bucket.
    Every tuple's bucket id (the paper's per-tuple binary search, §4.2) is
    compared against every bucket and OR-reduced over the page's slots — a
    fused compare-and-reduce rather than a scatter of all N tuples, which
    the v5e compiler took ~24 s to build at SF 10 shard sizes (this form:
    under 2 s).
    """
    num_pages, page_card = keys.shape
    ids = bucketize(hist, keys.reshape(-1)).reshape(num_pages, page_card)
    ids = jnp.where(valid, ids, -1)                             # hits no bucket
    buckets = jnp.arange(resolution, dtype=jnp.int32)
    return (ids[:, :, None] == buckets[None, None, :]).any(axis=1)


@partial(jax.jit, static_argnames=("resolution", "density_threshold"))
def group_pages(page_bits: jnp.ndarray, resolution: int,
                density_threshold: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Algorithm 2 grouping scan.

    page_bits: (num_pages, H) bool per-page bucket membership.
    Returns (cut_flags (num_pages,) bool, merged_bits (num_pages, H) bool) where
    merged_bits[p] is the working histogram *after* absorbing page p — the
    entry bitmap whenever cut_flags[p] is set.
    """
    h = resolution

    def step(acc, pb):
        merged = acc | pb
        dens = merged.sum() / h
        cut = dens > density_threshold
        nxt = jnp.where(cut, jnp.zeros_like(merged), merged)
        return nxt, (cut, merged)

    init = jnp.zeros((h,), dtype=bool)
    _, (flags, merged) = jax.lax.scan(step, init, page_bits)
    # Trailing partial entry: the last page always closes an entry (§4,
    # "store the partial histogram ... as an index entry" for the remainder).
    flags = flags.at[-1].set(True)
    return flags, merged


def finalize_entries(flags: np.ndarray, merged_bits: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Extract (starts, ends, entry_bitmaps_packed) from the grouping scan.

    Host-side (build-time only). ``ends`` are the cut pages; ``starts`` follow
    the previous cut. Bitmaps are packed to uint32 words.
    """
    flags = np.asarray(flags)
    merged_bits = np.asarray(merged_bits)
    ends = np.flatnonzero(flags).astype(np.int32)
    starts = np.concatenate([[0], ends[:-1] + 1]).astype(np.int32)
    entry_bits = merged_bits[ends]                               # (E, H) bool
    packed = np.asarray(bm.from_bool(jnp.asarray(entry_bits)))   # (E, W) uint32
    return starts, ends, packed
