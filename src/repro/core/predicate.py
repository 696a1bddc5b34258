"""Query predicates and their conversion to bucket bitmaps (§3.1).

Unit predicates are equality (``attr = v``) and range (``lo <= attr <= hi``);
conjunctions AND their bucket bitmaps — only buckets hit by *all* units are
kept (Fig. 2). Every predicate reduces to a closed interval [lo, hi] over the
attribute, so the converted bitmap is a contiguous run of set bits.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core.histogram import Histogram, bucketize

_INF = float("inf")


@dataclass(frozen=True)
class Predicate:
    """Closed-interval predicate over the indexed attribute.

    equality(v)    -> lo = hi = v
    greater(v)     -> lo = nextafter(v), hi = +inf   (strict >)
    conjunctions   -> intersection of intervals
    """

    lo: float = -_INF
    hi: float = _INF

    @staticmethod
    def equality(v: float) -> "Predicate":
        return Predicate(lo=float(v), hi=float(v))

    @staticmethod
    def between(lo: float, hi: float) -> "Predicate":
        return Predicate(lo=float(lo), hi=float(hi))

    @staticmethod
    def greater(v: float) -> "Predicate":
        return Predicate(lo=float(np.nextafter(np.float32(v), np.float32(_INF))), hi=_INF)

    @staticmethod
    def less(v: float) -> "Predicate":
        return Predicate(lo=-_INF, hi=float(np.nextafter(np.float32(v), np.float32(-_INF))))

    def and_(self, other: "Predicate") -> "Predicate":
        return Predicate(lo=max(self.lo, other.lo), hi=min(self.hi, other.hi))

    @property
    def empty(self) -> bool:
        return self.lo > self.hi

    def selectivity_interval(self) -> tuple[float, float]:
        return (self.lo, self.hi)


_F32_MAX = 3.4e38   # finite clamp for ±inf predicate endpoints


def _finite_bounds(preds: Sequence[Predicate]) -> tuple[np.ndarray, np.ndarray]:
    """Predicate intervals as finite float32 host arrays (one clamp rule for
    every conversion and inspection path)."""
    los = np.asarray([max(p.lo, -_F32_MAX) for p in preds], np.float32)
    his = np.asarray([min(p.hi, _F32_MAX) for p in preds], np.float32)
    return los, his


def to_bucket_bitmap(pred: Predicate, hist: Histogram) -> jnp.ndarray:
    """Convert a predicate to the packed bitmap of hit buckets (§3.1, Fig. 2).

    Returns a (W,) uint32 packed bitmap; at least one bucket is always hit for
    a non-empty predicate (SF*H >= 1 in the paper's cost model, §6.1).
    """
    return to_bucket_bitmaps([pred], hist)[0]


def intervals(preds: Sequence[Predicate]) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(los, his) float32 device arrays for a batch of predicates.

    Infinities are clamped to the float32 range so the inspection compares
    stay finite; an empty predicate keeps lo > hi and matches nothing.
    """
    los, his = _finite_bounds(preds)
    return jnp.asarray(los), jnp.asarray(his)


@jax.jit
def interval_bitmaps(bounds: jnp.ndarray, los: jnp.ndarray, his: jnp.ndarray,
                     nonempty: jnp.ndarray) -> jnp.ndarray:
    """Fused device half of the §3.1 conversion: intervals -> (Q, W) bitmaps.

    bounds: (H+1,) histogram boundaries (H is static from the shape); los/
    his: (Q,) finite interval endpoints; nonempty: (Q,) bool (False rows
    produce all-zero bitmaps). One jit dispatch replaces the dozen eager ops
    the conversion used to cost per batch — on the serving path this was
    ~40% of a compact batch's wall time on CPU. The endpoint bucketing is
    ``histogram.bucketize``'s searchsorted inlined so the whole conversion
    fuses.
    """
    h = bounds.shape[-1] - 1
    with jax.named_scope("hippo.convert"):
        b_lo = jnp.clip(jnp.searchsorted(bounds, los, side="right") - 1,
                        0, h - 1)
        b_hi = jnp.clip(jnp.searchsorted(bounds, his, side="right") - 1,
                        0, h - 1)
        idx = jnp.arange(bm.num_words(h) * bm.WORD_BITS, dtype=jnp.int32)
        bits = ((idx[None, :] >= b_lo[:, None])
                & (idx[None, :] <= b_hi[:, None])
                & (idx[None, :] < h) & nonempty[:, None])
        return bm.from_bool(bits)


@jax.jit
def interval_bitmaps_sharded(bounds: jnp.ndarray, los: jnp.ndarray,
                             his: jnp.ndarray, nonempty: jnp.ndarray
                             ) -> jnp.ndarray:
    """``interval_bitmaps`` per shard: (S, H+1) stacked bounds -> (S, Q, W).

    Row s converts the batch under shard s's boundary set, so the fused
    sharded search paths stay exact while shards serve different bounds
    epochs mid-drift-resummarization (``core.partition``) — and the steady
    state pays the same single dispatch, not one per shard.
    """
    return jax.vmap(interval_bitmaps, in_axes=(0, None, None, None))(
        bounds, los, his, nonempty)


def _nonempty(preds: Sequence[Predicate]) -> np.ndarray:
    return np.asarray([not p.empty for p in preds])


def to_bucket_bitmaps(preds: Sequence[Predicate], hist: Histogram) -> jnp.ndarray:
    """Batched §3.1 conversion: Q predicates -> (Q, W) packed query bitmaps.

    One fused dispatch (``interval_bitmaps``) converts all Q predicates;
    empty predicates produce all-zero rows. The scalar ``to_bucket_bitmap``
    is this with Q=1, so the paths agree by construction.
    """
    h = hist.resolution
    if not preds:
        return bm.zeros(h, 0)
    los, his = _finite_bounds(preds)
    return interval_bitmaps(hist.bounds, jnp.asarray(los), jnp.asarray(his),
                            jnp.asarray(_nonempty(preds)))


def matches(pred: Predicate, values: jnp.ndarray) -> jnp.ndarray:
    """Exact tuple-level predicate evaluation (used by page inspection)."""
    v = values.astype(jnp.float32)
    return (v >= pred.lo) & (v <= pred.hi)
