"""TPC-H-style Lineitem workload (§7): generator + Q6/Q15/Q20 analogues.

The paper builds indexes on Lineitem's ``partkey`` (uniform ints) and
``l_shipdate`` and runs range predicates at chosen selectivity factors. We
generate the columns the three queries touch; dates are days since epoch
(uniform over 7 years, as in TPC-H).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax.numpy as jnp

from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate
from repro.storage.table import PagedTable

DATE_LO, DATE_HI = 0, 7 * 365          # ~1992-01-01 .. 1998-12-31 in days
PARTKEY_MAX = 200_000


@dataclass
class Lineitem:
    partkey: np.ndarray
    shipdate: np.ndarray
    discount: np.ndarray
    quantity: np.ndarray
    extendedprice: np.ndarray
    suppkey: np.ndarray

    @property
    def card(self) -> int:
        return self.partkey.shape[0]


def generate_lineitem(card: int, seed: int = 0) -> Lineitem:
    rng = np.random.default_rng(seed)
    return Lineitem(
        partkey=rng.integers(1, PARTKEY_MAX, card).astype(np.float32),
        shipdate=rng.integers(DATE_LO, DATE_HI, card).astype(np.float32),
        discount=(rng.integers(0, 11, card) / 100.0).astype(np.float32),
        quantity=rng.integers(1, 51, card).astype(np.float32),
        extendedprice=rng.uniform(900.0, 105000.0, card).astype(np.float32),
        suppkey=rng.integers(1, 10_000, card).astype(np.float32),
    )


def build_shipdate_index(li: Lineitem, page_card: int = 50, resolution: int = 400,
                         density: float = 0.2) -> HippoIndex:
    table = PagedTable.from_values(li.shipdate, page_card=page_card,
                                   spare_pages=64)
    return HippoIndex.create(table, resolution=resolution, density=density)


def _page_select(idx: HippoIndex, lo: float, hi: float) -> np.ndarray:
    """Hippo access path: qualifying-tuple mask (flat, aligned to storage),
    set from every qualifying row id the search returns."""
    pred = Predicate.between(lo, hi)
    n = idx.count(pred)
    ids = np.asarray(idx.search_batch(
        [pred], top_k=1 << max(n - 1, 0).bit_length()).row_ids[0])
    mask = np.zeros(idx.table.cardinality, bool)
    mask[ids[ids >= 0]] = True
    return mask


def q6(li: Lineitem, idx: HippoIndex, date_lo: float, date_hi: float) -> float:
    """Forecasting revenue change: SUM(extendedprice * discount) over a
    shipdate range AND discount/quantity filters (plan: index scan on
    shipdate -> residual filters -> aggregate)."""
    sel = _page_select(idx, date_lo, date_hi)
    mask = sel & (li.discount >= 0.05) & (li.discount <= 0.07) & (li.quantity < 24)
    return float((li.extendedprice[mask] * li.discount[mask]).sum())


def q15(li: Lineitem, idx: HippoIndex, date_lo: float, date_hi: float):
    """Top supplier: the revenue view groups by suppkey over a shipdate
    range; the view is consumed twice (max + equality join), which is why the
    paper sees the index invoked twice."""
    best = None
    for _ in range(2):  # the view is evaluated twice in the paper's plan
        sel = _page_select(idx, date_lo, date_hi)
        rev = np.zeros(10_000, np.float64)
        np.add.at(rev, li.suppkey[sel].astype(np.int64),
                  (li.extendedprice[sel] * (1.0 - li.discount[sel])).astype(np.float64))
        best = (int(rev.argmax()), float(rev.max()))
    return best


def q20(li: Lineitem, idx: HippoIndex, date_lo: float, date_hi: float):
    """Potential part promotion (subquery form): per (partkey, suppkey) sum
    of quantity over a shipdate range; result feeds the outer join."""
    sel = _page_select(idx, date_lo, date_hi)
    key = (li.partkey[sel].astype(np.int64) * 10_000
           + li.suppkey[sel].astype(np.int64)) % (1 << 20)
    qty = np.zeros(1 << 20, np.float64)
    np.add.at(qty, key, li.quantity[sel].astype(np.float64))
    thresh = qty[key] * 0.5
    return int((li.quantity[sel] > thresh).sum())


def selectivity_window(sf: float) -> tuple[float, float]:
    """A shipdate window with the requested selectivity (uniform dates)."""
    width = (DATE_HI - DATE_LO) * sf
    lo = (DATE_HI - DATE_LO) / 2
    return lo, lo + width
