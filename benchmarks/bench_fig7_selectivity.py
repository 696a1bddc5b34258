"""Fig. 7: query time vs selectivity factor (0.001%..1%), Hippo vs B+-Tree
vs sequential scan. Prediction from the cost model (§6.1 with H=400, D=0.2):
the first three SFs cost ~0.2*Card inspected tuples, 1% costs ~0.8*Card.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import cost
from repro.core.baselines import BPlusTree, FullScan
from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate
from repro.storage.table import PagedTable
from repro.storage import tpch

CARD = 200_000
PAGE_CARD = 50
SFS = (0.00001, 0.0001, 0.001, 0.01)


def run(card=CARD) -> None:
    li = tpch.generate_lineitem(card)
    table = PagedTable.from_values(li.shipdate, PAGE_CARD)
    idx = HippoIndex.create(table, resolution=400, density=0.2)
    bt = BPlusTree.bulk_load(li.shipdate, PAGE_CARD)
    keys, valid = table.device_keys(), table.device_valid()

    for sf in SFS:
        lo, hi = tpch.selectivity_window(sf)
        pred = Predicate.between(lo, hi)

        us_hippo = timeit(lambda: idx.count(pred))
        res = idx.search(pred)
        us_btree = timeit(lambda: bt.count_range(lo, hi))
        us_scan = timeit(lambda: FullScan.search(keys, valid, lo, hi)[0])

        est = cost.query_time_tuples(sf, 400, 0.2, card)
        emit(f"fig7_sf{sf:g}", us_hippo,
             qps=round(1e6 / us_hippo, 1),
             btree_us=round(us_btree, 1), scan_us=round(us_scan, 1),
             pages_inspected=int(res.pages_inspected[0]),
             total_pages=table.num_pages,
             inspected_frac=round(int(res.pages_inspected[0]) / table.num_pages, 3),
             model_tuples=round(est), count=int(res.counts[0]))


if __name__ == "__main__":
    run()
