"""Fig. 8 + Table 3 (density rows): partial histogram density sweep.

D in {20%, 40%, 80%} at SF=0.1%: higher density => smaller index & init
(Table 3) but more possible-qualified pages => slower queries (Fig. 8).
"""
from __future__ import annotations

from benchmarks.common import emit, timeit
from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate
from repro.storage.table import PagedTable
from repro.storage import tpch

CARD = 200_000
PAGE_CARD = 50


def run(card=CARD) -> None:
    li = tpch.generate_lineitem(card)
    lo, hi = tpch.selectivity_window(0.001)
    pred = Predicate.between(lo, hi)
    base = None
    for d in (0.2, 0.4, 0.8):
        us_init = timeit(lambda: HippoIndex.create(
            PagedTable.from_values(li.shipdate, PAGE_CARD),
            resolution=400, density=d), warmup=1, iters=3)
        idx = HippoIndex.create(PagedTable.from_values(li.shipdate, PAGE_CARD),
                                resolution=400, density=d)
        us_q = timeit(lambda: idx.count(pred))
        res = idx.search(pred)
        size = idx.nbytes()
        if base is None:
            base = size
        emit(f"fig8_density{int(d*100)}", us_q,
             qps=round(1e6 / us_q, 1),
             init_us=round(us_init, 1), size_bytes=size,
             size_vs_d20=round(size / base, 3), entries=idx.num_entries,
             pages_inspected=int(res.pages_inspected[0]),
             total_pages=idx.table.num_pages)


if __name__ == "__main__":
    run()
