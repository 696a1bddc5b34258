"""Fig. 9 + Table 3 (resolution rows): histogram resolution sweep.

H in {400, 800, 1600} at SF=0.1%: higher resolution => fewer entries but
each bitmap is physically larger (moderate net size decrease, Table 3);
query time varies because the predicate hits more buckets (Fig. 9).
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
    for h in (400, 800, 1600):
        us_init = timeit(lambda: HippoIndex.create(
            PagedTable.from_values(li.shipdate, PAGE_CARD),
            resolution=h, density=0.2), warmup=1, iters=3)
        idx = HippoIndex.create(PagedTable.from_values(li.shipdate, PAGE_CARD),
                                resolution=h, density=0.2)
        us_q = timeit(lambda: idx.count(pred))
        res = idx.search(pred)
        emit(f"fig9_resolution{h}", us_q,
             qps=round(1e6 / us_q, 1),
             init_us=round(us_init, 1), size_bytes=idx.nbytes(),
             rle_bytes=idx.nbytes(compressed=True), entries=idx.num_entries,
             pages_inspected=int(res.pages_inspected[0]),
             total_pages=idx.table.num_pages)


if __name__ == "__main__":
    run()
