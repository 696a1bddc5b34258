"""§5 maintenance: eager insert (tuple-at-a-time vs vectorized batch), and
lazy delete + vacuum (entries re-summarized stay localized)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timeit
from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate
from repro.storage.table import PagedTable
from repro.storage import tpch

CARD = 100_000
PAGE_CARD = 50


def run(card=CARD) -> None:
    li = tpch.generate_lineitem(card)
    new_vals = tpch.generate_lineitem(card // 1000, seed=3).partkey

    def fresh():
        return HippoIndex.create(PagedTable.from_values(li.partkey, PAGE_CARD,
                                                        spare_pages=2048))

    idx = fresh()
    us_one = timeit(lambda: idx.insert(float(new_vals[0])), warmup=1, iters=5)

    idx2 = fresh()
    idx2.insert_batch(new_vals)  # compile both batch variants
    idx2.insert_batch(new_vals)
    us_batch_total = timeit(lambda: idx2.insert_batch(new_vals), warmup=0, iters=1)
    # qps = eager tuple inserts per second (the paper's maintenance-overhead
    # headline, and this suite's gated rate metric)
    emit("maint_insert_eager", us_one,
         qps=round(1e6 / us_one, 1),
         batch_total_us=round(us_batch_total, 1),
         batch_per_tuple_us=round(us_batch_total / len(new_vals), 1),
         n_batch=len(new_vals),
         speedup=round(us_one * len(new_vals) / us_batch_total, 1))

    # lazy delete + vacuum (compile the vacuum path on a sibling index first)
    warm = fresh()
    warm.table.delete_where(1000.0, 3000.0)
    warm.vacuum()
    idx3 = fresh()
    n_del = idx3.table.delete_where(1000.0, 3000.0)
    us_vacuum = timeit(lambda: idx3.vacuum() or 1, warmup=0, iters=1)
    emit("maint_vacuum", us_vacuum, deleted=n_del,
         entries_resummarized=idx3.counters.entries_resummarized,
         total_entries=idx3.num_entries)
    res = idx3.search(Predicate.between(1000.0, 3000.0))
    emit("maint_vacuum_exact", 0.0, count_after=int(res.counts[0]))


if __name__ == "__main__":
    run()
