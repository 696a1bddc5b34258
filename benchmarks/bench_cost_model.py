"""§6 cost-model validation: estimated vs measured entries / inspection
probability / insert I/Os on uniform data (the model's assumption)."""
from __future__ import annotations

import math

from benchmarks.common import emit
from repro.core import cost
from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate
from repro.storage.table import PagedTable
from repro.storage import tpch

CARD = 200_000
PAGE_CARD = 50


def run(card=CARD) -> None:
    li = tpch.generate_lineitem(card)
    for h, d in ((400, 0.2), (400, 0.4), (800, 0.2)):
        idx = HippoIndex.create(PagedTable.from_values(li.shipdate, PAGE_CARD),
                                resolution=h, density=d)
        est_entries = cost.num_entries(card, h, d)
        emit(f"cost_entries_h{h}_d{int(d*100)}", 0.0,
             measured=idx.num_entries, estimated=round(est_entries, 1),
             rel_err=round(abs(idx.num_entries - est_entries) / est_entries, 3))

        sf = 0.001
        lo, hi = tpch.selectivity_window(sf)
        res = idx.search(Predicate.between(lo, hi))
        measured_prob = int(res.pages_inspected[0]) / idx.table.num_pages
        est_prob = cost.prob_inspect(sf, h, d)
        emit(f"cost_prob_h{h}_d{int(d*100)}", 0.0,
             measured=round(measured_prob, 3), estimated=round(est_prob, 3))

        est_ios = cost.insert_time_ios(card, h, d)
        btree_ios = cost.btree_insert_time_ios(card)
        emit(f"cost_insert_ios_h{h}_d{int(d*100)}", 0.0,
             hippo=round(est_ios, 1), btree=round(btree_ios, 1),
             advantage=round(btree_ios / est_ios, 2))

    # coupon-collector worked examples from §6.2
    emit("cost_T_h1000_d10", 0.0, estimated=round(cost.tuples_per_entry(1000, 0.1), 1),
         paper=105.3)
    emit("cost_T_h10000_d20", 0.0, estimated=round(cost.tuples_per_entry(10000, 0.2)),
         paper=2230)


if __name__ == "__main__":
    run()
