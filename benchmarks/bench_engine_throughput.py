"""Batched query-engine throughput: one search program vs a per-query loop.

The production metric for an index serving many users is queries/second, not
single-query latency. This benchmark submits Q identical workloads three ways:

  loop    — Q separate ``index.search`` dispatches, a batch of one each
  batched — one ``core.index.search_compact_many`` device program over all
            Q predicates at the never-truncating cap
  engine  — ``QueryEngine.run_all`` (the same program behind the adaptive
            slab ladder + submit/slot bookkeeping)

Counts are asserted bit-identical between the paths before timing; the
``speedup`` derived field is loop_qps vs batched_qps at each Q.

  PYTHONPATH=src python -m benchmarks.bench_engine_throughput [--quick]
"""
from __future__ import annotations

import numpy as np

import jax

from benchmarks.common import emit, measure, timeit
from repro.core import index as hix
from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate, intervals, to_bucket_bitmaps
from repro.runtime.engine import QueryEngine
from repro.storage.table import PagedTable

CARD = 200_000
BATCHES = (8, 64, 256)


def _workload(rng, q: int) -> list[Predicate]:
    """Mixed selectivities: point-ish, 1%-ish, and broad range predicates."""
    preds = []
    for i in range(q):
        lo = float(rng.uniform(0, 1e6))
        width = float(rng.choice([100.0, 1e4, 2e5]))
        preds.append(Predicate.between(lo, lo + width))
    return preds


def run(card: int = CARD, batches=BATCHES) -> None:
    rng = np.random.default_rng(0)
    values = rng.uniform(0, 1e6, card)
    table = PagedTable.from_values(values, page_card=50)
    idx = HippoIndex.create(table, resolution=400, density=0.2)
    keys, valid = table.device_keys(), table.device_valid()

    for q in batches:
        preds = _workload(rng, q)

        def loop():
            return [idx.search(p).counts[0] for p in preds]

        def batched():
            # starts from Predicate objects, like the loop: conversion is paid
            qbms = to_bucket_bitmaps(preds, idx.state.histogram)
            los, his = intervals(preds)
            return hix.search_compact_many(
                idx.state, qbms, keys, valid, los, his,
                max_selected=idx.gather_cap).counts

        loop_counts = np.asarray(jax.device_get(loop()))
        batch_counts = np.asarray(batched())
        assert (loop_counts == batch_counts).all(), \
            f"batched counts diverge from the per-query loop at Q={q}"

        # interleaved so a noise window hits both contenders; the loop path
        # is all Python dispatch overhead and needs the extra reps to settle
        us_loop, us_batch = measure(loop, batched, warmup=2, reps=7)
        qps_loop = q / (us_loop / 1e6)
        qps_batch = q / (us_batch / 1e6)
        emit(f"engine_loop_q{q}", us_loop, qps=round(qps_loop, 1))
        emit(f"engine_search_many_q{q}", us_batch, qps=round(qps_batch, 1),
             speedup=round(qps_batch / qps_loop, 2))

        # the engine's bucket ladder is measured across selectivities in
        # bench_selectivity_sweep; this row adds submit/slot bookkeeping
        engine = QueryEngine(idx, batch=q)
        engine.run_all(preds)  # warm the trace before timing
        us_eng = timeit(lambda: engine.run_all(preds), warmup=1, iters=5)
        emit(f"engine_run_all_q{q}", us_eng,
             qps=round(q / (us_eng / 1e6), 1),
             occupancy=round(engine.stats.occupancy, 3))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(card=50_000 if args.quick else CARD,
        batches=(8, 64) if args.quick else BATCHES)
