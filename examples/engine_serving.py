"""Batched query serving: a stream of range predicates through QueryEngine.

    PYTHONPATH=src python examples/engine_serving.py

Simulates the multi-user serving scenario the engine exists for: a queue of
mixed-selectivity range queries is admitted into a fixed-slot batch and
executed one device program per batch (core.index.search_compact_many),
then the same stream is replayed through the per-query loop to show the
throughput gap, then through a sharded index (core.partition) where each
batch runs one program over every shard and reduces counts across them,
with tickets that also carry qualifying row ids, and finally with writes
mixed in: the async maintenance writer (runtime.writer) stages
inserts/deletes in per-shard queues and drains them between batches, with
staged rows overlaid into every count. Counts are asserted identical
between all paths.
"""
import time

import numpy as np

from repro.core.hippo import HippoIndex
from repro.core.partition import ShardedHippoIndex
from repro.core.predicate import Predicate
from repro.runtime.engine import QueryEngine
from repro.storage.table import PagedTable


def main():
    rng = np.random.default_rng(0)
    card, page_card = 100_000, 50
    # Sorted keys: the time-ordered append workload (think order dates) where
    # page ranges correlate with value ranges — the case Hippo's page
    # grouping is built for.
    values = np.sort(rng.uniform(0, 1_000_000, card))
    table = PagedTable.from_values(values, page_card=page_card)
    idx = HippoIndex.create(table, resolution=400, density=0.2)
    print(f"table: {card:,} rows / {table.num_pages} pages; "
          f"index: {idx.num_entries} entries, {idx.nbytes():,} B")

    # A bursty stream: 200 queries of mixed selectivity.
    preds = []
    for _ in range(200):
        lo = float(rng.uniform(0, 1e6))
        preds.append(Predicate.between(lo, lo + float(rng.choice([200.0, 1e4, 1e5]))))

    engine = QueryEngine(idx, batch=64)
    engine.run_all(preds)   # warm the compiled traces + the adaptive bucket
    t0 = time.perf_counter()
    counts = engine.run_all(preds)
    dt_engine = time.perf_counter() - t0
    st = engine.stats
    print(f"engine:  {len(preds)} queries in {dt_engine*1e3:.1f} ms "
          f"({len(preds)/dt_engine:.0f} q/s) — {st.batches} batches, "
          f"occupancy {st.occupancy:.0%} "
          f"({st.slots_filled} real / {st.pad_slots} pad slots)")

    idx.count(preds[0])                # warm the batch-of-one trace
    t0 = time.perf_counter()
    loop_counts = np.asarray([idx.count(p) for p in preds])
    dt_loop = time.perf_counter() - t0
    print(f"loop:    {len(preds)} queries in {dt_loop*1e3:.1f} ms "
          f"({len(preds)/dt_loop:.0f} q/s)")

    assert (counts == loop_counts).all(), "engine must be exact"
    print(f"counts identical across paths; engine speedup {dt_loop/dt_engine:.1f}x")

    # The same stream through a sharded partition layer: each batch runs one
    # program over every shard, inspecting the gathered union-of-selected-
    # pages slab — work proportional to what the batch selects (see
    # bench_selectivity_sweep for the workload where that wins big; this
    # broad mixed stream is its worst case) — and reduces counts across
    # the shard axis.
    t2 = PagedTable.from_values(values, page_card=page_card)
    sidx = ShardedHippoIndex.create(t2, num_shards=4, resolution=400, density=0.2)
    sharded = QueryEngine(sidx, batch=64)
    sharded.run_all(preds)                 # warm the traces + slab bucket
    t0 = time.perf_counter()
    shard_counts = sharded.run_all(preds)
    dt_shard = time.perf_counter() - t0
    ss = sharded.stats
    assert (shard_counts == loop_counts).all(), "sharded engine must be exact"
    print(f"sharded: {len(preds)} queries in {dt_shard*1e3:.1f} ms "
          f"({len(preds)/dt_shard:.0f} q/s) — selected-page ratio "
          f"{ss.selected_page_ratio:.0%}, gather occupancy "
          f"{ss.gather_occupancy:.0%}, {ss.compact_fallbacks} fallbacks")

    # With top_k set, tickets also carry qualifying global row ids.
    ids_engine = QueryEngine(sidx, batch=8, top_k=8)
    ticket = ids_engine.submit(preds[0])
    ids_engine.drain()
    vals = sidx.table.row_values(ticket.row_ids)
    lo, hi = ticket.pred.selectivity_interval()
    assert ((vals >= lo) & (vals <= hi)).all()
    print(f"row ids: ticket qid={ticket.qid} carries {len(ticket.row_ids)} "
          f"row ids of its {ticket.count} matches, e.g. "
          f"{[int(i) for i in ticket.row_ids[:3]]} -> {np.round(vals[:3], 1)}")

    # Mixed read/write serving: writes go through the engine's async
    # maintenance writer instead of running Algorithm 3 on the query path.
    # engine.write() stages the row in its shard's pending queue (a host
    # list append); the default drain policy applies one shard queue as a
    # fused batch between query batches, and explicit flush() drains the
    # rest. Staged rows are overlaid into every count, so results are exact
    # at all times — asserted against a synchronous twin below.
    t3 = PagedTable.from_values(values, page_card=page_card, spare_pages=2048)
    widx = ShardedHippoIndex.create(t3, num_shards=4, resolution=400, density=0.2)
    wengine = QueryEngine(widx, batch=64)          # drain_policy="between_batches"
    t4 = PagedTable.from_values(values, page_card=page_card, spare_pages=2048)
    twin = ShardedHippoIndex.create(t4, num_shards=4, resolution=400, density=0.2)

    new_rows = rng.uniform(0, 1e6, 64)
    for v in new_rows:
        wengine.write(float(v))                    # staged, off the query path
        twin.insert(float(v))                      # synchronous twin
    ws = wengine.stats
    print(f"writer:  staged {ws.queue_depth} rows across "
          f"{len(wengine.writer.pending_shards())} shard queue(s) "
          f"(peak depth {ws.peak_queue_depth})")
    async_counts = wengine.run_all(preds)          # drains ride along batches
    twin_counts = np.asarray([twin.count(p) for p in preds])
    assert (async_counts == twin_counts).all(), \
        "staged counts must match the synchronous twin"
    wengine.delete(250_000, 260_000)               # validity mask now, vacuum queued
    t4.delete_where(250_000, 260_000)
    twin.vacuum()
    drained = wengine.flush()                      # apply everything pending now
    ws = wengine.stats
    print(f"writer:  drained {ws.drained_rows} rows in {ws.drains} units "
          f"({ws.drain_us/1e3:.1f} ms total); flush applied {drained} rows, "
          f"queue depth {ws.queue_depth}")
    after = wengine.run_all(preds)
    twin_after = np.asarray([twin.count(p) for p in preds])
    assert (after == twin_after).all(), "post-flush counts must match the twin"
    print("writer:  counts identical to the synchronous twin before and after "
          "the flush")


if __name__ == "__main__":
    main()
