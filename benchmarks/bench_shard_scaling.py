"""Shard-scaling throughput: the sharded engine vs shard count on one device.

Every batch runs one program over every shard
(``core.index.search_compact_many_sharded``): each shard runs Algorithm 1
over its P/S-page slab, vmapped over the shard axis, and counts reduce
across it. On one device the shards share its cores, so this measures what
the shard axis costs or saves in the one program (per-shard slab buckets,
the reduce), not device parallelism — that needs the shard axis on a mesh.
Keys are sorted (the time-ordered append workload page grouping itself is
built for), so page ranges correlate with value ranges.

Counts are asserted bit-identical between every shard count and the
unsharded ``HippoIndex`` path before timing. The ``speedup`` field is
queries/sec vs the S=1 engine at Q=64.

  PYTHONPATH=src python -m benchmarks.bench_shard_scaling [--quick]
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timeit
from repro.core.hippo import HippoIndex
from repro.core.partition import ShardedHippoIndex
from repro.core.predicate import Predicate
from repro.runtime.engine import QueryEngine
from repro.storage.table import PagedTable

CARD = 400_000
SHARDS = (1, 2, 4, 8)
Q = 64


def _workload(rng, q: int) -> list[Predicate]:
    """Narrow-to-medium ranges over the sorted key domain."""
    preds = []
    for _ in range(q):
        lo = float(rng.uniform(0, 1e6))
        width = float(rng.choice([500.0, 2000.0, 8000.0]))
        preds.append(Predicate.between(lo, lo + width))
    return preds


def run(card: int = CARD, shards=SHARDS) -> None:
    rng = np.random.default_rng(0)
    values = np.sort(rng.uniform(0, 1e6, card))
    preds = _workload(rng, Q)

    ref_table = PagedTable.from_values(values.copy(), page_card=50)
    ref = HippoIndex.create(ref_table, resolution=400, density=0.2)
    want = np.asarray(ref.search_batch(preds).counts, np.int64)

    base_qps = None
    for s in shards:
        table = PagedTable.from_values(values.copy(), page_card=50)
        sidx = ShardedHippoIndex.create(table, num_shards=s,
                                        resolution=400, density=0.2)
        engine = QueryEngine(sidx, batch=Q)
        counts = engine.run_all(preds)        # also warms the slab bucket
        assert (counts == want).all(), \
            f"sharded counts diverge from the unsharded path at S={s}"

        us = timeit(lambda: engine.run_all(preds), warmup=1, iters=3)
        qps = Q / (us / 1e6)
        if base_qps is None:
            base_qps = qps
        emit(f"shard_scaling_s{s}_q{Q}", us, qps=round(qps, 1),
             speedup=round(qps / base_qps, 2),
             fallbacks=engine.stats.compact_fallbacks,
             occupancy=round(engine.stats.occupancy, 3))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(card=100_000 if args.quick else CARD,
        shards=(1, 2, 4) if args.quick else SHARDS)
