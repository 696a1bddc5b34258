"""Pallas TPU kernels for Hippo's compute hot-spots.

Each kernel directory contains:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding; compiled for the TPU unless the
              caller passes interpret=True)
  ref.py    — pure-jnp oracle used by tests and the chip smoke's parity check

Kernels:
  bitmap_and      — §3.2 joint-bucket filter: AND query bitmap against all
                    entry bitmaps, OR-reduce per entry (bit-level parallelism
                    on VPU lanes)
  batch_filter    — batched-engine form of bitmap_and: Q query bitmaps AND'd
                    against E entry bitmaps in one VMEM pass, (BLOCK_Q,
                    BLOCK_E) match tile per grid step so queries share entry
                    transfers; the sharded variant extends the grid over the
                    shard axis so one launch covers every (query, shard,
                    entry) tile
  bucketize       — §4.2 histogram probe: branchless compare-count of values
                    against resident bucket boundaries (replaces binary search)
  page_inspect    — §3.3 inspection: masked predicate evaluation + per-page
                    counts over the whole table
  compact_inspect — gather-path inspection: fused filter-match × interval
                    test over the batch's gathered possible-qualified-page
                    slab, (BLOCK_Q, BLOCK_M) count tile per grid step — the
                    inspect phase of core.index.search_compact_many, with
                    cost proportional to pages selected instead of table size
"""
