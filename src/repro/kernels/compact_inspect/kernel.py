"""Pallas kernel: fused filter-match × gathered-slab inspection.

The compact search pipeline (``core.index.search_compact_many``) gathers the
batch's union of possible-qualified pages into one (M, C) slab and inspects
every query against it. This kernel fuses the two per-(query, page) factors
of that inspection in one grid: the filter-match bit (query q selected slab
page m — the gathered restriction of Algorithm 1 step 2) and the exact
interval test of the page's tuples (step 3), reducing to a per-(query, page)
qualifying count. One (BLOCK_Q, 2) interval tile and one (BLOCK_M, C) slab
tile are resident per grid step; every query block reuses the slab tile's
HBM->VMEM transfer, the compact analogue of batch_filter's shared entry
tiles.

VMEM per step: BLOCK_M*C*(4+1) slab + BLOCK_Q*(BLOCK_M + 2*4) masks/intervals
+ BLOCK_Q*BLOCK_M*4 out + the (BLOCK_Q, BLOCK_M, C) boolean intermediate;
with BLOCK_Q=8, BLOCK_M=64, C=128 that is ~105 KiB — comfortable in v5e VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_Q = 8    # queries per grid step (sublane-aligned)
BLOCK_M = 128  # gathered slab pages per grid step (lane-aligned)


def _kernel(keys_ref, valid_ref, selmask_ref, interval_ref, count_ref):
    k = keys_ref[...]                               # (BLOCK_M, C) f32
    live = valid_ref[...].astype(jnp.int32) != 0    # (BLOCK_M, C)
    sel = selmask_ref[...].astype(jnp.int32)        # (BLOCK_Q, BLOCK_M) 0/1
    ones = jnp.ones((1, k.shape[1]), jnp.bfloat16)
    for q in range(BLOCK_Q):
        lo = interval_ref[q, 0]
        hi = interval_ref[q, 1]
        qual = (live & (k >= lo) & (k <= hi)).astype(jnp.bfloat16)
        # Per-page counts as a lane-major (1, BLOCK_M) row: contracting the
        # slot axis of ones (1, C) against qual (BLOCK_M, C) on the MXU both
        # sums and transposes. 0/1 operands and f32 accumulation are exact.
        row = jax.lax.dot_general(ones, qual, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        count_ref[pl.ds(q, 1), :] = row.astype(jnp.int32) * sel[q:q + 1, :]


def compact_inspect_kernel(keys: jnp.ndarray, valid: jnp.ndarray,
                           sel_mask: jnp.ndarray, intervals: jnp.ndarray,
                           *, interpret: bool = False) -> jnp.ndarray:
    """keys: (M, C) f32 gathered slab; valid: (M, C) uint8; sel_mask: (Q, M)
    uint8 per-query selected-page mask; intervals: (Q, 2) f32 [lo, hi] rows.
    Q % BLOCK_Q == 0, M % BLOCK_M == 0, C % 128 == 0.
    Returns counts (Q, M) int32 — qualifying tuples per (query, slab page)."""
    m, c = keys.shape
    q, _ = sel_mask.shape
    grid = (q // BLOCK_Q, m // BLOCK_M)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_M, c), lambda i, j: (j, 0)),
            pl.BlockSpec((BLOCK_M, c), lambda i, j: (j, 0)),
            pl.BlockSpec((BLOCK_Q, BLOCK_M), lambda i, j: (i, j)),
            pl.BlockSpec((BLOCK_Q, 2), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_Q, BLOCK_M), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, m), jnp.int32),
        interpret=interpret,
    )(keys, valid, sel_mask, intervals)
