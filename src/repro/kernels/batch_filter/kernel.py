"""Pallas kernel: fused batched joint-bucket filter (§3.2 with a query axis).

For query q and entry e: out[q, e] = any_w(queries[q, w] & entries[e, w]) —
the batched-engine form of the bitmap_and kernel. One grid step loads a
(BLOCK_Q, W) query tile and a (BLOCK_E, W) entry tile into VMEM and produces
the full (BLOCK_Q, BLOCK_E) match tile in one pass, so Q queries share each
entry tile's HBM->VMEM transfer instead of re-streaming the index per query.

VMEM budget per grid step: BLOCK_E * PADDED_W * 4 B (entries) + BLOCK_Q *
PADDED_W * 4 B (queries) + BLOCK_Q * BLOCK_E * 4 B (out) plus the broadcast
joint intermediate BLOCK_Q * BLOCK_E * PADDED_W bits. With BLOCK_Q=8,
BLOCK_E=128, PADDED_W=128 the tiles are ~132 KiB and the intermediate stays
well under a MiB — comfortable inside a v5e core's ~16 MiB VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_Q = 8    # queries per grid step (sublane-aligned)
BLOCK_E = 128  # entries per grid step (lane-aligned)


def _kernel(queries_ref, entries_ref, out_ref):
    q = queries_ref[...]                        # (BLOCK_Q, W) uint32
    e = entries_ref[...]                        # (BLOCK_E, W) uint32
    joint = (q[:, None, :] & e[None, :, :]) != 0  # (BLOCK_Q, BLOCK_E, W)
    out_ref[...] = jnp.any(joint, axis=-1).astype(jnp.int32)


def batch_filter_kernel(queries: jnp.ndarray, entries: jnp.ndarray,
                        *, interpret: bool = False) -> jnp.ndarray:
    """queries: (Q, W) uint32 (Q % BLOCK_Q == 0); entries: (E, W) uint32
    (E % BLOCK_E == 0, W % 128 == 0). Returns (Q, E) int32 0/1."""
    q, w = queries.shape
    e, _ = entries.shape
    grid = (q // BLOCK_Q, e // BLOCK_E)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_Q, w), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_E, w), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_Q, BLOCK_E), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, e), jnp.int32),
        interpret=interpret,
    )(queries, entries)


def _kernel_sharded(queries_ref, entries_ref, out_ref):
    q = queries_ref[...]                        # (BLOCK_Q, W) uint32
    e = entries_ref[0]                          # (BLOCK_E, W) uint32
    joint = (q[:, None, :] & e[None, :, :]) != 0  # (BLOCK_Q, BLOCK_E, W)
    out_ref[0] = jnp.any(joint, axis=-1).astype(jnp.int32)


def batch_filter_sharded_kernel(queries: jnp.ndarray, entries: jnp.ndarray,
                                *, interpret: bool = False) -> jnp.ndarray:
    """Shard-axis extension of ``batch_filter_kernel``: the grid gains a
    leading shard dimension so one fused launch covers every (query, shard,
    entry) tile — the match phase of
    ``core.index.search_compact_many_sharded``.

    queries: (Q, W) uint32 (Q % BLOCK_Q == 0), shared across shards;
    entries: (S, E, W) uint32 (E % BLOCK_E == 0, W % 128 == 0), one entry
    table per shard. Returns (S, Q, E) int32 0/1. The query tile is reused
    across the shard axis, so S shards re-stream only their own entry tiles;
    VMEM per grid step is the unsharded budget plus one (1, BLOCK_E, W) slab.
    """
    q, w = queries.shape
    s, e, _ = entries.shape
    grid = (s, q // BLOCK_Q, e // BLOCK_E)
    return pl.pallas_call(
        _kernel_sharded,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_Q, w), lambda k, i, j: (i, 0)),
            pl.BlockSpec((1, BLOCK_E, w), lambda k, i, j: (k, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_Q, BLOCK_E), lambda k, i, j: (k, i, j)),
        out_shape=jax.ShapeDtypeStruct((s, q, e), jnp.int32),
        interpret=interpret,
    )(queries, entries)
