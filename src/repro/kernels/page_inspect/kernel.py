"""Pallas kernel: possible-qualified-page inspection (§3.3).

Fuses the three per-tuple predicates of Algorithm 1 step 3 — page selected,
tuple live, key within [lo, hi] — and reduces to a per-page qualifying count,
emitting the exact qualifying-tuple mask. One (BLOCK_P, C) tile of the key
column is streamed through VMEM per grid step; selected/validity masks ride
along as uint8 tiles (bool refs are not TPU-tileable).

The interval endpoints arrive as a (1, 2) float32 operand broadcast to every
grid step — scalar parameters as a resident VMEM block.

VMEM per step: BLOCK_P*C*(4+1+ ) + outs ~ BLOCK_P*(C*5 + C + 4) bytes; with
BLOCK_P=64, C=128: ~48 KiB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_P = 64   # pages per grid step


def _kernel(keys_ref, valid_ref, mask_ref, interval_ref, qual_ref, count_ref):
    k = keys_ref[...]                              # (BLOCK_P, C) f32
    # Masks compare as int32: Mosaic cannot relayout the i1 vector of a
    # uint8 compare against a lane-broadcast (BLOCK_P, 1) page mask.
    live = valid_ref[...].astype(jnp.int32) != 0   # (BLOCK_P, C)
    sel = jnp.broadcast_to(mask_ref[...], k.shape) != 0
    lo = interval_ref[0, 0]
    hi = interval_ref[0, 1]
    qual = (sel & live & (k >= lo) & (k <= hi)).astype(jnp.int32)
    qual_ref[...] = qual.astype(jnp.uint8)
    count_ref[...] = qual.sum(axis=1, keepdims=True)


def page_inspect_kernel(keys: jnp.ndarray, valid: jnp.ndarray, mask: jnp.ndarray,
                        interval: jnp.ndarray, *, interpret: bool = False):
    """keys: (P, C) f32; valid: (P, C) uint8; mask: (P, 1) int32;
    interval: (1, 2) f32 [lo, hi]. P % BLOCK_P == 0, C % 128 == 0.
    Returns (qual (P, C) uint8, counts (P, 1) int32)."""
    p, c = keys.shape
    grid = (p // BLOCK_P,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_P, c), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_P, c), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_P, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_P, c), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_P, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, c), jnp.uint8),
            jax.ShapeDtypeStruct((p, 1), jnp.int32),
        ],
        interpret=interpret,
    )(keys, valid, mask, interval)
