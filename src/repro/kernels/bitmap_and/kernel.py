"""Pallas kernel: joint-bucket filter (§3.2, Fig. 3).

For each index entry e: out[e] = any_w(entries[e, w] & query[w]) — one AND +
OR-reduction per entry over the packed bitmap words. The paper's "bitwise
'AND'ing the bytes from both sides, aka bit-level parallelism" maps onto the
8x128 VPU: a (BLOCK_E, 128) tile processes 128 words of 8 entries per vreg op.

VMEM budget per grid step: BLOCK_E * PADDED_W * 4 B (entries) + PADDED_W * 4 B
(query, broadcast) + BLOCK_E * 4 B (out). With BLOCK_E=512, PADDED_W=128 this
is ~256 KiB — far under the ~16 MiB VMEM of a v5e core, leaving room for
double buffering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_E = 512  # entries per grid step (8-sublane aligned)


def _kernel(entries_ref, query_ref, out_ref):
    e = entries_ref[...]                # (BLOCK_E, W) uint32
    q = query_ref[...]                  # (1, W) uint32
    joint = ((e & q) != 0).astype(jnp.int32)   # VPU lane-parallel AND
    out_ref[...] = jnp.max(joint, axis=1, keepdims=True)


def bitmap_and_any_kernel(entries: jnp.ndarray, query: jnp.ndarray,
                          *, interpret: bool = False) -> jnp.ndarray:
    """entries: (E, W) uint32 (E % BLOCK_E == 0, W % 128 == 0);
    query: (1, W) uint32. Returns (E, 1) int32 0/1."""
    e, w = entries.shape
    grid = (e // BLOCK_E,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_E, w), lambda i: (i, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_E, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e, 1), jnp.int32),
        interpret=interpret,
    )(entries, query)
