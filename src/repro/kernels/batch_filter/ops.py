"""batch_filter public wrappers — the fused §3.2 match phase of the engine.

Shapes/dtypes: ``batch_filter(queries (Q, W) uint32, entries (E, W) uint32)
-> (Q, E) int32 0/1`` — joint-bucket test of every query bitmap against
every entry bitmap; ``batch_filter_sharded`` adds a leading shard axis,
``entries (S, E, W) -> (S, Q, E)``, one grid over the whole shard axis.
W = ceil(resolution / 32) packed words (``core.bitmap``).

Wrappers pad Q/E to kernel block multiples and W to the 128-lane width
(zero pads AND to zero, so padding never creates a match), then slice the
result back. The kernel compiles for the TPU by default; callers on a CPU
pass ``interpret=True`` explicitly (the interpreter is a validation tool,
never a silent fallback). ``ref.py`` holds the jnp reference twin.

Equivalence contract: the sharded form is the unsharded form vmapped over
the shard axis — ``batch_filter_sharded(q, e)[s] == batch_filter(q, e[s])``
bit-exactly, which is what lets ``core.index.search_many_sharded`` reduce
per-shard results into the unsharded answer.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.batch_filter.kernel import (BLOCK_E, BLOCK_Q,
                                               batch_filter_kernel,
                                               batch_filter_sharded_kernel)
from repro.kernels.batch_filter.ref import (batch_filter_ref,
                                            batch_filter_sharded_ref)


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("interpret",))
def batch_filter(queries: jnp.ndarray, entries: jnp.ndarray,
                 interpret: bool = False) -> jnp.ndarray:
    """Joint-bucket test of every query bitmap against every entry bitmap.

    queries: (Q, W) uint32, entries: (E, W) uint32 -> (Q, E) int32 0/1.
    """
    q, w = queries.shape
    e, _ = entries.shape
    qp = _pad_to(queries, 0, BLOCK_Q)
    qp = _pad_to(qp, 1, 128)
    ep = _pad_to(entries, 0, BLOCK_E)
    ep = _pad_to(ep, 1, 128)
    out = batch_filter_kernel(qp, ep, interpret=interpret)
    return out[:q, :e]


@partial(jax.jit, static_argnames=("interpret",))
def batch_filter_sharded(queries: jnp.ndarray, entries: jnp.ndarray,
                         interpret: bool = False) -> jnp.ndarray:
    """Joint-bucket test of every query against every shard's entry table.

    queries: (Q, W) uint32, entries: (S, E, W) uint32 -> (S, Q, E) int32 0/1
    — the fused match phase over the whole shard axis.
    """
    q, w = queries.shape
    s, e, _ = entries.shape
    qp = _pad_to(queries, 0, BLOCK_Q)
    qp = _pad_to(qp, 1, 128)
    ep = _pad_to(entries, 1, BLOCK_E)
    ep = _pad_to(ep, 2, 128)
    out = batch_filter_sharded_kernel(qp, ep, interpret=interpret)
    return out[:, :q, :e]


__all__ = ["batch_filter", "batch_filter_ref",
           "batch_filter_sharded", "batch_filter_sharded_ref"]
