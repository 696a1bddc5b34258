"""bitmap_and public wrapper — the §3.2 single-query entry filter.

Shapes/dtypes: ``bitmap_and_any(entries (E, W) uint32, query (W,) uint32)
-> (E,) int32 0/1`` — 1 iff entry e shares at least one set bucket bit with
the query bitmap (the paper's joint-bucket test, Fig. 3). W =
ceil(resolution / 32) packed words (``core.bitmap``).

The wrapper pads E to the kernel block and W to the 128-lane width (zero
pads AND to zero, so padding never creates a match) and slices back. The
kernel compiles for the TPU by default; callers on a CPU pass
``interpret=True`` explicitly. ``ref.py`` is the jnp reference twin. The
batched engine uses ``kernels.batch_filter`` (a leading query axis, plus a
sharded grid); per shard and per query all three agree bit-exactly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.bitmap_and.kernel import BLOCK_E, bitmap_and_any_kernel
from repro.kernels.bitmap_and.ref import bitmap_and_any_ref


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("interpret",))
def bitmap_and_any(entries: jnp.ndarray, query: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """Joint-bucket test of every entry bitmap against the query bitmap.

    entries: (E, W) uint32, query: (W,) uint32 -> (E,) int32 0/1.
    """
    e, w = entries.shape
    ep = _pad_to(entries, 0, BLOCK_E)
    ep = _pad_to(ep, 1, 128)
    qp = _pad_to(query[None, :], 1, 128)
    out = bitmap_and_any_kernel(ep, qp, interpret=interpret)
    return out[:e, 0]


__all__ = ["bitmap_and_any", "bitmap_and_any_ref"]
