"""page_inspect public wrapper — §3.3 exact inspection of candidate pages.

Shapes/dtypes: ``page_inspect(keys (P, C) f32, valid (P, C) bool, mask (P,)
bool, lo, hi) -> (qual (P, C) bool, counts (P,) int32)`` — the exact
tuple-level predicate test over the pages Algorithm 1's bitmap filter could
not rule out (``mask``), with per-page qualifying counts. C is the page
cardinality (``page_card``), lo/hi the closed predicate interval (±inf
already clamped to finite f32 by ``core.predicate``).

The wrapper pads P to the kernel block (padded pages carry mask=False and
count 0) and slices back. The kernel compiles for the TPU by default;
callers on a CPU pass ``interpret=True`` explicitly. ``ref.py`` is the jnp
reference twin. Inspection is exact, which is the root of the layer
equivalence contract: per-shard inspections over a partition of the page
space sum bit-identically to the unsharded inspection, so every search
path (scalar, batched, sharded, staged-overlay) returns the same counts.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.page_inspect.kernel import BLOCK_P, page_inspect_kernel
from repro.kernels.page_inspect.ref import page_inspect_ref


@partial(jax.jit, static_argnames=("interpret",))
def page_inspect(keys: jnp.ndarray, valid: jnp.ndarray, mask: jnp.ndarray,
                 lo, hi, interpret: bool = False):
    """Inspect possible-qualified pages: exact qualifying mask + page counts.

    keys: (P, C) f32, valid: (P, C) bool, mask: (P,) bool.
    Returns (qual (P, C) bool, counts (P,) int32).
    """
    p, c = keys.shape
    pad_p = (-p) % BLOCK_P
    pad_c = (-c) % 128
    kp = jnp.pad(keys.astype(jnp.float32), ((0, pad_p), (0, pad_c)),
                 constant_values=jnp.inf)
    vp = jnp.pad(valid.astype(jnp.uint8), ((0, pad_p), (0, pad_c)))
    mp = jnp.pad(mask.astype(jnp.int32), (0, pad_p))[:, None]
    interval = jnp.stack([jnp.float32(lo), jnp.float32(hi)])[None, :]
    qual, counts = page_inspect_kernel(kp, vp, mp, interval, interpret=interpret)
    return qual[:p, :c].astype(bool), counts[:p, 0]


__all__ = ["page_inspect", "page_inspect_ref"]
