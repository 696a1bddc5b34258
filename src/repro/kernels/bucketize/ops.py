"""bucketize public wrapper — the §4.1 complete-histogram probe.

Shapes/dtypes: ``bucketize_values(values (N,) f32, bounds (H+1,) f32,
resolution: int) -> (N,) int32`` bucket ids in [0, H), clamped at the
domain edges. ``bounds`` are the strictly-increasing equi-depth boundaries
(``core.histogram``); the kernel binary-searches them per value.

The wrapper pads N to the kernel block and H+1 to the 128-lane width with
+inf so padding never wins a comparison, then slices back. The kernel
compiles for the TPU by default; callers on a CPU pass ``interpret=True``
explicitly. ``ref.py`` is the jnp reference twin — both match bit-exactly
for strictly-increasing boundaries. Build (Algorithm 2), search (predicate
conversion), and maintenance (Algorithm 3) all bucketize through this one
surface, which is what keeps the unsharded and sharded indexes agreeing:
shards share the global ``bounds``, so a value buckets identically no
matter which shard owns its page.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.bucketize.kernel import BLOCK_N, bucketize_kernel
from repro.kernels.bucketize.ref import bucketize_ref


@partial(jax.jit, static_argnames=("resolution", "interpret"))
def bucketize_values(values: jnp.ndarray, bounds: jnp.ndarray, resolution: int,
                     interpret: bool = False) -> jnp.ndarray:
    """Bucket ids for ``values`` against ``bounds`` ((H+1,) ascending).

    Pads N to the kernel tile and H+1 to lane width (+inf so padding never
    counts). Matches ``bucketize_ref`` bit-exactly for strictly-increasing
    boundaries.
    """
    n = values.shape[0]
    pad_n = (-n) % BLOCK_N
    v = jnp.pad(values.astype(jnp.float32), (0, pad_n))
    h1 = bounds.shape[0]
    pad_h = (-h1) % 128
    b = jnp.pad(bounds.astype(jnp.float32), (0, pad_h),
                constant_values=jnp.inf)[None, :]
    out = bucketize_kernel(v, b, resolution, interpret=interpret)
    return out[:n]


__all__ = ["bucketize_values", "bucketize_ref"]
