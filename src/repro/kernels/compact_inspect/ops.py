"""compact_inspect public wrapper — the fused inspect phase of the gather
(compact) search pipeline.

Shapes/dtypes: ``compact_inspect(keys (M, C) f32, valid (M, C) bool,
sel_mask (Q, M) bool, los (Q,), his (Q,)) -> counts (Q, M) int32`` — M is
the gathered slab width (``max_selected`` pages of the batch's union,
``core.index.search_compact_many``), C the page cardinality, and
``sel_mask[q, m]`` the filter-match bit restricting query q to the slab
pages its bitmap filter could not rule out. ``counts[q].sum()`` is query
q's qualifying-tuple count over the slab — bit-identical to the compact
search's count for untruncated queries, which is the kernel-level statement
of the compact/dense equivalence contract.

The wrapper pads M to the kernel block (padded slab pages carry valid=False
and sel_mask=False), Q to the query block (padded queries carry the empty
interval lo > hi), and C to the 128-lane width (padded slots carry +inf
keys and valid=False), then slices back. The kernel compiles for the TPU
by default; callers on a CPU pass ``interpret=True`` explicitly. ``ref.py``
is the jnp reference twin.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.compact_inspect.kernel import (BLOCK_M, BLOCK_Q,
                                                  compact_inspect_kernel)
from repro.kernels.compact_inspect.ref import compact_inspect_ref


@partial(jax.jit, static_argnames=("interpret",))
def compact_inspect(keys: jnp.ndarray, valid: jnp.ndarray,
                    sel_mask: jnp.ndarray, los, his,
                    interpret: bool = False) -> jnp.ndarray:
    """Fused selected-mask × interval inspection of a gathered page slab.

    keys: (M, C) f32, valid: (M, C) bool, sel_mask: (Q, M) bool,
    los/his: (Q,) f32. Returns counts (Q, M) int32.
    """
    m, c = keys.shape
    q = sel_mask.shape[0]
    pad_m = (-m) % BLOCK_M
    pad_q = (-q) % BLOCK_Q
    pad_c = (-c) % 128
    kp = jnp.pad(keys.astype(jnp.float32), ((0, pad_m), (0, pad_c)),
                 constant_values=jnp.inf)
    vp = jnp.pad(valid.astype(jnp.uint8), ((0, pad_m), (0, pad_c)))
    sp = jnp.pad(sel_mask.astype(jnp.uint8), ((0, pad_q), (0, pad_m)))
    iv = jnp.stack([jnp.asarray(los, jnp.float32),
                    jnp.asarray(his, jnp.float32)], axis=1)       # (Q, 2)
    if pad_q:
        # padded query rows must match nothing: empty interval (lo=1 > hi=0)
        pad_iv = jnp.tile(jnp.asarray([1.0, 0.0], jnp.float32), (pad_q, 1))
        iv = jnp.concatenate([iv, pad_iv], axis=0)
    counts = compact_inspect_kernel(kp, vp, sp, iv, interpret=interpret)
    return counts[:q, :m]


__all__ = ["compact_inspect", "compact_inspect_ref"]
