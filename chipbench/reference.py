"""Plain reference of an inclusive range query over one key column.

The table is the loaded column in row-id order. A query [lo, hi] is
answered with the number of rows whose key lies in it and the first
``top_k`` of their row ids, ascending. Keys and bounds are float32, as the
configuration states.

``control_answers`` is the same scan with keys and bounds rounded to
bfloat16, the precision below float32, run where the program runs. It is
the control that the comparison has to fail.

Nothing here imports the program under test.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 22     # rows scanned at a time for the first row ids


class RangeScan:
    def __init__(self, keys: np.ndarray, top_k: int):
        self.keys = np.asarray(keys, np.float32)
        self.top_k = top_k

    def answer(self, lo: float, hi: float) -> tuple[int, np.ndarray]:
        lo, hi = np.float32(lo), np.float32(hi)
        count, ids = 0, []
        for start in range(0, self.keys.size, CHUNK):
            part = self.keys[start:start + CHUNK]
            hit = (part >= lo) & (part <= hi)
            n = int(np.count_nonzero(hit))
            if n and len(ids) < self.top_k:
                ids.extend((np.flatnonzero(hit)[: self.top_k - len(ids)]
                            + start).tolist())
            count += n
        return count, np.asarray(ids, np.int64)

    def answers(self, queries) -> list[tuple[int, np.ndarray]]:
        """Answers of (lo, hi) pairs; numpy releases the GIL inside each
        scan, so a few threads overlap them."""
        workers = min(8, os.cpu_count() or 1)
        with ThreadPoolExecutor(max_workers=workers) as ex:
            return list(ex.map(lambda q: self.answer(*q), queries))


def control_answers(keys: np.ndarray, top_k: int,
                    queries) -> list[tuple[int, np.ndarray]]:
    """The reference in bfloat16 on JAX's default device."""
    import jax
    import jax.numpy as jnp

    dev_keys = jnp.asarray(np.asarray(keys, np.float32)).astype(jnp.bfloat16)

    @jax.jit
    def scan(k, lo, hi):
        hit = (k >= lo) & (k <= hi)
        ids = jnp.nonzero(hit, size=top_k, fill_value=-1)[0]
        return hit.sum(dtype=jnp.int32), ids

    out = []
    for lo, hi in queries:
        count, ids = scan(dev_keys, jnp.bfloat16(lo), jnp.bfloat16(hi))
        ids = np.asarray(ids)
        out.append((int(count), ids[ids >= 0].astype(np.int64)))
    return out
