"""Plain reference of an inclusive range query over one key column that
grows by appends.

The table is the loaded column in row-id order, followed by the inserted
rows in the order they were acknowledged: a heap append, so the k-th
acknowledged insert is row ``rows + k``. A query (lo, hi, acked) sees the
loaded rows and the first ``acked`` inserts (those acknowledged before it
was submitted), and is answered with the number of those rows whose key
lies in [lo, hi] and the first ``top_k`` of their row ids, ascending. Keys
and bounds are float32, as the configuration states.

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
    def __init__(self, keys: np.ndarray, top_k: int,
                 inserts: np.ndarray | None = None):
        self.keys = np.asarray(keys, np.float32)
        self.inserts = np.asarray([] if inserts is None else inserts,
                                  np.float32)
        self.top_k = top_k

    def answer(self, lo: float, hi: float,
               acked: int = 0) -> tuple[int, np.ndarray]:
        lo, hi = np.float32(lo), np.float32(hi)
        if acked > self.inserts.size:
            raise ValueError(f"{acked} inserts acknowledged, "
                             f"{self.inserts.size} known")
        count, ids = 0, []
        parts = [(self.keys, 0), (self.inserts[:acked], self.keys.size)]
        for col, base in parts:
            for start in range(0, col.size, CHUNK):
                part = col[start:start + CHUNK]
                hit = (part >= lo) & (part <= hi)
                n = int(np.count_nonzero(hit))
                if n and len(ids) < self.top_k:
                    ids.extend((np.flatnonzero(hit)[: self.top_k - len(ids)]
                                + base + start).tolist())
                count += n
        return count, np.asarray(ids, np.int64)

    def answers(self, queries) -> list[tuple[int, np.ndarray]]:
        """Answers of (lo, hi) or (lo, hi, acked) queries; numpy releases
        the GIL inside each scan, so a few threads overlap them."""
        workers = min(8, os.cpu_count() or 1)
        with ThreadPoolExecutor(max_workers=workers) as ex:
            return list(ex.map(lambda q: self.answer(*q), queries))


def control_answers(keys: np.ndarray, top_k: int, queries,
                    inserts: np.ndarray | None = None
                    ) -> list[tuple[int, np.ndarray]]:
    """The reference in bfloat16 on JAX's default device, over the loaded
    rows and each query's acknowledged inserts."""
    import jax
    import jax.numpy as jnp

    def bf16(a):
        return jnp.asarray(np.asarray(a, np.float32)).astype(jnp.bfloat16)

    dev_keys = bf16(keys)
    dev_inserts = bf16([] if inserts is None else inserts)

    def hits(k, lo, hi):
        hit = (k >= lo) & (k <= hi)
        ids = jnp.nonzero(hit, size=top_k, fill_value=-1)[0]
        return hit.sum(dtype=jnp.int32), ids

    scan = jax.jit(hits)
    # the first n inserts: rows past the prefix never hit
    scan_prefix = jax.jit(lambda k, lo, hi, n: hits(
        jnp.where(jnp.arange(k.size) < n, k, jnp.inf), lo, hi))

    out = []
    for lo, hi, *acked in queries:
        lo, hi = jnp.bfloat16(lo), jnp.bfloat16(hi)
        count, ids = scan(dev_keys, lo, hi)
        ids = np.asarray(ids)
        ids = ids[ids >= 0].astype(np.int64)
        n = acked[0] if acked else 0
        if n:
            extra, more = scan_prefix(dev_inserts, lo, hi, n)
            more = np.asarray(more)
            ids = np.concatenate([ids, more[more >= 0] + keys.size])[:top_k]
            count += extra
        out.append((int(count), ids))
    return out
