"""Hippo-indexed data pipeline: predicate-filtered, deterministic, prefetched.

Selection runs Algorithm 1 over the corpus metadata table: the quality-range
predicate is AND-filtered against the page summaries, only possible-qualified
pages are inspected, and the exact qualifying sequence set comes back. The
pipeline then samples batches from that set with a *stateless* step->batch
mapping (a counter-based RNG keyed on (seed, step)), so restarts and elastic
re-sharding reproduce the exact same batch for any step — the checkpoint only
needs to store the step number (see runtime/fault.py).
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, field

import numpy as np

from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate
from repro.data.corpus import PagedCorpus


@dataclass
class HippoDataPipeline:
    corpus: PagedCorpus
    index: HippoIndex
    predicate: Predicate
    seed: int = 0
    selected_ids: np.ndarray = field(default=None)
    pages_inspected: int = 0

    @staticmethod
    def create(corpus: PagedCorpus, predicate: Predicate, *, resolution: int = 128,
               density: float = 0.15, seed: int = 0) -> "HippoDataPipeline":
        index = HippoIndex.create(corpus.table, resolution=resolution,
                                  density=density)
        pipe = HippoDataPipeline(corpus=corpus, index=index, predicate=predicate,
                                 seed=seed)
        pipe.refresh_selection()
        return pipe

    # -- selection (the paper's access path) ---------------------------------

    def refresh_selection(self) -> None:
        n = self.index.count(self.predicate)
        res = self.index.search_batch([self.predicate],
                                      top_k=1 << max(n - 1, 0).bit_length())
        ids = np.asarray(res.row_ids[0])            # ascending, -1 padded
        self.selected_ids = ids[(ids >= 0) & (ids < self.corpus.num_seqs)]
        self.pages_inspected = int(res.pages_inspected[0])
        if self.selected_ids.size == 0:
            raise ValueError("predicate selects no sequences")

    # -- deterministic batching ------------------------------------------------

    def batch_ids(self, step: int, batch_size: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, step))
        return rng.choice(self.selected_ids, size=batch_size,
                          replace=self.selected_ids.size < batch_size)

    def get_batch(self, step: int, batch_size: int) -> dict:
        ids = self.batch_ids(step, batch_size)
        toks = self.corpus.tokens[ids]
        b, s = toks.shape
        return {
            "inputs": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
            "positions": np.broadcast_to(np.arange(s - 1, dtype=np.int32)[None],
                                         (b, s - 1)).copy(),
        }

    # -- prefetch -----------------------------------------------------------------

    def iter_batches(self, start_step: int, num_steps: int, batch_size: int,
                     prefetch: int = 2):
        """Background-thread prefetched iterator (host-side input pipeline)."""
        q: queue.Queue = queue.Queue(maxsize=prefetch)
        stop = object()

        def producer():
            for s in range(start_step, start_step + num_steps):
                q.put((s, self.get_batch(s, batch_size)))
            q.put(stop)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is stop:
                break
            yield item
