"""The one traffic generator: closed-loop query streams, read from a mix file.

A mix (``traffic/<name>.json``) is data only:

  source         where the streams and their predicates come from
  streams        closed-loop query streams; each round every stream submits
                 one range query and waits for its answer
  warmup_rounds  rounds run before the window (counted as set-up)
  templates      the queries a stream runs, each with its ``windows``: the
                 inclusive [lo, hi] key ranges its substitution parameters
                 can give, in the configuration's key unit

Each stream runs every template once per cycle, in an order drawn anew for
each cycle, and each run of a template takes one of its windows at random.
So every seed sends the same templates equally often, in another order.
"""
from __future__ import annotations

import numpy as np

from chipbench import datagen


class Streams:
    """Rounds of one query per stream, from one seed."""

    def __init__(self, mix: dict, seed: int):
        self.streams = int(mix["streams"])
        self.templates = [np.asarray(t["windows"], np.int64)
                          for t in mix["templates"]]
        self._g = datagen.rng(seed, datagen.QUERIES)
        self._todo: list[list[int]] = [[] for _ in range(self.streams)]

    def _next_template(self, stream: int) -> int:
        if not self._todo[stream]:
            self._todo[stream] = self._g.permutation(
                len(self.templates)).tolist()
        return self._todo[stream].pop()

    def queries(self) -> np.ndarray:
        """(streams, 2) float32 inclusive [lo, hi] windows of one round."""
        out = []
        for s in range(self.streams):
            windows = self.templates[self._next_template(s)]
            out.append(windows[self._g.integers(len(windows))])
        return np.stack(out).astype(np.float32)


def sample(seed: int, n_queries: int, last_round: range, k: int) -> np.ndarray:
    """Ascending indices of the window queries the reference checks: ``k``
    drawn from the seed, and every query of the last round."""
    g = datagen.rng(seed, datagen.SAMPLE)
    drawn = g.choice(n_queries, min(k, n_queries), replace=False)
    return np.union1d(drawn, np.asarray(last_round, np.int64))
