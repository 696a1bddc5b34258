"""The one traffic generator: closed-loop query streams and an optional
refresh stream, read from a mix file.

A mix (``traffic/<name>.json``) is data only:

  source         where the streams and their predicates come from
  streams        closed-loop query streams; each round every stream submits
                 one range query and waits for its answer
  warmup_rounds  rounds run before the window (counted as set-up)
  templates      the queries a stream runs, each in one of two forms, keys
                 in the configuration's key unit:
                   ``windows``          the inclusive [lo, hi] key ranges its
                                        substitution parameters can give
                   ``width``, ``first`` a window of ``width`` keys whose first
                                        key is uniform over the whole numbers
                                        of the inclusive range ``first``
  refresh        optional: a refresh stream beside the query streams
                 (``Refresh``); ``function`` "RF1" (TPC-H's new sales) and
                 ``orders_per_round``

Each stream runs every template once per cycle, in an order drawn anew for
each cycle, and each run of a template takes one of its windows at random
(or its first key, for a ``width`` template). So every seed sends the same
templates equally often, in another order.
"""
from __future__ import annotations

import numpy as np

from chipbench import datagen


class Streams:
    """Rounds of one query per stream, from one seed."""

    def __init__(self, mix: dict, seed: int):
        self.streams = int(mix["streams"])
        self.templates = [_template(t) for t in mix["templates"]]
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
            t = self.templates[self._next_template(s)]
            if isinstance(t, tuple):
                width, lo, hi = t
                first = int(self._g.integers(lo, hi + 1))
                out.append(np.array([first, first + width - 1], np.int64))
            else:
                out.append(t[self._g.integers(len(t))])
        return np.stack(out).astype(np.float32)


def _template(t: dict):
    """A ``windows`` template as its (n, 2) array, a ``width`` template as
    (width, first_lo, first_hi)."""
    if "windows" in t:
        return np.asarray(t["windows"], np.int64)
    width, (lo, hi) = int(t["width"]), (int(k) for k in t["first"])
    if width < 1 or lo > hi:
        raise ValueError(f"template {t.get('name')!r}: width {width} and "
                         f"first [{lo}, {hi}] give no window")
    return width, lo, hi


class Refresh:
    """TPC-H's RF1 ("new sales", 3.0.1 sec. 2.5) as a stream of rounds, from
    one seed: each round takes the next ``orders_per_round`` new orders and
    yields their lineitems' keys, in orderkey order, by the configuration's
    own ``key`` rule (dbgen's: O_ORDERDATE over the same range, 1 to 7 lines
    an order, each line's key drawn from it). A round's line counts are
    conditioned on ``orders_per_round`` times the mean line count, as the
    load's are on the published rows, so every seed and round inserts the
    same number of rows."""

    def __init__(self, block: dict, config: dict, seed: int):
        if block.get("function") != "RF1":
            raise ValueError(f"refresh function {block.get('function')!r}: "
                             f"only RF1 (new sales) is generated")
        self.orders = int(block["orders_per_round"])
        lo, hi = config["lines_per_order"]
        if self.orders < 1 or (lo + hi) % 2:
            raise ValueError(f"RF1 of {self.orders} orders of {lo}-{hi} "
                             f"lines has no whole mean row count")
        self._lines = (lo, hi)
        self.rows_per_round = self.orders * (lo + hi) // 2
        self._key = config["key"]
        self._g = datagen.rng(seed, datagen.REFRESH)

    def rows(self) -> np.ndarray:
        """float32 keys of the next round's new lineitems."""
        for _ in range(100):
            try:
                counts = datagen.lines_per_order(
                    self._g, self.orders, *self._lines, self.rows_per_round)
                break
            except ValueError:
                # a draw of few orders too far from the mean to condition
                # by single lines: draw the round's line counts again
                continue
        else:
            raise ValueError(f"no line counts of {self.orders} orders sum "
                             f"to {self.rows_per_round}")
        return datagen.column_values(self._g, self._key, counts)


def refresh_of(mix: dict, config: dict, seed: int) -> Refresh | None:
    """The mix's refresh stream, or None for a read-only mix."""
    block = mix.get("refresh")
    return None if block is None else Refresh(block, config, seed)


def sample(seed: int, n_queries: int, last_round: range, k: int) -> np.ndarray:
    """Ascending indices of the window queries the reference checks: ``k``
    drawn from the seed, and every query of the last round."""
    g = datagen.rng(seed, datagen.SAMPLE)
    drawn = g.choice(n_queries, min(k, n_queries), replace=False)
    return np.union1d(drawn, np.asarray(last_round, np.int64))
