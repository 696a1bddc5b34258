"""One TPC-H lineitem column, drawn from a seed by dbgen's rules.

TPC-H 3.0.1 §4.2.3: the ORDERS table holds SF x 1,500,000 orders; each order
has 1 to 7 lineitems, stored in orderkey order. A column's value is the sum
of an order-level draw and a line-level draw, each uniform over whole
numbers, as the configuration's ``key`` rule states:

  l_shipdate  O_ORDERDATE uniform in [1992-01-01, 1998-12-31 - 151 days],
              plus uniform [1, 121] days per line (keys are days since
              1992-01-01)
  l_partkey   uniform in [1, SF x 200,000] per line (no order-level draw)

Only the indexed column is generated. Every key is a whole number below
2**24, so float32 holds it exactly.

The line counts are conditioned on the configuration's ``rows`` (the
published lineitem cardinality), so every seed loads a table of the same
size and the same tail fill: a run's work then differs between seeds only in
the values, not in the sizes. The few orders that the conditioning moves by
one line stay inside [1, 7].
"""
from __future__ import annotations

import numpy as np

# independent streams of one seed: the loaded table, the query streams, the
# checked sample, the refresh stream's new orders
LOAD, QUERIES, SAMPLE, REFRESH = 0, 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of ``seed`` (any integer, negative too)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2**64, stream]))


def key_range(config: dict) -> tuple[int, int]:
    """Smallest and largest key the column's rule can draw."""
    parts = [config["key"][k] for k in ("per_order", "per_line")
             if config["key"].get(k)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def lines_per_order(g: np.random.Generator, orders: int, lo: int, hi: int,
                    rows: int) -> np.ndarray:
    """(orders,) line counts uniform in [lo, hi], conditioned to sum to
    ``rows`` by moving randomly chosen orders one line up or down."""
    counts = g.integers(lo, hi + 1, orders, dtype=np.int32)
    diff = rows - int(counts.sum(dtype=np.int64))
    if diff:
        room = np.flatnonzero(counts < hi if diff > 0 else counts > lo)
        if room.size < abs(diff):
            raise ValueError(f"{orders} orders of {lo}-{hi} lines cannot "
                             f"hold {rows} rows")
        counts[g.choice(room, abs(diff), replace=False)] += np.sign(diff)
    return counts


def column_values(g: np.random.Generator, key: dict,
                  counts: np.ndarray) -> np.ndarray:
    """float32 keys of every line of the orders, in orderkey order."""
    n = int(counts.sum(dtype=np.int64))
    values = np.zeros(n, np.int32)
    if key.get("per_order"):
        lo, hi = key["per_order"]
        values += np.repeat(g.integers(lo, hi + 1, counts.size,
                                       dtype=np.int32), counts)
    if key.get("per_line"):
        lo, hi = key["per_line"]
        values += g.integers(lo, hi + 1, n, dtype=np.int32)
    return values.astype(np.float32)


def load_column(config: dict, seed: int) -> np.ndarray:
    """The indexed column of the whole table as loaded, in row-id order."""
    g = rng(seed, LOAD)
    lo, hi = config["lines_per_order"]
    counts = lines_per_order(g, config["orders"], lo, hi, config["rows"])
    return column_values(g, config["key"], counts)

