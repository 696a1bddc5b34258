"""The comparison that decides ``correct``.

Each sampled query's answer is held against the reference's: the count must
be equal, and the row ids must be the reference's first ``top_k`` row ids,
ascending. Both are exact (the configuration's guarantee), so every number
compared has the limit 0. A query with no answer is counted apart, and so
is a refresh write that raised: its rows were never acknowledged.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"unanswered": 0, "count_mismatches": 0, "rowid_mismatches": 0,
          "refresh_failures": 0}
# window queries drawn from the seed for the comparison, besides every query
# of the last round
SAMPLE = 48


def compare(got, want, refresh_failures: int = 0) -> dict[str, int]:
    """Numbers compared, from parallel lists of (count, row_ids) answers;
    a ``got`` answer of ``None`` never came. ``refresh_failures`` counts the
    refresh writes that raised."""
    out = dict.fromkeys(LIMITS, 0)
    out["refresh_failures"] = refresh_failures
    for g, (count, ids) in zip(got, want, strict=True):
        if g is None or g[0] is None:
            out["unanswered"] += 1
            continue
        out["count_mismatches"] += int(g[0] != count)
        g_ids = np.asarray(g[1] if g[1] is not None else [], np.int64)
        out["rowid_mismatches"] += int(not np.array_equal(g_ids, ids))
    return out


def is_correct(numbers: dict[str, int]) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())


def with_limits(numbers: dict[str, int]) -> dict[str, dict]:
    return {k: {"value": numbers[k], "limit": lim} for k, lim in LIMITS.items()}
