"""Property-based tests: Hippo's exactness invariant (§2 "guarantees the
query result accuracy") must hold for arbitrary data, parameters, predicates,
and maintenance histories."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st

from repro.core import index as hix
from repro.core.hippo import HippoIndex
from repro.core.predicate import Predicate, to_bucket_bitmap
from repro.storage.table import PagedTable


def brute_force(table, lo, hi):
    live = table.valid[: table.num_pages]
    keys = table.keys[: table.num_pages]
    return int((live & (keys >= lo) & (keys <= hi)).sum())


@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(5, 400),
    page_card=st.sampled_from([4, 8, 16]),
    resolution=st.sampled_from([8, 32, 64]),
    density=st.sampled_from([0.1, 0.25, 0.5, 0.9]),
    bounds=st.tuples(st.floats(-100, 100), st.floats(-100, 100)),
)
@settings(max_examples=25, deadline=None)
def test_search_always_exact(seed, n, page_card, resolution, density, bounds):
    rng = np.random.default_rng(seed)
    values = rng.uniform(-100, 100, n)
    table = PagedTable.from_values(values, page_card=page_card, spare_pages=8)
    idx = HippoIndex.create(table, resolution=resolution, density=density)
    lo, hi = min(bounds), max(bounds)
    pred = Predicate.between(lo, hi)
    res = idx.search(pred)
    assert int(res.counts[0]) == brute_force(table, lo, hi)
    # Soundness: every truly-qualified page is inspected (no false negatives).
    qual_pages = (
        table.valid[: table.num_pages]
        & (table.keys[: table.num_pages] >= lo)
        & (table.keys[: table.num_pages] <= hi)
    ).any(axis=1)
    qbm = to_bucket_bitmap(pred, idx.state.histogram)
    inspected = np.asarray(
        hix._page_match(idx.state, qbm[None, :], table.num_pages)[0][0])
    assert int(inspected.sum()) == int(res.pages_inspected[0])
    assert not (qual_pages & ~inspected).any()


@given(
    seed=st.integers(0, 2**31 - 1),
    ops=st.lists(
        st.one_of(
            st.tuples(st.just("insert"), st.floats(-50, 50, allow_nan=False)),
            st.tuples(st.just("delete"), st.floats(-50, 50, allow_nan=False)),
            st.tuples(st.just("vacuum"), st.just(0.0)),
        ),
        min_size=1,
        max_size=12,
    ),
)
@settings(max_examples=15, deadline=None)
def test_maintenance_history_preserves_exactness(seed, ops):
    rng = np.random.default_rng(seed)
    values = rng.uniform(-50, 50, 120)
    table = PagedTable.from_values(values, page_card=8, spare_pages=64)
    idx = HippoIndex.create(table, resolution=16, density=0.3)
    for op, arg in ops:
        if op == "insert":
            idx.insert(float(arg))
        elif op == "delete":
            table.delete_where(float(arg) - 2.0, float(arg) + 2.0)
        else:
            idx.vacuum()
        got = idx.count(Predicate.between(-10.0, 10.0))
        assert got == brute_force(table, -10.0, 10.0)
