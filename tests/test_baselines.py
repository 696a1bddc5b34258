import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.baselines import BPlusTree, FullScan, MinMaxIndex
from repro.core.hippo import HippoIndex
from repro.storage.table import PagedTable
from repro.core.predicate import Predicate


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    values = rng.uniform(0, 1000, 5000)
    table = PagedTable.from_values(values, page_card=50, spare_pages=32)
    return values, table


def test_btree_range_search_exact(data):
    values, table = data
    t = BPlusTree.bulk_load(values, page_card=50, fanout=32)
    for lo, hi in [(0, 1000), (100, 110), (500.5, 500.6), (-10, -5)]:
        got = t.count_range(lo, hi)
        want = int(((values >= lo) & (values <= hi)).sum())
        assert got == want


def test_btree_insert_and_split(data):
    values, _ = data
    t = BPlusTree.bulk_load(values[:500], page_card=50, fanout=16)
    rng = np.random.default_rng(1)
    extra = rng.uniform(0, 1000, 200)
    for i, v in enumerate(extra):
        t.insert(float(v), i)
    assert t.num_keys == 700
    assert t.io.node_splits > 0
    all_vals = np.concatenate([values[:500], extra])
    assert t.count_range(0, 1000) == int(((all_vals >= 0) & (all_vals <= 1000)).sum())


def test_btree_delete(data):
    values, _ = data
    t = BPlusTree.bulk_load(values[:100], page_card=50, fanout=16)
    v = float(np.float32(values[7]))
    assert t.delete(v)
    assert t.num_keys == 99


def test_btree_storage_dominates_hippo(data):
    """Table 1a / Fig 6a: per-tuple B+-Tree entries vs Hippo page summaries."""
    values, table = data
    t = BPlusTree.bulk_load(values, page_card=50, fanout=256)
    idx = HippoIndex.create(table, resolution=400, density=0.2)
    assert t.nbytes() > 10 * idx.nbytes()


def test_minmax_exact_but_weak_on_unordered(data):
    values, table = data
    mm = MinMaxIndex.build(table.device_keys(), table.device_valid(), pages_per_range=1)
    hippo = HippoIndex.create(table, resolution=400, density=0.2)
    lo, hi = 500.0, 501.0  # SF ~ 0.1%
    cnt, pages = mm.search(table.device_keys(), table.device_valid(), lo, hi)
    want = int(((values >= lo) & (values <= hi)).sum())
    assert int(cnt) == want
    res = hippo.search(Predicate.between(lo, hi))
    # On unordered data, min-max ranges cover everything -> near full scan,
    # while Hippo prunes (§8's motivating comparison).
    assert int(pages) > 0.9 * table.num_pages
    assert int(res.pages_inspected[0]) < 0.5 * table.num_pages


def test_minmax_strong_on_sorted():
    values = np.sort(np.random.default_rng(2).uniform(0, 1000, 5000))
    table = PagedTable.from_values(values, page_card=50)
    mm = MinMaxIndex.build(table.device_keys(), table.device_valid())
    cnt, pages = mm.search(table.device_keys(), table.device_valid(), 100.0, 110.0)
    assert int(cnt) == int(((values >= 100) & (values <= 110)).sum())
    assert int(pages) < 0.05 * table.num_pages


def test_fullscan(data):
    values, table = data
    cnt, pages = FullScan.search(table.device_keys(), table.device_valid(), 100.0, 200.0)
    assert int(cnt) == int(((values >= 100) & (values <= 200)).sum())
    assert int(pages) == table.num_pages
