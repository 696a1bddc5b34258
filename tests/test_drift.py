"""Drift-adaptive re-summarization: a remap onto new histogram bounds must
never change a single count — before, during (partially drained, mixed
bounds epochs), or after — through the index's search, the engine at
its slab bucket and at the cap's fallback, and the staged overlay; a
refused remap must roll back
cleanly with the old bounds still serving; and the auto trigger must
schedule and drain through the normal policies."""
import numpy as np
import pytest

from repro.core.partition import ShardedHippoIndex
from repro.core.predicate import Predicate
from repro.runtime.engine import QueryEngine
from repro.runtime.writer import MaintenanceWriter
from repro.storage.table import PagedTable

pytestmark = pytest.mark.drift


def make_sidx(values, num_shards=4, page_card=8, resolution=32, density=0.25,
              spare_pages=256, **kw):
    table = PagedTable.from_values(np.asarray(values).copy(),
                                   page_card=page_card,
                                   spare_pages=spare_pages)
    return ShardedHippoIndex.create(table, num_shards=num_shards,
                                    resolution=resolution, density=density,
                                    **kw)


def brute_force(table, preds) -> np.ndarray:
    live = table.valid[: table.num_pages]
    keys = table.keys[: table.num_pages]
    return np.asarray([(live & (keys >= p.lo) & (keys <= p.hi)).sum()
                       for p in preds], np.int64)


def drift_preds():
    """A small selectivity sweep: empty, point, narrow-in-base,
    narrow-in-drifted-region, spanning, and full-table predicates."""
    return [
        Predicate(lo=5.0, hi=1.0),              # empty
        Predicate.equality(50.0),               # point (may be 0: still exact)
        Predicate.between(20.0, 24.0),          # narrow, pre-drift region
        Predicate.between(108.0, 114.0),        # narrow, drifted region
        Predicate.between(80.0, 125.0),         # spans the old range boundary
        Predicate.between(-1e30, 1e30),         # full table
    ]


# ---------------------------------------------------------------------------
# The acceptance invariant: counts bit-identical around a remap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [1, 4])
@pytest.mark.parametrize("staged", [False, True])
def test_resummarize_counts_bit_identical(num_shards, staged):
    """Counts against brute force across selectivity x shard count x
    staged-overlay, on the engine, the index's search and the engine's
    gather-and-fallback ladder, before a
    remap, after the remap alone (staged rows still queued), and after the
    rows drain."""
    rng = np.random.default_rng(3 * num_shards + staged)
    base = np.sort(rng.uniform(0, 100, 300))
    aidx = make_sidx(base, num_shards=num_shards)
    engine = QueryEngine(aidx, batch=8, drain_policy="manual",
                         auto_resummarize=False)
    drained = rng.uniform(100, 130, 48)          # drift beyond the base range
    for v in drained:
        engine.write(float(v))
    engine.flush()                               # landed under the old bounds
    pending = rng.uniform(125, 140, 12) if staged else np.zeros(0)
    for v in pending:
        engine.write(float(v))

    preds = drift_preds()
    want = brute_force(aidx.table, preds) + np.asarray(
        [((pending >= p.lo) & (pending <= p.hi)).sum() for p in preds])

    def check_all_paths(msg):
        np.testing.assert_array_equal(engine.run_all(preds), want, err_msg=msg)
        np.testing.assert_array_equal(
            np.asarray(aidx.search_batch(preds).counts), want, err_msg=msg)
        # a one-page slab walks the gather branch and the cap's fallback
        narrow = QueryEngine(aidx, batch=8, compact_bucket=1,
                             drain_policy="manual", writer=engine.writer)
        np.testing.assert_array_equal(narrow.run_all(preds), want, err_msg=msg)

    check_all_paths("before resummarize")
    w = engine.writer
    w.schedule_resummarize()
    w.drain(max_units=num_shards)        # remap units drain first, rows stay
    assert w.queue_depth == pending.size
    assert list(aidx.bounds_epochs) == [1] * num_shards
    check_all_paths("after resummarize, rows still staged")
    engine.flush()
    assert w.queue_depth == 0
    want = brute_force(aidx.table, preds)
    check_all_paths("after resummarize + drain")


def test_partial_resummarize_serves_mixed_epochs_exactly():
    """A partially drained remap leaves shards on different bounds epochs;
    every path must stay exact through the mix (per-shard conversion)."""
    rng = np.random.default_rng(17)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 400)))
    writer = MaintenanceWriter(aidx)
    for v in rng.uniform(100, 120, 32):
        writer.write(float(v))
    writer.flush()
    preds = drift_preds()
    want = brute_force(aidx.table, preds)
    writer.schedule_resummarize()
    writer.drain(max_units=2)
    assert list(aidx.bounds_epochs) == [1, 1, 0, 0]    # mid-transition
    np.testing.assert_array_equal(
        np.asarray(aidx.search_batch(preds).counts), want)
    engine = QueryEngine(aidx, batch=8, drain_policy="manual", writer=writer)
    np.testing.assert_array_equal(engine.run_all(preds), want)
    narrow = QueryEngine(aidx, batch=8, compact_bucket=1,
                         drain_policy="manual", writer=writer)
    np.testing.assert_array_equal(narrow.run_all(preds), want)
    writer.flush()
    assert list(aidx.bounds_epochs) == [1, 1, 1, 1]
    np.testing.assert_array_equal(engine.run_all(preds), want)


def test_resummarize_refusal_rolls_back():
    """A remap that refuses at drain time (invalid pending bounds) releases
    the swap guard, keeps the old bounds serving exactly, and leaves the
    unit pending for a corrected schedule."""
    rng = np.random.default_rng(23)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 200)))
    writer = MaintenanceWriter(aidx)
    preds = drift_preds()
    want = brute_force(aidx.table, preds)
    writer.schedule_resummarize(np.linspace(0.0, 100.0, 10))   # wrong length
    with pytest.raises(RuntimeError, match="resummarize refused"):
        writer.flush()
    assert aidx.swap_in_flight is None                 # guard released
    assert list(aidx.bounds_epochs) == [0, 0, 0, 0]    # old bounds serving
    assert len(writer.pending_resummarize_shards()) == aidx.num_shards
    assert writer.stats.resummarizes == 0
    np.testing.assert_array_equal(
        np.asarray(aidx.search_batch(preds).counts), want)
    # rescheduling replaces the pending bounds; the retry drains cleanly
    # (the refused round consumed no epoch: nothing was applied under it)
    writer.schedule_resummarize(
        np.linspace(-1.0, 101.0, aidx.cfg.resolution + 1))
    writer.flush()
    assert list(aidx.bounds_epochs) == [1, 1, 1, 1]
    np.testing.assert_array_equal(
        np.asarray(aidx.search_batch(preds).counts), want)


# ---------------------------------------------------------------------------
# Policy: the auto trigger and the pruning payoff
# ---------------------------------------------------------------------------

def test_auto_resummarize_triggers_and_drains_via_policy():
    """Drifting writes cross the edge-overflow threshold -> a remap is
    scheduled automatically and the between-batches policy drains it off the
    query path, counts exact at every step; completion rearms the tracker."""
    rng = np.random.default_rng(29)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 200)))
    engine = QueryEngine(aidx, batch=4, drift_threshold=0.5,
                         drift_min_observed=8)     # between_batches default
    written = rng.uniform(100, 115, 16).astype(np.float32)
    for v in written:                              # all beyond the old range
        engine.write(float(v))
    assert engine.writer.pending_resummarize_shards() == [0, 1, 2, 3]
    assert engine.stats.edge_overflow_ratio == 1.0
    preds = drift_preds()
    # every written row counts, whether it still waits staged or has landed
    want = brute_force(aidx.table, preds) + np.asarray(
        [((written >= p.lo) & (written <= p.hi)).sum() for p in preds])
    assert engine.writer.queue_depth == written.size
    while engine.writer.pending_units:
        np.testing.assert_array_equal(engine.run_all(preds), want)
    np.testing.assert_array_equal(engine.run_all(preds), want)
    assert engine.stats.resummarizes == aidx.num_shards
    assert engine.stats.edge_overflow_ratio == 0.0   # tracker rearmed
    assert not engine.writer.pending_resummarize_shards()
    # in-range writes never re-trigger
    for v in rng.uniform(50, 115, 16):
        engine.write(float(v))
    assert not engine.writer.pending_resummarize_shards()


def test_resummarize_restores_pruning_quality():
    """The perf mechanism: after monotone drift, a remap gives the drifted
    region real bucket resolution, so a narrow query there inspects far
    fewer pages than under the clamped build-time bounds (counts equal brute
    force on both)."""
    rng = np.random.default_rng(31)
    base = np.sort(rng.uniform(0, 100, 800))
    drift = np.sort(rng.uniform(100, 120, 160))    # append-ordered drift keys
    engines = {}
    for adaptive in (False, True):
        aidx = make_sidx(base, resolution=64, density=0.1)
        engine = QueryEngine(aidx, batch=4, drain_policy="manual",
                             auto_resummarize=False)
        for v in drift:
            engine.write(float(v))
        if adaptive:
            engine.resummarize()     # remap first, then the rows drain
        else:
            engine.flush()
        engines[adaptive] = engine
    pred = Predicate.between(108.0, 111.0)
    insp = {k: int(np.asarray(e.index.search_batch([pred]).pages_inspected)[0])
            for k, e in engines.items()}
    for e in engines.values():
        np.testing.assert_array_equal(
            e.run_all([pred]), brute_force(e.index.table, [pred]))
    assert insp[True] < insp[False], insp
    # window measurement around the remap landed in the stats
    st = engines[True].stats
    assert st.resummarizes == 4
    assert st.pruning_before_resummarize == 0.0    # no batches ran before it


@pytest.mark.parametrize("reservoir", ["constant", "duplicate_heavy",
                                       "single_point_drift"])
def test_resummarize_remap_bit_identical_under_adversarial_bounds(reservoir):
    """Satellite invariant: bounds rebuilt from a degenerate reservoir
    (constant, duplicate-heavy, single far point) still pass the drain's
    strictness validation, and the remap changes no count on any path —
    the epsilon-laddered buckets are empty, not wrong."""
    rng = np.random.default_rng(41)
    samples = {
        "constant": np.full(256, 42.0, np.float32),
        "duplicate_heavy": rng.choice(
            np.asarray([10.0, 20.0, 30.0], np.float32), 256),
        "single_point_drift": np.full(256, 1e6, np.float32),
    }
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 300)))
    writer = MaintenanceWriter(aidx)
    preds = drift_preds()
    want = brute_force(aidx.table, preds)
    from repro.core import histogram as hg
    bounds = np.asarray(hg.rebuild(aidx.histogram,
                                   samples[reservoir]).bounds)
    assert (np.diff(bounds) > 0).all()
    writer.schedule_resummarize(bounds)
    writer.flush()                     # a refusal would raise here
    assert list(aidx.bounds_epochs) == [1] * aidx.num_shards
    np.testing.assert_array_equal(
        np.asarray(aidx.search_batch(preds).counts), want)
    engine = QueryEngine(aidx, batch=8, drain_policy="manual", writer=writer)
    np.testing.assert_array_equal(engine.run_all(preds), want)


def test_engine_drift_knob_validation():
    rng = np.random.default_rng(37)
    aidx = make_sidx(rng.uniform(0, 100, 100))
    with pytest.raises(ValueError, match="drift_threshold"):
        QueryEngine(aidx, drift_threshold=0.0)
    with pytest.raises(RuntimeError, match="writer-backed"):
        QueryEngine(aidx, drain_policy="sync").resummarize()
    writer = MaintenanceWriter(aidx)
    with pytest.raises(RuntimeError, match="no drift sample"):
        writer.schedule_resummarize()
