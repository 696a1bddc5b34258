"""Learned summaries (``core.learned``, PR 7): the piecewise-linear CDF fit
and its boundary materialization must (a) satisfy the fit contract — fixed
segment budget, monotone knots, error-bounded against the boundary-allocation
CDF; (b) produce bounds indistinguishable *in correctness* from equal-mass
bounds — counts bit-identical to brute force across selectivity x shard
count x staged overlay, including mid-resummarize mixed epochs; and (c) wire
through the policy surfaces — index ``summary`` knob, writer refit + per-shard
model recording, engine stats — with the equal-mass path as fallback/oracle.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import histogram as hg
from repro.core import learned as ln
from repro.core.partition import SUMMARY_POLICIES, ShardedHippoIndex
from repro.core.predicate import Predicate
from repro.runtime import writer as writer_mod
from repro.runtime.engine import QueryEngine
from repro.runtime.writer import MaintenanceWriter
from repro.storage.table import PagedTable

pytestmark = pytest.mark.learned


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


def sweep_preds(values):
    """Selectivity sweep anchored on the data's quantiles: empty, point,
    narrow, medium, wide, full-table."""
    q = np.quantile(values, [0.1, 0.12, 0.5, 0.7, 0.02, 0.98])
    return [
        Predicate(lo=5.0, hi=1.0),                       # empty
        Predicate.equality(float(values[len(values) // 2])),
        Predicate.between(float(q[0]), float(q[1])),     # ~2% band
        Predicate.between(float(q[2]), float(q[3])),     # ~20% band
        Predicate.between(float(q[4]), float(q[5])),     # ~96% band
        Predicate.between(-1e30, 1e30),                  # full table
    ]


# ---------------------------------------------------------------------------
# Fit contract
# ---------------------------------------------------------------------------

def test_fit_cdf_monotone_error_bounded_fixed_shape():
    rng = np.random.default_rng(0)
    sample = rng.lognormal(0.0, 1.5, 20_000).astype(np.float32)
    for segments in (4, 16, 64):
        m = ln.fit_cdf(sample, segments=segments)
        assert m.knots_x.shape == (segments + 1,)     # fixed padded shape
        assert m.knots_y.shape == (segments + 1,)
        assert 2 <= m.n_knots <= segments + 1
        kx, ky = m.knots_x[: m.n_knots], m.knots_y[: m.n_knots]
        assert (np.diff(kx) > 0).all() and (np.diff(ky) >= 0).all()
        assert 0.0 <= ky[0] and ky[-1] == pytest.approx(1.0)
        # achieved error is a true sup-norm bound over the fit points
        x, y = ln._weighted_cdf_points(sample, None)
        assert np.abs(m.cdf(x) - y).max() <= m.max_error + 1e-12
    # more segments never fit worse
    errs = [ln.fit_cdf(sample, segments=s).max_error for s in (4, 16, 64)]
    assert errs[0] >= errs[1] >= errs[2]


def test_fit_cdf_exact_when_budget_covers_the_points():
    x = np.asarray([0.0, 1.0, 2.0, 10.0], np.float32)
    m = ln.fit_cdf(x, segments=8)
    assert m.max_error == pytest.approx(0.0, abs=1e-12)
    assert m.used_segments <= 3


def test_fit_cdf_degenerate_and_validation():
    with pytest.raises(ln.DegenerateSample):
        ln.fit_cdf(np.full(100, 3.0, np.float32))
    with pytest.raises(ln.DegenerateSample):
        ln.fit_cdf(np.zeros(0, np.float32))
    with pytest.raises(ValueError, match="segments"):
        ln.fit_cdf(np.asarray([1.0, 2.0]), segments=0)
    with pytest.raises(ValueError, match="weights shape"):
        ln.fit_cdf(np.asarray([1.0, 2.0]), np.asarray([1.0]))
    with pytest.raises(ValueError, match="positive total"):
        ln.fit_cdf(np.asarray([1.0, 2.0]), np.asarray([0.0, 0.0]))


def test_mass_clamp_water_fills_heavy_hitters():
    """The boundary-allocation correction: per-key mass caps at the clamp,
    total stays 1, and the freed mass redistributes proportionally; when
    every key saturates the allocation goes uniform."""
    mass = np.asarray([0.6, 0.2, 0.1, 0.05, 0.05])
    out = ln._clamp_masses(mass, 0.25)
    assert out.sum() == pytest.approx(1.0)
    assert out.max() <= 0.25 + 1e-12
    assert out[0] == pytest.approx(0.25)          # heavy hitter capped
    assert (np.diff(out[1:]) <= 1e-12).all()      # order preserved below cap
    # unclamped distributions pass through untouched
    np.testing.assert_array_equal(ln._clamp_masses(np.full(8, 0.125), 0.25),
                                  np.full(8, 0.125))
    # fewer distinct keys than buckets: uniform is the fixed point
    np.testing.assert_allclose(
        ln._clamp_masses(np.asarray([0.9, 0.1]), 0.05), [0.5, 0.5])


def test_boundaries_strict_and_writer_drain_valid():
    """Materialized bounds always satisfy the writer's drain validation:
    (H+1,) float32, strictly increasing — even from duplicate-heavy and
    large-magnitude samples."""
    rng = np.random.default_rng(1)
    samples = [
        rng.zipf(1.3, 30_000).astype(np.float32),
        # float32 ulp at 1e9 is 64: ~150 distinct values < H=400, so the
        # materialized grid must fall back on whole-ulp separation
        (1e9 + rng.uniform(0, 1e4, 5000)).astype(np.float32),
        np.asarray([1.0, 1.0, 1.0, 2.0], np.float32),
    ]
    for sample in samples:
        for resolution in (8, 64, 400):
            hist, model = ln.build_histogram(sample, resolution)
            b = np.asarray(hist.bounds)
            assert b.shape == (resolution + 1,) and b.dtype == np.float32
            assert (np.diff(b) > 0).all()
            assert model is not None


def test_build_histogram_fallback_on_degenerate_sample():
    hist, model = ln.build_histogram(np.full(100, 7.0, np.float32), 16)
    assert model is None
    b = np.asarray(hist.bounds)
    assert b.shape == (17,) and (np.diff(b) > 0).all()


def test_learned_bounds_use_more_buckets_on_duplicate_heavy_keys():
    """The pruning mechanism the benchmark measures: equal-mass quantiles
    tie on heavy values and ladder into empty stripes; the learned fit
    clamps per-key mass and spends those boundaries where tuples are."""
    rng = np.random.default_rng(2)
    z = rng.zipf(1.3, 100_000).astype(np.float64)
    z = z[z < 20_000].astype(np.float32)
    H = 400

    def occupied(hist):
        ids = np.asarray(hg.bucketize(hist, jnp.asarray(z)))
        return np.unique(ids).size

    eq = occupied(hg.build(jnp.asarray(z), H))
    lr = occupied(ln.build_histogram(z, H)[0])
    assert lr >= 1.3 * eq, (eq, lr)


def test_learned_rebuild_favors_reservoir_resolution():
    """The drift-refit lever: the reservoir carries 1 - OLD_MASS_FRACTION
    of the boundary budget, strictly more than rebuild's equal-mass half."""
    rng = np.random.default_rng(3)
    base = hg.build(jnp.asarray(rng.uniform(0, 1e5, 65536)), 100)
    res = rng.uniform(3e5, 3.1e5, 4096).astype(np.float32)
    learned_b = np.asarray(ln.learned_rebuild(base, res, 100)[0].bounds)
    eq_b = np.asarray(hg.rebuild(base, res, 100).bounds)

    def in_window(b):
        return int(((b >= 3e5) & (b <= 3.11e5)).sum())

    assert in_window(learned_b) > in_window(eq_b)
    assert (np.diff(learned_b) > 0).all()
    with pytest.raises(ValueError, match="non-empty sample"):
        ln.learned_rebuild(base, np.zeros(0))
    with pytest.raises(ValueError, match="old_mass"):
        ln.learned_rebuild(base, res, old_mass=1.0)


# ---------------------------------------------------------------------------
# The acceptance invariant: learned bounds never change a count
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [1, 4])
@pytest.mark.parametrize("staged", [False, True])
def test_learned_counts_bit_identical(num_shards, staged):
    """Counts vs brute force across selectivity x shard count x staged
    overlay on the engine, the index's search and the engine's
    gather-and-fallback ladder — under learned
    build-time bounds, then through a learned drift refit (remap drained
    alone, rows still staged), then fully drained."""
    rng = np.random.default_rng(5 * num_shards + staged)
    base = np.sort(np.concatenate([
        rng.uniform(0, 100, 240),
        rng.choice(np.asarray([20.0, 50.0], np.float32), 60),  # heavy ties
    ]))
    aidx = make_sidx(base, num_shards=num_shards, summary="learned")
    assert aidx.summary == "learned"
    assert all(m is not None for m in aidx.summary_models)
    engine = QueryEngine(aidx, batch=8, drain_policy="manual",
                         auto_resummarize=False)
    drained = rng.uniform(100, 130, 48)
    for v in drained:
        engine.write(float(v))
    engine.flush()
    pending = rng.uniform(125, 140, 12) if staged else np.zeros(0)
    for v in pending:
        engine.write(float(v))

    preds = sweep_preds(base) + [Predicate.between(105.0, 112.0)]
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

    check_all_paths("learned build-time bounds")
    w = engine.writer
    w.schedule_resummarize()                  # index policy: learned refit
    assert w.stats.learned_refits == 1 and w._pending_model is not None
    w.drain(max_units=num_shards)             # remap first, rows stay staged
    assert w.queue_depth == pending.size
    assert list(aidx.bounds_epochs) == [1] * num_shards
    assert all(m is not None for m in aidx.summary_models)
    check_all_paths("after learned resummarize, rows still staged")
    engine.flush()
    want = brute_force(aidx.table, preds)
    check_all_paths("after learned resummarize + drain")


def test_learned_mixed_epochs_serve_exactly():
    """A partially drained learned remap: some shards on the fitted bounds,
    some on the old — per-shard predicate conversion keeps every path exact,
    and models swap in per shard, not wholesale."""
    rng = np.random.default_rng(17)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 400)), summary="learned")
    writer = MaintenanceWriter(aidx)
    for v in rng.uniform(100, 120, 32):
        writer.write(float(v))
    writer.flush()
    preds = sweep_preds(np.asarray(
        aidx.table.keys[: aidx.table.num_pages]).ravel())
    want = brute_force(aidx.table, preds)
    build_models = list(aidx.summary_models)
    writer.schedule_resummarize()
    writer.drain(max_units=2)
    assert list(aidx.bounds_epochs) == [1, 1, 0, 0]
    assert aidx.summary_models[0] is not build_models[0]    # refit swapped in
    assert aidx.summary_models[3] is build_models[3]        # still the old one
    np.testing.assert_array_equal(
        np.asarray(aidx.search_batch(preds).counts), want)
    engine = QueryEngine(aidx, batch=8, drain_policy="manual", writer=writer)
    np.testing.assert_array_equal(engine.run_all(preds), want)
    writer.flush()
    refit = aidx.summary_models[0]
    assert all(m is refit for m in aidx.summary_models)
    np.testing.assert_array_equal(engine.run_all(preds), want)


# ---------------------------------------------------------------------------
# Policy plumbing: knobs, stats, fallback
# ---------------------------------------------------------------------------

def test_summary_policy_validation():
    rng = np.random.default_rng(19)
    vals = rng.uniform(0, 100, 100)
    with pytest.raises(ValueError, match="summary"):
        make_sidx(vals, summary="nope")
    aidx = make_sidx(vals)
    assert aidx.summary == "equal_mass"
    assert aidx.summary_models == [None] * aidx.num_shards
    with pytest.raises(ValueError, match="summary"):
        QueryEngine(aidx, summary="nope")
    writer = MaintenanceWriter(aidx)
    with pytest.raises(ValueError, match="policy"):
        writer.schedule_resummarize(policy="nope")
    assert "equal_mass" in SUMMARY_POLICIES and "learned" in SUMMARY_POLICIES


def test_engine_summary_knob_overrides_index_policy():
    """An equal-mass index driven by an engine with summary='learned' refits
    learned (and vice versa): the engine knob wins over the index default."""
    rng = np.random.default_rng(23)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 300)))    # equal_mass index
    engine = QueryEngine(aidx, batch=8, drain_policy="manual",
                         auto_resummarize=False, summary="learned")
    for v in rng.uniform(100, 120, 32):
        engine.write(float(v))
    engine.resummarize()
    assert engine.stats.learned_refits == 1
    assert engine.stats.learned_fallbacks == 0
    assert all(m is not None for m in aidx.summary_models)
    # and the reverse: learned index, engine forces the equal-mass oracle
    lidx = make_sidx(np.sort(rng.uniform(0, 100, 300)), summary="learned")
    oracle = QueryEngine(lidx, batch=8, drain_policy="manual",
                         auto_resummarize=False, summary="equal_mass")
    for v in rng.uniform(100, 120, 32):
        oracle.write(float(v))
    oracle.resummarize()
    assert oracle.stats.learned_refits == 0
    assert all(m is None for m in lidx.summary_models)


def test_auto_resummarize_uses_index_policy():
    """The drift auto-trigger inherits the learned policy from the index:
    no engine configuration needed for a learned index to stay learned."""
    rng = np.random.default_rng(29)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 200)), summary="learned")
    engine = QueryEngine(aidx, batch=4, drift_threshold=0.5,
                         drift_min_observed=8)
    for v in rng.uniform(100, 115, 16):
        engine.write(float(v))
    assert engine.writer.stats.learned_refits == 1
    while engine.writer.pending_units:
        engine.run_all([Predicate.between(0.0, 1e9)])
    assert engine.stats.learned_refits == 1
    assert all(m is not None for m in aidx.summary_models)


def test_learned_fallback_records_stat_and_equal_mass_bounds(monkeypatch):
    """When the learned fit declines (degenerate reservoir), the schedule
    falls back to equal-mass bounds, counts stay exact, models record None,
    and ``learned_fallbacks`` — not ``learned_refits`` — ticks."""
    rng = np.random.default_rng(31)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 300)), summary="learned")
    writer = MaintenanceWriter(aidx)
    for v in rng.uniform(100, 120, 32):
        writer.write(float(v))
    writer.flush()

    def degenerate(hist, sample, *a, **kw):
        return hg.rebuild(hist, sample), None

    monkeypatch.setattr(writer_mod.ln, "learned_rebuild", degenerate)
    preds = sweep_preds(np.asarray(
        aidx.table.keys[: aidx.table.num_pages]).ravel())
    want = brute_force(aidx.table, preds)
    writer.schedule_resummarize()
    assert writer.stats.learned_fallbacks == 1
    assert writer.stats.learned_refits == 0
    writer.flush()
    assert all(m is None for m in aidx.summary_models)
    np.testing.assert_array_equal(
        np.asarray(aidx.search_batch(preds).counts), want)


def test_explicit_bounds_clear_pending_model():
    """A manual-bounds schedule is policy-free: whatever the index policy,
    the drained shards record no model (the bounds came from the caller)."""
    rng = np.random.default_rng(37)
    aidx = make_sidx(np.sort(rng.uniform(0, 100, 300)), summary="learned")
    writer = MaintenanceWriter(aidx)
    writer.schedule_resummarize(
        np.linspace(-1.0, 101.0, aidx.cfg.resolution + 1))
    writer.flush()
    assert all(m is None for m in aidx.summary_models)
    assert writer.stats.learned_refits == 0
