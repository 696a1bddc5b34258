"""Benchmark harness: one module per paper table/figure or subsystem claim.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).
Scale with --quick for CI-speed runs; ``--list`` prints every registered
benchmark with the one-line description from its module docstring;
``--json out.json`` additionally writes the machine-readable result set
(per-suite rows with parsed derived fields plus the run config, strict
JSON — nan/inf sanitized to null) so the repo can accumulate
``BENCH_*.json`` trajectory files across PRs; ``--check BASELINE.json``
turns the run into a regression gate — the fresh rows are compared against
the committed trajectory and the process exits nonzero with a per-row
delta table when any suite's ``qps`` or ``achieved_gbps`` drops more than
the tolerance (benchmarks/check.py; default 20%, per-row overridable).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7] [--list]
      [--json out.json] [--check BENCH_baseline.json] [--tolerance 0.2]
      [--row-tolerance drift_adaptive=0.5]
"""
from __future__ import annotations

import argparse
import json
import math
import platform
import sys
import time

from benchmarks import (
    bench_async_maintenance,
    bench_cost_model,
    bench_drift,
    bench_engine_throughput,
    bench_fig6_overhead,
    bench_fig7_selectivity,
    bench_fig8_density,
    bench_fig9_resolution,
    bench_fig10_tpch,
    bench_kernels,
    bench_learned,
    bench_maintenance,
    bench_recovery,
    bench_selectivity_sweep,
    bench_shard_scaling,
    bench_storage,
)
from benchmarks import check, common

# One registry: suite name -> (module, quick-aware runner). The module half
# feeds --list (its docstring) and tests/test_docs.py's coverage check.
REGISTRY = {
    "fig6": (bench_fig6_overhead, lambda quick: bench_fig6_overhead.run(
        scales=(20_000, 100_000) if quick else bench_fig6_overhead.SCALES)),
    "fig7": (bench_fig7_selectivity, lambda quick: bench_fig7_selectivity.run(
        card=50_000 if quick else bench_fig7_selectivity.CARD)),
    "fig8": (bench_fig8_density, lambda quick: bench_fig8_density.run(
        card=50_000 if quick else bench_fig8_density.CARD)),
    "fig9": (bench_fig9_resolution, lambda quick: bench_fig9_resolution.run(
        card=50_000 if quick else bench_fig9_resolution.CARD)),
    "fig10": (bench_fig10_tpch, lambda quick: bench_fig10_tpch.run(
        card=50_000 if quick else bench_fig10_tpch.CARD)),
    "cost_model": (bench_cost_model, lambda quick: bench_cost_model.run(
        card=50_000 if quick else bench_cost_model.CARD)),
    "maintenance": (bench_maintenance, lambda quick: bench_maintenance.run(
        card=50_000 if quick else bench_maintenance.CARD)),
    "kernels": (bench_kernels, lambda quick: bench_kernels.run()),
    "engine": (bench_engine_throughput,
               lambda quick: bench_engine_throughput.run(
                   card=50_000 if quick else bench_engine_throughput.CARD,
                   batches=(8, 64) if quick else bench_engine_throughput.BATCHES)),
    "shard_scaling": (bench_shard_scaling,
                      lambda quick: bench_shard_scaling.run(
                          card=100_000 if quick else bench_shard_scaling.CARD,
                          shards=(1, 2, 4) if quick else bench_shard_scaling.SHARDS)),
    "async_maintenance": (bench_async_maintenance,
                          lambda quick: bench_async_maintenance.run(
                              card=50_000 if quick else bench_async_maintenance.CARD,
                              rounds=3 if quick else bench_async_maintenance.ROUNDS)),
    "selectivity_sweep": (bench_selectivity_sweep,
                          lambda quick: bench_selectivity_sweep.run(
                              card=100_000 if quick else bench_selectivity_sweep.CARD,
                              selectivities=(0.01, 0.5) if quick
                              else bench_selectivity_sweep.SELECTIVITIES)),
    "drift": (bench_drift,
              lambda quick: bench_drift.run(
                  card=10_000 if quick else bench_drift.CARD,
                  rounds=3 if quick else bench_drift.ROUNDS,
                  inserts=600 if quick else bench_drift.INSERTS)),
    "learned": (bench_learned,
                lambda quick: bench_learned.run(
                    card=10_000 if quick else bench_learned.CARD,
                    rounds=2 if quick else bench_learned.ROUNDS,
                    inserts=1200 if quick else bench_learned.INSERTS)),
    "storage": (bench_storage, lambda quick: bench_storage.run(
        card=50_000 if quick else bench_storage.CARD)),
    "recovery": (bench_recovery, lambda quick: bench_recovery.run(
        card=30_000 if quick else bench_recovery.CARD,
        rounds=4 if quick else bench_recovery.ROUNDS,
        writes_per_round=120 if quick
        else bench_recovery.WRITES_PER_ROUND)),
}

MODULES = {name: mod for name, (mod, _) in REGISTRY.items()}
SUITES = {name: fn for name, (_, fn) in REGISTRY.items()}


def describe(name: str) -> str:
    """First line of the bench module's docstring (enforced non-empty by
    tests/test_docs.py and the --list path)."""
    doc = MODULES[name].__doc__ or ""
    first = doc.strip().splitlines()[0].strip() if doc.strip() else ""
    return first or f"<{name}: missing module docstring>"


def parse_derived(derived: str) -> dict:
    """Parse a row's ';'-separated ``key=value`` derived field, coercing
    values to int/float/bool where they parse (the JSON half of the CSV
    contract in benchmarks/common.py). Non-finite numbers (a qps division
    on a zero timing prints ``nan``/``inf``) become ``None`` so the JSON
    document stays strict and the regression gate is never fed a value
    that compares as neither pass nor fail."""
    out = {}
    for item in derived.split(";"):
        if not item:
            continue
        key, _, val = item.partition("=")
        if val in ("True", "False"):
            out[key] = val == "True"
            continue
        for cast in (int, float):
            try:
                num = cast(val)
                break
            except ValueError:
                continue
        else:
            out[key] = val
            continue
        out[key] = num if math.isfinite(num) else None
    return out


def _finite(val):
    """Strict-JSON scalar: non-finite floats become None."""
    if isinstance(val, float) and not math.isfinite(val):
        return None
    return val


def rows_to_json(suite_rows: dict[str, list], *, quick: bool) -> dict:
    """Machine-readable result document for ``--json``: every emitted row
    grouped by suite, derived fields parsed, plus the run configuration —
    the schema the repo's ``BENCH_*.json`` trajectory files accumulate.
    Strict JSON throughout: every non-finite value is sanitized to null so
    any consumer (the regression gate first) can parse with allow_nan off."""
    return {
        "schema": 1,
        "generated_unix_s": int(time.time()),
        "config": {
            "quick": quick,
            "python": platform.python_version(),
            "platform": platform.platform(),
        },
        "suites": {
            suite: [{"name": name,
                     "us_per_call": _finite(round(us, 1)),
                     "qps": parse_derived(derived).get("qps"),
                     "derived": parse_derived(derived)}
                    for name, us, derived in rows]
            for suite, rows in suite_rows.items()
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, choices=sorted(SUITES),
                    action="append",
                    help="run only this suite (repeatable)")
    ap.add_argument("--list", action="store_true",
                    help="print each registered benchmark and its one-line "
                         "description, then exit")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write the run's rows as machine-readable JSON "
                         "(per-suite, derived fields parsed) to OUT")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="after the run, gate the fresh rows against this "
                         "committed BENCH_*.json trajectory: exit 1 when "
                         "any qps/achieved_gbps drops past tolerance")
    ap.add_argument("--tolerance", type=float,
                    default=check.DEFAULT_TOLERANCE,
                    help="allowed fractional drop per gated metric "
                         "(default %(default)s)")
    ap.add_argument("--row-tolerance", action="append", default=[],
                    metavar="ROW=FRAC",
                    help="per-row tolerance override (repeatable; bare row "
                         "name or suite/row)")
    args = ap.parse_args(argv)

    if args.list:
        width = max(len(n) for n in SUITES)
        for name in SUITES:
            print(f"{name:<{width}}  {describe(name)}")
        return 0

    # fail fast on an unreadable baseline / bad override before benching
    try:
        row_tol = check.parse_row_tolerances(args.row_tolerance)
        baseline = check.load_trajectory(args.check) if args.check else None
    except (check.BaselineError, ValueError) as e:
        print(f"# {e}", file=sys.stderr)
        return 2

    print("name,us_per_call,derived")
    t0 = time.time()
    suite_rows: dict[str, list] = {}
    for name, fn in SUITES.items():
        if args.only and name not in args.only:
            continue
        print(f"# --- {name} ---", flush=True)
        before = len(common.ROWS)
        fn(args.quick)
        suite_rows[name] = common.ROWS[before:]
    print(f"# total_wall_s={time.time()-t0:.1f}", file=sys.stderr)
    doc = rows_to_json(suite_rows, quick=args.quick)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True, allow_nan=False)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)
    if baseline is not None:
        if baseline.get("config", {}).get("quick") != args.quick:
            print("# WARNING: baseline quick flag differs from this run — "
                  "rows time different scales; refresh the baseline at the "
                  "matching scale", file=sys.stderr)
        deltas = check.compare(baseline, doc, tolerance=args.tolerance,
                               row_tolerance=row_tol)
        print(check.delta_table(deltas))
        if check.failures(deltas):
            print(f"# REGRESSION vs {args.check}", file=sys.stderr)
            return 1
        print(f"# gate ok vs {args.check}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()          # before the first jit of any suite
    sys.exit(main())
