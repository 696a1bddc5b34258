#!/usr/bin/env python3
"""Chip smoke test: the served Hippo path at TPC-H SF 10 on one TPU chip.

Drives, in one process and through the entry points a user calls, the
deployment of the paper's §7: TPC-H ``lineitem`` at SF 10 (59,986,052 rows)
in a ``PagedTable``, a 4-shard Hippo index on ``l_shipdate`` (page_card 50,
H=400, D=0.2) built by ``ShardedHippoIndex.create``, and a ``QueryEngine`` in
its default compact mode with the maintenance writer and durable storage.

Phases, each checked before the next starts (the first failure raises and
the script exits non-zero):

  load        generate lineitem from --seed, build the sharded index
  reads       batches of 64 range queries at the Fig. 7 selectivities
              (0.001 %, 0.01 %, 0.1 %, 1 %) through QueryEngine(batch=64,
              top_k=32); every count and row-id prefix is compared with a
              numpy brute-force scan of the same keys (lo <= k <= hi, f32)
  writes      ~6,000 staged inserts (a tenth of an SF 10 RF1), one delete
              range and a flush(), then the same comparison
  durability  the same with storage_dir set, close(), QueryEngine.recover(),
              and the comparison once more
  kernels     each of the five Pallas kernels at one shape taken from the
              built index, compared with its ref.py twin

Phase times on the earlier lines are those of one cold run (compilation
included), not measurements. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--seed N] [--rows N]

Needs a TPU: with no accelerator it exits non-zero before any work. JAX's
compilation cache goes to $JAX_COMPILATION_CACHE_DIR when set, else to
``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ROWS_SF10 = 59_986_052          # TPC-H lineitem cardinality at SF 10
SELECTIVITIES = (1e-5, 1e-4, 1e-3, 1e-2)   # paper Fig. 7
BATCH, TOP_K = 64, 32
NUM_SHARDS, PAGE_CARD, RESOLUTION, DENSITY = 4, 50, 400, 0.2


class SmokeFailure(AssertionError):
    """A phase's result disagrees with the numpy reference."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Reference:
    """Brute-force numpy twin of the table: keys in row-id order plus a live
    mask. Appends and deletes mirror the engine's writes; a scan answers a
    range query with its count and its first qualifying row ids."""

    def __init__(self, keys: np.ndarray):
        self.keys = np.asarray(keys, np.float32).copy()
        self.live = np.ones(self.keys.shape, bool)

    def insert(self, values: np.ndarray) -> None:
        values = np.asarray(values, np.float32)
        self.keys = np.concatenate([self.keys, values])
        self.live = np.concatenate([self.live, np.ones(values.shape, bool)])

    def delete(self, lo: np.float32, hi: np.float32) -> int:
        hit = self.live & (self.keys >= lo) & (self.keys <= hi)
        self.live &= ~hit
        return int(hit.sum())

    def scan(self, lo: np.float32, hi: np.float32) -> tuple[int, np.ndarray]:
        hit = self.live & (self.keys >= lo) & (self.keys <= hi)
        return int(np.count_nonzero(hit)), np.flatnonzero(hit)[:TOP_K]

    def scan_all(self, windows: np.ndarray) -> list[tuple[int, np.ndarray]]:
        # numpy releases the GIL inside each scan, so threads overlap them
        with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
            return list(ex.map(lambda w: self.scan(w[0], w[1]), windows))


def window_days(selectivity: float) -> int:
    """Whole days a window spans at ``selectivity`` of the date domain.
    ``l_shipdate`` is a whole number of days, so a window narrower than one
    day holds no row or a whole day: the Fig. 7 selectivities below one
    day's share (0.001 % and 0.01 % of 2,555 days) round up to one day."""
    from repro.storage.tpch import DATE_HI, DATE_LO
    return max(1, round((DATE_HI - DATE_LO) * selectivity))


def query_windows(rng: np.random.Generator, selectivity: float,
                  n: int) -> np.ndarray:
    """(n, 2) f32 [lo, hi] shipdate windows of ``window_days(selectivity)``
    whole days at uniformly drawn first days, so every window holds rows."""
    from repro.storage.tpch import DATE_HI, DATE_LO
    days = window_days(selectivity)
    lo = rng.integers(DATE_LO, DATE_HI - days + 1, n)
    return np.stack([lo, lo + days - 1], axis=1).astype(np.float32)


def run_queries(engine, ref: Reference, windows_by_sel: dict, phase: str,
                log) -> None:
    """One batch per selectivity through the engine; every count and row-id
    prefix must equal the brute-force scan."""
    from repro.core.predicate import Predicate
    for sel, windows in windows_by_sel.items():
        t0 = time.perf_counter()
        tickets = [engine.submit(Predicate.between(float(lo), float(hi)))
                   for lo, hi in windows]
        engine.drain()
        dt = time.perf_counter() - t0
        want = ref.scan_all(windows)
        for t, (lo, hi), (count, ids) in zip(tickets, windows, want):
            check(t.count == count,
                  f"{phase}: count of [{lo}, {hi}] is {t.count}, "
                  f"brute force says {count}")
            check(np.array_equal(t.row_ids, ids),
                  f"{phase}: row ids of [{lo}, {hi}] are {t.row_ids[:8]}..., "
                  f"brute force says {ids[:8]}...")
        counts = [c for c, _ in want]
        check(max(counts) > 0, f"{phase}: no {sel:.3%} window holds a row")
        log(f"[{phase}] selectivity={sel:.3%} days={window_days(sel)} "
            f"queries={len(tickets)} empty={counts.count(0)} "
            f"counts min={min(counts)} "
            f"max={max(counts)} sum={sum(counts)} "
            f"row_ids=ok batch_s={dt:.3f}")


def stage_writes(engine, ref: Reference, values: np.ndarray,
                 delete: tuple[float, float], phase: str, log) -> None:
    """Stage inserts and one delete range through the engine, flush, and
    mirror both into the reference."""
    t0 = time.perf_counter()
    for v in values:
        engine.write(float(v))
    ref.insert(values)
    lo, hi = np.float32(delete[0]), np.float32(delete[1])
    deleted = engine.delete(float(lo), float(hi))
    want = ref.delete(lo, hi)
    check(deleted == want, f"{phase}: delete [{lo}, {hi}] removed {deleted} "
                           f"tuples, brute force says {want}")
    engine.flush()
    check(engine.stats.queue_depth == 0, f"{phase}: flush left "
                                         f"{engine.stats.queue_depth} staged")
    log(f"[{phase}] inserts={len(values)} deleted={deleted} "
        f"drains={engine.stats.drains} write_s={time.perf_counter() - t0:.3f}")


def check_kernels(sidx, windows: np.ndarray, interpret: bool, log) -> None:
    """Each Pallas kernel at one shape drawn from shard 0 of the built index,
    compared bit-exactly with its jnp reference twin."""
    import jax.numpy as jnp

    from repro.core.predicate import Predicate, to_bucket_bitmaps
    from repro.kernels.batch_filter.ops import batch_filter
    from repro.kernels.batch_filter.ref import batch_filter_ref
    from repro.kernels.bitmap_and.ops import bitmap_and_any
    from repro.kernels.bitmap_and.ref import bitmap_and_any_ref
    from repro.kernels.bucketize.ops import bucketize_values
    from repro.kernels.bucketize.ref import bucketize_ref
    from repro.kernels.compact_inspect.ops import compact_inspect
    from repro.kernels.compact_inspect.ref import compact_inspect_ref
    from repro.kernels.page_inspect.ops import page_inspect
    from repro.kernels.page_inspect.ref import page_inspect_ref

    st = sidx.state.shards
    n = int(st.num_slots[0])
    entries = st.bitmaps[0][:n]                                  # (E, W)
    preds = [Predicate.between(float(lo), float(hi)) for lo, hi in windows]
    qbms = to_bucket_bitmaps(preds, sidx.shard_histogram(0))     # (Q, W)
    los, his = jnp.asarray(windows[:, 0]), jnp.asarray(windows[:, 1])
    spec = sidx.spec
    keys = sidx.table.device_keys_sharded(spec.num_shards,
                                          spec.pages_per_shard)[0]
    valid = sidx.table.device_valid_sharded(spec.num_shards,
                                            spec.pages_per_shard)[0]
    lo0, hi0 = windows[0]
    host_keys = np.asarray(keys)
    page_mask = jnp.asarray(((host_keys >= lo0) & (host_keys <= hi0)
                             ).any(axis=1))                      # (PPS,)
    m = min(4096, keys.shape[0])
    sel = jnp.asarray(np.random.default_rng(0).random((len(preds), m)) < 0.3)

    def same(a, b) -> bool:
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))

    cases = [
        ("bitmap_and", f"E={n} W={entries.shape[1]}",
         lambda: [bitmap_and_any(entries, qbms[0], interpret=interpret)],
         lambda: [bitmap_and_any_ref(entries, qbms[0])]),
        ("batch_filter", f"Q={len(preds)} E={n}",
         lambda: [batch_filter(qbms, entries, interpret=interpret)],
         lambda: [batch_filter_ref(qbms, entries)]),
        ("bucketize", f"N={keys.size} H={RESOLUTION}",
         lambda: [bucketize_values(keys.reshape(-1), st.bounds[0], RESOLUTION,
                                   interpret=interpret)],
         lambda: [bucketize_ref(keys.reshape(-1), st.bounds[0], RESOLUTION)]),
        ("page_inspect", f"P={keys.shape[0]} C={keys.shape[1]}",
         lambda: page_inspect(keys, valid, page_mask, lo0, hi0,
                              interpret=interpret),
         lambda: page_inspect_ref(keys, valid, page_mask, lo0, hi0)),
        ("compact_inspect", f"Q={len(preds)} M={m} C={keys.shape[1]}",
         lambda: [compact_inspect(keys[:m], valid[:m], sel, los, his,
                                  interpret=interpret)],
         lambda: [compact_inspect_ref(keys[:m], valid[:m], sel, los, his)]),
    ]
    for name, shape, kernel, ref in cases:
        t0 = time.perf_counter()
        got = kernel()
        check(same(got, ref()), f"kernels: {name} ({shape}) differs from "
                                f"its reference")
        log(f"[kernels] {name} {shape} interpret={interpret} matches_ref=True "
            f"s={time.perf_counter() - t0:.3f}")


def run_smoke(rows: int, seed: int, *, interpret: bool, storage_dir,
              log=print) -> None:
    """Every phase on whatever backend JAX has; raises SmokeFailure (or the
    phase's own error) at the first failure."""
    import jax

    from repro.core.partition import ShardedHippoIndex
    from repro.runtime.engine import QueryEngine
    from repro.storage.table import PagedTable
    from repro.storage.tpch import DATE_LO, generate_lineitem

    # -- load and build ------------------------------------------------------
    t0 = time.perf_counter()
    li = generate_lineitem(rows, seed)
    table = PagedTable.from_values(li.shipdate, page_card=PAGE_CARD)
    ref = Reference(li.shipdate)
    del li
    t_load = time.perf_counter() - t0
    t0 = time.perf_counter()
    sidx = ShardedHippoIndex.create(table, num_shards=NUM_SHARDS,
                                    resolution=RESOLUTION, density=DENSITY)
    jax.block_until_ready(sidx.state)
    t_build = time.perf_counter() - t0
    entries = [int(e) for e in sidx.shard_entry_counts()]
    check(table.cardinality == rows, f"load: table holds "
                                     f"{table.cardinality} of {rows} rows")
    check(sidx.summarized_until == table.num_pages - 1,
          f"load: index covers pages up to {sidx.summarized_until}, table "
          f"has {table.num_pages}")
    spec = sidx.spec
    owned = [min(spec.pages_per_shard, max(0, table.num_pages - spec.page_lo(s)))
             for s in range(spec.num_shards)]
    check(all((e > 0) == (p > 0) for e, p in zip(entries, owned)),
          f"load: entries per shard {entries} for pages per shard {owned}")
    log(f"[load] rows={rows} pages={table.num_pages} shards={NUM_SHARDS} "
        f"pages_per_shard={spec.pages_per_shard} "
        f"entries_per_shard={entries} load_s={t_load:.3f} "
        f"build_s={t_build:.3f}")

    rng = np.random.default_rng(seed + 1)
    windows = {sel: query_windows(rng, sel, BATCH) for sel in SELECTIVITIES}
    n_writes = max(rows // 10_000, 50)     # a tenth of an RF1 at any SF

    # -- reads ---------------------------------------------------------------
    engine = QueryEngine(sidx, batch=BATCH, top_k=TOP_K)
    run_queries(engine, ref, windows, "reads", log)
    log(f"[reads] compact_batches={engine.stats.compact_batches} "
        f"fallbacks={engine.stats.compact_fallbacks}")

    # -- writes --------------------------------------------------------------
    inserts = generate_lineitem(n_writes, seed + 2).shipdate
    stage_writes(engine, ref, inserts, (DATE_LO + 100, DATE_LO + 101),
                 "writes", log)
    run_queries(engine, ref, windows, "writes", log)

    # -- durability ----------------------------------------------------------
    t0 = time.perf_counter()
    durable = QueryEngine(sidx, batch=BATCH, top_k=TOP_K,
                          storage_dir=storage_dir)
    log(f"[durability] initial_snapshot_s={time.perf_counter() - t0:.3f}")
    inserts = generate_lineitem(n_writes, seed + 3).shipdate
    stage_writes(durable, ref, inserts, (DATE_LO + 200, DATE_LO + 201),
                 "durability", log)
    run_queries(durable, ref, windows, "durability", log)
    durable.close()
    t0 = time.perf_counter()
    recovered = QueryEngine.recover(storage_dir, batch=BATCH, top_k=TOP_K)
    log(f"[durability] persists={durable.stats.persists} "
        f"recover_s={time.perf_counter() - t0:.3f}")
    run_queries(recovered, ref, windows, "recovered", log)
    recovered.close()

    # -- kernels -------------------------------------------------------------
    check_kernels(recovered.index, windows[SELECTIVITIES[-1]], interpret, log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data and queries")
    ap.add_argument("--rows", type=int, default=ROWS_SF10,
                    help="lineitem rows (default: SF 10)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache}", flush=True)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="hippo_smoke_") as store:
        run_smoke(args.rows, args.seed, interpret=False, storage_dir=store,
                  log=lambda s: print(s, flush=True))
    print(f"[done] total_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
