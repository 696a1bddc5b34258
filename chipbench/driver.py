"""Drive one cell through the served entry points, measure it, check it.

The system under test is ``repro``: a ``PagedTable`` of the generated
column, a ``ShardedHippoIndex`` built by ``create`` and a ``QueryEngine``
configured as the configuration's ``engine`` block says (``engine_args``).
The harness calls ``QueryEngine.submit``, ``run_batch`` and, for a mix with a
refresh stream, the write entry point (``write_many``), and nothing beneath
them. It reads ``EngineStats`` counters, the index's state arrays (their
sizes and entry counts, outside the window) and the device's memory
statistics.

A run: generate the column from the seed, build, warm up with the mix's
``warmup_rounds`` rounds (every shape the window uses compiles there), then
run whole rounds until ``seconds`` have passed, and close the window. A
round submits one query per stream, runs batches until all are answered,
and then, for a refresh mix, writes the refresh stream's next rows and waits
for their acknowledgement; so no insert is acknowledged while a query is
pending, and each query sees exactly the inserts acknowledged before its
``submit``. Set-up is everything before the window, from process start.
After the window the sampled answers are compared with the plain reference
(``check``), which tracks every acknowledged insert.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
import traceback

import numpy as np

from chipbench import catalog, check, costs, datagen, loadgen, reference, tracing

SRC = catalog.REPO_ROOT / "src"
# fires for every program compiled or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Names of the programs compiled (or loaded from the persistent cache)
    while ``on``."""

    def __init__(self):
        import jax.monitoring as mon
        self._mon = mon
        self.names: list[str] = []
        self.on = False
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, _secs: float, fun_name: str = "?",
                  **_kw) -> None:
        if self.on and event == COMPILE_EVENT:
            self.names.append(fun_name)

    def take(self) -> list[str]:
        names, self.names = self.names, []
        return names

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._duration)


@dataclasses.dataclass
class Query:
    lo: float
    hi: float
    acked: int          # refresh rows acknowledged when it was submitted
    ticket: object


class ReaderContext:
    """What a per-layer metric's ``read(ctx)`` may use."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._peaks = None

    @property
    def peaks(self) -> dict:
        if self._peaks is None:
            self._peaks = catalog.peaks(self.device_kind)
        return self._peaks


# ``engine`` keys a configuration may state; each is a QueryEngine argument
ENGINE_KEYS = ("batch", "mode", "top_k", "drain_policy", "drain_depth",
               "drain_units")


def engine_args(config: dict, num_pages: int) -> tuple[dict, dict, dict]:
    """Keyword arguments of ``PagedTable.from_values``,
    ``ShardedHippoIndex.create`` and ``QueryEngine`` for a table of
    ``num_pages`` loaded pages. Optional keys pass through only where the
    configuration states them: ``spare_pages`` (empty pages at the table's
    tail, and shard slabs wide enough to cover them) and the engine
    keys of ``ENGINE_KEYS`` beyond batch, mode and top_k."""
    ix, en = config["index"], config["engine"]
    unknown = set(en) - set(ENGINE_KEYS)
    if unknown:
        raise ValueError(f"engine keys {sorted(unknown)} are not among "
                         f"{ENGINE_KEYS}")
    table = {"page_card": ix["page_card"]}
    index = {"num_shards": ix["num_shards"], "resolution": ix["resolution"],
             "density": ix["density"]}
    spare = config.get("spare_pages")
    if spare is not None:
        table["spare_pages"] = int(spare)
        index["pages_per_shard"] = -(-(num_pages + int(spare))
                                     // ix["num_shards"])
    return table, index, dict(en)


def build_engine(config: dict, keys: np.ndarray):
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax
    from repro.core.partition import ShardedHippoIndex
    from repro.runtime.engine import QueryEngine
    from repro.storage.table import PagedTable

    pages = -(-keys.size // config["index"]["page_card"])
    table_kw, index_kw, engine_kw = engine_args(config, pages)
    table = PagedTable.from_values(keys, **table_kw)
    index = ShardedHippoIndex.create(table, **index_kw)
    jax.block_until_ready(index.state)
    return QueryEngine(index, **engine_kw)


def write_many(engine, values: np.ndarray) -> None:
    """Insert ``values``, in order, through the served write path; they are
    acknowledged when this returns. The engine's own ``write_many`` where it
    has one, else its ``write``, row by row."""
    many = getattr(engine, "write_many", None)
    if many is not None:
        many(values)
        return
    for v in values.tolist():
        engine.write(v)


class Driver:
    """Closed-loop rounds of query streams, and of an optional refresh
    stream, against one engine."""

    def __init__(self, engine, streams: loadgen.Streams,
                 capture: tracing.Capture,
                 refresh: loadgen.Refresh | None = None):
        from repro.core.predicate import Predicate
        self._pred = Predicate.between
        self.engine = engine
        self.streams = streams
        self.capture = capture
        self.refresh = refresh
        self.queries: list[Query] = []
        self.batches = 0
        self.inserted: list[np.ndarray] = []   # acknowledged rows, in order
        self.acked = 0                         # rows in ``inserted``
        self.write_rows = 0                    # rows handed to write_many
        self.write_failures = 0                # write_many calls that raised
        self.failed_rows = 0                   # rows of those calls
        self.write_s = 0.0                     # host seconds in write_many

    def round(self) -> None:
        eng, span = self.engine, self.capture.span
        mine = []
        with span("submit"):
            for lo, hi in self.streams.queries():
                mine.append(Query(float(lo), float(hi), self.acked,
                                  eng.submit(self._pred(float(lo),
                                                        float(hi)))))
        pending = {id(q.ticket) for q in mine}
        while pending:
            with span("run_batch"):
                done = eng.run_batch()
            self.batches += 1
            if not done:
                raise RuntimeError("run_batch retired nothing with queries "
                                   "pending")
            pending -= {id(t) for t in done}
        self.queries.extend(mine)
        if self.refresh is not None and not self.write_failures:
            self._write(self.refresh.rows())

    def _write(self, rows: np.ndarray) -> None:
        """One refresh through ``write_many``. A call that raises is counted
        as failed, with all its rows, and ends the refresh stream: what it
        left in the table is unknown to the reference."""
        self.write_rows += rows.size
        t0 = time.perf_counter()
        try:
            with self.capture.span("write_many"):
                write_many(self.engine, rows)
        except Exception:     # a failed write is a result of the run
            self.write_failures += 1
            self.failed_rows += rows.size
            _log(f"[refresh] write_many of {rows.size} rows raised:\n"
                 f"{traceback.format_exc()}")
            return
        finally:
            self.write_s += time.perf_counter() - t0
        self.inserted.append(rows)
        self.acked += rows.size


def _stats(engine) -> dict:
    return dataclasses.asdict(engine.stats)


def run_cell(cell: catalog.Cell, seed: int, seconds: float, trace: bool,
             t_start: float | None = None) -> dict:
    """One run of ``cell``; returns the result line's object. Checks no
    device: the caller does that."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    config, mix = cell.config, cell.mix
    t0 = time.perf_counter()
    keys = datagen.load_column(config, seed)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine = build_engine(config, keys)
    t_build = time.perf_counter() - t0
    capture = tracing.Capture(trace)
    driver = Driver(engine, loadgen.Streams(mix, seed), capture,
                    loadgen.refresh_of(mix, config, seed))
    counter = CompileCounter()
    t0 = time.perf_counter()
    counter.on = True
    for _ in range(int(mix["warmup_rounds"])):
        driver.round()
    warm_compiles = len(counter.take())
    counter.on = False
    t_warm = time.perf_counter() - t0
    # outside the window: the entry count the least-bytes count needs
    live_entries = int(np.asarray(engine.index.state.shards.num_entries).sum())
    first_query, first_batch = len(driver.queries), driver.batches
    first_write = (driver.write_rows, driver.failed_rows, driver.write_s)
    stats_before = _stats(engine)
    capture.start()
    setup_s = time.perf_counter() - t_start
    counter.on = True
    t_open = time.perf_counter()
    with capture.span("window"):
        while True:
            driver.round()
            if time.perf_counter() - t_open >= seconds:
                break
    t_close = time.perf_counter()
    counter.on = False
    window_compiles = counter.take()
    xplane = capture.stop()
    counter.close()
    window_s = t_close - t_open
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    stats_after = _stats(engine)
    index_bytes = sum(int(a.nbytes)
                      for a in jax.tree_util.tree_leaves(engine.index.state))
    rows = keys.size + driver.acked
    window = driver.queries[first_query:]
    batches = driver.batches - first_batch
    answered = sum(q.ticket.done for q in window)
    write_rows, failed_rows, write_s = (
        now - before for now, before in zip(
            (driver.write_rows, driver.failed_rows, driver.write_s),
            first_write))
    idx = loadgen.sample(seed, len(window),
                         range(len(window) - driver.streams.streams,
                               len(window)), check.SAMPLE)
    got = [(q.ticket.count, q.ticket.row_ids) if q.ticket.done else None
           for q in (window[i] for i in idx)]
    todo = [(window[i].lo, window[i].hi, window[i].acked) for i in idx]
    delta = {k: stats_after[k] - stats_before[k]
             for k in ("compact_fallbacks", "selected_pages",
                       "table_pages_seen", "drained_rows", "drain_us")}
    _log(f"[run] cell={cell.name} seed={seed} rows={keys.size} "
         f"generate_s={t_gen:.3f} build_s={t_build:.3f} "
         f"warmup_s={t_warm:.3f} warmup_compiles={warm_compiles} "
         f"setup_s={setup_s:.3f}")
    _log(f"[window] seconds={window_s:.3f} batches={batches} "
         f"queries={len(window)} compiles_in_window={len(window_compiles)} "
         f"fallbacks={delta['compact_fallbacks']} "
         f"memory_peak_bytes={memory_peak} index_bytes={index_bytes}")
    if driver.refresh is not None:
        # host time a row spends in write_many, and in the drains that
        # apply staged rows to the index (inside run_batch)
        per_row = lambda s: s / write_rows * 1e3 if write_rows else 0.0
        _log(f"[refresh] rows={write_rows} failed_rows={failed_rows} "
             f"write_s={write_s:.6f} "
             f"write_ms_per_row={per_row(write_s):.6f} "
             f"drained_rows={delta['drained_rows']} "
             f"drain_s={delta['drain_us'] * 1e-6:.6f} "
             f"drain_ms_per_row={per_row(delta['drain_us'] * 1e-6):.6f} "
             f"table_rows={rows}")
    if window_compiles:
        _log(f"[window] compiled: {sorted(set(window_compiles))}")
    inserts = np.concatenate(driver.inserted or [np.zeros(0, np.float32)])
    write_failures = driver.write_failures
    del engine, driver
    gc.collect()

    summary = None
    if xplane is not None:
        summary = tracing.reduce(xplane)
        capture.cleanup()
        _log(f"[trace] window_s={summary.window_s:.6f} "
             f"busy_s={summary.busy_s:.6f} devices={summary.devices}")

    t0 = time.perf_counter()
    ref = reference.RangeScan(keys, config["engine"]["top_k"], inserts)
    numbers = check.compare(got, ref.answers(todo), write_failures)
    _log(f"[check] sampled={len(todo)} reference_s="
         f"{time.perf_counter() - t0:.3f}")

    # operations: the window's queries and its refresh rows
    result = {"correct": check.is_correct(numbers),
              "attempted": len(window) + write_rows,
              "failed": len(window) - answered + failed_rows}
    if trace:
        ix = config["index"]
        ctx = ReaderContext(trace=summary, batches=batches,
                            selected_pages=delta["selected_pages"],
                            table_pages_seen=delta["table_pages_seen"],
                            least_bytes=costs.search_least_bytes(
                                delta["selected_pages"], ix["page_card"],
                                batches * live_entries, ix["resolution"]),
                            device_kind=dev.device_kind)
        values = {m["name"]: cell.readers[m["name"]](ctx)
                  for m in cell.per_layer}
        units = {m["name"]: m["unit"] for m in cell.per_layer}
    else:
        values = {"setup_s": setup_s,
                  "ops_s": answered / window_s,
                  "index_bytes_per_row": index_bytes / rows}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {k: values.get(k) for k in units}
    result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                         for k, v in values.items() if v is not None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result["device"] = device
    result["compiles_in_window"] = len(window_compiles)
    result["checks"] = check.with_limits(numbers)
    for k, v in result["checks"].items():
        _log(f"[check] {k}={v['value']} limit={v['limit']}")
    return result
