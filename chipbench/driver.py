"""Drive one cell through the served entry points, measure it, check it.

The system under test is ``repro``: a ``PagedTable`` of the generated
column, a ``ShardedHippoIndex`` built by ``create`` and a ``QueryEngine``
configured as the configuration's ``engine`` block says. The harness calls
``QueryEngine.submit`` and ``run_batch``, and nothing beneath them. It reads
``EngineStats`` counters, the index's state arrays (their sizes and entry
counts, outside the window) and the device's memory statistics.

A run: generate the column from the seed, build, warm up with the mix's
``warmup_rounds`` rounds (every shape the window uses compiles there), then
run whole rounds until ``seconds`` have passed, and close the window.
Set-up is everything before the window, from process start. After the
window the sampled answers are compared with the plain reference
(``check``).
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

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


def build_engine(config: dict, keys: np.ndarray):
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax
    from repro.core.partition import ShardedHippoIndex
    from repro.runtime.engine import QueryEngine
    from repro.storage.table import PagedTable

    ix, en = config["index"], config["engine"]
    table = PagedTable.from_values(keys, page_card=ix["page_card"])
    index = ShardedHippoIndex.create(table, num_shards=ix["num_shards"],
                                     resolution=ix["resolution"],
                                     density=ix["density"])
    jax.block_until_ready(index.state)
    return QueryEngine(index, batch=en["batch"], mode=en["mode"],
                       top_k=en["top_k"])


class Driver:
    """Closed-loop rounds of query streams against one engine."""

    def __init__(self, engine, streams: loadgen.Streams,
                 capture: tracing.Capture):
        from repro.core.predicate import Predicate
        self._pred = Predicate.between
        self.engine = engine
        self.streams = streams
        self.capture = capture
        self.queries: list[Query] = []
        self.batches = 0

    def round(self) -> None:
        eng, span = self.engine, self.capture.span
        mine = []
        with span("submit"):
            for lo, hi in self.streams.queries():
                mine.append(Query(float(lo), float(hi),
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
    driver = Driver(engine, loadgen.Streams(mix, seed), capture)
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
    window = driver.queries[first_query:]
    batches = driver.batches - first_batch
    answered = sum(q.ticket.done for q in window)
    idx = loadgen.sample(seed, len(window),
                         range(len(window) - driver.streams.streams,
                               len(window)), check.SAMPLE)
    got = [(q.ticket.count, q.ticket.row_ids) if q.ticket.done else None
           for q in (window[i] for i in idx)]
    todo = [(window[i].lo, window[i].hi) for i in idx]
    delta = {k: stats_after[k] - stats_before[k]
             for k in ("compact_fallbacks", "selected_pages",
                       "table_pages_seen")}
    _log(f"[run] cell={cell.name} seed={seed} rows={keys.size} "
         f"generate_s={t_gen:.3f} build_s={t_build:.3f} "
         f"warmup_s={t_warm:.3f} warmup_compiles={warm_compiles} "
         f"setup_s={setup_s:.3f}")
    _log(f"[window] seconds={window_s:.3f} batches={batches} "
         f"queries={len(window)} compiles_in_window={len(window_compiles)} "
         f"fallbacks={delta['compact_fallbacks']} "
         f"memory_peak_bytes={memory_peak} index_bytes={index_bytes}")
    if window_compiles:
        _log(f"[window] compiled: {sorted(set(window_compiles))}")
    del engine, driver
    gc.collect()

    summary = None
    if xplane is not None:
        summary = tracing.reduce(xplane)
        capture.cleanup()
        _log(f"[trace] window_s={summary.window_s:.6f} "
             f"busy_s={summary.busy_s:.6f} devices={summary.devices}")

    t0 = time.perf_counter()
    ref = reference.RangeScan(keys, config["engine"]["top_k"])
    numbers = check.compare(got, ref.answers(todo))
    _log(f"[check] sampled={len(todo)} reference_s="
         f"{time.perf_counter() - t0:.3f}")

    result = {"correct": check.is_correct(numbers),
              "attempted": len(window),
              "failed": len(window) - answered}
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
                  "index_bytes_per_row": index_bytes / keys.size}
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
