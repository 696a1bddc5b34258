"""Profiler names of the served search path, checked on the CPU.

The device program names Algorithm 1's stages with
``jax.named_scope("hippo.<stage>")``; the names must survive ``jit`` and
``vmap`` into the ``op_name`` metadata of the lowered HLO, which is what a
device trace's operations carry. The engine names its host work with
``jax.profiler.TraceAnnotation("hippo.<span>")``; a ``QueryEngine`` run
under the profiler must record each span, nested in ``hippo.run_batch``,
with its args.
"""
import re
from functools import partial
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import index as hix
from repro.core.partition import ShardedHippoIndex
from repro.core.predicate import Predicate, intervals, interval_bitmaps_sharded
from repro.runtime.engine import QueryEngine
from repro.storage.table import PagedTable

STEP2 = {"hippo.entry_filter", "hippo.page_expand"}
COMPACT = STEP2 | {"hippo.select", "hippo.gather", "hippo.inspect",
                   "hippo.row_ids"}


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(7)
    table = PagedTable.from_values(
        rng.uniform(0, 1000, 3200).astype(np.float32), page_card=50)
    return ShardedHippoIndex.create(table, num_shards=4, resolution=64,
                                    density=0.2)


def _args(index):
    preds = [Predicate.between(10.0 * i, 10.0 * i + 40.0) for i in range(8)]
    los, his = intervals(preds)
    keys, valid = index._slabs()
    return index._query_bitmaps(preds), keys, valid, los, his


def _lowered(index, name):
    qbms, keys, valid, los, his = _args(index)
    shards = index.state.shards
    staged = (jnp.zeros((4, 8), jnp.float32), jnp.ones((4, 8), bool))
    if name == "search_compact_many_sharded":
        return hix.search_compact_many_sharded.lower(
            shards, qbms, keys, valid, los, his, max_selected=16, top_k=32)
    if name == "search_many_sharded":
        return hix.search_many_sharded.lower(shards, qbms, keys, valid, los,
                                             his)
    if name == "search_compact_many_sharded_staged":
        fn = partial(hix.search_compact_many_sharded_staged, max_selected=16,
                     top_k=32)
        return jax.jit(fn).lower(shards, qbms, keys, valid, los, his, *staged)
    return interval_bitmaps_sharded.lower(shards.bounds, los, his,
                                          jnp.ones(los.shape, bool))


@pytest.mark.parametrize("name,scopes", [
    ("search_compact_many_sharded", COMPACT),
    ("search_many_sharded", STEP2 | {"hippo.inspect"}),
    ("search_compact_many_sharded_staged", COMPACT | {"hippo.staged_overlay"}),
    ("interval_bitmaps_sharded", {"hippo.convert"}),
])
def test_lowered_program_names_each_stage_in_op_name(index, name, scopes):
    text = _lowered(index, name).as_text(dialect="hlo", debug_info=True)
    found = {s for op in re.findall(r'op_name="([^"]*)"', text)
             for s in re.findall(r"hippo\.[a-z_]+", op)}
    assert found == scopes


CALLEES = re.compile(r"\b(?:to_apply|condition|body|calls)=%?([\w.-]+)")


def _loop_op_names(hlo_text: str) -> list[str]:
    """The full ``op_name`` of every ``while`` in an HLO module's text: its
    own, under those of the instructions that call its computation, as
    the compiler joins them when it inlines the calls."""
    callers: dict[str, list[tuple[str, str]]] = {}
    loops, comp = [], None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split()[-2].lstrip("%")
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        op = op.group(1) if op else ""
        for callee in CALLEES.findall(line):
            callers.setdefault(callee, []).append((comp, op))
        if re.search(r"\bwhile\(", line):
            loops.append((comp, op))

    def paths(comp: str) -> list[str]:
        up = callers.get(comp, [])
        return [f"{p}/{op}" for c, op in up for p in paths(c)] if up \
            else [""]

    return [f"{p}/{op}" for c, op in loops for p in paths(c)]


def test_page_expansion_runs_no_loop(index):
    """Page expansion is one linear pass: no ``while`` (the rounds of a
    binary search of every page) runs under ``hippo.page_expand``. The
    ``row_ids`` stage's own small search may loop, and is seen."""
    text = _lowered(index, "search_compact_many_sharded").as_text(
        dialect="hlo", debug_info=True)
    loops = _loop_op_names(text)
    assert any("hippo.row_ids" in n for n in loops)
    assert not [n for n in loops if "hippo.page_expand" in n]


def _host_spans(trace_dir: Path) -> list[tuple[str, int, int, dict]]:
    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hippo."):
                    spans.append((e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns),
                                  dict(e.stats)))
    return spans


def test_engine_records_its_spans_nested_in_run_batch(index, tmp_path):
    eng = QueryEngine(index, batch=8, mode="compact", top_k=4,
                      compact_bucket=1)
    wide = Predicate.between(0.0, 1000.0)
    eng.run_all([wide, Predicate.between(5.0, 9.0)])       # compile
    eng.write(500.0)                  # a staged row: the next batch drains it
    with jax.profiler.trace(str(tmp_path)):
        eng._compact_bucket = 1       # so the wide query truncates again
        counts = eng.run_all([wide, Predicate.between(5.0, 9.0)])
    assert counts[0] == 3201          # the staged row drained before the batch

    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    assert set(names) == {"hippo.run_batch", "hippo.drain", "hippo.dispatch",
                          "hippo.readback", "hippo.fallback"}
    runs = [s for s in spans if s[0] == "hippo.run_batch"]
    assert [r[3] for r in runs] == [{"batch": 1, "active": 2}]
    (_, r0, r1, _), = runs
    assert all(r0 <= a and b <= r1 for _, a, b, _ in spans)
    args = {n: st for n, _, _, st in spans if n != "hippo.run_batch"}
    assert args["hippo.drain"] == {"units": 1}
    assert args["hippo.fallback"] == {"width": 8}
    assert sorted(st["bucket"] for n, _, _, st in spans
                  if n == "hippo.dispatch") == [1, index.gather_cap]
    (_, f0, f1, _), = [s for s in spans if s[0] == "hippo.fallback"]
    inside = [n for n, a, b, _ in spans if f0 <= a and b <= f1]
    assert sorted(inside) == ["hippo.dispatch", "hippo.fallback",
                              "hippo.readback"]


def _scopes(lowered) -> set[str]:
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return {s for op in re.findall(r'op_name="([^"]*)"', text)
            for s in re.findall(r"hippo\.[a-z_]+", op)}


def test_insert_programs_name_their_stages(index):
    """The append's device programs: the batched Algorithm 3 names its two
    stages, and the slab patch its own."""
    from repro.core.partition import apply_appended
    from repro.storage.table import _patch_slab
    rows = (jnp.zeros((8,), jnp.float32), jnp.zeros((8,), jnp.int32),
            jnp.ones((8,), bool))
    assert _scopes(apply_appended.lower(index.cfg, index.state, jnp.int32(3),
                                        *rows)) == {"hippo.insert_existing",
                                                    "hippo.insert_pages"}
    keys, valid = index._slabs()
    block = (jnp.zeros((2, 50), jnp.float32), jnp.ones((2, 50), bool))
    assert _scopes(_patch_slab.lower(keys, valid, jnp.int32(0), jnp.int32(4),
                                     *block)) == {"hippo.slab_append"}


@pytest.mark.parametrize("policy", ["sync", "between_batches"])
def test_engine_records_the_write_many_span(policy, tmp_path):
    rng = np.random.default_rng(9)
    table = PagedTable.from_values(
        rng.uniform(0, 1000, 800).astype(np.float32), page_card=50,
        spare_pages=40)
    idx = ShardedHippoIndex.create(table, num_shards=4, resolution=64,
                                   density=0.2)
    eng = QueryEngine(idx, batch=8, drain_policy=policy)
    eng.write_many(rng.uniform(0, 1000, 5))          # compile
    with jax.profiler.trace(str(tmp_path)):
        eng.write_many(rng.uniform(0, 1000, 70))
    spans = _host_spans(tmp_path)
    assert [(n, st) for n, _, _, st in spans] == [("hippo.write_many",
                                                   {"rows": 70})]
