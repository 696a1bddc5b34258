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
IN_PLACE = STEP2 | {"hippo.inspect", "hippo.row_ids"}
COMPACT = IN_PLACE | {"hippo.select", "hippo.gather"}
ROWS = 64000     # 1,280 pages: shards wide enough for a gathering bucket


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(7)
    table = PagedTable.from_values(
        rng.uniform(0, 1000, ROWS).astype(np.float32), page_card=50)
    return ShardedHippoIndex.create(table, num_shards=4, resolution=64,
                                    density=0.2)


def _gather_bucket(index) -> int:
    """The widest slab that the compact search still gathers."""
    pps = index.spec.pages_per_shard
    below = max(m for m in range(1, pps + 1)
                if not index.inspects_in_place(m))
    assert below >= 2 and index.inspects_in_place(below + 1)
    return below


def _args(index):
    preds = [Predicate.between(10.0 * i, 10.0 * i + 40.0) for i in range(8)]
    los, his = intervals(preds)
    keys, valid = index._slabs()
    return index._query_bitmaps(preds), keys, valid, los, his


def _lowered(index, name):
    """The lowered program ``name``; the compact search gathers at the
    widest gathering bucket, and with ``[in_place]`` runs at the cap."""
    qbms, keys, valid, los, his = _args(index)
    shards = index.state.shards
    staged = (jnp.zeros((4, 8), jnp.float32), jnp.ones((4, 8), bool))
    width = (index.gather_cap if name.endswith("[in_place]")
             else _gather_bucket(index))
    if name.startswith("search_compact_many_sharded_staged"):
        fn = partial(hix.search_compact_many_sharded_staged,
                     max_selected=width, top_k=32)
        return jax.jit(fn).lower(shards, qbms, keys, valid, los, his, *staged)
    if name.startswith("search_compact_many_sharded"):
        return hix.search_compact_many_sharded.lower(
            shards, qbms, keys, valid, los, his, max_selected=width,
            top_k=32)
    if name == "search_many_sharded":
        return hix.search_many_sharded.lower(shards, qbms, keys, valid, los,
                                             his)
    return interval_bitmaps_sharded.lower(shards.bounds, los, his,
                                          jnp.ones(los.shape, bool))


@pytest.mark.parametrize("name,scopes", [
    ("search_compact_many_sharded", COMPACT),
    ("search_compact_many_sharded[in_place]", IN_PLACE),
    ("search_many_sharded", STEP2 | {"hippo.inspect"}),
    ("search_compact_many_sharded_staged", COMPACT | {"hippo.staged_overlay"}),
    ("interval_bitmaps_sharded", {"hippo.convert"}),
])
def test_lowered_program_names_each_stage_in_op_name(index, name, scopes):
    text = _lowered(index, name).as_text(dialect="hlo", debug_info=True)
    found = {s for op in re.findall(r'op_name="([^"]*)"', text)
             for s in re.findall(r"hippo\.[a-z_]+", op)}
    assert found == scopes


DEFINED = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(\w+)\[([\d,]*)\]")
GATHER = re.compile(r"=\s*\w+\[([\d,]*)\][^=]*?\bgather\(%?([\w.-]+)")


def _gathers(hlo_text: str) -> list[tuple[tuple, tuple]]:
    """(operand shape, result shape) of every ``gather`` in an HLO module's
    text, the operand's shape looked up where it is defined."""
    shapes = {}
    for line in hlo_text.splitlines():
        d = DEFINED.match(line)
        if d:
            shapes[d.group(1)] = tuple(int(x) for x in d.group(3).split(",")
                                       if x)
    dims = lambda t: tuple(int(x) for x in t.split(",") if x)
    return [(shapes[g.group(2)], dims(g.group(1)))
            for g in map(GATHER.search, hlo_text.splitlines()) if g]


def test_in_place_program_gathers_no_slab(index):
    """At the cap, the program reads the (S, PPS, C) key and validity slabs
    where they lie: a gather from them returns only the pages the row ids
    read again, never a slab's worth of pages."""
    text = _lowered(index, "search_compact_many_sharded[in_place]").as_text(
        dialect="hlo")
    keys, _ = index._slabs()
    from_slab = [r for a, r in _gathers(text) if a == keys.shape]
    assert from_slab                          # the row ids read pages again
    assert all(np.prod(r) < np.prod(keys.shape) for r in from_slab), \
        from_slab


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
    rows = index.table.cardinality
    eng.run_all([wide, Predicate.between(5.0, 9.0)])       # compile
    eng.write(500.0)                  # a staged row: the next batch drains it
    with jax.profiler.trace(str(tmp_path)):
        eng._compact_bucket = 1       # so the wide query truncates again
        counts = eng.run_all([wide, Predicate.between(5.0, 9.0)])
    assert counts[0] == rows + 1      # the staged row drained before the batch

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
    # the bucket-1 batch gathers; its fallback at the cap inspects in place
    assert sorted((st["bucket"], st["in_place"]) for n, _, _, st in spans
                  if n == "hippo.dispatch") == [(1, False),
                                                (index.gather_cap, True)]
    (_, f0, f1, _), = [s for s in spans if s[0] == "hippo.fallback"]
    inside = [n for n, a, b, _ in spans if f0 <= a and b <= f1]
    assert sorted(inside) == ["hippo.dispatch", "hippo.fallback",
                              "hippo.readback"]


def test_engine_counts_in_place_dispatches(index, tmp_path):
    """``EngineStats.compact_in_place`` counts every compact dispatch that
    inspects in place, the primary batch and the truncation fallback
    alike, as the ``hippo.dispatch`` spans' ``in_place`` argument says."""
    eng = QueryEngine(index, batch=8, mode="compact", top_k=4,
                      compact_bucket=1)
    wide, narrow = Predicate.between(0.0, 1000.0), Predicate.between(5.0, 9.0)
    eng.run_all([wide, narrow])                            # compile
    with jax.profiler.trace(str(tmp_path)):
        eng._compact_bucket = 1
        eng.run_all([wide, narrow])   # gathers, the fallback in place
        eng.run_all([wide, narrow])   # at the widened bucket: in place
    st = eng.stats
    # 3 batches, 2 of them with one fallback dispatch (of both queries)
    assert st.compact_batches == 3 and st.compact_fallbacks == 4
    assert st.compact_in_place == 3
    flags = [a["in_place"] for n, _, _, a in _host_spans(tmp_path)
             if n == "hippo.dispatch"]
    assert sorted(flags) == [False, True, True]


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
