"""The reduction from a profiler trace to device time per search stage: on a
hand-written trace whose answers are counted by hand, and on a CPU run of
the tiny cell, whose trace carries the HLO protos that name each stage."""
from pathlib import Path

import pytest

from chipbench import catalog, stages, tracing

DATA = Path(__file__).resolve().parent / "data"
MS = 1e-3
COMPACT = {"hippo.entry_filter", "hippo.page_expand", "hippo.select",
           "hippo.gather", "hippo.inspect", "hippo.row_ids"}


@pytest.fixture(scope="module")
def nested(tmp_path_factory) -> Path:
    from jax.profiler import ProfileData
    text = (DATA / "nested_trace.pbtxt").read_text()
    path = tmp_path_factory.mktemp("trace") / "nested.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_device_ops_count_nested_time_once(nested):
    ops = dict(stages.reduce(nested).device_ops)
    # while.1 lasts 5 ms, 3 ms of it inside fusion.2 and fusion.3
    assert ops == pytest.approx({
        "hippo.page_expand:while.1": 2.0 * MS,
        "hippo.page_expand:fusion.2": 1.5 * MS,
        "hippo.page_expand:fusion.3": 1.5 * MS,
        "hippo.gather:fusion.4": 1.0 * MS,
        "copy.5": 0.5 * MS}, abs=1e-12)


def test_scopes_hold_self_time_by_innermost_stage(nested):
    s = stages.reduce(nested)
    assert s.scopes == pytest.approx({"hippo.page_expand": 5.0 * MS,
                                      "hippo.gather": 1.0 * MS,
                                      "unscoped": 0.5 * MS}, abs=1e-12)
    assert s.scoped_share == pytest.approx(6.0 / 6.5)


def test_busy_and_window_are_those_of_tracing_reduce(nested):
    base, s = tracing.reduce(nested), stages.reduce(nested)
    assert (s.window_s, s.busy_s, s.devices) == (base.window_s, base.busy_s,
                                                 base.devices)
    assert s.busy_s == pytest.approx(6.5 * MS, abs=1e-12)
    assert sum(v for _, v in s.device_ops) == pytest.approx(s.busy_s,
                                                            abs=1e-12)
    # the raw durations count the while's body twice
    assert sum(v for _, v in base.device_ops) == pytest.approx(9.5 * MS,
                                                               abs=1e-12)


def test_idle_gaps_are_named_by_the_innermost_engine_or_harness_span(nested):
    s = stages.reduce(nested)
    assert dict(s.idle_gaps) == pytest.approx(
        {"submit": 2.0 * MS, "hippo.dispatch": 1.0 * MS,
         "hippo.readback": 0.5 * MS}, abs=1e-12)
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, abs=1e-12)
    # the harness's own reduction still names them by its spans alone
    assert dict(tracing.reduce(nested).idle_gaps) == pytest.approx(
        {"submit": 2.0 * MS, "run_batch": 1.5 * MS}, abs=1e-12)


@pytest.mark.parametrize("op_name,stage", [
    ("jit(f)/vmap(hippo.page_expand)/jit(searchsorted)/while/body/lt",
     "hippo.page_expand"),
    ("jit(f)/hippo.select/hippo.inspect/reduce_sum", "hippo.inspect"),
    ("jit(f)/add", None),
    (None, None),
])
def test_stage_is_the_innermost_hippo_segment(op_name, stage):
    assert stages.stage_of(op_name) == stage


def test_self_times_of_siblings_that_overlap_are_not_nested():
    # b starts inside a and ends after it: neither holds the other
    assert stages.self_times([(0, 10), (5, 15), (6, 8)], 0, 20) == \
        pytest.approx([10e-9, 8e-9, 2e-9])


def test_a_cpu_run_of_the_tiny_cell_names_every_stage(tiny_root, tmp_path):
    """On the CPU no device plane exists, so no stage has device time; the
    trace still carries the engine's spans and the HLO protos that map the
    search program's instructions to its stages."""
    from jax.profiler import ProfileData
    cell = catalog.load_cell("ship_tpch4", root=tiny_root,
                             bench_dir=catalog.BENCH_DIR)
    xplane, batches = stages.record(cell, 2**31 + 5, 0.2, tmp_path)
    assert batches >= 1
    s = stages.reduce(xplane)
    assert s.window_s > 0 and s.busy_s == 0 and s.scopes == {}
    hlo = stages.trace_hlo(xplane)
    search = [t for m, t in hlo.items()
              if m.startswith("jit_search_compact_many_sharded(")]
    assert search
    assert {stages.stage_of(op) for t in search for op in t.values()} \
        >= COMPACT
    convert = [t for m, t in hlo.items()
               if m.startswith("jit_interval_bitmaps_sharded(")]
    assert {stages.stage_of(op) for t in convert for op in t.values()} \
        >= {"hippo.convert"}
    data = ProfileData.from_file(str(xplane))
    spans = {e.name for p in data.planes for line in p.lines
             for e in line.events if e.name.startswith("hippo.")}
    assert spans == {"hippo.run_batch", "hippo.dispatch", "hippo.readback"}
    # the CPU's own op events name their module by stat
    op_name = stages.OpNames(hlo)
    named = {stages.stage_of(op_name(e, [])) for p in data.planes
             for line in p.lines for e in line.events
             if tracing._stat(e, "hlo_op") is not None}
    assert named >= COMPACT | {"hippo.convert"}


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns
        self.stats = []           # no tf_op, hlo_module or program_id stat


def test_an_op_without_a_module_stat_takes_the_module_it_ran_inside():
    op_name = stages.OpNames({
        "jit_a(1)": {"fusion.3": "jit(a)/hippo.gather/x"},
        "jit_b(2)": {"fusion.3": "jit(b)/hippo.select/y"}})
    modules = [(0, 100, "jit_a(1)"), (100, 200, "jit_b(2)")]
    ev = lambda start: _Event("%fusion.3 = f32[4]{0} fusion(...)", start, 10)
    assert op_name(ev(20), modules) == "jit(a)/hippo.gather/x"
    assert op_name(ev(150), modules) == "jit(b)/hippo.select/y"
    assert op_name(ev(195), modules) is None     # ends past its module
    assert op_name(ev(250), modules) is None
