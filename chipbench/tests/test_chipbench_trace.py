"""The reduction from a profiler trace to busy time, device operations and
idle gaps, on a small trace whose answers are counted by hand."""
from pathlib import Path

import pytest

from chipbench import tracing

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def small_trace(tmp_path_factory) -> Path:
    from jax.profiler import ProfileData
    text = (DATA / "small_trace.pbtxt").read_text()
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_busy_time_is_the_union_of_device_ops_inside_the_window(small_trace):
    s = tracing.reduce(small_trace)
    assert s.window_s == pytest.approx(0.010, abs=1e-12)
    # [1.0, 1.2] + [2.0, 6.0] + [8.5, 10.0] ms; TPU:1 ran nothing inside
    assert s.busy_s == pytest.approx(0.0057, abs=1e-12)
    assert s.devices == 1


def test_device_ops_are_clipped_and_named_by_module(small_trace):
    ops = dict(tracing.reduce(small_trace).device_ops)
    assert ops == pytest.approx({"jit_search:fusion.1": 0.0045,
                                 "copy.2": 0.0015, "warmup.0": 0.0002},
                                abs=1e-12)
    names = [n for n, _ in tracing.reduce(small_trace).device_ops]
    assert names == ["jit_search:fusion.1", "copy.2", "warmup.0"]


def test_idle_gaps_are_named_by_the_innermost_harness_span(small_trace):
    s = tracing.reduce(small_trace)
    # [1.2, 2.0] in run_batch, [6.0, 8.5] in write, [10.0, 11.0] after the
    # last run_batch ended: only the window span covers its middle
    assert dict(s.idle_gaps) == pytest.approx(
        {"write": 0.0025, "window": 0.0010, "run_batch": 0.0008}, abs=1e-12)
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, abs=1e-12)


def test_a_trace_without_the_window_span_is_refused(tmp_path):
    from jax.profiler import ProfileData
    text = (DATA / "small_trace.pbtxt").read_text().replace(
        '"chipbench.window"', '"something.else"')
    path = tmp_path / "nowindow.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError):
        tracing.reduce(path)
