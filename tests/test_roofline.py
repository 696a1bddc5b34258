"""Roofline package: the per-kernel traffic models, the hardware table, and
the achieved-bandwidth statement every kernel bench row derives from."""
import math

import pytest

from repro import roofline as rl
from repro.roofline import analysis, report


def test_kernel_registry_covers_all_five_kernels():
    assert set(rl.KERNELS) == {"bitmap_and", "batch_filter", "bucketize",
                               "page_inspect", "compact_inspect"}


def test_bitmap_and_cost_counts_mandatory_traffic():
    c = rl.KERNELS["bitmap_and"](e=65_536, w=13)
    # entries + query read, one flag per entry written
    assert c.bytes_moved == (65_536 * 13 + 13 + 65_536) * 4
    assert c.ops == 2 * 65_536 * 13
    assert 0 < c.arithmetic_intensity < 1      # memory-bound territory


def test_costs_scale_linearly_in_the_streamed_axis():
    for kernel, small, big, axis in (
            ("bitmap_and", dict(e=1024, w=13), dict(e=2048, w=13), "e"),
            ("batch_filter", dict(q=8, e=1024, w=13),
             dict(q=8, e=2048, w=13), "e"),
            ("bucketize", dict(n=1024, h=400), dict(n=2048, h=400), "n"),
            ("page_inspect", dict(p=512, c=128), dict(p=1024, c=128), "p"),
            ("compact_inspect", dict(q=8, m=512, c=128),
             dict(q=8, m=1024, c=128), "m")):
        lo, hi = rl.KERNELS[kernel](**small), rl.KERNELS[kernel](**big)
        ratio = hi.bytes_moved / lo.bytes_moved
        assert 1.8 < ratio <= 2.05, (kernel, axis, ratio)
        assert hi.ops == 2 * lo.ops, (kernel, axis)


def test_all_kernels_are_memory_bound_on_both_hardware_rows():
    """Hippo's phases sit far under every ridge — the roofline statement is
    a bandwidth statement on v5e and on this host alike."""
    shapes = {
        "bitmap_and": dict(e=65_536, w=13),
        "batch_filter": dict(q=64, e=16_384, w=13),
        "bucketize": dict(n=1_048_576, h=400),
        "page_inspect": dict(p=16_384, c=128),
        "compact_inspect": dict(q=64, m=2_048, c=128),
    }
    for name, shape in shapes.items():
        cost = rl.KERNELS[name](**shape)
        for hw in (rl.TPU_V5E, rl.hardware("cpu_stream")):
            verdict = rl.roofline(cost, 1e-3, hw)
            assert verdict["bound"] == "memory", (name, hw.name)


def test_roofline_math():
    hw = rl.Hardware("toy", mem_bw=100e9, vector_ops=1e12)
    cost = analysis.KernelCost("toy_kernel", bytes_moved=1e9, ops=1e9)
    out = rl.roofline(cost, seconds=0.02, hw=hw)
    assert out["achieved_gbps"] == pytest.approx(50.0)   # 1 GB / 20 ms
    assert out["roofline_us"] == pytest.approx(10_000.0)  # 1 GB / 100 GB/s
    assert out["roofline_frac"] == pytest.approx(0.5)
    assert out["bound"] == "memory" and out["kernel"] == "toy_kernel"
    # compute-bound when the ops term dominates
    heavy = analysis.KernelCost("heavy", bytes_moved=1.0, ops=1e12)
    assert rl.roofline(heavy, 1.0, hw)["bound"] == "compute"
    with pytest.raises(ValueError):
        rl.roofline(cost, 0.0, hw)


def test_hardware_table_and_detection():
    assert rl.hardware("tpu_v5e").mem_bw == 819e9
    cpu = rl.hardware("cpu_stream")
    assert cpu.name == "cpu_stream"
    # measured STREAM bandwidth is cached and plausible for any host
    assert 1e9 < cpu.mem_bw < 1e12
    assert rl.hardware("cpu_stream") is cpu             # lru-cached
    assert rl.TPU_V5E.ridge_ai > 1.0
    with pytest.raises(KeyError):
        rl.hardware("abacus")


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def _pin_device(monkeypatch, platform, kind):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: [_FakeDevice(platform, kind)])


def test_hardware_detection_reads_device_kind(monkeypatch):
    """With no name, the row comes from the first device: a v5e by its
    ``device_kind``, a CPU by the measured STREAM row."""
    assert rl.hardware().name == "cpu_stream"          # this host's CPU
    _pin_device(monkeypatch, "tpu", "TPU v5 lite")
    assert rl.hardware() is rl.TPU_V5E


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "TPU v5"])
def test_hardware_unknown_tpu_kind_raises(monkeypatch, kind):
    """A TPU the table does not hold is an error, not v5e's peaks."""
    _pin_device(monkeypatch, "tpu", kind)
    with pytest.raises(KeyError, match=kind):
        rl.hardware()


def test_measure_cpu_stream_is_positive_and_cached():
    a = rl.measure_cpu_stream(mbytes=8, reps=2)
    b = rl.measure_cpu_stream(mbytes=8, reps=2)
    assert a == b and math.isfinite(a) and a > 0


def test_report_builds_table_from_trajectory_doc():
    doc = {"suites": {"kernels": [
        {"name": "kernel_bitmap_and_64k", "us_per_call": 1500.0,
         "derived": {"bytes": 3_670_068, "ops": 1_703_936}},
        {"name": "no_traffic_row", "us_per_call": 3.0, "derived": {}},
    ]}}
    table = report.build_table(doc, "tpu_v5e")
    assert "kernel_bitmap_and_64k" in table
    assert "no_traffic_row" not in table       # rows without bytes/ops skip
    assert "819 GB/s" in table and "memory" in table
    empty = report.build_table({"suites": {}}, "tpu_v5e")
    assert "no kernels-suite rows" in empty


def test_report_cli_round_trip(tmp_path, capsys):
    import json
    doc = {"suites": {"kernels": [
        {"name": "kernel_bucketize_1m", "us_per_call": 28_000.0,
         "derived": {"bytes": 8_390_212, "ops": 9_437_184}}]}}
    p = tmp_path / "bench.json"
    p.write_text(json.dumps(doc))
    assert report.main([str(p), "--hardware", "tpu_v5e"]) == 0
    assert "kernel_bucketize_1m" in capsys.readouterr().out
