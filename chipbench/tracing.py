"""Profiler capture around the measured window, and its reduction to
device busy time, the top device operations and the idle gaps.

The harness records its own host spans (``jax.profiler.TraceAnnotation``)
around each call into the system (``chipbench.submit``,
``chipbench.run_batch``), all inside one ``chipbench.window`` span. The reduction reads the profiler's
``.xplane.pb`` with ``jax.profiler.ProfileData``:

  window_s    length of the ``chipbench.window`` span
  busy_s      union of the intervals in which an operation ran on a device
              (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
              clipped to the window, averaged over the devices that ran one
  device_ops  device seconds per operation name, largest first
  idle_gaps   seconds in which no device operation ran, by the innermost
              harness span the host was in at the middle of each gap
"""
from __future__ import annotations

import contextlib
import shutil
import tempfile
from dataclasses import dataclass
from pathlib import Path

PREFIX = "chipbench."
WINDOW = PREFIX + "window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINES = ("XLA Ops", "XLA Modules")   # the first one a plane has


class Capture:
    """Harness spans, and a profiler trace while ``enabled``."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Path | None = None

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(PREFIX + name)

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        self.dir = Path(tempfile.mkdtemp(prefix="chipbench_trace_"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # harness spans only, no call tracing
        opts.enable_hlo_proto = False
        opts.host_tracer_level = 1       # user annotations; runtime events off
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self) -> Path | None:
        """Stop tracing; returns the ``.xplane.pb`` written."""
        if not self.enabled:
            return None
        import jax
        jax.profiler.stop_trace()
        return xplane_file(self.dir)

    def cleanup(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


def xplane_file(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


@dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def reduce(xplane: Path, top: int = 10) -> Summary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane))
    spans: list[tuple[int, int, str]] = []
    devices: list[list] = []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(DEVICE_PLANE):
            name = next((n for n in OPS_LINES if n in lines), None)
            if name is not None:
                devices.append(list(lines[name].events))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((int(e.start_ns),
                                  int(e.start_ns + e.duration_ns), e.name))
    windows = [s for s in spans if s[2] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = windows[0][:2]
    per_op: dict[str, float] = {}
    busy_ns, used, first_busy = [], 0, None
    for events in devices:
        iv = []
        for e in events:
            a = max(int(e.start_ns), w0)
            b = min(int(e.start_ns + e.duration_ns), w1)
            if b <= a:
                continue
            iv.append((a, b))
            module = _stat(e, "hlo_module")
            key = f"{module}:{e.name}" if module else e.name
            per_op[key] = per_op.get(key, 0.0) + (b - a) * 1e-9
        if not iv:
            continue
        merged = _union(iv)
        busy_ns.append(sum(b - a for a, b in merged))
        used += 1
        if first_busy is None:
            first_busy = merged
    gaps: dict[str, float] = {}
    if first_busy is not None:
        inner = sorted((s for s in spans if s[2] != WINDOW),
                       key=lambda s: s[1] - s[0])
        edges = [w0] + [x for ab in first_busy for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            owner = next((s[2] for s in inner if s[0] <= mid < s[1]), WINDOW)
            name = owner[len(PREFIX):]
            gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
    by_size = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=(sum(busy_ns) / used * 1e-9) if used else 0.0,
                   devices=used, device_ops=by_size(per_op),
                   idle_gaps=by_size(gaps))
