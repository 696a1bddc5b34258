#!/usr/bin/env python3
"""Device time per stage of the search program, and the engine's host spans,
from one profiler trace of a cell's window.

The search program names its stages with ``jax.named_scope("hippo.<stage>")``
and the engine its host work with ``jax.profiler.TraceAnnotation``
(``hippo.run_batch``, ``hippo.drain``, ``hippo.dispatch``, ``hippo.readback``,
``hippo.fallback``). This reduction adds to ``tracing.reduce``, whose
``window_s`` and ``busy_s`` it keeps as they are:

  scopes      device self-seconds per stage inside the window: the innermost
              ``hippo.<stage>`` of each operation's ``op_name``; operations
              with none go under ``unscoped``
  device_ops  device self-seconds per operation, named ``<stage>:<name>``
              where it has a stage. Self time is an operation's duration
              less the part covered by operations nested inside it on the
              same line (a ``while`` and the fusions of its body), so
              nothing is counted twice
  idle_gaps   as ``tracing.reduce``'s, named by the innermost harness or
              engine span the host was in; engine spans keep their full
              ``hippo.`` names

An operation's ``op_name`` is its ``tf_op`` stat where the trace has one;
otherwise it is looked up by module and instruction name in the HLO protos
the profiler writes with ``enable_hlo_proto`` (the ``/host:metadata``
plane). A fusion carries the ``op_name`` of its root instruction, so its
time goes to the stage of that root.

    python3 chipbench/stages.py --workload <cell> --seed <n> --seconds <s>
        [--orders N --rows N] [--keep DIR]

runs the cell as ``run.py --trace 1`` does (``chipbench.driver``), prints
each stage's device milliseconds per batch and the scoped share of busy
time, and with ``--keep`` keeps the trace there. ``--orders``/``--rows`` cut the
configuration's table, as the benchmark's own tests do. Needs a TPU.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import tracing  # noqa: E402

STAGE = re.compile(r"hippo\.[a-z_]+")
SPAN_PREFIXES = (tracing.PREFIX, "hippo.")
UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"
MODULES_LINE = "XLA Modules"
INSTRUCTION = re.compile(r"%?([^\s=%]+)")   # "%fusion.3 = f32[..." -> fusion.3


@dataclass
class Stages:
    window_s: float
    busy_s: float
    devices: int
    scopes: dict[str, float]
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]

    @property
    def scoped_share(self) -> float:
        """Share of the window's device self time that has a stage."""
        total = sum(self.scopes.values())
        return (total - self.scopes.get(UNSCOPED, 0.0)) / total if total \
            else 0.0


def stage_of(op_name: str | None) -> str | None:
    """The innermost ``hippo.<stage>`` path segment of an ``op_name``."""
    found = STAGE.findall(op_name or "")
    return found[-1] if found else None


# -- the HLO protos of a trace, read off the protobuf wire format -----------
# ``ProfileData`` shows neither event metadata stats nor bytes; the few
# fields read here are those of tsl's xplane.proto and xla's hlo.proto.

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one message; length-delimited values as
    memoryviews, varints as ints."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _sub(buf, field: int):
    return (v for f, v in _fields(buf) if f == field)


def _text(buf, field: int) -> str | None:
    return next((bytes(v).decode() for v in _sub(buf, field)), None)


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` of a serialized ``HloProto``
    (hlo_module 1; computations 3; instructions 2; name 1, metadata 7;
    op_name 2)."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for instr in _sub(comp, 2):
                name = _text(instr, 1)
                op = next((_text(m, 2) for m in _sub(instr, 7)), None)
                if name and op:
                    out[name] = op
    return out


def trace_hlo(xplane: Path) -> dict[str, dict[str, str]]:
    """Module name (``jit_f(5)``) -> ``hlo_op_names`` of its HLO proto, for
    every module the trace holds one for (XSpace planes 1; XPlane name 2,
    event_metadata 4, stat_metadata 5; map entries key 1, value 2;
    metadata name 2, stats 5; XStat metadata_id 1, bytes_value 6)."""
    out = {}
    for plane in _sub(memoryview(Path(xplane).read_bytes()), 1):
        if _text(plane, 2) != METADATA_PLANE:
            continue
        ids = {next(_sub(e, 1), None) for e in _sub(plane, 5)
               for m in _sub(e, 2) if _text(m, 2) == HLO_PROTO}
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                for stat in _sub(meta, 5):
                    if next(_sub(stat, 1), None) in ids:
                        for proto in _sub(stat, 6):
                            out[_text(meta, 2)] = hlo_op_names(proto)
    return out


class OpNames:
    """``op_name`` of each device operation of one trace, given the trace's
    ``trace_hlo``."""

    def __init__(self, hlo: dict[str, dict[str, str]]):
        self.hlo = hlo

    def module(self, event, modules: list[tuple[int, int, str]]):
        name = tracing._stat(event, "hlo_module")
        if name is not None:
            pid = tracing._stat(event, "program_id")
            return f"{name}({pid})" if pid is not None else name
        k = bisect.bisect_right(modules, (int(event.start_ns), 2**63)) - 1
        if k >= 0 and modules[k][1] >= int(event.start_ns + event.duration_ns):
            return modules[k][2]
        return None

    def __call__(self, event, modules: list[tuple[int, int, str]]
                 ) -> str | None:
        op = tracing._stat(event, "tf_op")
        if op is not None:
            return op
        m = INSTRUCTION.match(event.name)
        table = self.hlo.get(self.module(event, modules))
        return table.get(m.group(1)) if table and m else None


def _span_name(name: str) -> str:
    return name[len(tracing.PREFIX):] if name.startswith(tracing.PREFIX) \
        else name


def self_times(events: list[tuple[int, int]], w0: int, w1: int
               ) -> list[float]:
    """Seconds of each ``(start_ns, end_ns)`` event inside ``[w0, w1]``
    that no event nested inside it on the same line covers."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    children: dict[int, list[int]] = defaultdict(list)
    stack: list[int] = []
    for i in order:
        a, b = events[i]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        parent = next((j for j in reversed(stack) if events[j][1] >= b),
                      None)
        if parent is not None:
            children[parent].append(i)
        stack.append(i)

    def clip(i):
        a, b = events[i]
        return max(a, w0), min(b, w1)

    out = []
    for i in range(len(events)):
        a, b = clip(i)
        if b <= a:
            out.append(0.0)
            continue
        covered = tracing._union([c for c in map(clip, children[i])
                                  if c[1] > c[0]])
        out.append(max(0, (b - a) - sum(y - x for x, y in covered)) * 1e-9)
    return out


def reduce(xplane: Path, top: int = 10) -> Stages:
    """The stages of one trace; ``top`` operations and gaps, largest
    first. Device seconds are averaged over the devices, as busy time is."""
    from jax.profiler import ProfileData
    base = tracing.reduce(xplane, top)
    data = ProfileData.from_file(str(xplane))
    op_name = OpNames(trace_hlo(xplane))
    spans: list[tuple[int, int, str]] = []
    devices: list[tuple[list, list]] = []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(tracing.DEVICE_PLANE):
            name = next((n for n in tracing.OPS_LINES if n in lines), None)
            if name is not None:
                modules = sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in (lines[MODULES_LINE].events
                              if MODULES_LINE in lines else ()))
                devices.append((list(lines[name].events), modules))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    spans.append((int(e.start_ns),
                                  int(e.start_ns + e.duration_ns), e.name))
    w0, w1 = next(s[:2] for s in spans if s[2] == tracing.WINDOW)
    scopes: dict[str, float] = defaultdict(float)
    per_op: dict[str, float] = defaultdict(float)
    first_busy = None
    for events, modules in devices:
        iv = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
              for e in events]
        for e, secs in zip(events, self_times(iv, w0, w1)):
            if not secs:
                continue
            stage = stage_of(op_name(e, modules))
            scopes[stage or UNSCOPED] += secs
            per_op[f"{stage}:{e.name}" if stage else e.name] += secs
        inside = [(max(a, w0), min(b, w1)) for a, b in iv
                  if min(b, w1) > max(a, w0)]
        if first_busy is None and inside:
            first_busy = tracing._union(inside)
    gaps: dict[str, float] = defaultdict(float)
    if first_busy is not None:
        inner = sorted((s for s in spans if s[2] != tracing.WINDOW),
                       key=lambda s: s[1] - s[0])
        edges = [w0] + [x for ab in first_busy for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            owner = next((s[2] for s in inner if s[0] <= mid < s[1]),
                         tracing.WINDOW)
            gaps[_span_name(owner)] += (b - a) * 1e-9
    used = max(base.devices, 1)
    by_size = lambda d: sorted(((k, v / used) for k, v in d.items()),
                               key=lambda kv: -kv[1])[:top]
    return Stages(window_s=base.window_s, busy_s=base.busy_s,
                  devices=base.devices,
                  scopes={k: v / used for k, v in scopes.items()},
                  device_ops=by_size(per_op),
                  idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1])[:top])


def record(cell, seed: int, seconds: float, out: Path,
           config: dict | None = None) -> tuple[Path, int]:
    """Build the cell's engine, warm it up as ``driver.run_cell`` does and
    trace whole rounds inside a ``chipbench.window`` span until ``seconds``
    have passed, with the HLO protos in the trace. Returns the
    ``.xplane.pb`` (under ``out``) and the window's batch count."""
    import time

    import jax
    from chipbench import datagen, driver, loadgen

    config = config or cell.config
    engine = driver.build_engine(config, datagen.load_column(config, seed))
    capture = tracing.Capture(True)
    drv = driver.Driver(engine, loadgen.Streams(cell.mix, seed), capture,
                        loadgen.refresh_of(cell.mix, config, seed))
    for _ in range(int(cell.mix["warmup_rounds"])):
        drv.round()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = True        # the op_name of every instruction
    jax.profiler.start_trace(str(out), profiler_options=opts)
    first, t_open = drv.batches, time.perf_counter()
    with capture.span("window"):
        while True:
            drv.round()
            if time.perf_counter() - t_open >= seconds:
                break
    jax.profiler.stop_trace()
    return tracing.xplane_file(out), drv.batches - first


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--orders", type=int)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--keep", type=Path)
    args = ap.parse_args(argv)

    from chipbench import catalog
    cell = catalog.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chipbench.stages: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(catalog.REPO_ROOT / "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    config = dict(cell.config)
    if args.orders:
        config.update(orders=args.orders, rows=args.rows)
    out = Path(tempfile.mkdtemp(prefix="chipbench_stages_"))
    try:
        xplane, batches = record(cell, args.seed, args.seconds, out, config)
        s = reduce(xplane)
        if args.keep:
            args.keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, args.keep / xplane.name)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    per_batch = {k: v / batches * 1e3 for k, v in sorted(s.scopes.items())}
    print(f"[stages] cell={cell.name} seed={args.seed} batches={batches} "
          f"busy_ms_per_batch={s.busy_s / batches * 1e3} "
          f"scoped_share={100 * s.scoped_share}%", file=sys.stderr)
    for k, v in per_batch.items():
        print(f"[stages] {k}_ms_per_batch={v}", file=sys.stderr)
    print(json.dumps({"cell": cell.name, "seed": args.seed,
                      "batches": batches, "window_s": s.window_s,
                      "busy_s": s.busy_s, "scoped_share": s.scoped_share,
                      "ms_per_batch": per_batch,
                      "device_ops": s.device_ops,
                      "idle_gaps": s.idle_gaps}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
