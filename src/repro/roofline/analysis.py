"""Analytic memory/compute roofline for the five Hippo Pallas kernels.

Hippo's hot phases (bitmap_and / batch_filter / bucketize / page_inspect /
compact_inspect) are elementwise scans and reductions: arithmetic intensity
is a handful of vector ops per byte, far below any accelerator's
compute/bandwidth ridge, so every one of them is memory-bound and the honest
performance statement is *achieved bytes/s as a fraction of the memory
roofline*. This module turns a timed run into that statement:

  cost = KERNELS["bitmap_and"](e=65536, w=13)     # analytic bytes + ops
  rl   = roofline(cost, seconds, hardware("cpu_stream"))
  rl["achieved_gbps"], rl["roofline_frac"], rl["bound"]

The bytes/ops models count *mandatory* main-memory traffic (every operand
read once, every output written once) and vector ops on the padded dense
shapes the kernels actually execute — no cache modeling. A ``roofline_frac``
above 1.0 therefore means the working set fit in cache (common for the
smaller CPU configs), not a broken clock; on TPU, where VMEM residency is
explicit, the model is the classic HBM roofline.

The hardware table carries the v5e numbers the kernel block shapes were
sized for plus a measured-STREAM entry for this CPU host, so CPU trajectory
files are gated against what the machine can actually sustain rather than
paper numbers. ``hardware()`` with no argument reads the first JAX device:
a TPU is looked up by its ``device_kind`` (a kind missing from the table is
an error, never a default), anything else gets the measured CPU row.
"""
from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Hardware:
    """One row of the roofline hardware table.

    ``mem_bw`` is sustainable main-memory bandwidth in bytes/s (HBM for TPU,
    measured STREAM-copy for CPU); ``vector_ops`` is elementwise ops/s on
    the unit these kernels map to (VPU lanes for TPU, SIMD for CPU).
    """
    name: str
    mem_bw: float
    vector_ops: float
    note: str = ""

    @property
    def ridge_ai(self) -> float:
        """Ops/byte above which a kernel stops being memory-bound."""
        return self.vector_ops / self.mem_bw


# v5e per chip: 819 GB/s HBM; VPU = 8x128 lanes x ~4 ALUs x ~940 MHz ~= 3.9
# Tops/s elementwise (order-of-magnitude — these kernels sit at ~1 op/byte,
# ~5x under the ridge, so the memory term dominates regardless).
TPU_V5E = Hardware("tpu_v5e", mem_bw=819e9, vector_ops=3.9e12,
                   note="v5e chip: HBM 819 GB/s, VPU 8x128 lanes")

# TPU rows keyed by ``jax.devices()[0].device_kind``. Peaks: Google Cloud
# documentation, "TPU v5e" (819 GB/s of HBM per chip).
TPU_BY_KIND = {"TPU v5 lite": TPU_V5E}


@functools.lru_cache(maxsize=None)
def measure_cpu_stream(mbytes: int = 64, reps: int = 5) -> float:
    """Measured STREAM-copy bandwidth of this host in bytes/s (min-time rep).

    A 64 MiB float64 copy defeats every cache level that matters; traffic is
    2 bytes moved per byte of array (read + write). Cached per process so
    benchmark loops pay the ~100 ms measurement once.
    """
    n = mbytes * 2**20 // 8
    src = np.full(n, 1.0)
    dst = np.empty_like(src)
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = min(best, time.perf_counter() - t0)
    return 2 * 8 * n / best


@functools.lru_cache(maxsize=None)
def _cpu_stream_hardware() -> Hardware:
    bw = measure_cpu_stream()
    # SIMD elementwise throughput estimate: ~4 lanes x 2 ports x ~3 GHz.
    # Like the VPU number it only decides the (never-reached) ridge.
    return Hardware("cpu_stream", mem_bw=bw, vector_ops=24e9 * 1.0,
                    note=f"measured STREAM copy {bw / 1e9:.1f} GB/s")


def hardware(name: str | None = None) -> Hardware:
    """Look up a hardware-table row by name; ``None`` reads the device.

    On a TPU the row is keyed by ``device_kind``; a kind the table does not
    hold raises ``KeyError`` instead of borrowing another chip's peaks."""
    if name is None:
        import jax
        dev = jax.devices()[0]
        if dev.platform == "tpu":
            if dev.device_kind not in TPU_BY_KIND:
                raise KeyError(f"no roofline row for TPU kind "
                               f"{dev.device_kind!r}; have: "
                               f"{sorted(TPU_BY_KIND)}")
            return TPU_BY_KIND[dev.device_kind]
        name = "cpu_stream"
    if name == "tpu_v5e":
        return TPU_V5E
    if name == "cpu_stream":
        return _cpu_stream_hardware()
    raise KeyError(f"unknown hardware {name!r}; have: tpu_v5e, cpu_stream")


# ---------------------------------------------------------------------------
# per-kernel traffic/ops models
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class KernelCost:
    """Mandatory main-memory bytes and elementwise vector ops for one call."""
    kernel: str
    bytes_moved: float
    ops: float

    @property
    def arithmetic_intensity(self) -> float:
        return self.ops / self.bytes_moved if self.bytes_moved else 0.0


def bitmap_and_cost(*, e: int, w: int) -> KernelCost:
    """§3.2 single-query filter: (E, W) u32 entries AND a (W,) u32 query,
    any-reduced to (E,) i32. Reads E*W words + the query, writes E flags."""
    bytes_moved = (e * w + w + e) * 4
    ops = 2.0 * e * w              # AND + nonzero/or-reduce per word
    return KernelCost("bitmap_and", bytes_moved, ops)


def batch_filter_cost(*, q: int, e: int, w: int, s: int = 1) -> KernelCost:
    """PR 1/2 fused batch filter: (Q, W) queries x (S, E, W) entries ->
    (S, Q, E) flags. Entries are read once per query (the (Q, E) grid
    re-streams the entry tile per query row)."""
    bytes_moved = (s * q * e * w + q * w + s * q * e) * 4
    ops = 3.0 * s * q * e * w      # AND + nonzero + or-reduce
    return KernelCost("batch_filter", bytes_moved, ops)


def bucketize_cost(*, n: int, h: int) -> KernelCost:
    """§4.2 bucket probe: N f32 values binary-searched into H buckets.
    Values in, ids out; the (H+1,) bounds table is VMEM/cache resident."""
    bytes_moved = (2 * n + (h + 1)) * 4
    ops = float(n) * math.ceil(math.log2(h + 1))
    return KernelCost("bucketize", bytes_moved, ops)


def page_inspect_cost(*, p: int, c: int) -> KernelCost:
    """§3.3 false-positive filter: (P, C) f32 keys + (P, C) bool validity
    under a (P,) page mask -> (P, C) qualifying bools + (P,) i32 counts."""
    bytes_moved = p * c * 4 + p * c + p + p * c + p * 4
    ops = 5.0 * p * c              # 2 cmps + 2 ands + count-reduce
    return KernelCost("page_inspect", bytes_moved, ops)


def compact_inspect_cost(*, q: int, m: int, c: int) -> KernelCost:
    """PR 4 gather-slab inspect: (M, C) f32 gathered keys + validity,
    (Q, M) selection mask, (Q,) bounds -> (Q, M) i32 counts. The slab is
    re-streamed per query row like batch_filter's entry tile."""
    bytes_moved = q * m * c * 4 + q * m * c + q * m + q * 8 + q * m * 4
    ops = 5.0 * q * m * c          # sel & valid & 2 cmps + count-reduce
    return KernelCost("compact_inspect", bytes_moved, ops)


KERNELS = {
    "bitmap_and": bitmap_and_cost,
    "batch_filter": batch_filter_cost,
    "bucketize": bucketize_cost,
    "page_inspect": page_inspect_cost,
    "compact_inspect": compact_inspect_cost,
}


# ---------------------------------------------------------------------------
# roofline statement
# ---------------------------------------------------------------------------

def roofline_from_traffic(bytes_moved: float, ops: float, seconds: float,
                          hw: Hardware) -> dict:
    """Roofline verdict for any (bytes, ops, time) triple on ``hw``.

    ``roofline_us`` is the analytic floor (slower of the memory and compute
    terms); ``roofline_frac`` = floor / measured — 1.0 means the run hit the
    roofline, >1.0 means the model's mandatory-traffic assumption was beaten
    (cache residency on CPU).
    """
    if seconds <= 0:
        raise ValueError(f"seconds must be positive, got {seconds}")
    t_mem = bytes_moved / hw.mem_bw
    t_ops = ops / hw.vector_ops
    t_roof = max(t_mem, t_ops)
    return {
        "hardware": hw.name,
        "bytes": float(bytes_moved),
        "ops": float(ops),
        "achieved_gbps": bytes_moved / seconds / 1e9,
        "roofline_gbps": hw.mem_bw / 1e9,
        "roofline_us": t_roof * 1e6,
        "roofline_frac": t_roof / seconds,
        "bound": "memory" if t_mem >= t_ops else "compute",
    }


def roofline(cost: KernelCost, seconds: float, hw: Hardware) -> dict:
    out = roofline_from_traffic(cost.bytes_moved, cost.ops, seconds, hw)
    out["kernel"] = cost.kernel
    return out
