"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips — the ``pod`` axis carries
pure data parallelism (params replicated across pods, gradients all-reduced
over the slow inter-pod links; FSDP + TP stay inside a pod where ICI is fast).

A FUNCTION, not a module constant: importing this module must never touch JAX
device state (the dry-run sets XLA_FLAGS before any jax import; tests build
small meshes of their own).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever local devices exist (tests, examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_shard_mesh(num_shards: int):
    """1-D ``data`` mesh for Hippo shard placement (``core.partition``).

    Uses the largest divisor of ``num_shards`` that fits the local device
    count, so the shard axis of a ``ShardedHippoState`` always divides the
    mesh (each device serves a contiguous block of shards; one device =
    everything replicated, which is the CPU test case).
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    n = jax.device_count()
    d = max(k for k in range(1, min(num_shards, n) + 1) if num_shards % k == 0)
    return jax.make_mesh((d,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def batch_axes(mesh) -> tuple:
    """Mesh axes a batch dimension shards over (pod+data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
