"""Elastic scaling: a checkpoint written under one mesh restores onto a
different device count (subprocess meshes of 4 and 8 virtual devices)."""
import json
import os
import subprocess
import sys
import textwrap
import pytest

pytestmark = pytest.mark.slow

_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.checkpointing import restore_checkpoint, save_checkpoint
    from repro.runtime.elastic import reshard_for_mesh, validate_divisibility

    mesh = jax.make_mesh(({d}, {m}), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    template = {{"w": jnp.zeros((8, 16)), "b": jnp.zeros((16,))}}
    if "{phase}" == "save":
        tree = {{"w": jnp.arange(128, dtype=jnp.float32).reshape(8, 16),
                "b": jnp.arange(16, dtype=jnp.float32)}}
        sharded = reshard_for_mesh(tree, {{"w": P("data", "model"),
                                          "b": P("model")}}, mesh)
        save_checkpoint("{ckpt}", 7, sharded)
        print(json.dumps({{"ok": True}}))
    else:
        step, tree = restore_checkpoint("{ckpt}", treedef_like=template)
        tree = reshard_for_mesh(tree, {{"w": P("data", "model"),
                                       "b": P("model")}}, mesh)
        total = float(tree["w"].sum()) + float(tree["b"].sum())
        nshards = len(tree["w"].sharding.device_set)
        print(json.dumps({{"step": step, "total": total,
                          "shards": nshards}}))
""")


def _run(phase, n, d, m, ckpt):
    prog = _PROG.format(phase=phase, n=n, d=d, m=m, ckpt=ckpt)
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             # virtual-device mesh => host platform; without this the child
             # probes for real TPUs (minutes of metadata retries on CI hosts)
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")})
    assert res.returncode == 0, res.stderr[-1500:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_restore_onto_larger_and_smaller_mesh(tmp_path):
    ckpt = str(tmp_path / "ck")
    _run("save", 4, 2, 2, ckpt)                      # written on 4 devices
    out8 = _run("restore", 8, 4, 2, ckpt)            # grow to 8
    assert out8["step"] == 7
    assert out8["total"] == float(sum(range(128)) + sum(range(16)))
    assert out8["shards"] == 8
    out2 = _run("restore", 2, 2, 1, ckpt)            # shrink to 2
    assert out2["total"] == out8["total"]
    assert out2["shards"] == 2
