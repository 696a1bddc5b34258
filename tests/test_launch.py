"""Launch-layer tests: mesh construction, sharding specs, and a small-mesh
lower+compile of each step kind (subprocess with 8 virtual devices)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.launch.shardings import param_spec
from jax.sharding import PartitionSpec as P


def test_param_spec_rules():
    cfg = get_config("llama4-maverick-400b-a17b")

    class K:  # fake path keys
        def __init__(self, key):
            self.key = key

    # EP for divisible experts
    spec = param_spec(cfg, (K("units"), K("b1_moe"), K("moe"), K("w_gate")), None)
    assert spec == P(None, "model", "data", None)
    cfg2 = get_config("qwen2-moe-a2.7b")  # 60 experts -> TP inside expert
    spec = param_spec(cfg2, (K("units"), K("b0_moe"), K("moe"), K("w_gate")), None)
    assert spec == P(None, None, "data", "model")
    spec = param_spec(cfg2, (K("units"), K("b0_moe"), K("moe"), K("w_down")), None)
    assert spec == P(None, None, "model", "data")
    # shared experts are dense ffn, not expert-sharded
    spec = param_spec(cfg2, (K("units"), K("b0_moe"), K("moe"), K("shared"),
                             K("w_gate")), None)
    assert spec == P(None, "data", "model")
    # norms replicate
    assert param_spec(cfg, (K("units"), K("b0_attn"), K("norm1"), K("scale")),
                      None) == P()


_SMALL_MESH_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import get_config, SHAPES
    from repro.launch import steps as steps_lib
    from repro.launch.shardings import (make_opt_shardings,
        make_param_shardings, replicated, train_batch_shardings,
        tree_cache_shardings)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("{arch}").reduced(d_model=128, num_heads=4,
                                       num_kv_heads=4, head_dim=32,
                                       vocab_size=512, d_ff=256)
    out = {{}}
    with mesh:
        p_shape = steps_lib.params_shape(cfg)
        p_sh = make_param_shardings(cfg, mesh, p_shape)
        kind = "{kind}"
        if kind == "train":
            class Shape: seq_len=64; global_batch=8; kind="train"; name="t"
            o_shape = steps_lib.opt_state_shape(cfg, p_shape, "float32")
            o_sh = make_opt_shardings(cfg, mesh, o_shape)
            b_sh = train_batch_shardings(cfg, mesh, 8)
            specs = steps_lib.input_specs(cfg, Shape, "train")
            step = steps_lib.make_train_step(cfg, accum=2)
            c = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                        out_shardings=(p_sh, o_sh, None)
                        ).lower(p_shape, o_shape, specs).compile()
        else:
            class Shape: seq_len=64; global_batch=8; kind="decode"; name="d"
            c_shape = steps_lib.cache_shape(cfg, 8, 64)
            c_sh = tree_cache_shardings(cfg, mesh, c_shape, 8)
            tok_sh = train_batch_shardings(cfg, mesh, 8)["inputs"]
            specs = steps_lib.input_specs(cfg, Shape, "decode")
            step = steps_lib.make_decode_step(cfg)
            c = jax.jit(step,
                        in_shardings=(p_sh, c_sh, tok_sh, replicated(mesh)),
                        out_shardings=(None, c_sh)
                        ).lower(p_shape, c_shape, specs["tokens"],
                                specs["pos"]).compile()
        ca = c.cost_analysis() or {{}}
        if isinstance(ca, (list, tuple)):   # jax 0.4.x returns [dict]
            ca = ca[0] if ca else {{}}
        out["flops"] = float(ca.get("flops", 0))
        out["mem"] = c.memory_analysis().temp_size_in_bytes
    print(json.dumps(out))
""")


@pytest.mark.slow  # subprocess mesh + full lower/compile per arch
@pytest.mark.parametrize("arch,kind", [
    ("yi-6b", "train"),
    ("qwen2-moe-a2.7b", "train"),
    ("recurrentgemma-9b", "decode"),
    ("rwkv6-3b", "decode"),
])
def test_small_mesh_lower_compile(arch, kind):
    """The dry-run machinery works on an 8-device mesh for every step kind
    and block family (full 512-device run lives in repro.launch.dryrun)."""
    prog = _SMALL_MESH_PROG.format(arch=arch, kind=kind)
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             # virtual-device mesh => host platform; without this the child
             # probes for real TPUs (minutes of metadata retries on CI hosts)
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")})
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["flops"] > 0
