"""``chip_smoke.py``: its body on CPU at a tiny size, its refusal without a
chip, and the compilation-cache placement its entry point relies on."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


@pytest.mark.slow
def test_smoke_body_passes_every_phase_on_cpu(tmp_path):
    """Every phase of the smoke, at a few thousand rows with interpret-mode
    kernels, checks out against its numpy reference."""
    smoke = _load_smoke()
    lines = []
    smoke.run_smoke(3_000, 0, interpret=True, storage_dir=tmp_path / "store",
                    log=lines.append)
    phases = [line.split("]")[0].lstrip("[") for line in lines]
    for phase in ("load", "reads", "writes", "durability", "recovered"):
        assert phase in phases, (phase, lines)
    # one checked batch per Fig. 7 selectivity in every query phase
    for phase in ("reads", "writes", "durability", "recovered"):
        checked = [ln for ln in lines
                   if ln.startswith(f"[{phase}] selectivity=")]
        assert len(checked) == len(smoke.SELECTIVITIES), checked
        assert all("row_ids=ok" in ln for ln in checked)
    kernels = [ln for ln in lines if ln.startswith("[kernels]")]
    assert len(kernels) == 5 and all("matches_ref=True" in ln
                                     for ln in kernels), kernels


def test_reference_scan_counts_and_first_ids():
    smoke = _load_smoke()
    import numpy as np
    ref = smoke.Reference(np.asarray([3, 1, 2, 3, 5], np.float32))
    assert ref.scan(np.float32(2), np.float32(3))[0] == 3
    assert list(ref.scan(np.float32(2), np.float32(3))[1]) == [0, 2, 3]
    assert ref.delete(np.float32(3), np.float32(3)) == 2
    ref.insert(np.asarray([2.5], np.float32))
    count, ids = ref.scan(np.float32(2), np.float32(3))
    assert count == 2 and list(ids) == [2, 5]


@pytest.mark.parametrize("selectivity", [1e-5, 1e-4, 1e-3, 1e-2])
def test_query_windows_are_whole_days(selectivity):
    """Windows span whole shipdate days inside the generated domain, at
    least one, so a window below one day's share still holds rows."""
    import numpy as np
    from repro.storage.tpch import DATE_HI, DATE_LO
    smoke = _load_smoke()
    days = smoke.window_days(selectivity)
    assert days == max(1, round((DATE_HI - DATE_LO) * selectivity))
    w = smoke.query_windows(np.random.default_rng(0), selectivity, 256)
    assert w.shape == (256, 2) and w.dtype == np.float32
    assert (w == np.round(w)).all()
    assert (w[:, 1] - w[:, 0] == days - 1).all()
    assert w[:, 0].min() >= DATE_LO and w[:, 1].max() <= DATE_HI - 1


def test_smoke_refuses_without_a_chip(tmp_path):
    """On the CPU the script exits non-zero and prints no ``ok`` line."""
    proc = subprocess.run([sys.executable, str(SCRIPT)], cwd=tmp_path,
                          env=_cpu_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_smoke_alone_fails(tmp_path):
    """Copied out of the checkout, without the package, it cannot run."""
    (tmp_path / "chip_smoke.py").write_text(SCRIPT.read_text())
    env = _cpu_env()
    env.pop("PYTHONPATH")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


# Compiles one function after enable_compile_cache(), with the default
# directory pointed into the test's tmp_path (argv[1]) so the checkout's own
# .jax_cache is left alone.
_CACHE_PROBE = """
import json, pathlib, sys
import jax, jax.numpy as jnp
from repro.runtime import compile_cache
compile_cache.DEFAULT_DIR = pathlib.Path(sys.argv[1])
path = compile_cache.enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"path": str(path)}))
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_placement(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set the entries land there and nowhere
    else; without it they land at the default directory, the same one on
    every run."""
    default = tmp_path / "checkout_cache"
    extra = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    env_dir = tmp_path / "env_cache"
    if from_env:
        extra["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    for _ in range(2):          # the same directory on every run
        proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE,
                               str(default)], cwd=tmp_path,
                              env=_cpu_env(**extra), capture_output=True,
                              text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        used = Path(json.loads(proc.stdout.strip().splitlines()[-1])["path"])
        assert used == (env_dir if from_env else default)
    assert any(used.iterdir())
    other = default if from_env else env_dir
    assert not other.exists()


def test_default_cache_dir_is_fixed_inside_the_checkout():
    from repro.runtime import compile_cache
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"
