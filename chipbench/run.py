#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` and, when traced, ``breakdown``; its
last key, ``checks``, gives each number compared with its limit, as do the
last lines of standard error.

Needs a TPU with as many chips as the cell asks for: otherwise it exits 2
and prints no result. JAX's persistent compilation cache goes to
``$JAX_COMPILATION_CACHE_DIR`` when set, else to ``.jax_cache`` in the
checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import catalog  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = catalog.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(catalog.REPO_ROOT / "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    # small programs too, so a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from chipbench.driver import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
