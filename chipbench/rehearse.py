#!/usr/bin/env python3
"""Compile the cells' device programs for a described TPU v5e, no chip needed.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/rehearse.py [config ...]

Run by hand before a chip run. For each configuration (default: all under
``configs/``) it compiles, for one chip of a described ``v5e:2x2``, the
search programs a cell runs at that configuration's shapes: the sharded
compact search at the engine's first bucket (64 pages) and at the
never-truncating cap, with ``top_k`` row ids. It prints each program's
argument, output and temporary bytes from the compiler's memory analysis.
Nothing runs, so nothing here is a time or a result.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

FIRST_BUCKET = 64


def programs(config: dict, spec):
    import jax.numpy as jnp
    from chipbench.driver import engine_args
    from repro.core import index as hix
    from repro.core.partition import default_max_slots, default_pages_per_shard

    ix, en = config["index"], config["engine"]
    shards, card, res = ix["num_shards"], ix["page_card"], ix["resolution"]
    words = -(-res // 32)
    pages = -(-config["rows"] // card)
    # the shard slabs the cell builds: wide enough for its spare pages
    pps = (engine_args(config, pages)[1].get("pages_per_shard")
           or default_pages_per_shard(pages, shards))
    slots = default_max_slots(pps)
    q, top_k = en["batch"], en["top_k"]

    def state(lead=()):
        return hix.HippoState(
            bounds=spec(lead + (res + 1,), jnp.float32),
            bitmaps=spec(lead + (slots, words), jnp.uint32),
            starts=spec(lead + (slots,), jnp.int32),
            ends=spec(lead + (slots,), jnp.int32),
            sorted_order=spec(lead + (slots,), jnp.int32),
            slot_live=spec(lead + (slots,), jnp.bool_),
            num_entries=spec(lead, jnp.int32),
            num_slots=spec(lead, jnp.int32),
            summarized_until=spec(lead, jnp.int32))

    batch = (state((shards,)), spec((shards, q, words), jnp.uint32),
             spec((shards, pps, card), jnp.float32),
             spec((shards, pps, card), jnp.bool_),
             spec((q,), jnp.float32), spec((q,), jnp.float32))
    for m, name in ((FIRST_BUCKET, "bucket64"), (pps, "cap")):
        yield (f"search_compact_many_sharded[{name}={m}, top_k={top_k}]",
               hix.search_compact_many_sharded.lower(
                   *batch, max_selected=m, top_k=top_k))


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    names = (argv if argv is not None else sys.argv[1:]) or sorted(
        p.stem for p in (BENCH / "configs").glob("*.json"))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for name in names:
        config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        for prog, lowered in programs(config, spec):
            m = lowered.compile().memory_analysis()
            gib = lambda n: n / 2**30
            print(f"{name} {prog}: args={gib(m.argument_size_in_bytes):.3f} "
                  f"GiB out={gib(m.output_size_in_bytes):.3f} GiB "
                  f"temp={gib(m.temp_size_in_bytes):.3f} GiB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
