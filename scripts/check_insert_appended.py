#!/usr/bin/env python3
"""Check that the batched eager insert equals the row-by-row one at a
deployment's own size.

    python3 scripts/check_insert_appended.py [--config tpch_sf30_shipdate_rf1]
        [--seed N] [--orders N --rows N] [--refresh-orders N]

Builds the configuration's table and index as the benchmark does
(``chipbench.driver.build_engine``), draws one round of TPC-H RF1 rows
(``chipbench.loadgen.Refresh``, ``--refresh-orders`` orders), and applies
them to two copies of the state of the shard they land in: once with
``core.index.insert_appended`` and once with ``insert_tuple`` row by row,
both with relocation off. The logical entries (starts, ends and bitmaps in
sorted order), the entry count and the last summarized page must be equal.
The last line of standard output is a JSON object with ``equal``; the exit
code is 0 when they are. ``--orders``/``--rows`` cut the table, as the
benchmark's own tests do.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import datagen, driver, loadgen  # noqa: E402


def logical(st) -> tuple:
    e = int(st.num_entries)
    order = np.asarray(st.sorted_order)[:e]
    return (np.asarray(st.starts)[order], np.asarray(st.ends)[order],
            np.asarray(st.bitmaps)[order], int(st.summarized_until))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="tpch_sf30_shipdate_rf1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--orders", type=int)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--refresh-orders", type=int, default=8182)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.core import index as hix
    from repro.core.partition import shard_state

    config = json.loads((REPO / "chipbench" / "configs"
                         / f"{args.config}.json").read_text())
    if args.orders:
        config.update(orders=args.orders, rows=args.rows)
    index = driver.build_engine(config,
                                datagen.load_column(config, args.seed)).index
    rows = loadgen.Refresh({"function": "RF1",
                            "orders_per_round": args.refresh_orders},
                           config, args.seed).rows()
    table, spec = index.table, index.spec
    pages = (table.tail + np.arange(rows.size)) // table.page_card
    s = spec.owner(int(pages[0]))
    if spec.owner(int(pages[-1])) != s:
        raise SystemExit("the round spans two shards: pick another size")
    local = (pages - spec.page_lo(s)).astype(np.int32)
    cfg = dataclasses.replace(index.cfg, relocate_on_update=False)
    start = jax.tree.map(jnp.copy, shard_state(index.state.shards, s))
    jax.block_until_ready(start)

    padded = hix.pad_rows(rows, local)
    jax.block_until_ready(hix.insert_appended(
        cfg, jax.tree.map(jnp.copy, start), *padded))          # compile
    t0 = time.perf_counter()
    batched = jax.block_until_ready(hix.insert_appended(
        cfg, jax.tree.map(jnp.copy, start), *padded))
    batched_s = time.perf_counter() - t0

    st = start
    t0 = time.perf_counter()
    for v, p in zip(rows.tolist(), local.tolist()):
        st = hix.insert_tuple(cfg, st, jnp.float32(v), jnp.int32(p))
    jax.block_until_ready(st)
    loop_s = time.perf_counter() - t0

    got, want = logical(batched), logical(st)
    equal = (all(np.array_equal(a, b) for a, b in zip(got[:3], want[:3]))
             and got[3] == want[3])
    print(json.dumps({
        "equal": bool(equal), "config": args.config, "seed": args.seed,
        "device": jax.devices()[0].device_kind, "shard": s,
        "rows": int(rows.size), "first_page": int(local[0]),
        "entries_before": int(start.num_entries),
        "entries_after": [int(batched.num_entries), int(st.num_entries)],
        "summarized_until": [got[3], want[3]],
        "batched_s": batched_s, "loop_s": loop_s}), flush=True)
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
