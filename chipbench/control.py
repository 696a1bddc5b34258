#!/usr/bin/env python3
"""The control of the comparison: the reference in bfloat16 in the
program's place. It has to come out not correct.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 [--rounds 16]

For each seed it generates the cell's table and the same closed-loop rounds
a run would send (``--rounds`` of them after the warm-up, each followed by
the refresh stream's rows where the mix has one, all acknowledged), draws
the same sample of queries, answers them with ``reference.control_answers`` (keys and
bounds rounded to bfloat16, on the chip) and compares those answers with the
float32 reference, as a run compares the program's. It prints the numbers
compared, one line per seed. Not part of a benchmark run. Needs a TPU, as a
run does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import catalog, check, datagen, loadgen, reference  # noqa: E402


def control_numbers(cell: catalog.Cell, seed: int, rounds: int) -> dict:
    config, mix = cell.config, cell.mix
    keys = datagen.load_column(config, seed)
    streams = loadgen.Streams(mix, seed)
    refresh = loadgen.refresh_of(mix, config, seed)
    warmup = int(mix["warmup_rounds"])
    queries, inserted, acked = [], [], 0
    for r in range(warmup + rounds):
        window = streams.queries()
        if r >= warmup:
            queries += [(float(lo), float(hi), acked) for lo, hi in window]
        if refresh is not None:
            inserted.append(refresh.rows())
            acked += inserted[-1].size
    inserts = np.concatenate(inserted) if inserted else None
    idx = loadgen.sample(seed, len(queries),
                         range(len(queries) - streams.streams, len(queries)),
                         check.SAMPLE)
    todo = [queries[i] for i in idx]
    top_k = config["engine"]["top_k"]
    want = reference.RangeScan(keys, top_k, inserts).answers(todo)
    got = reference.control_answers(keys, top_k, todo, inserts)
    return check.compare(got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--rounds", type=int, default=16)
    args = ap.parse_args(argv)
    cell = catalog.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(cell, seed, args.rounds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": check.is_correct(numbers),
                          "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
