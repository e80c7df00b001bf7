#!/usr/bin/env python3
"""The controls of `warm-rs10.4-filer4m`, on the chip at the cell's own size:

    chiprun -- python3 benchmarks/controls_chunks.py --seeds 3

A benchmark run never runs them; they show that the comparison deciding
`correct` can fail on the guarantee this configuration adds, "a chunk needle
is its five intervals, each the bytes the layout puts there, a lost one
rebuilt". Each is the cell's own run with one thing otherwise:

- span_byte: one byte in 64 of every reconstructed row altered where the codec
  returns it (`benchmarks/lib/server_child.py --fault ec_decode_byte`, as
  `controls.py` plants it under `degraded-get-c16`): a rebuilt span of up to
  1 MiB has the right length and the wrong bytes. The program checks a
  needle's CRC after the join, so every GET that meets the lost shard gets an
  error and no body: `bodies_wrong` > 0, not correct; the GETs that meet no
  lost shard come back right.
- block_off: every interval on data shard 5, a healthy one, read one block
  further down its shard file, as a locate that is a row off would
  (`benchmarks/lib/chunk_fault_child.py --fault ec_interval_block_off`):
  again the CRC fails (or the read is short in the shard's last row) and no
  GET that touches shard 5 gets its body: `bodies_wrong` > 0, not correct.

Each line printed is one reading; the last says whether every control came out
as not correct.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import common  # noqa: E402

CELL = "warm-rs10.4-filer4m.degraded-chunk-get-c16"

# name -> (run.py's --fault, what is otherwise in the cell's traffic)
CONTROLS = {
    "span_byte": ("ec_decode_byte", {}),
    "block_off": (None, {"server_fault": "ec_interval_block_off"}),
}


def otherwise(load, changes: dict):
    """`common.load`, with `changes` laid over the cell's traffic."""

    def patched(*parts):
        data = load(*parts)
        if parts == ("workloads", CELL + ".json") and changes:
            data = copy.deepcopy(data)
            data["traffic"].update(changes)
        return data

    return patched


def control_run(seed: int, seconds: float, rehearse: bool = False, control: str = "span_byte") -> dict:
    fault, changes = CONTROLS[control]
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds, trace=0,
                              rehearse=rehearse, fault=fault)
    load = common.load
    common.load = otherwise(load, changes)
    try:
        line, _compared = bench_run.run(args)
    finally:
        common.load = load
    wrong = line["compared"]["bodies_wrong"]
    return {"control": control, "seed": seed, "attempted": line["attempted"],
            "failed": line["failed"], "bodies_wrong": wrong["value"],
            "correct": line["correct"],
            "not_correct": line["correct"] is False and wrong["value"] > wrong["limit"]
            and not bench_run.verdict(line["compared"])}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_501)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    readings = []
    for j in range(args.seeds * len(CONTROLS)):
        control, seed = list(CONTROLS)[j % len(CONTROLS)], args.first_seed + j
        try:
            readings.append(control_run(seed, args.seconds, control=control))
        except Exception as e:  # a control that cannot run proves nothing
            readings.append({"control": control, "seed": seed,
                             "not_correct": False, "error": f"{type(e).__name__}: {e}"[:1500]})
        print(json.dumps(readings[-1]), flush=True)
    ok = all(r["not_correct"] for r in readings)
    print(json.dumps({"every_control_not_correct": ok, "readings": len(readings)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
