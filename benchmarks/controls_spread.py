#!/usr/bin/env python3
"""The controls of `warm-rs10.4-spread4`, on the chip at the cell's own size:

    chiprun -- python3 benchmarks/controls_spread.py --seeds 3

A benchmark run never runs them; they show that the comparison deciding
`correct` can fail on the guarantee this configuration adds, "with any one
server lost every needle still reads back byte for byte, from survivors that
other servers serve". Each is the cell's own run with one thing otherwise:

- span_byte: every peer alters one byte in 256 of each shard span it serves, a
  bit that follows the shard id (the same bit in every survivor cancels)
  (`benchmarks/lib/peer_child.py --fault ec_span_byte`): the survivors a
  reconstruct fetches have the right length and the wrong bytes. The program
  checks a needle's CRC after decoding, so the altered bytes come back as
  errors, not as wrong bodies: either way no right body, `bodies_wrong` > 0
  before the loss (`healthy_bodies_wrong`) and in the window, not correct.
- two_lost: two peers are lost, 6 to 8 shards of 14, more than the 4
  parities. No needle of a lost shard can be read: the GETs get no body
  (`bodies_wrong` counts every GET that was answered otherwise than 200 with
  the right bytes), `lost_beyond_parity` > 0, not correct.

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

CELL = "warm-rs10.4-spread4.server-lost-get-c16"

# name -> (what is otherwise in the cell's traffic, the comparisons that have to fail)
CONTROLS = {
    "span_byte": ({"peer_fault": "ec_span_byte"}, ("bodies_wrong", "healthy_bodies_wrong")),
    "two_lost": ({"lose": {"peers": 2}}, ("bodies_wrong", "lost_beyond_parity")),
}


def otherwise(load, changes: dict):
    """`common.load`, with `changes` laid over the cell's traffic."""

    def patched(*parts):
        data = load(*parts)
        if parts == ("workloads", CELL + ".json"):
            data = copy.deepcopy(data)
            for key, value in changes.items():
                if isinstance(value, dict):
                    data["traffic"][key].update(value)
                else:
                    data["traffic"][key] = value
        return data

    return patched


def control_run(seed: int, seconds: float, rehearse: bool = False, control: str = "span_byte") -> dict:
    changes, keys = CONTROLS[control]
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds, trace=0,
                              rehearse=rehearse, fault=None)
    load = common.load
    common.load = otherwise(load, changes)
    try:
        line, _compared = bench_run.run(args)
    finally:
        common.load = load
    held = {key: line["compared"][key] for key in keys}
    return {"control": control, "seed": seed, "attempted": line["attempted"],
            "failed": line["failed"], **{key: c["value"] for key, c in held.items()},
            "correct": line["correct"],
            "not_correct": line["correct"] is False
            and all(c["value"] > c["limit"] for c in held.values())
            and not bench_run.verdict(line["compared"])}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_401)
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
