#!/usr/bin/env python3
"""The controls of `warm-rs10.4-maint`, on the chip at the cell's own size:

    chiprun -- python3 benchmarks/controls_maint.py --seeds 2

A benchmark run never runs them; they show that the comparison deciding
`correct` can fail on the guarantee this configuration adds, "only volumes that
are full AND quiet are converted", one control for each half. Each is the
cell's own run with one flag typed otherwise than the maintenance script says
it in every second job of the window (the others type the script's line, so
the window still has bytes to make a rate of), while the plain reference
(`reference/ec_selection.py`) goes on judging by the configuration's line:

- fullness: `-fullPercent=0` where the script says 95. The program converts the
  decoy volume of the collection too, and the run has to come out as not
  correct by `selection_extra` (a volume replied that the reference does not
  name).
- quiet: `-quietFor=87600h`, ten years, where the script says 1h. Every record
  of the cell is stamped 2023, so no volume has been quiet that long: a program
  that reads the flag converts nothing of that collection, and the run has to
  come out as not correct by `selection_missed`. A program that ignores
  `-quietFor` converts as ever, comes out correct, and fails this control: the
  cell's own volumes are all quiet, so this is where the quiet half of the
  guarantee is held.

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

CELL = "warm-rs10.4-maint.ec-encode-full4"


# name -> (what the script says, what the control types, the comparison that has to fail)
CONTROLS = {
    "fullness": ("-fullPercent=95", "-fullPercent=0", "selection_extra"),
    "quiet": ("-quietFor=1h", "-quietFor=87600h", "selection_missed"),
}


def typing(load, said: str, typed: str):
    """`common.load`, with `typed` where the cell's command says `said`, in
    every second job: the set-up's job and the window's first keep the
    script's own line."""

    def patched(*parts):
        data = load(*parts)
        if parts == ("workloads", CELL + ".json"):
            data = copy.deepcopy(data)
            command = data["traffic"]["command"]
            if said not in command:
                raise common.Failed(f"the control expects {said} in {command!r}")
            data["traffic"]["command"] = [command, command.replace(said, typed)]
        return data

    return patched


def control_run(seed: int, seconds: float, rehearse: bool = False, control: str = "fullness") -> dict:
    said, typed, key = CONTROLS[control]
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds, trace=0,
                              rehearse=rehearse, fault=None)
    load = common.load
    common.load = typing(load, said, typed)
    try:
        line, _compared = bench_run.run(args)
    finally:
        common.load = load
    held = line["compared"][key]
    return {"control": f"ec.encode typed with {typed} under the reference's {said}",
            "seed": seed, "attempted": line["attempted"], "failed": line["failed"],
            key: held["value"], "limit": held["limit"],
            "shard_files_missized": line["compared"]["shard_files_missized"]["value"],
            "correct": line["correct"],
            "not_correct": line["correct"] is False and held["value"] > held["limit"]
            and not bench_run.verdict(line["compared"])}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=3_000_000_301)
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
