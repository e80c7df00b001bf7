#!/usr/bin/env python3
"""The control and the faults, on the chip at the cells' own sizes:

    chiprun -- python3 benchmarks/controls.py --seeds 3

A benchmark run never runs these; they show that the comparison deciding
`correct` can fail. Each line printed is one reading; the last says whether
every control and fault came out as not correct.

- `warm-rs10.4` control: the reference codec put in the program's place with
  the configuration's guarantee broken — parity shard 13 written as a copy of
  shard 12, RS(10,3) under RS(10,4)'s name — at 1 GiB, through the same
  comparison a run makes (`shell_jobs`' digests and bytes). No server, no chip:
  the reading is the comparison's. It stands for both cells: a degraded read
  decodes with the rows of the same matrix.
- faults, through `run.py --fault ...` with a short window at the cell's own
  load: one parity byte of every encoded chunk altered where it is produced
  (`ec_parity_byte`); one byte in 64 of every reconstructed row altered where
  the codec returns it (`ec_decode_byte`).
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import shutil
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import common  # noqa: E402
from benchmarks.lib.stores import sealed_template  # noqa: E402
from benchmarks.lib.traffic import shell_jobs  # noqa: E402
from benchmarks.reference import rs_codec  # noqa: E402

FAULTS = [
    ("warm-rs10.4.ec-encode", "ec_parity_byte", "shard_blocks_differing"),
    ("warm-rs10.4.degraded-get-c16", "ec_decode_byte", "bodies_wrong"),
]


def write_rows(job: tuple) -> int:
    dat_path, base, rows, parity_matrix = job
    dat = np.memmap(dat_path, dtype=np.uint8, mode="r")
    spans = rs_codec.row_spans(len(dat), 10)
    fds = [os.open(f"{base}.ec{i:02d}", os.O_WRONLY) for i in range(14)]
    try:
        for r in rows:
            dat_off, shard_off, block = spans[r]
            data = rs_codec.data_rows(dat, dat_off, block, 10)
            for i, row in enumerate(np.concatenate([data, rs_codec.apply_matrix(parity_matrix, data)])):
                os.pwrite(fds[i], row.tobytes(), shard_off)
    finally:
        for fd in fds:
            os.close(fd)
    return len(rows)


def write_shards(store: dict, base: str, parity_matrix: np.ndarray, pool_map, workers: int) -> None:
    """The 14 shard files `<base>.ecNN` of the store's .dat under this parity matrix."""
    size = rs_codec.shard_size(store["dat_bytes"], 10)
    for i in range(14):
        with open(f"{base}.ec{i:02d}", "wb") as f:
            f.truncate(size)
    rows = list(range(len(rs_codec.row_spans(store["dat_bytes"], 10))))
    parts = [rows[w::workers] for w in range(workers)]
    pool_map(write_rows, [(store["dat"], base, part, parity_matrix) for part in parts if part])


def codec_control(seed: int, pool, workers: int) -> dict:
    config = common.load("configs", "warm-rs10.4.json")
    recipe = config["store"]
    tmp, memory = bench_run.make_scratch(config["placement"])  # as a run places its files
    try:
        dirs = argparse.Namespace(scratch=tmp, data=memory)
        store = sealed_template.build(recipe, dirs, seed, pool.map, workers)
        base = os.path.join(memory, "control")
        broken = rs_codec.Codec(10, 4).parity_matrix.copy()
        broken[3] = broken[2]
        write_shards(store, base, broken, pool.map, workers)
        got = shell_jobs.digests_of_files(base, store["dat_bytes"], 10, 4, pool.map, workers)
        want = shell_jobs.reference_digests(store, 10, 4, pool.map, workers)
        blocks = shell_jobs.digests_differing(got, want)
        # shard 12 and three of the twelve before it: recovery has to lean on shard 13
        lost = sorted(np.random.default_rng([seed, 0xEC]).choice(12, 3, replace=False).tolist()) + [12]
        differing, _compared, unrecovered = shell_jobs.compare_files(
            store, [base], 10, 4, seed, pool.map, workers, lost=lost)
        return {"control": "warm-rs10.4 codec with shard 13 a copy of shard 12", "seed": seed,
                "dat_bytes": store["dat_bytes"], "shard_blocks_differing": blocks,
                "shard_bytes_differing": differing,
                "recovered_bytes_differing": unrecovered, "limit": 0,
                "not_correct": blocks > 0 and differing > 0 and unrecovered > 0}
    finally:
        shutil.rmtree(memory, ignore_errors=True)
        shutil.rmtree(tmp, ignore_errors=True)


def fault_run(workload: str, fault: str, number: str, seed: int, seconds: float) -> dict:
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", "0", "--fault", fault],
        cwd=common.CHECKOUT, capture_output=True, text=True, timeout=1500,
    )
    lines = done.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        return {"fault": fault, "workload": workload, "seed": seed, "not_correct": False,
                "error": done.stderr[-1500:]}
    got = line.get("compared", {}).get(number, {})
    return {"fault": fault, "workload": workload, "seed": seed, "attempted": line.get("attempted"),
            number: got.get("value"), "limit": got.get("limit"), "correct": line.get("correct"),
            "not_correct": line.get("correct") is False and got.get("value", 0) > got.get("limit", 0)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    workers = bench_run.worker_count()
    readings = []
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        for j in range(args.seeds):
            t0 = time.perf_counter()
            readings.append(codec_control(args.first_seed + j, pool, workers))
            readings[-1]["seconds"] = round(time.perf_counter() - t0, 2)
            print(json.dumps(readings[-1]), flush=True)
    for workload, fault, number in FAULTS:
        for j in range(args.seeds):
            readings.append(fault_run(workload, fault, number, args.first_seed + 100 + j, args.seconds))
            print(json.dumps(readings[-1]), flush=True)
    ok = all(r["not_correct"] for r in readings)
    print(json.dumps({"every_control_and_fault_not_correct": ok, "readings": len(readings)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
