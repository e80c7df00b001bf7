"""One load-generating process of `http_closed_loop_chunks_spread`: the loop of
`http_chunk_client_proc.py` (receive into one buffer, compare in place, the
needles on a lost shard asked first), which counts beside each GET answered
with the right body where the plain rule puts its intervals
(benchmarks/reference/ec_sources.py): on the server asked, on a live peer,
rebuilt.

The job is `http_chunk_client_proc.py`'s; its `pick` also names the shards
of the server asked (`held_shards`), and `sources_file` is where the counts of
`local` and `served` intervals go. `extras_file` gets that client's four keys
(`bytes_good`, `intervals`, `lost_intervals` = rebuilt, `lost_needles`), so the
traffic reads it as `http_closed_loop_chunks` reads it.
"""

from __future__ import annotations

import array
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib.http_chunk_client_proc import ChunkWorker  # noqa: E402
from benchmarks.lib.stores import sealed_chunks  # noqa: E402
from benchmarks.reference import ec_sources  # noqa: E402

# the columns of a needle's tally, as ec_sources.tally gives them
COLUMNS = ("intervals", "local", "served", "lost_intervals", "lost_needles")


class SourcesWorker(ChunkWorker):
    def start_counts(self) -> None:
        self.bytes_good = 0
        self.made_of = [0] * len(COLUMNS)


def tallies(reader, pick: dict) -> np.ndarray:
    """int[needles, 5]: a needle's intervals by ec_sources.tally."""
    held, lost = set(pick["held_shards"]), set(pick["lost_shards"])
    return np.array([ec_sources.tally(reader.intervals(i), held, lost)
                     for i in range(reader.count)], dtype=np.int64)


def main() -> None:
    job = json.loads(sys.stdin.readline())
    reader = sealed_chunks.Reader(job["store"], job["seed"], job["pick"])
    table = tallies(reader, job["pick"])
    workers = [SourcesWorker(job, job["first_index"] + j, reader, job["first"][j], table)
               for j in range(job["connections"])]
    for w in workers:
        w.start()
    for w in workers:
        w.ready.wait()
    for w in workers:  # each waits for `go`: the warm-up's counts end here
        w.start_counts()
    print(json.dumps({"ready": True, "warm_bad": sum(w.warm[1] + w.warm[2] for w in workers),
                      "warm_good": sum(w.warm[0] for w in workers),
                      "error": next((w.error for w in workers if w.error), None)}), flush=True)
    line = sys.stdin.readline().split()
    if not line or line[0] != "go":
        return
    cpu0 = time.process_time()
    deadline = float(line[1])  # on time.perf_counter()'s clock, which processes share
    for w in workers:
        w.deadline = deadline
        w.go.set()
    for w in workers:
        w.join()
    cpu1 = time.process_time()
    all_latency = array.array("d")
    for w in workers:
        all_latency.extend(w.latency)
    with open(job["latency_file"], "wb") as f:
        all_latency.tofile(f)
    made_of = {key: sum(w.made_of[j] for w in workers) for j, key in enumerate(COLUMNS)}
    extras = {"bytes_good": sum(w.bytes_good for w in workers),
              **{key: made_of[key] for key in ("intervals", "lost_intervals", "lost_needles")}}
    with open(job["extras_file"], "w") as f:
        json.dump(extras, f)
    with open(job["sources_file"], "w") as f:
        json.dump({key: made_of[key] for key in ("local", "served")}, f)
    print(json.dumps({
        **extras,
        "good": sum(w.good for w in workers),
        "good_by_deadline": sum(w.good_by_deadline for w in workers), "wrong": sum(w.wrong for w in workers),
        "unanswered": sum(w.unanswered for w in workers),
        "shed": sum(w.shed for w in workers), "sent": sum(w.sent for w in workers),
        "start_at": min(w.start_at for w in workers), "end_at": max(w.end_at for w in workers),
        "cpu_s": cpu1 - cpu0,
        "error": next((w.error for w in workers if w.error), None),
    }), flush=True)


if __name__ == "__main__":
    main()
