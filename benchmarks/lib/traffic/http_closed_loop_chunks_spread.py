"""Traffic kind `http_closed_loop_chunks_spread`: the chunk needles of a
`sealed_chunks` store (`http_closed_loop_chunks`) on the spread cluster of
`http_closed_loop_spread`, with one server lost.

Set-up is `http_closed_loop_spread`'s, all of it (peers, `ec.encode`, the
spread held to the plain rule, healthy GETs through the chip's server, SIGKILL
of the peer that holds data shard `lose.holder_of_shard`, the wait for the
master and the chip server's table), with chunk needles where that kind has
small ones: the healthy GETs are `healthy_gets` whole chunks spread over the
data shards, each compared; a probe of the lost server is a chunk with an
interval on its lowest data shard. Then `http_closed_loop_chunks`'s warm-up
and window: every needle with an interval on a lost shard asked once between
the connections, `warm_gets_per_connection` drawn GETs each, and the window's
GETs drawn uniformly among all chunk needles, by a load client of its own
(benchmarks/lib/http_chunk_sources_client_proc.py).

What the window adds to both kinds' readings: the bytes the live peers served
(`ec_shard_read_served_bytes_total` on their own `/metrics`, scraped at the
window's edges, as their CPU is read from `/proc/<pid>/stat`:
`peer_served_bytes`, for `peers.cpu_ms_per_mb`), and for the GETs answered with
the right body the plain reference's count of intervals by source
(benchmarks/reference/ec_sources.py): `check` holds the program's
`ec_read_intervals_total{source="local"}` and `{source="remote"}` to it
(`local_intervals_miscounted`, `served_intervals_miscounted`, limit 0), beside
`http_closed_loop_chunks`'s counts of all intervals, rebuilt ones and degraded
needles, and `http_closed_loop_spread`'s set-up readings.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time

from .. import common
from ..http_client_proc import Connection
from ..server import parse_prom, sum_metric
from ..stores import sealed_chunks
from . import http_closed_loop_chunks, http_closed_loop_spread

INTERVALS = http_closed_loop_chunks.INTERVALS
ATTEMPTS = http_closed_loop_spread.ATTEMPTS
SERVED_BYTES = "seaweedfs_tpu_ec_shard_read_served_bytes_total"


class Traffic(http_closed_loop_spread.Traffic, http_closed_loop_chunks.Traffic):
    """`http_closed_loop_spread`'s stage, set-up, window readings and checks;
    `http_closed_loop_chunks`'s server fault, first needles and checks."""

    def __init__(self, ctx):
        super().__init__(ctx)
        self.sources: list = []

    # ------------------------------------------------------------- set-up
    def chunk_reader(self) -> sealed_chunks.Reader:
        return sealed_chunks.Reader(self.ctx.store, self.ctx.seed, {"volume": self.ctx.params["volume"]})

    def draw_on(self, shard: int, rng) -> tuple:
        """A chunk needle with an interval on `shard`, as the target of a GET
        and its body."""
        reader = self.reader
        on = [i for i in range(reader.count) if any(s == shard for s, _o, _n in reader.intervals(i))]
        i = on[rng.randrange(len(on))]
        return reader.target(i), reader.body(i)

    def healthy_gets(self, server, k: int) -> None:
        """Whole chunks through the chip's server while every server is up,
        as many on each data shard; peers serve the intervals they hold."""
        p = self.ctx.params
        t0 = time.perf_counter()
        self.reader = self.chunk_reader()
        rng = random.Random(self.ctx.seed * 4096 + 4095)
        served0 = sum_metric(server.metrics(), ATTEMPTS, outcome="served")
        conn, wrong, sent = Connection(server.volume), 0, 0
        try:
            for shard in range(k):
                for _ in range(max(1, int(p["healthy_gets"]) // k)):
                    wrong += int(not self.get_one(conn, *self.draw_on(shard, rng)))
                    sent += 1
        finally:
            conn.close()
        served = sum_metric(server.metrics(), ATTEMPTS, outcome="served") - served0
        self.readings += [("healthy_bodies_wrong", wrong, 0),
                          ("healthy_remote_reads_never_moved", int(not served), 0)]
        common.say("healthy_gets", seconds=round(time.perf_counter() - t0, 3), sent=sent,
                   wrong=wrong, remote_served=served)

    def prepare(self, server) -> None:
        super().prepare(server)
        p, k = self.ctx.params, self.ctx.config["geometry"]["data_shards"]
        # what the chunk reader and the load clients need of the loss
        p["pick"] = {**p["pick"], "lost_shards": sorted(self.lost_shards),
                     "held_shards": sorted(self.held), "data_shards": k}

    def warm(self, server) -> None:
        """`http_closed_loop_chunks`'s warm-up, with this kind's load client."""
        p, store = self.ctx.params, self.ctx.store
        server.wait_volumes(store["volumes"])
        self.prepare(server)
        n_proc = int(p["client_processes"])
        per = int(p["connections"]) // n_proc
        if per * n_proc != int(p["connections"]):
            raise common.Failed("connections must divide evenly over client_processes")
        first = self.first_needles(per * n_proc)
        for j in range(n_proc):
            path = os.path.join(self.ctx.scratch, f"latency{j}.f64")
            extras = os.path.join(self.ctx.scratch, f"extras{j}.json")
            sources = os.path.join(self.ctx.scratch, f"sources{j}.json")
            job = {
                "hostport": server.volume, "connections": per, "first_index": j * per,
                "seed": self.ctx.seed, "pick": p["pick"],
                "store": {k: v for k, v in store.items() if isinstance(v, (int, str, dict))},
                "warm_gets": int(p["warm_gets_per_connection"]), "latency_file": path,
                "first": first[j * per : (j + 1) * per], "extras_file": extras,
                "sources_file": sources,
            }
            proc = subprocess.Popen(
                [sys.executable, os.path.join(common.LIB, "http_chunk_sources_client_proc.py")],
                cwd=common.CHECKOUT, env=common.child_env(), stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True,
            )
            proc.stdin.write(json.dumps(job) + "\n")
            proc.stdin.flush()
            self.procs.append(proc)
            self.files.append(path)
            self.extras.append(extras)
            self.sources.append(sources)
        for proc in self.procs:
            ready = json.loads(proc.stdout.readline() or "{}")
            if not ready.get("ready"):
                raise common.Failed(f"a load client failed its warm-up: {ready}")
            if ready.get("warm_bad"):  # the window will meet them too, and count them
                common.say("warm_up_bad_answers", **ready)

    # ------------------------------------------------------------ the window
    def peer_served_bytes(self, server) -> float:
        """Shard bytes the live peers have streamed to others, by their own count."""
        return sum(sum_metric(parse_prom(server.get("/metrics", peer.url).decode()), SERVED_BYTES)
                   for peer in self.live())

    def run(self, server, seconds: float, tracer) -> dict:
        served0 = self.peer_served_bytes(server)
        result = super().run(server, seconds, tracer)
        result["peer_served_bytes"] = self.peer_served_bytes(server) - served0
        for key in ("local", "served"):
            result[f"ref_{key}_intervals"] = 0
        for path in self.sources:
            with open(path) as f:
                for key, value in json.load(f).items():
                    result[f"ref_{key}_intervals"] += value
        return result

    def check(self, server, result: dict, observed) -> list:
        compared = super().check(server, result, observed)
        got = observed.prom_delta
        if got(INTERVALS) is not None:
            compared += [
                ("local_intervals_miscounted",
                 abs(got(INTERVALS, source="local") - result["ref_local_intervals"]), 0),
                ("served_intervals_miscounted",
                 abs(got(INTERVALS, source="remote") - result["ref_served_intervals"]), 0),
            ]
        return compared
