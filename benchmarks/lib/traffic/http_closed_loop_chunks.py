"""Traffic kind `http_closed_loop_chunks`: `http_closed_loop`'s steps (stage,
prepare, the window, its percentiles) over the chunk needles of a
`sealed_chunks` store, with a load client of its own
(benchmarks/lib/http_chunk_client_proc.py: a 4 MiB body costs
`http_client_proc.py` 18 ms of CPU to receive, which would make the generator
the thing measured).

What is otherwise:

- the next needle is drawn uniformly among ALL chunk needles, none picked by
  shard: the share of GETs that meets a lost shard is the layout's own;
- before a connection's drawn warm GETs, the connections ask between them,
  once each, for every needle the plain reference puts on a lost shard: a
  window's GET can rebuild no span that the warm-up has not, so whatever
  shapes the program decodes them in have been compiled before it opens,
  and the benchmark knows nothing of the program's span or the kernel's
  granule;
- the window also gives `bytes_good` and what the plain reference says the
  GETs it sent are made of (`ref_intervals`, `ref_lost_intervals`,
  `ref_lost_needles`), from the clients' extras files;
- `check` holds the program's own counts of the window to those, all exact:
  `intervals_miscounted`, `degraded_intervals_miscounted` (reconstructed +
  cache), `degraded_needles_miscounted`; and `compiles_inside_window`,
  `device_decode_share_short` (decode dispatches under another backend than
  the device's; not in a rehearsal, where the CPU stands in). A comparison
  whose counter family the program does not have is left out, so a tree from
  before the counters comes out correct on what it can show.

`server_fault` (never in a cell's file: `benchmarks/controls_chunks.py` sets
it) starts the server child through `benchmarks/lib/chunk_fault_child.py`,
which knows one fault more than `server_child.py`.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys

from .. import common
from ..stores import sealed_chunks
from . import http_closed_loop

INTERVALS = "seaweedfs_tpu_ec_read_intervals_total"
NEEDLES = "seaweedfs_tpu_ec_needle_reads_total"
DISPATCHES = "seaweedfs_tpu_rs_dispatches_total"
COMPILES = "seaweedfs_tpu_jax_compiles_total"
EXTRAS = ("bytes_good", "intervals", "lost_intervals", "lost_needles")


def start_with_faults(server) -> None:
    """`Server.start`, through the launcher that knows `server.fault`."""
    env = common.child_env()
    if server.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    else:
        env.pop("JAX_PLATFORMS", None)
    master_port, volume_port = (hp.split(":")[1] for hp in (server.master, server.volume))
    cmd = [sys.executable, os.path.join(common.LIB, "chunk_fault_child.py"),
           "--control-dir", server.control_dir, "--fault", server.fault,
           "--", "server", "-dir", server.data_dir, "-port", master_port,
           "-volumePort", volume_port, *server.flags]
    server._log = open(server.log_path, "wb")
    server.proc = subprocess.Popen(
        cmd, cwd=common.CHECKOUT, env=env, stdin=subprocess.PIPE,
        stdout=server._log, stderr=subprocess.STDOUT, start_new_session=True,
    )


class Traffic(http_closed_loop.Traffic):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.extras: list = []

    def stage(self, server) -> None:
        super().stage(server)
        fault = self.ctx.params.get("server_fault")
        if fault:
            server.fault = fault
            server.start = lambda: start_with_faults(server)

    def first_needles(self, connections: int) -> list:
        """For each connection, its share of the needles that lie on a lost
        shard: between them the connections ask for each once."""
        reader = sealed_chunks.Reader(self.ctx.store, self.ctx.seed, self.ctx.params["pick"])
        met = [i for i, (_n, _on_lost, any_lost) in enumerate(reader.tallies()) if any_lost]
        random.Random(self.ctx.seed * 4096 + 4093).shuffle(met)
        common.say("warm_lost_needles", needles=len(met), of=reader.count)
        return [met[j::connections] for j in range(connections)]

    def warm(self, server) -> None:
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
            job = {
                "hostport": server.volume, "connections": per, "first_index": j * per,
                "seed": self.ctx.seed, "pick": p["pick"],
                "store": {k: v for k, v in store.items() if isinstance(v, (int, str, dict))},
                "warm_gets": int(p["warm_gets_per_connection"]), "latency_file": path,
                "first": first[j * per : (j + 1) * per], "extras_file": extras,
            }
            proc = subprocess.Popen(
                [sys.executable, os.path.join(common.LIB, "http_chunk_client_proc.py")],
                cwd=common.CHECKOUT, env=common.child_env(), stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True,
            )
            proc.stdin.write(json.dumps(job) + "\n")
            proc.stdin.flush()
            self.procs.append(proc)
            self.files.append(path)
            self.extras.append(extras)
        for proc in self.procs:
            ready = json.loads(proc.stdout.readline() or "{}")
            if not ready.get("ready"):
                raise common.Failed(f"a load client failed its warm-up: {ready}")
            if ready.get("warm_bad"):  # the window will meet them too, and count them
                common.say("warm_up_bad_answers", **ready)

    def run(self, server, seconds: float, tracer) -> dict:
        result = super().run(server, seconds, tracer)
        total = dict.fromkeys(EXTRAS, 0)
        for path in self.extras:
            with open(path) as f:
                for key, value in json.load(f).items():
                    total[key] += value
        result["bytes_good"] = total["bytes_good"]
        result.update({f"ref_{key}": total[key] for key in EXTRAS[1:]})
        return result

    def check(self, server, result: dict, observed) -> list:
        compared = super().check(server, result, observed)
        got = observed.prom_delta
        # a tree without a counter family shows nothing there
        if got(INTERVALS) is not None:
            rebuilt = got(INTERVALS, source="reconstructed") + got(INTERVALS, source="cache")
            compared += [
                ("intervals_miscounted", abs(got(INTERVALS) - result["ref_intervals"]), 0),
                ("degraded_intervals_miscounted", abs(rebuilt - result["ref_lost_intervals"]), 0),
            ]
        if got(NEEDLES) is not None:
            compared.append(("degraded_needles_miscounted",
                             abs(got(NEEDLES, kind="degraded") - result["ref_lost_needles"]), 0))
        compared.append(("compiles_inside_window", got(COMPILES) or 0, 0))
        if not self.ctx.rehearse:
            elsewhere = (got(DISPATCHES, op="decode") or 0) - (got(DISPATCHES, op="decode", backend="device") or 0)
            compared.append(("device_decode_share_short", elsewhere, 0))
        return compared
