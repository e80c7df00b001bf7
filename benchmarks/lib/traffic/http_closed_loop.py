"""Traffic kind `http_closed_loop`: `connections` keep-alive connections, each
sending its next GET when the reply is in, split over `client_processes`
processes (benchmarks/lib/http_client_proc.py) so that the generator is never
the one full core. The files come from the configuration's store; which one a
connection asks for next is drawn uniformly from the seed.

A traced run records the seconds of the window that the cell's file names
under `trace` (`start_s`, `seconds`).

The window runs from the moment the processes are told to go for --seconds:
`get_rate` is the right bodies that were in by then, over --seconds. A request in
flight at that moment is finished, compared and its latency kept: `get_p95_ms` is
over every request the window started (one asked again after a 503 waits out its
Retry-After, a second by default; counting its wait into the rate's time would
let a single request move the rate by a tenth). Every body is compared as it
arrives; a GET that never got the right body counts as slower than any limit.
"""

from __future__ import annotations

import array
import json
import os
import subprocess
import sys
import time

from .. import common
from ..http_client_proc import FAILED_S
from ..rpc import Rpc
from ..shell import Repl
from ..stores import sealed_template

FAILED_MS = 1e9  # what http_client_proc.py's FAILED_S becomes: slower than any limit


class Traffic:
    def __init__(self, ctx):
        self.ctx = ctx
        self.procs: list = []
        self.files: list = []

    def stage(self, server) -> None:
        """A store built straight into the server's directory needs nothing; a
        sealed template is laid out as the volumes the cell's file asks for."""
        for vid in self.ctx.params.get("link_volumes", []):
            sealed_template.link_volume(self.ctx.store, server.data_dir, vid)

    def prepare(self, server) -> None:
        """The steps the cell's file lists under `prepare`, once the server is
        up: lines for a one-shot shell, calls of the volume server's gRPC."""
        steps = self.ctx.params.get("prepare", [])
        if not steps:
            return
        rpc = Rpc()
        try:
            for step in steps:
                if "shell" in step:
                    repl = Repl(server.master, os.path.join(self.ctx.scratch, "shell.log"))
                    try:
                        for line in step["shell"]:
                            got = repl.ask(line["say"], line["expect"], 900)
                            if line["expect"] not in got:
                                raise common.Failed(f"shell {line['say']!r}: {got!r}")
                    finally:
                        repl.close()
                else:
                    rpc.call(server.volume, "volume", step["rpc"], step["request"], timeout=300)
        finally:
            rpc.close()

    def warm(self, server) -> None:
        p, store = self.ctx.params, self.ctx.store
        server.wait_volumes(store["volumes"])
        self.prepare(server)
        n_proc = int(p["client_processes"])
        per = int(p["connections"]) // n_proc
        if per * n_proc != int(p["connections"]):
            raise common.Failed("connections must divide evenly over client_processes")
        for j in range(n_proc):
            path = os.path.join(self.ctx.scratch, f"latency{j}.f64")
            job = {
                "hostport": server.volume, "connections": per, "first_index": j * per,
                "seed": self.ctx.seed, "pick": p.get("pick", {}),
                "store": {k: v for k, v in store.items() if isinstance(v, (int, str, dict))},
                "warm_gets": int(p["warm_gets_per_connection"]), "latency_file": path,
            }
            proc = subprocess.Popen(
                [sys.executable, os.path.join(common.LIB, "http_client_proc.py")],
                cwd=common.CHECKOUT, env=common.child_env(), stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True,
            )
            proc.stdin.write(json.dumps(job) + "\n")
            proc.stdin.flush()
            self.procs.append(proc)
            self.files.append(path)
        for proc in self.procs:
            ready = json.loads(proc.stdout.readline() or "{}")
            if not ready.get("ready"):
                raise common.Failed(f"a load client failed its warm-up: {ready}")
            if ready.get("warm_bad"):  # the window will meet them too, and count them
                common.say("warm_up_bad_answers", **ready)

    def run(self, server, seconds: float, tracer) -> dict:
        if tracer is not None:
            want = self.ctx.params.get("trace", {"start_s": 2.0, "seconds": 3.0})
            tracer.during(min(want["start_s"], seconds * 0.2), min(want["seconds"], seconds * 0.6))
        cpu0 = server.cpu_seconds()
        t0 = time.perf_counter()
        for proc in self.procs:
            proc.stdin.write(f"go {t0 + seconds!r}\n")
            proc.stdin.flush()
        parts = []
        for proc in self.procs:
            line = proc.stdout.readline()
            if not line:
                raise common.Failed(f"a load client died (exit {proc.wait()})")
            parts.append(json.loads(line))
        cpu1 = server.cpu_seconds()
        t1 = max(part["end_at"] for part in parts)
        latency = array.array("d")
        for path in self.files:
            with open(path, "rb") as f:
                latency.frombytes(f.read())
        total = {k: sum(part[k] for part in parts)
                 for k in ("good", "good_by_deadline", "wrong", "unanswered", "shed", "sent", "cpu_s")}
        for part in parts:
            if part.get("error"):
                common.say("client_error", error=part["error"])
        ms = sorted(FAILED_MS if s >= FAILED_S else s * 1000.0 for s in latency)
        window = t1 - t0
        return {
            "window_s": window, "attempted": len(ms),
            "failed": total["wrong"] + total["unanswered"],
            "gets_good": total["good"], "gets_good_by_deadline": total["good_by_deadline"],
            "gets_wrong": total["wrong"],
            "gets_unanswered": total["unanswered"], "gets_sent": total["sent"],
            "sheds": total["shed"], "client_cpu_s": total["cpu_s"],
            "server_cpu_s": cpu1 - cpu0,
            "get_p50_ms": percentile(ms, 50), "get_p99_ms": percentile(ms, 99),
            "end_to_end": {
                "get_rate": total["good_by_deadline"] / seconds,
                "get_p95_ms": percentile(ms, 95),
            },
        }

    def after_window(self, server) -> None:
        for proc in self.procs:
            proc.stdin.close()
            proc.wait(30)
        self.procs = []

    def check(self, server, result: dict, observed) -> list:
        return [
            ("bodies_wrong", result["gets_wrong"], 0),
            ("gets_unanswered", result["gets_unanswered"], 0),
            # a counter the cell's file says has to move, else the path it is about never ran
            *[(f"{name}_never_moved", int(not observed.prom_delta(family)), 0)
              for name, family in self.ctx.params.get("must_move", {}).items()],
        ]


def percentile(ordered: list, p: float):
    """Nearest rank over every request of the window."""
    if not ordered:
        return None
    rank = max(1, -(-len(ordered) * p // 100))
    return ordered[int(rank) - 1]
