"""The benchmark's side of the one child: start it, ask it, stop it.

Trimmed from chip_smoke.py's `Server` and `Probe`, which ran on the chip
(PR 22). The benchmark's own process never imports JAX: what the device is, it
reads from the child's /status.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

from . import common

PORT_LO, PORT_HI, GRPC_OFFSET = 20000, 29000, 10000


def free_port_pair(taken: set) -> int:
    """A port p with p and p + 10000 (its gRPC twin) both free."""
    first = os.getpid() * 61 % (PORT_HI - PORT_LO)
    for i in range(PORT_HI - PORT_LO):
        p = PORT_LO + (first + i) % (PORT_HI - PORT_LO)
        if p in taken or p + GRPC_OFFSET in taken:
            continue
        try:
            with socket.socket() as a, socket.socket() as b:
                a.bind(("127.0.0.1", p))
                b.bind(("127.0.0.1", p + GRPC_OFFSET))
            return p
        except OSError:
            continue
    raise RuntimeError("no free port pair")


def parse_prom(text: str) -> dict:
    """Prometheus text -> {`name{labels}`: value}."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            try:
                out[key] = float(val)
            except ValueError:
                pass
    return out


def sum_metric(samples: dict, name: str, **labels) -> float:
    want = [f'{k}="{v}"' for k, v in labels.items()]
    return sum(
        v for key, v in samples.items()
        if (key == name or key.startswith(name + "{")) and all(w in key for w in want)
    )


class Server:
    def __init__(self, root: str, data_dir: str, flags: list, rehearse: bool,
                 fault: str | None = None):
        self.root = root
        self.data_dir = data_dir  # the server's own -dir
        self.control_dir = os.path.join(root, "control")
        self.log_path = os.path.join(root, "server.log")
        for d in (self.data_dir, self.control_dir):
            os.makedirs(d, exist_ok=True)
        master_port = free_port_pair(set())
        volume_port = free_port_pair({master_port, master_port + GRPC_OFFSET})
        self.master = f"127.0.0.1:{master_port}"
        self.volume = f"127.0.0.1:{volume_port}"
        self.flags = flags
        self.rehearse = rehearse
        self.fault = fault
        self.proc = None
        self._log = None
        self._next_id = 0

    def start(self) -> None:
        env = common.child_env()
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"  # said outright, as the tests say it
        else:
            env.pop("JAX_PLATFORMS", None)
        master_port, volume_port = (hp.split(":")[1] for hp in (self.master, self.volume))
        cmd = [sys.executable, os.path.join(common.LIB, "server_child.py"),
               "--control-dir", self.control_dir]
        if self.fault:
            cmd += ["--fault", self.fault]
        cmd += ["--", "server", "-dir", self.data_dir, "-port", master_port,
                "-volumePort", volume_port, *self.flags]
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            cmd, cwd=common.CHECKOUT, env=env, stdin=subprocess.PIPE,
            stdout=self._log, stderr=subprocess.STDOUT, start_new_session=True,
        )

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise common.Failed(
                f"the server child exited with {self.proc.returncode}:\n" + self.log_tail()
            )

    def log_tail(self, n: int = 4000) -> str:
        if self._log:
            self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def get(self, target: str, hostport: str | None = None, timeout: float = 30) -> bytes:
        host, port = (hostport or self.volume).split(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
        try:
            conn.request("GET", target)
            r = conn.getresponse()
            body = r.read()
            if r.status != 200:
                raise common.Failed(f"GET {target}: {r.status} {body[:200]!r}")
            return body
        finally:
            conn.close()

    def wait_ready(self, limit_s: float = 240) -> dict:
        """The device as the child's /status says it."""
        t0 = time.perf_counter()
        while True:
            self.alive()
            try:
                status = json.loads(self.get("/status", timeout=5))
                break
            except (OSError, common.Failed, ValueError):
                if time.perf_counter() - t0 > limit_s:
                    raise common.Failed("the server is not ready:\n" + self.log_tail())
                time.sleep(0.1)
        dev = status.get("Device") or {}
        return {"platform": dev.get("platform"), "kind": dev.get("device_kind"),
                "count": dev.get("count")}

    def wait_volumes(self, want: int, limit_s: float = 60) -> None:
        """Until the master has heard of `want` volumes from the volume server."""
        t0 = time.perf_counter()
        while True:
            topo = json.loads(self.get("/dir/status", self.master))["Topology"]
            if topo.get("volume_count", 0) >= want:
                return
            if time.perf_counter() - t0 > limit_s:
                raise common.Failed(f"the master knows {topo.get('volume_count')} volumes, want {want}")
            time.sleep(0.1)

    def metrics(self) -> dict:
        return parse_prom(self.get("/metrics").decode())

    def debug_json(self, page: str) -> dict:
        return json.loads(self.get(page))

    def cpu_seconds(self) -> float:
        """User + system CPU seconds of the child, all its threads."""
        with open(f"/proc/{self.proc.pid}/stat") as f:
            fields = f.read().rpartition(")")[2].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")

    def ask(self, op: str, limit_s: float = 120, **kw) -> dict:
        """One command to the launcher's control thread, and its answer."""
        self._next_id += 1
        cmd = {"id": self._next_id, "op": op, **kw}
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        self.proc.stdin.flush()
        path = os.path.join(self.control_dir, f"{cmd['id']}.json")
        t0 = time.perf_counter()
        while not os.path.exists(path):
            self.alive()
            if time.perf_counter() - t0 > limit_s:
                raise common.Failed(f"the launcher did not answer {op} in {limit_s} s")
            time.sleep(0.02)
        with open(path) as f:
            reply = json.load(f)
        if reply.get("error"):
            raise common.Failed(f"launcher {op}: {reply['error']}")
        return reply

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGTERM)
            try:
                self.proc.wait(15)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait()
        if self.proc is not None and self.proc.stdin:
            self.proc.stdin.close()
        if self._log:
            self._log.close()
