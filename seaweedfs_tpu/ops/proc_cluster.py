"""Subprocess cluster fixture: every role a real OS process.

The bench legs' `_start_cluster_thread` scaffolding proves the serving
planes inside ONE process (dedicated thread + event loop). That shape
cannot host process-level chaos — SIGKILL has no per-thread aim — so this
module promotes it to real processes: master + N volume servers + a filer
fleet + S3 gateway + blob backend, each spawned through the `weed-tpu`
CLI entry points (`python -m seaweedfs_tpu <role> ...`), with

- readiness probes (`/metrics` answering 200 before a child counts as
  up, with the child's log tail in the error when it does not);
- env-var plumbing for fault plans: `SEAWEEDFS_TPU_FAULTS` carries an
  inline-JSON `FaultPlan` per child (util/faults loads it at import), so
  seeded in-process faults fire inside real subprocesses;
- per-process log capture (`<root>/logs/<name>.log`) and /metrics
  scraping helpers, because a subprocess's counters are only reachable
  over HTTP;
- guaranteed teardown: children run in their own sessions (process
  groups), `stop()` is idempotent (SIGCONT + SIGTERM, then SIGKILL), a
  module atexit sweep reaps anything a crashed test left behind — no
  orphaned children on failure;
- process-level fault delivery for `util/faults.ProcessFault` schedules:
  hard kill (SIGKILL), pause/resume brownout (SIGSTOP/SIGCONT), and
  restart-with-recovery (SIGKILL + respawn on the same port/dirs + wait
  ready). `run_fault_schedule` drives a seeded schedule on a thread and
  records every delivery, so a soak run's process chaos is reproducible
  from its seed and auditable after the fact.

The blob backend is spawned as the cold tier: the master gets a
`-tierConfig` naming the blob process's S3-shaped endpoint and pushes the
backend to volume servers via heartbeats, so cold-tier offload/recall
crosses a REAL process boundary.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from typing import Optional

from ..util.faults import FaultPlan, ProcessFault, partition

# distinct band from bench.py's _free_port_pair (18200-19200): a soak
# leg running inside the bench process must not race its threaded legs
# for ports
_PORT_LO, _PORT_HI = 19300, 20800
_GRPC_OFFSET = 10000


class StartupError(RuntimeError):
    """A child failed to come up (probe timeout or early exit)."""


def wan_partition_plan(
    peer_addrs: list,
    start: float = 0.0,
    duration: Optional[float] = None,
    seed: int = 0,
) -> FaultPlan:
    """A per-child fault plan cutting the WAN toward `peer_addrs` (the
    OTHER cluster's listen addresses, host:port): every RPC/HTTP call
    from the child toward any of those addresses raises ConnectionError
    for `duration` seconds starting `start` seconds after the child
    imports (ISSUE 19 cross-cluster partition seam).

    Install it on BOTH clusters' children (each side gets a plan naming
    the OTHER side's addresses) via ``fault_plans={"*": plan}`` — the cut
    is then bidirectional at every process boundary, exactly like a
    firewalled inter-DC link. Windows are measured per-child from import,
    so sides that spawned seconds apart cut within that skew of each
    other; bound assertions accordingly."""
    plan = FaultPlan(seed=seed)
    for addr in peer_addrs:
        plan.add(partition(a=addr, start=start, duration=duration))
        # gRPC twins live at port+offset: cut them with the same window,
        # or metadata streams survive while chunk HTTP dies
        host, _, port = str(addr).rpartition(":")
        try:
            g = int(port) + _GRPC_OFFSET
        except ValueError:
            continue
        plan.add(
            partition(a=f"{host}:{g}", start=start, duration=duration)
        )
    return plan


def free_port_pair(taken: Optional[set] = None) -> int:
    """A port p with p and p+10000 both bindable (HTTP + gRPC pair),
    outside `taken`. Scanned, not bound-and-released-at-0: the gRPC twin
    must be free too, and the kernel cannot promise a pair."""
    taken = taken or set()
    # each process scans from its own place in the band: concurrent
    # clusters (xdist workers, a smoke beside a soak) that all started at
    # _PORT_LO picked the same "free" pair between scan and bind
    span = _PORT_HI - _PORT_LO
    first = os.getpid() * 61 % span
    for i in range(span):
        p = _PORT_LO + (first + i) % span
        if p in taken or (p + _GRPC_OFFSET) in taken:
            continue
        try:
            with socket.socket() as s1, socket.socket() as s2:
                s1.bind(("127.0.0.1", p))
                s2.bind(("127.0.0.1", p + _GRPC_OFFSET))
            return p
        except OSError:
            continue
    raise RuntimeError("no free port pair in band")


def parse_prom(text: str) -> dict:
    """Prometheus exposition text -> {sample_key: value}. The key is the
    raw `name{labels}` prefix — `sum_metric` does label matching."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


def sum_metric(samples: dict, name: str, **labels) -> float:
    """Sum every sample of `name` whose label set includes all given
    label pairs (substring match on the rendered `k="v"` form)."""
    total = 0.0
    want = [f'{k}="{v}"' for k, v in labels.items()]
    for key, val in samples.items():
        if key != name and not key.startswith(name + "{"):
            continue
        if all(w in key for w in want):
            total += val
    return total


@dataclass
class ProcSpec:
    """Everything needed to (re)spawn one child identically."""

    name: str
    role: str  # master|volume|filer|s3|blob
    port: int
    argv: list = field(default_factory=list)
    env: dict = field(default_factory=dict)
    log_path: str = ""


class Child:
    def __init__(self, spec: ProcSpec):
        self.spec = spec
        self.proc: Optional[subprocess.Popen] = None
        self._log = None

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def pid(self) -> Optional[int]:
        return self.proc.pid if self.proc is not None else None

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def spawn(self) -> None:
        self._log = open(self.spec.log_path, "ab")
        self._log.write(
            f"--- spawn {self.name}: {' '.join(self.spec.argv)}\n".encode()
        )
        self._log.flush()
        # own session => own process group: teardown signals the GROUP,
        # so helpers a child forks die with it
        self.proc = subprocess.Popen(
            self.spec.argv,
            stdout=self._log,
            stderr=subprocess.STDOUT,
            env=self.spec.env,
            start_new_session=True,
            cwd=os.path.dirname(self.spec.log_path) or None,
        )

    def log_tail(self, lines: int = 30) -> str:
        try:
            with open(self.spec.log_path, "rb") as f:
                data = f.read()[-8192:]
            return "\n".join(
                data.decode("utf-8", "replace").splitlines()[-lines:]
            )
        except OSError:
            return "<no log>"

    def close_log(self) -> None:
        if self._log is not None:
            try:
                self._log.close()
            except OSError:
                pass
            self._log = None


# crash-safety net: clusters register here and an atexit sweep reaps
# whatever a failing test's teardown never reached
_LIVE: set = set()
_LIVE_LOCK = threading.Lock()


def _atexit_sweep() -> None:
    with _LIVE_LOCK:
        clusters = list(_LIVE)
    for c in clusters:
        try:
            c.stop()
        except Exception:
            pass


atexit.register(_atexit_sweep)


def _signal_group(pid: int, sig: int) -> None:
    try:
        os.killpg(pid, sig)
    except (ProcessLookupError, PermissionError):
        pass


class ProcCluster:
    """Master + `volumes` volume servers (+ filers + S3 + blob), each a
    subprocess. Use as a context manager, or call start()/stop().

    fault_plans: {child-name | role | "*": FaultPlan-or-dict} — each
    child whose name or role matches gets the plan serialized into its
    `SEAWEEDFS_TPU_FAULTS`, so seeded in-process faults fire inside that
    subprocess from import time.

    chip_child: a chip belongs to one process at a time, so exactly one
    named child inherits this process's JAX environment (and with it the
    chip, where the host has one); every other child gets
    `JAX_PLATFORMS=cpu` set outright — its device planes then run on the
    host on purpose, and its `/status` says so.
    """

    def __init__(
        self,
        root: str,
        volumes: int = 2,
        filers: int = 0,
        with_s3: bool = False,
        with_blob: bool = False,
        iam_cfg: Optional[dict] = None,
        fault_plans: Optional[dict] = None,
        env: Optional[dict] = None,
        pulse_seconds: float = 0.25,
        ready_timeout: float = 30.0,
        needle_map: str = "memory",
        batch_lookup: str = "off",
        max_volumes: int = 50,
        data_center: str = "",
        racks: Optional[list] = None,
        geo_source: str = "",
        durable_filers: bool = False,
        fleet: bool = False,
        fleet_bounds: Optional[list] = None,
        followers: int = 0,
        chip_child: str = "volume-0",
    ):
        self.root = os.path.abspath(root)
        self.chip_child = chip_child
        self.n_volumes = volumes
        self.n_filers = filers
        self.with_s3 = with_s3
        self.with_blob = with_blob
        self.iam_cfg = iam_cfg
        self.fault_plans = fault_plans or {}
        self.extra_env = dict(env or {})
        self.pulse_seconds = pulse_seconds
        self.ready_timeout = ready_timeout
        self.needle_map = needle_map
        self.batch_lookup = batch_lookup
        self.max_volumes = max_volumes
        # geo plane (ISSUE 19): DC label flows to every volume server
        # (-dataCenter) and filer; racks (cycled per volume index) spread
        # the cluster across failure domains; geo_source makes every
        # filer a second-site replica tailing that PRIMARY filer; durable
        # filers get sqlite stores + segmented meta logs + geo cursor
        # files under root, so kill/restart resumes instead of wiping
        self.data_center = data_center
        self.racks = list(racks or [])
        self.geo_source = geo_source
        self.durable_filers = durable_filers
        # metadata fleet (ISSUE 20): fleet=True pre-writes a FLEETMAP
        # under root assigning each filer a directory-prefix range and
        # spawns every filer as a range-owning member; followers spawns
        # N read-only replicas tailing filer-0's meta log
        self.fleet = fleet
        self.fleet_bounds = fleet_bounds
        self.n_followers = followers
        self.fleet_map_path = ""
        self.children: dict[str, Child] = {}
        self.fault_events: list[dict] = []
        self._ports: set = set()
        self._stop_evt = threading.Event()
        self._timers: list[threading.Timer] = []
        self._driver: Optional[threading.Thread] = None
        self._started = False
        self.master_port: Optional[int] = None
        self.s3_port: Optional[int] = None
        self.blob_port: Optional[int] = None

    # ---------------- spawning ----------------
    def _port(self) -> int:
        p = free_port_pair(self._ports)
        self._ports.add(p)
        self._ports.add(p + _GRPC_OFFSET)
        return p

    def _child_env(self, name: str, role: str) -> dict:
        env = dict(os.environ)
        env.update(self.extra_env)
        env["SEAWEEDFS_TPU_PULSE_SECONDS"] = str(self.pulse_seconds)
        env["PYTHONUNBUFFERED"] = "1"
        if name != self.chip_child:
            env["JAX_PLATFORMS"] = "cpu"
        # children run with their log dir as cwd: the package must be
        # importable by path, not by the parent's cwd
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        prev = env.get("PYTHONPATH", "")
        if pkg_root not in prev.split(os.pathsep):
            env["PYTHONPATH"] = (
                pkg_root + (os.pathsep + prev if prev else "")
            )
        plan = (
            self.fault_plans.get(name)
            or self.fault_plans.get(role)
            or self.fault_plans.get("*")
        )
        if plan is not None:
            pd = plan if isinstance(plan, dict) else plan.to_dict()
            env["SEAWEEDFS_TPU_FAULTS"] = json.dumps(pd)
        else:
            # never inherit a plan meant for the PARENT process
            env.pop("SEAWEEDFS_TPU_FAULTS", None)
        return env

    def _add(self, name: str, role: str, port: int, args: list) -> Child:
        spec = ProcSpec(
            name=name,
            role=role,
            port=port,
            argv=[sys.executable, "-m", "seaweedfs_tpu", role, *args],
            env=self._child_env(name, role),
            log_path=os.path.join(self.root, "logs", f"{name}.log"),
        )
        child = Child(spec)
        self.children[name] = child
        child.spawn()
        return child

    def start(self) -> "ProcCluster":
        os.makedirs(os.path.join(self.root, "logs"), exist_ok=True)
        with _LIVE_LOCK:
            _LIVE.add(self)
        try:
            self._start_inner()
        except BaseException:
            self.stop()
            raise
        self._started = True
        return self

    def _start_inner(self) -> None:
        tier_cfg_path = ""
        if self.with_blob:
            self.blob_port = self._port()
            blob_dir = os.path.join(self.root, "blob")
            self._add(
                "blob", "blob", self.blob_port,
                ["-port", str(self.blob_port), "-dir", blob_dir],
            )
            tier_cfg = {
                "s3": {
                    "default": {
                        "enabled": True,
                        "endpoint": f"http://127.0.0.1:{self.blob_port}",
                        "bucket": "cold",
                    }
                }
            }
            tier_cfg_path = os.path.join(self.root, "tier.json")
            with open(tier_cfg_path, "w") as f:
                json.dump(tier_cfg, f)

        self.master_port = self._port()
        margs = ["-port", str(self.master_port)]
        if tier_cfg_path:
            margs += ["-tierConfig", tier_cfg_path]
        self._add("master", "master", self.master_port, margs)
        maddr = f"127.0.0.1:{self.master_port}"
        # the master must be fully up (HTTP AND gRPC) before any
        # dependent spawns: a child whose first master RPC lands on a
        # not-yet-bound gRPC port pushes its cached channel into
        # reconnect backoff and keeps failing after the master is up
        self._wait_ready(
            self.children["master"],
            time.monotonic() + self.ready_timeout,
        )

        for i in range(self.n_volumes):
            vp = self._port()
            vdir = os.path.join(self.root, f"vol{i}")
            os.makedirs(vdir, exist_ok=True)
            vargs = [
                "-port", str(vp), "-dir", vdir,
                "-max", str(self.max_volumes),
                "-mserver", maddr,
                "-index", self.needle_map,
                "-batchLookup", self.batch_lookup,
            ]
            if self.data_center:
                vargs += ["-dataCenter", self.data_center]
            if self.racks:
                vargs += ["-rack", self.racks[i % len(self.racks)]]
            self._add(f"volume-{i}", "volume", vp, vargs)

        filer_ports = [self._port() for _ in range(self.n_filers)]
        if self.fleet and self.n_filers > 0:
            # the map MUST exist before any member spawns: a member's
            # first ownership check reads it during startup
            from ..filer.fleet import FleetMap, write_fleet_map

            self.fleet_map_path = os.path.join(self.root, "FLEETMAP")
            write_fleet_map(
                self.fleet_map_path,
                FleetMap(
                    [f"127.0.0.1:{p}" for p in filer_ports],
                    bounds=self.fleet_bounds,
                ),
            )
        for i, fp in enumerate(filer_ports):
            peers = ",".join(
                f"127.0.0.1:{p}" for j, p in enumerate(filer_ports)
                if j != i
            )
            fargs = ["-port", str(fp), "-master", maddr]
            if self.fleet_map_path:
                # fleet members own disjoint ranges — peer meta
                # aggregation would copy every range everywhere
                fargs += [
                    "-fleetMap", self.fleet_map_path,
                    "-fleetSelf", f"127.0.0.1:{fp}",
                ]
            elif peers:
                fargs += ["-peers", peers]
            if self.data_center:
                fargs += ["-dataCenter", self.data_center]
            if self.durable_filers:
                fargs += [
                    "-store", os.path.join(self.root, f"filer{i}.db"),
                    "-metaLog", os.path.join(self.root, f"filer{i}-mlog"),
                ]
            if self.geo_source:
                fargs += ["-geoSource", self.geo_source]
                if self.durable_filers:
                    # a durable cursor only makes sense over a durable
                    # namespace: resuming past events a wiped in-memory
                    # store never kept would lose them
                    fargs += [
                        "-geoState",
                        os.path.join(self.root, f"filer{i}-geo.json"),
                    ]
            self._add(f"filer-{i}", "filer", fp, fargs)

        for i in range(self.n_followers):
            fp = self._port()
            fargs = [
                "-port", str(fp), "-master", maddr,
                "-followSource", f"127.0.0.1:{filer_ports[0]}",
            ]
            if self.durable_filers:
                fargs += [
                    "-store", os.path.join(self.root, f"follower{i}.db"),
                ]
            self._add(f"follower-{i}", "filer", fp, fargs)

        if self.with_s3:
            self.s3_port = self._port()
            s3_filer_port = self._port()
            sargs = [
                "-port", str(self.s3_port),
                "-filerPort", str(s3_filer_port),
                "-master", maddr,
            ]
            if self.iam_cfg:
                iam_path = os.path.join(self.root, "iam.json")
                with open(iam_path, "w") as f:
                    json.dump(self.iam_cfg, f)
                sargs += ["-config", iam_path]
            self._add("s3", "s3", self.s3_port, sargs)

        # one readiness pass over everything spawned: children boot
        # concurrently, the deadline is shared
        deadline = time.monotonic() + self.ready_timeout
        for child in self.children.values():
            self._wait_ready(child, deadline)
        self._wait_volumes_registered(deadline)

    def _wait_volumes_registered(self, deadline: float) -> None:
        """Listeners up is not assignable: the first write races the
        first volume heartbeat unless the master has seen every volume
        server report capacity."""
        if self.n_volumes == 0:
            return
        url = f"http://127.0.0.1:{self.master_port}/dir/status"
        while True:
            try:
                with urllib.request.urlopen(url, timeout=2.0) as r:
                    topo = json.load(r).get("Topology") or {}
                nodes = [
                    dn
                    for dc in topo.get("data_centers", ())
                    for rack in dc.get("racks", ())
                    for dn in rack.get("data_nodes", ())
                    if dn.get("max_volume_count", 0) > 0
                ]
                if len(nodes) >= self.n_volumes:
                    return
            except (urllib.error.URLError, OSError, ValueError):
                pass
            if time.monotonic() > deadline:
                raise StartupError(
                    f"master saw fewer than {self.n_volumes} volume "
                    f"servers within {self.ready_timeout}s"
                )
            time.sleep(0.05)

    # roles whose server also binds port+_GRPC_OFFSET: readiness
    # must cover BOTH listeners — the HTTP side comes up first in
    # server start(), so probing /metrics alone lets a fast sibling
    # (e.g. the S3 gateway's first AssignVolume) race the master's
    # gRPC bind and die on connection-refused
    _GRPC_ROLES = ("master", "volume", "filer")

    def _wait_ready(self, child: Child, deadline: float) -> None:
        url = f"http://127.0.0.1:{child.spec.port}/metrics"
        http_ok = False
        while True:
            if not child.alive():
                raise StartupError(
                    f"{child.name} exited rc={child.proc.returncode} "
                    f"during startup; log tail:\n{child.log_tail()}"
                )
            if not http_ok:
                try:
                    with urllib.request.urlopen(url, timeout=1.0) as r:
                        http_ok = r.status == 200
                except (urllib.error.URLError, OSError, TimeoutError):
                    pass
            if http_ok:
                if child.spec.role not in self._GRPC_ROLES:
                    return
                s = socket.socket()
                s.settimeout(1.0)
                try:
                    s.connect(
                        ("127.0.0.1", child.spec.port + _GRPC_OFFSET)
                    )
                    return
                except OSError:
                    pass
                finally:
                    s.close()
            if time.monotonic() > deadline:
                raise StartupError(
                    f"{child.name} not ready on :{child.spec.port} within "
                    f"{self.ready_timeout}s; log tail:\n{child.log_tail()}"
                )
            time.sleep(0.05)

    # ---------------- teardown ----------------
    def stop(self) -> None:
        self._stop_evt.set()
        for t in self._timers:
            t.cancel()
        self._timers.clear()
        if self._driver is not None and self._driver.is_alive():
            self._driver.join(10)
        self._driver = None
        for child in reversed(list(self.children.values())):
            self._terminate(child)
        with _LIVE_LOCK:
            _LIVE.discard(self)

    def _terminate(self, child: Child, grace: float = 5.0) -> None:
        if child.proc is None:
            child.close_log()
            return
        if child.proc.poll() is None:
            pid = child.proc.pid
            # a paused (SIGSTOPped) child cannot act on SIGTERM; resume
            # it first so graceful shutdown has a chance
            _signal_group(pid, signal.SIGCONT)
            _signal_group(pid, signal.SIGTERM)
            try:
                child.proc.wait(grace)
            except subprocess.TimeoutExpired:
                _signal_group(pid, signal.SIGKILL)
                try:
                    child.proc.wait(grace)
                except subprocess.TimeoutExpired:
                    pass
        child.close_log()

    def __enter__(self) -> "ProcCluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---------------- introspection ----------------
    @property
    def master_address(self) -> str:
        return f"127.0.0.1:{self.master_port}"

    def address(self, name: str) -> str:
        return f"127.0.0.1:{self.children[name].spec.port}"

    def pids(self) -> dict:
        return {n: c.pid for n, c in self.children.items()}

    def _get(self, name: str, path: str, timeout: float = 5.0) -> bytes:
        url = f"http://{self.address(name)}{path}"
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read()

    def scrape_metrics(self, name: str, timeout: float = 5.0) -> dict:
        return parse_prom(
            self._get(name, "/metrics", timeout).decode("utf-8", "replace")
        )

    def debug_json(self, name: str, path: str, timeout: float = 5.0):
        return json.loads(self._get(name, path, timeout))

    def served_pid(self, name: str) -> int:
        """The PID actually answering HTTP on the child's port (from its
        /debug/overload identity) — distinct-process proof, not just a
        distinct Popen handle."""
        return int(self.debug_json(name, "/debug/overload")["pid"])

    # ---------------- process-level faults ----------------
    def kill(self, name: str) -> None:
        """Hard kill: SIGKILL the child's process group, no respawn."""
        child = self.children[name]
        if child.proc is not None and child.proc.poll() is None:
            _signal_group(child.proc.pid, signal.SIGKILL)
            child.proc.wait(10)

    def pause(self, name: str) -> None:
        child = self.children[name]
        if child.alive():
            _signal_group(child.proc.pid, signal.SIGSTOP)

    def resume(self, name: str) -> None:
        child = self.children[name]
        if child.proc is not None and child.proc.poll() is None:
            _signal_group(child.proc.pid, signal.SIGCONT)

    def restart(self, name: str, down_s: float = 0.0,
                ready_timeout: Optional[float] = None) -> int:
        """Restart-with-recovery: SIGKILL, optional down time, respawn
        the same spec (same port, same dirs — durable state survives),
        wait ready. Returns the new PID."""
        self.kill(name)
        child = self.children[name]
        child.close_log()
        registers = child.spec.role == "volume"
        if registers:
            # the master drops the node when its heartbeat stream breaks;
            # respawned before that, the new process's registration would
            # be torn down with the old stream's
            self._wait_listed(child, False, time.monotonic() + 5.0)
        if down_s > 0:
            self._stop_evt.wait(down_s)
        child.spawn()
        deadline = time.monotonic() + (ready_timeout or self.ready_timeout)
        self._wait_ready(child, deadline)
        # listening is not registered: a lookup right after the restart
        # races the first heartbeat, which carries the node's volumes
        if registers and not self._wait_listed(child, True, deadline):
            raise StartupError(
                f"{child.name} is up but the master does not list it "
                f"within {ready_timeout or self.ready_timeout}s"
            )
        return child.proc.pid

    def _wait_listed(self, child: Child, want: bool, deadline: float) -> bool:
        """Until the master's topology lists (or no longer lists) the
        volume server `child`; False at the deadline. A master that
        cannot be asked (killed by the same fault schedule) ends the
        wait as satisfied: there is nobody to register with."""
        url = f"http://127.0.0.1:{self.master_port}/dir/status"
        me = f"127.0.0.1:{child.spec.port}"
        while True:
            try:
                with urllib.request.urlopen(url, timeout=2.0) as r:
                    topo = json.load(r).get("Topology") or {}
            except (urllib.error.URLError, OSError, ValueError):
                if not any(
                    c.alive() for c in self.children.values()
                    if c.spec.role == "master"
                ):
                    return True
                topo = None
            if topo is not None:
                listed = any(
                    dn.get("url") == me
                    for dc in topo.get("data_centers", ())
                    for rack in dc.get("racks", ())
                    for dn in rack.get("data_nodes", ())
                )
                if listed == want:
                    return True
            if time.monotonic() > deadline or self._stop_evt.is_set():
                return False
            time.sleep(0.05)

    def apply_fault(self, f: ProcessFault, epoch: float) -> dict:
        child = self.children.get(f.target)
        ev = {
            "at_s": f.at_s,
            "kind": f.kind,
            "target": f.target,
            "t_fired": round(time.monotonic() - epoch, 3),
            "pid_before": child.pid if child else None,
        }
        if child is None:
            ev["error"] = "unknown target"
            return ev
        if f.kind == "kill":
            self.kill(f.target)
            ev["pid_after"] = None
        elif f.kind == "pause":
            self.pause(f.target)
            t = threading.Timer(
                max(f.duration_s, 0.05), self.resume, args=(f.target,)
            )
            t.daemon = True
            t.start()
            self._timers.append(t)
            ev["resume_after_s"] = f.duration_s
            ev["pid_after"] = child.pid
        elif f.kind == "restart":
            ev["pid_after"] = self.restart(f.target, down_s=f.duration_s)
        else:
            ev["error"] = f"unknown kind {f.kind!r}"
        return ev

    def run_fault_schedule(self, schedule: list[ProcessFault],
                           block: bool = False) -> None:
        """Deliver a seeded schedule (util/faults.process_fault_schedule)
        relative to NOW. Runs on a driver thread unless block=True;
        every delivery lands in self.fault_events. stop() aborts the
        driver and cancels pending resumes."""
        epoch = time.monotonic()

        def drive() -> None:
            for f in sorted(schedule, key=lambda x: x.at_s):
                delay = epoch + f.at_s - time.monotonic()
                if delay > 0 and self._stop_evt.wait(delay):
                    return
                if self._stop_evt.is_set():
                    return
                try:
                    self.fault_events.append(self.apply_fault(f, epoch))
                except Exception as e:
                    self.fault_events.append({
                        "at_s": f.at_s, "kind": f.kind,
                        "target": f.target,
                        "error": f"{type(e).__name__}: {e}",
                    })

        if block:
            drive()
        else:
            self._driver = threading.Thread(target=drive, daemon=True)
            self._driver.start()

    def join_fault_schedule(self, timeout: float = 60.0) -> None:
        if self._driver is not None:
            self._driver.join(timeout)
