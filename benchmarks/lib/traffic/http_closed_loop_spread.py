"""Traffic kind `http_closed_loop_spread`: `http_closed_loop`'s window against
an EC volume whose 14 shards lie on four volume servers, one of them lost.

The one server child (benchmarks/lib/server.py: master + the volume server
that holds the chip) gets `peers.count` peer volume servers beside it, each the
program's own `python -m seaweedfs_tpu volume -mserver <that master> ...` with
the flags and the environment the configuration's file gives under `peers`
(the host codec, `JAX_PLATFORMS=cpu` said outright: a chip belongs to one
process, and no peer may open it). A peer's directory is beside the server's
own in the run's directory on `/dev/shm`; its ports are probed as the server's
are, and a peer that dies before the master lists it (`address already in
use`: the pair is probed before the child binds it) is started again on
another pair. Peers are started in `stage`, while the server child starts, and
die with this process (PR_SET_PDEATHSIG) or in `after_window`, which run.py
also calls from its `finally`: no peer outlives a run, however it ends.

Set-up, all before the window and so inside `setup_s`:

1. wait until the master lists every server, and each peer's `/status` says it
   attached no device;
2. the shell lines of `prepare` (`lock`, `ec.encode -volumeId N`, `unlock`,
   run by `http_closed_loop`'s own `prepare`): the
   program generates the shards on the chip's server, spreads them
   (`plan_balanced_spread`, `VolumeEcShardsCopy`, mount, delete at the source)
   and drops the `.dat`;
3. read the spread back from the master (`LookupEcVolume`, once every shard
   has a holder) and hold it to the plain rule
   (`benchmarks/reference/ec_spread.py`): `shards_unplaced`, `shards_doubled`,
   `spread_uneven`; `source_dat_left`;
4. `healthy_gets` GETs through the chip's server over needles of every data
   shard, every body compared (`healthy_bodies_wrong`); shards on peers are
   read over `VolumeEcShardRead`, so `ec_remote_attempts_total{outcome=
   "served"}` has to move (`healthy_remote_reads_never_moved`);
5. lose a server, by the rule `lose` names: SIGKILL of the peer that holds
   data shard `holder_of_shard`, or, where the spread put that shard on the
   chip's server, of the peer that holds the lowest data shard that is not
   there (`peers`: how many to lose, 1 in the cell; the control loses 2);
6. wait until the master's `LookupEcVolume` names no holder for the lost
   shards, hold its answer to the guarantee (`lost_shards_listed`,
   `holders_not_live`, `lost_beyond_parity`), then GET needles of the lost
   shard until the chip server's own table is of that answer
   (`ec_remote_attempts_total{outcome="no_holder"}` moves: the table is
   refreshed every `SHARD_LOCATION_TTL` or when a listed holder failed). The
   seconds both took are printed as a note (`lost`);
7. `http_closed_loop`'s warm-up and window, over the needles whose record
   starts on the lost peer's lowest data shard.

In the window the live peers' CPU seconds are read from `/proc/<pid>/stat`
(`peer_cpu_s`, for `peers.cpu_cores`) and their `/metrics` before and after
(`peer_spans_served`, a note). `check` adds to `http_closed_loop`'s: what
set-up compared, and — where the program counts them — that a cold
reconstruct fetched at least the plain reference's least number of survivors
from other servers (`remote_survivor_reads_short`). The counter is this PR's;
on a tree without it the comparison is left out, as is the per-layer metric.

`peer_fault` (never in a cell's file: `benchmarks/controls_spread.py` sets
it) starts the peers through `benchmarks/lib/peer_child.py --fault <name>`.
"""

from __future__ import annotations

import ctypes
import json
import os
import random
import signal
import subprocess
import sys
import time

from .. import common
from ..http_client_proc import Connection
from ..rpc import Rpc
from ..server import GRPC_OFFSET, Server, free_port_pair, parse_prom, sum_metric
from ..stores import sealed_template
from ...reference import ec_spread
from . import http_closed_loop

ATTEMPTS = "seaweedfs_tpu_ec_remote_attempts_total"
SURVIVOR_READS = "seaweedfs_tpu_ec_remote_shard_reads_total"
COLD = "seaweedfs_tpu_ec_reconstructions_total"
SPANS_SERVED = "seaweedfs_tpu_request_seconds_count"
PEER_STARTS = 3  # a peer is started at most this often
PR_SET_PDEATHSIG = 1
_PRCTL = ctypes.CDLL(None).prctl  # found here, not between fork and exec
_PRCTL.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
_PRCTL.restype = ctypes.c_int


def _die_with_parent() -> None:
    _PRCTL(PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0)


class Peer:
    """One peer volume server: the program's `volume` command as a child."""

    def __init__(self, index: int, root: str, log_dir: str, master: str, spec: dict, fault):
        self.index, self.master, self.spec, self.fault = index, master, spec, fault
        self.dir = os.path.join(root, f"peer{index}")
        self.log_path = os.path.join(log_dir, f"peer{index}.log")
        os.makedirs(self.dir, exist_ok=True)
        self.proc = self.url = self._log = None
        self.starts = 0

    def start(self, taken: set) -> None:
        port = free_port_pair(taken)
        taken.update((port, port + GRPC_OFFSET))
        self.url = f"127.0.0.1:{port}"
        env = common.child_env()
        env.update(self.spec["environment"])
        args = [*self.spec["command"], "-port", str(port), "-dir", self.dir,
                "-mserver", self.master, *self.spec["flags"]]
        if self.fault:
            cmd = [sys.executable, os.path.join(common.LIB, "peer_child.py"),
                   "--fault", self.fault, "--", *args]
        else:
            cmd = [sys.executable, "-m", "seaweedfs_tpu", *args]
        if self._log:
            self._log.close()
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(cmd, cwd=common.CHECKOUT, env=env, stdin=subprocess.DEVNULL,
                                     stdout=self._log, stderr=subprocess.STDOUT,
                                     preexec_fn=_die_with_parent)
        self.starts += 1

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    # a child with a log and a pid, as the server child is: read the same way
    log_tail = Server.log_tail
    cpu_seconds = Server.cpu_seconds

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
        if self.proc is not None:
            self.proc.wait()
        if self._log:
            self._log.close()
            self._log = None


class Traffic(http_closed_loop.Traffic):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.peers: list = []
        self.taken: set = set()
        self.readings: list = []  # what set-up compared: (name, value, limit)
        self.lost: list = []  # the peers that were killed
        self.held: set = set()  # the shards on the chip's server
        self.lost_shards: set = set()

    # ------------------------------------------------------------ the peers
    def stage(self, server) -> None:
        super().stage(server)
        spec = self.ctx.config["peers"]
        for hp in (server.master, server.volume):
            port = int(hp.split(":")[1])
            self.taken.update((port, port + GRPC_OFFSET))
        root = os.path.dirname(server.data_dir)
        for j in range(1, int(spec["count"]) + 1):
            peer = Peer(j, root, self.ctx.scratch, server.master, spec,
                        self.ctx.params.get("peer_fault"))
            peer.start(self.taken)
            self.peers.append(peer)

    def live(self) -> list:
        return [p for p in self.peers if p not in self.lost]

    def wait_peers(self, server, limit_s: float = 90) -> None:
        """Until the master lists the chip's server and every peer."""
        t0 = time.perf_counter()
        while True:
            for peer in self.peers:
                if not peer.alive():
                    tail = peer.log_tail(2000)
                    if peer.starts >= PEER_STARTS:
                        raise common.Failed(f"peer {peer.index} died {peer.starts} times:\n{tail}")
                    common.say("peer_started_again", peer=peer.index, exit=peer.proc.returncode,
                               address_in_use="address already in use" in tail.lower(),
                               log=tail[-300:])
                    peer.start(self.taken)
            listed = set(self.data_nodes(server))
            if listed >= {server.volume, *(p.url for p in self.peers)}:
                return
            if time.perf_counter() - t0 > limit_s:
                raise common.Failed(f"the master lists {sorted(listed)}, not every peer")
            time.sleep(0.1)

    def data_nodes(self, server) -> list:
        topo = json.loads(server.get("/dir/status", server.master))["Topology"]
        return [dn["url"] for dc in topo.get("data_centers", []) for rack in dc.get("racks", [])
                for dn in rack.get("data_nodes", [])]

    # ------------------------------------------------------------- set-up
    def holders(self, rpc, server) -> dict:
        """{shard id: [url]} as the master answers `LookupEcVolume` now."""
        vid = int(self.ctx.params["volume"])
        try:
            reply = rpc.call(server.master, "master", "LookupEcVolume", {"volume_id": vid})
        except RuntimeError:  # "ec volume N not found": nobody holds a shard yet
            return {}
        return {int(e["shard_id"]): [loc["url"] for loc in e["locations"]]
                for e in reply.get("shard_id_locations", [])}

    def prepare(self, server) -> None:
        p, config = self.ctx.params, self.ctx.config
        k, m = config["geometry"]["data_shards"], config["geometry"]["parity_shards"]
        vid = int(p["volume"])
        t0 = time.perf_counter()
        self.wait_peers(server)
        for peer in self.peers:
            device = json.loads(server.get("/status", peer.url)).get("Device") or {}
            if device.get("platform") == "tpu":
                raise common.Failed(f"peer {peer.index} attached the chip: {device}")
        common.say("peers", seconds=round(time.perf_counter() - t0, 3),
                   urls=[peer.url for peer in self.peers], starts=[peer.starts for peer in self.peers])

        t0 = time.perf_counter()
        super().prepare(server)  # the shell lines of the cell's file: lock, ec.encode, unlock
        rpc = Rpc()
        try:
            nodes = [server.volume, *(peer.url for peer in self.peers)]
            holders = self.wait_for(lambda: self.holders(rpc, server),
                                    lambda h: len(h) == k + m, 30, "a holder for every shard")
            spread = ec_spread.judge_spread(holders, self.data_nodes(server), k + m)
            self.readings += [(name, value, 0) for name, value in spread.items()]
            dat = os.path.join(server.data_dir, f"{vid}.dat")
            self.readings.append(("source_dat_left", int(os.path.lexists(dat)), 0))
            self.held = {s for s, at in holders.items() if server.volume in at}
            common.say("spread", seconds=round(time.perf_counter() - t0, 3), **spread,
                       counts={n: sum(n in at for at in holders.values()) for n in nodes},
                       expected=ec_spread.balanced_counts(k + m, len(nodes)),
                       on_the_chips_server=sorted(self.held))

            self.healthy_gets(server, k)
            self.lose(server, rpc, holders, k, m)
        finally:
            rpc.close()

    def wait_for(self, read, good, limit_s: float, what: str):
        t0 = time.perf_counter()
        while True:
            got = read()
            if good(got):
                return got
            if time.perf_counter() - t0 > limit_s:
                raise common.Failed(f"no {what} within {limit_s} s: {got}")
            time.sleep(0.1)

    def draw_on(self, shard: int, rng) -> tuple:
        """A small needle whose record starts on data shard `shard` by the
        plain reference's rule, as the target of a GET and its body."""
        reader, dat_bytes = self.reader, self.ctx.store["dat_bytes"]
        k = self.ctx.config["geometry"]["data_shards"]
        while True:
            i = rng.randrange(reader.p["small"])
            if ec_spread.locate(int(reader.p["offset"][i]), 1, dat_bytes, k)[0][0] == shard:
                reader.candidates = [i]
                return reader.draw(rng)

    def healthy_gets(self, server, k: int) -> None:
        """GETs over needles of every data shard while every server is up."""
        p = self.ctx.params
        t0 = time.perf_counter()
        self.reader = sealed_template.Reader(self.ctx.store, self.ctx.seed, {"volume": p["volume"]})
        rng = random.Random(self.ctx.seed * 4096 + 4095)
        served0 = sum_metric(server.metrics(), ATTEMPTS, outcome="served")
        conn, wrong, sent = Connection(server.volume), 0, 0
        try:
            for shard in range(k):
                for _ in range(int(p["healthy_gets"]) // k):
                    wrong += int(not self.get_one(conn, *self.draw_on(shard, rng)))
                    sent += 1
        finally:
            conn.close()
        served = sum_metric(server.metrics(), ATTEMPTS, outcome="served") - served0
        self.readings += [("healthy_bodies_wrong", wrong, 0),
                          ("healthy_remote_reads_never_moved", int(not served), 0)]
        common.say("healthy_gets", seconds=round(time.perf_counter() - t0, 3), sent=sent,
                   wrong=wrong, remote_served=served)

    def get_one(self, conn, target: str, want: bytes) -> bool:
        """One GET, its body compared; a shed one is asked again."""
        for _ in range(50):
            try:
                status, _head, body = conn.get(target)
            except (OSError, ValueError):
                conn.close()
                time.sleep(0.05)
                continue
            if status != 503:
                return status == 200 and body == want
            time.sleep(0.1)
        return False

    def lose(self, server, rpc, holders: dict, k: int, m: int) -> None:
        p = self.ctx.params
        rule = p["lose"]
        by_url = {peer.url: peer for peer in self.peers}
        for _ in range(int(rule["peers"])):
            gone = {s for s, at in holders.items() if any(by_url.get(u) in self.lost for u in at)}
            shard = int(rule["holder_of_shard"])
            if shard in self.held or shard in gone:
                shard = min(s for s in range(k) if s not in self.held and s not in gone)
            self.lost.append(by_url[holders[shard][0]])
        lost_urls = {peer.url for peer in self.lost}
        self.lost_shards = {s for s, at in holders.items() if set(at) & lost_urls}
        on_shard = min(s for s in self.lost_shards if s < k)
        t0 = time.perf_counter()
        for peer in self.lost:
            peer.kill()  # SIGKILL: the master learns of it from the broken stream alone
        after = self.wait_for(lambda: self.holders(rpc, server),
                              lambda h: not any(set(at) & lost_urls for at in h.values()),
                              60, "answer of the master without the lost server")
        master_s = time.perf_counter() - t0
        live = {server.volume, *(peer.url for peer in self.live())}
        self.readings += [
            ("lost_shards_listed", sum(1 for s in self.lost_shards if after.get(s)), 0),
            ("holders_not_live", sum(1 for at in after.values() for u in at if u not in live), 0),
            ("lost_beyond_parity", max(0, len(self.lost_shards) - m), 0),
        ]
        # the chip server's own table: GETs on the lost shard until one of them
        # finds the master's fresh answer naming nobody
        rng = random.Random(self.ctx.seed * 4096 + 4094)
        conn, probes, wrong = Connection(server.volume), 0, 0
        try:
            nobody0 = sum_metric(server.metrics(), ATTEMPTS, outcome="no_holder")
            while sum_metric(server.metrics(), ATTEMPTS, outcome="no_holder") == nobody0:
                if time.perf_counter() - t0 > 60 or (probes >= 3 and len(self.lost_shards) > m):
                    break  # past the parities no read can succeed: the window will say so
                wrong += int(not self.get_one(conn, *self.draw_on(on_shard, rng)))
                probes += 1
        finally:
            conn.close()
        self.readings.append(("probe_bodies_wrong", wrong, 0))
        p["pick"] = {"volume": p["volume"], "on_shard": on_shard}
        common.say("lost", peers=[peer.index for peer in self.lost], urls=sorted(lost_urls),
                   shards=sorted(self.lost_shards), window_on_shard=on_shard,
                   master_seconds=round(master_s, 3),
                   table_seconds=round(time.perf_counter() - t0, 3), probes=probes,
                   probes_wrong=wrong,
                   least_remote_survivors=ec_spread.least_remote_survivors(
                       on_shard, self.held, self.lost_shards, k, k + m))

    # ------------------------------------------------------------ the window
    def peer_spans(self, server) -> float:
        """VolumeEcShardRead streams the live peers have served (0 where the
        program does not count them)."""
        total = 0.0
        for peer in self.live():
            total += sum_metric(parse_prom(server.get("/metrics", peer.url).decode()),
                                SPANS_SERVED, operation="VolumeEcShardRead")
        return total

    def run(self, server, seconds: float, tracer) -> dict:
        cpu0 = sum(peer.cpu_seconds() for peer in self.live())
        spans0 = self.peer_spans(server)
        result = super().run(server, seconds, tracer)
        result["peer_cpu_s"] = sum(peer.cpu_seconds() for peer in self.live()) - cpu0
        result["peer_spans_served"] = self.peer_spans(server) - spans0
        result["peers_alive"] = sum(peer.alive() for peer in self.live())
        return result

    def after_window(self, server) -> None:
        try:
            super().after_window(server)
        finally:
            for peer in self.peers:
                peer.kill()

    def check(self, server, result: dict, observed) -> list:
        config = self.ctx.config
        k, m = config["geometry"]["data_shards"], config["geometry"]["parity_shards"]
        compared = super().check(server, result, observed) + self.readings
        compared.append(("peers_died_in_the_window", len(self.live()) - result["peers_alive"], 0))
        reads, cold = observed.prom_delta(SURVIVOR_READS), observed.prom_delta(COLD, kind="cold")
        on_shard = self.ctx.params["pick"]["on_shard"]
        least = ec_spread.least_remote_survivors(on_shard, self.held, self.lost_shards, k, k + m)
        if reads is not None and cold and least is not None:
            compared.append(("remote_survivor_reads_short", max(0, cold * least - reads), 0))
        return compared
