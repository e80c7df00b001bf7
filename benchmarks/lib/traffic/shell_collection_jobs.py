"""Traffic kind `shell_collection_jobs`: the master's maintenance pass, by hand.
One `python -m seaweedfs_tpu shell` REPL kept open for the window; one
`ec.encode -collection <c> ...` after another, each converting every volume of
its collection that the command itself selects, each waiting for its reply.

Parameters (the cell's file): `command` with `{collection}` in it, `reply` that
a converted volume's line holds (a list of commands is typed in turn, job after
job, the set-up's job typing the first: the controls'), `full_percent` and
`quiet_for` (what the script's line says, for the reference's selection), `collections` (how many the
window may use; no new command starts once the time is used), `warm_collections`
(converted in set-up, so every shape of the window has compiled), `full_volumes`
and `small_volumes` a collection (links of the store's `full` and `small`
templates: the small ones are the decoys the selection has to leave alone) and
`trace_job` (which job of the window a traced run records, whole; a traced run
whose first jobs already use the time goes on until that job is done).

The store is `sealed_collections`; the layout is this module's: collection
`c<j>` holds the volumes (j-1)*n+1 .. j*n, the full ones first.

What is timed is the jobs: each from its command to the last line of its reply,
one after another. The rate is the .dat bytes of the volumes that replied
`reply` over the sum of those times, the CPU the server's over the same spans.
Between two jobs, outside any span, the harness does to every converted volume
what `shell_jobs` does to its one: sizes, a digest of every block of the k + m
files, unmount, delete. It also looks at every other volume: its .dat still
there, no shard file, not read-only at the master.

Compared, once the window has closed and the server is gone: what each job
converted against the plain selection (`reference/ec_selection.py`) over the
volumes as the harness laid them out; every digest against the benchmark's own
codec; the last job's files byte for byte and the data back from k of k + m;
where the bytes were encoded; that no batch fell back and no file was left
under a temporary name.
"""

from __future__ import annotations

import os
import re
import select
import time

from ...reference import ec_selection, rs_codec
from .. import common
from ..rpc import Rpc
from ..shell import Repl
from ..stores import sealed_collections
from . import shell_jobs

ENCODED_BYTES = shell_jobs.ENCODED_BYTES
FALLBACKS = "seaweedfs_tpu_ec_encode_batch_fallback_total"
VOLUME_LINE = re.compile(r"^volume (\d+): (.*)$")


def ask_all(repl: Repl, command: str, limit_s: float) -> list:
    """Send one line; return every line of its reply. The reply has ended when
    the shell's next prompt stands alone behind at least one whole line."""
    repl.proc.stdin.write(command.encode() + b"\n")
    repl.proc.stdin.flush()
    deadline = time.perf_counter() + limit_s
    fd = repl.proc.stdout.fileno()
    lines: list = []
    while True:
        while b"\n" in repl._buf:
            line, _, repl._buf = repl._buf.partition(b"\n")
            lines.append(line.decode(errors="replace").replace("> ", "").strip())
        if lines and repl._buf.strip() == b">":
            return lines
        left = deadline - time.perf_counter()
        if left <= 0 or repl.proc.poll() is not None:
            raise common.Failed(f"shell: no end to the answer to {command!r} ({lines[-3:]!r})")
        if select.select([fd], [], [], min(left, 1.0))[0]:
            chunk = os.read(fd, 65536)
            if not chunk:
                raise common.Failed(f"shell closed its output after {command!r}")
            repl._buf += chunk


def hold_selection(expected: list, replied: dict, reply: str) -> dict:
    """One job's reply lines `{vid: text}` against the ids the reference names:
    how many it names that did not reply `reply`, and how many replied that it
    does not name. The one comparison a run, the control and the tests make."""
    encoded = {vid for vid, text in replied.items() if reply in text}
    return {"missed": len(set(expected) - encoded), "extra": len(set(replied) - set(expected))}


def server_limit_mb(flags: list) -> int:
    return int(flags[flags.index("-volumeSizeLimitMB") + 1])


class Traffic:
    def __init__(self, ctx):
        self.ctx = ctx
        p = ctx.params
        self.k = int(ctx.config["geometry"]["data_shards"])
        self.m = int(ctx.config["geometry"]["parity_shards"])
        self.commands = [p["command"]] if isinstance(p["command"], str) else list(p["command"])
        self.collections = int(p["collections"])
        self.warm_collections = int(p.get("warm_collections", 1))
        self.n_full, self.n_small = int(p["full_volumes"]), int(p["small_volumes"])
        self.full_percent = float(p["full_percent"])
        self.quiet_s = ec_selection.duration_seconds(p["quiet_for"])
        self.limit_mb = int(p.get("volume_size_limit_mb") or server_limit_mb(ctx.config["server_flags"]))
        # (id, collection, size, modified_at, base name) of every volume not yet converted
        self.volumes: dict = {}
        self.jobs: list = []  # (collection, seconds, cpu seconds, good, bytes converted)
        self.digests: list = []  # of every converted volume of the right sizes, in order
        self.kept: list = []  # base names whose shard files stay: the last job's
        self.count = dict.fromkeys(
            ("missed", "extra", "touched", "read_only", "missized", "left_tmp", "modified_at_wrong"), 0)
        self.converted_volumes = 0
        self.repl = self.rpc = None

    def collection(self, j: int) -> str:
        return f"c{j}"

    # ---- set-up
    def stage(self, server) -> None:
        if self.ctx.rehearse:  # the master's limit at a rehearsal's size
            at = server.flags.index("-volumeSizeLimitMB") + 1
            server.flags = [*server.flags[:at], str(self.limit_mb), *server.flags[at + 1:]]
        store = self.ctx.store
        per = self.n_full + self.n_small
        for j in range(1, self.collections + self.warm_collections + 1):
            for p in range(per):
                vid = (j - 1) * per + p + 1
                template = store["full"] if p < self.n_full else store["small"]
                base = sealed_collections.link_volume(template, server.data_dir, self.collection(j), vid)
                self.volumes[vid] = (vid, self.collection(j), template["dat_bytes"],
                                     sealed_collections.modified_at(template), base)

    def warm(self, server) -> None:
        server.wait_volumes(len(self.volumes))
        self.repl = Repl(server.master, os.path.join(self.ctx.scratch, "shell.log"))
        self.rpc = Rpc()
        said = {int(v["id"]): int(v.get("modified_at_second", 0)) for v in self._master_volumes(server)}
        self.count["modified_at_wrong"] = sum(
            1 for vid, v in self.volumes.items() if said.get(vid) != v[3])
        got = self.repl.ask("lock", "locked", 60)
        if "locked" not in got:
            raise common.Failed(f"shell lock: {got!r}")
        for j in range(self.collections + 1, self.collections + self.warm_collections + 1):
            converted = self._job(server, j, self.commands[0])
            self.jobs.pop()  # a wrong selection shows again in the window, where it counts
            if not converted:
                raise common.Failed(f"the warm-up job of collection {self.collection(j)} converted nothing")
            for vid, base in converted:
                self._digest(base)  # the workers have read a conversion once
                self._drop(server, j, vid)
        for key in self.count:  # what the warm-up counted is no part of the window
            if key != "modified_at_wrong":
                self.count[key] = 0

    def _master_volumes(self, server) -> list:
        topo = self.rpc.call(server.master, "master", "VolumeList", {})["topology_info"]
        return [v for dc in topo.get("data_centers", []) for rack in dc.get("racks", [])
                for dn in rack.get("data_nodes", []) for v in dn.get("volumes", [])]

    def _job(self, server, j: int, command: str) -> list:
        """One command, timed, and what it left behind, looked at outside the
        span. Returns (vid, base) of the volumes that replied `reply`."""
        p = self.ctx.params
        name = self.collection(j)
        expected = ec_selection.select(
            [v[:4] for v in self.volumes.values()], name, self.limit_mb,
            self.full_percent, self.quiet_s, time.time())
        cpu0 = server.cpu_seconds()
        t0 = time.perf_counter()
        lines = ask_all(self.repl, command.format(collection=name), 900)
        seconds = time.perf_counter() - t0
        cpu = server.cpu_seconds() - cpu0
        replied = {int(m.group(1)): m.group(2) for m in map(VOLUME_LINE.match, lines) if m}
        held = hold_selection(expected, replied, p["reply"])
        good = bool(expected) and not held["missed"] and not held["extra"]
        converted = [(vid, self.volumes[vid][4]) for vid, text in sorted(replied.items())
                     if p["reply"] in text and vid in self.volumes]
        nbytes = sum(self.volumes[vid][2] for vid, _b in converted)
        self.jobs.append((name, seconds, cpu, good, nbytes))
        if not good:
            common.say("job_failed", collection=name, expected=expected, reply=lines[:8])
        self.count["missed"] += held["missed"]
        self.count["extra"] += held["extra"]
        for vid, _base in converted:
            del self.volumes[vid]
        self._look_at_the_others(server)
        return converted

    def _look_at_the_others(self, server) -> None:
        """Every volume no job has converted: its .dat is there, it has no shard
        or index file of an EC volume, the master does not call it read-only;
        and no file of the directory stands under a temporary name."""
        names = os.listdir(server.data_dir)
        self.count["left_tmp"] += sum(1 for n in names if n.endswith(".tmp"))
        for _vid, _c, _size, _at, base in self.volumes.values():
            stem = os.path.basename(base) + "."
            ec = [n for n in names if n.startswith(stem) and re.match(r"ec(\d\d|x|j)$", n[len(stem):])]
            if ec or not os.path.lexists(base + ".dat"):
                self.count["touched"] += 1
        self.count["read_only"] += sum(
            1 for v in self._master_volumes(server)
            if int(v["id"]) in self.volumes and v.get("read_only"))

    def _digest(self, base: str) -> bytes:
        return shell_jobs.digests_of_files(base, self.ctx.store["full"]["dat_bytes"], self.k, self.m,
                                           self.ctx.pool_map, self.ctx.workers)

    def _drop(self, server, j: int, vid: int) -> None:
        for method in ("VolumeEcShardsUnmount", "VolumeEcShardsDelete"):
            self.rpc.call(server.volume, "volume", method,
                          {"volume_id": vid, "collection": self.collection(j),
                           "shard_ids": list(range(self.k + self.m))})

    # ---- the window
    def run(self, server, seconds: float, tracer) -> dict:
        want = rs_codec.shard_size(self.ctx.store["full"]["dat_bytes"], self.k)
        trace_job = int(self.ctx.params.get("trace_job", 2))
        t0 = time.perf_counter()
        for j in range(1, self.collections + 1):
            traced = tracer is not None and j == trace_job
            if traced:
                tracer.begin()
            converted = self._job(server, j, self.commands[(j - 1) % len(self.commands)])
            if traced:
                tracer.end()
            server.alive()
            # a traced run goes on at least to the job it records, whole
            last = j == self.collections or (
                sum(job[1] for job in self.jobs) >= seconds and (tracer is None or tracer.ended))
            self.converted_volumes += len(converted)
            for vid, base in converted:
                sizes = [os.path.getsize(f) if os.path.exists(f) else -1
                         for f in shell_jobs.shard_paths(base, self.k + self.m)]
                self.count["missized"] += sum(1 for s in sizes if s != want)
                if all(s == want for s in sizes):
                    self.digests.append(self._digest(base))
                if last:
                    self.kept.append(base)
                else:
                    self._drop(server, j, vid)
            if last:
                break
        t1 = time.perf_counter()
        if len(self.jobs) == self.collections:
            common.say("collections_ran_out", collections=self.collections,
                       hint="the window ended early: give the cell more collections")
        good = [job for job in self.jobs if job[3]]
        converted_bytes = sum(job[4] for job in self.jobs)
        timed = sum(job[1] for job in self.jobs)
        cpu = sum(job[2] for job in self.jobs)
        return {
            "window_s": timed, "wall_s": t1 - t0,
            "attempted": len(self.jobs), "failed": len(self.jobs) - len(good),
            "converted_bytes": converted_bytes, "converted_volumes": self.converted_volumes,
            "jobs_s": [round(job[1], 4) for job in self.jobs],
            "server_cpu_s": cpu,
            "end_to_end": {
                "ec_encode_rate": converted_bytes / 1e9 / timed,
                "ec_encode_host_cpu": cpu / (converted_bytes / 1e9) if converted_bytes else None,
            },
        }

    def after_window(self, server) -> None:
        if self.repl is not None:
            try:
                self.repl.ask("unlock", "unlocked", 30)
            finally:
                self.repl.close()
                self.repl = None
        if self.rpc is not None:
            self.rpc.close()
            self.rpc = None

    # ---- the comparison, once the window has closed and the server is gone
    def check(self, server, result: dict, observed) -> list:
        store, k, m = self.ctx.store["full"], self.k, self.m
        want = shell_jobs.reference_digests(store, k, m, self.ctx.pool_map, self.ctx.workers)
        blocks_differing = sum(shell_jobs.digests_differing(got, want) for got in self.digests)
        blocks_short = (self.converted_volumes - len(self.digests)) * (len(want) // shell_jobs.DIGEST_BYTES)
        differing = compared = unrecovered = 0
        if self.kept and not self.count["missized"]:
            differing, compared, unrecovered = shell_jobs.compare_files(
                store, self.kept, k, m, self.ctx.seed, self.ctx.pool_map, self.ctx.workers)
        kept_bytes = len(self.kept) * (k + m) * rs_codec.shard_size(store["dat_bytes"], k)
        on_device = observed.prom_delta(ENCODED_BYTES, backend="device") or 0
        elsewhere = (observed.prom_delta(ENCODED_BYTES) or 0) - on_device
        c = self.count
        return [
            ("jobs_failed", result["failed"], 0),
            ("selection_missed", c["missed"], 0),
            ("selection_extra", c["extra"], 0),
            ("volumes_wrongly_touched", c["touched"], 0),
            ("volumes_wrongly_read_only", c["read_only"], 0),
            ("modified_at_wrong", c["modified_at_wrong"], 0),
            ("shard_files_missized", c["missized"], 0),
            ("shard_blocks_differing", blocks_differing, 0),
            ("shard_blocks_compared_short", blocks_short, 0),
            ("shard_bytes_differing", differing, 0),
            ("shard_bytes_compared_short", max(0, kept_bytes - compared), 0),
            ("recovered_bytes_differing", unrecovered, 0),
            ("bytes_encoded_off_device", int(elsewhere), 0),
            ("bytes_uncounted_on_device", int(abs(on_device - result["converted_bytes"])), 0),
            ("batch_fallbacks", int(observed.prom_delta(FALLBACKS) or 0), 0),
            ("files_left_under_temporary_names", c["left_tmp"], 0),
        ]
