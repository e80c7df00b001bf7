"""Traffic kind `shell_jobs`: one `python -m seaweedfs_tpu shell` REPL kept open
for the window, one command after another, each waiting for its reply line.

Parameters (the cell's file): `command` with `{vid}` in it, `reply` that a good
answer holds, `volumes` (how many sealed copies of the template the server is
given; no new command starts once the time is used, so this only has to be
more than a window can use), `warm_volumes` (converted in set-up, so every
shape of the window has compiled before it opens) and `trace_job` (which job of
the window a traced run records, whole).

What is timed is the jobs: each from its command to its reply line, one after
another, and no new one starts once those times add up to --seconds. The rate
is the bytes converted over the sum of those times, the CPU the server's over
the same spans. Between two jobs, outside any span, the harness does the same
to every conversion: reads the sizes of its k + m shard files, takes a 128-bit
BLAKE2b digest of every block of every file (parallel processes), and then
unmounts and deletes the shards over gRPC, as a node deletes what `ec.encode`
spread to other nodes. So the window holds one conversion's files at a time,
written over the pages the one before it freed: a window's worth would not fit,
and pages the machine has not touched yet cost the writer a third more.

Compared, once the window has closed and the server is gone: every digest of
every conversion against the benchmark's own codec on the same .dat; the last
conversion, whose files stay, byte for byte, and the data back from k of its
k + m shards.
"""

from __future__ import annotations

import hashlib
import os
import time

import numpy as np

from ...reference import rs_codec
from .. import common
from ..rpc import Rpc
from ..shell import Repl
from ..stores import sealed_template

ENCODED_BYTES = "seaweedfs_tpu_ec_encoded_bytes_total"
DIGEST_BYTES = 16


class Traffic:
    def __init__(self, ctx):
        self.ctx = ctx
        p = ctx.params
        self.k = int(ctx.config["geometry"]["data_shards"])
        self.m = int(ctx.config["geometry"]["parity_shards"])
        self.volumes = int(p["volumes"])
        self.warm_volumes = int(p.get("warm_volumes", 1))
        self.jobs: list = []  # (vid, seconds, server cpu seconds, good)
        self.digests: list = []  # of every good conversion, in order
        self.repl = None
        self.rpc = None
        self.kept = None  # the volume whose shard files stay: the last one
        self.missized = 0

    # ---- set-up
    def stage(self, server) -> None:
        store = self.ctx.store
        for vid in range(1, self.volumes + self.warm_volumes + 1):
            sealed_template.link_volume(store, server.data_dir, vid)

    def warm(self, server) -> None:
        server.wait_volumes(self.volumes + self.warm_volumes)
        self.repl = Repl(server.master, os.path.join(self.ctx.scratch, "shell.log"))
        self.rpc = Rpc()
        got = self.repl.ask("lock", "locked", 60)
        if "locked" not in got:
            raise common.Failed(f"shell lock: {got!r}")
        for vid in range(self.volumes + 1, self.volumes + self.warm_volumes + 1):
            self._job(server, vid)
            if not self.jobs.pop()[3]:
                raise common.Failed(f"the warm-up conversion of volume {vid} failed")
            self._digest(server, vid)  # the workers have read a conversion once
            self._drop(server, vid)

    def _job(self, server, vid: int) -> None:
        p = self.ctx.params
        cpu0 = server.cpu_seconds()
        t0 = time.perf_counter()
        got = self.repl.ask(p["command"].format(vid=vid), f"volume {vid}:", 900)
        seconds = time.perf_counter() - t0
        good = p["reply"] in got
        self.jobs.append((vid, seconds, server.cpu_seconds() - cpu0, good))
        if not good:
            common.say("job_failed", volume=vid, reply=got[:300])

    def _digest(self, server, vid: int) -> bytes:
        store = self.ctx.store
        return digests_of_files(os.path.join(server.data_dir, str(vid)), store["dat_bytes"],
                                self.k, self.m, self.ctx.pool_map, self.ctx.workers)

    def _drop(self, server, vid: int) -> None:
        """Unmount and delete a conversion's shards, as chip_smoke.py does."""
        for method in ("VolumeEcShardsUnmount", "VolumeEcShardsDelete"):
            self.rpc.call(server.volume, "volume", method,
                          {"volume_id": vid, "collection": "",
                           "shard_ids": list(range(self.k + self.m))})

    # ---- the window
    def run(self, server, seconds: float, tracer) -> dict:
        want = rs_codec.shard_size(self.ctx.store["dat_bytes"], self.k)
        trace_job = int(self.ctx.params.get("trace_job", 2))
        t0 = time.perf_counter()
        for vid in range(1, self.volumes + 1):
            traced = tracer is not None and vid == trace_job
            if traced:
                tracer.begin()
            self._job(server, vid)
            if traced:
                tracer.end()
            server.alive()
            last = vid == self.volumes or sum(j[1] for j in self.jobs) >= seconds
            if self.jobs[-1][3]:
                base = os.path.join(server.data_dir, str(vid))
                sizes = [os.path.getsize(f) if os.path.exists(f) else -1
                         for f in shard_paths(base, self.k + self.m)]
                self.missized += sum(1 for s in sizes if s != want)
                if all(s == want for s in sizes):
                    self.digests.append(self._digest(server, vid))
                if last:
                    self.kept = vid
                else:
                    self._drop(server, vid)
            if last:
                break
        t1 = time.perf_counter()
        dat_bytes = self.ctx.store["dat_bytes"]
        good = [j for j in self.jobs if j[3]]
        if len(self.jobs) == self.volumes:
            common.say("volumes_ran_out", volumes=self.volumes,
                       hint="the window ended early: give the cell more volumes")
        converted = len(good) * dat_bytes
        timed = sum(j[1] for j in self.jobs)
        cpu = sum(j[2] for j in self.jobs)
        return {
            "window_s": timed, "wall_s": t1 - t0,
            "attempted": len(self.jobs), "failed": len(self.jobs) - len(good),
            "converted_bytes": converted,
            "jobs_s": [round(j[1], 4) for j in self.jobs],
            "server_cpu_s": cpu,
            "end_to_end": {
                "ec_encode_rate": converted / 1e9 / timed,
                "ec_encode_host_cpu": cpu / (converted / 1e9) if converted else None,
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
        store, k, m = self.ctx.store, self.k, self.m
        good = result["attempted"] - result["failed"]
        want = reference_digests(store, k, m, self.ctx.pool_map, self.ctx.workers)
        blocks_differing = sum(digests_differing(got, want) for got in self.digests)
        blocks_short = (good - len(self.digests)) * (len(want) // DIGEST_BYTES)
        differing = compared = unrecovered = 0
        bases = [] if self.kept is None or self.missized else [os.path.join(server.data_dir, str(self.kept))]
        if bases:
            differing, compared, unrecovered = compare_files(
                store, bases, k, m, self.ctx.seed, self.ctx.pool_map, self.ctx.workers)
        kept_bytes = (k + m) * rs_codec.shard_size(store["dat_bytes"], k)
        on_device = observed.prom_delta(ENCODED_BYTES, backend="device") or 0
        elsewhere = (observed.prom_delta(ENCODED_BYTES) or 0) - on_device
        return [
            ("conversions_failed", result["failed"], 0),
            ("shard_files_missized", self.missized, 0),
            ("shard_blocks_differing", blocks_differing, 0),
            ("shard_blocks_compared_short", blocks_short, 0),
            ("shard_bytes_differing", differing, 0),
            ("shard_bytes_compared_short", max(0, kept_bytes - compared), 0),
            ("recovered_bytes_differing", unrecovered, 0),
            ("bytes_encoded_off_device", int(elsewhere), 0),
            ("bytes_uncounted_on_device", int(abs(on_device - result["converted_bytes"])), 0),
        ]


def shard_paths(base: str, total: int) -> list:
    return [f"{base}.ec{i:02d}" for i in range(total)]


def row_parts(dat_bytes: int, k: int, workers: int) -> list:
    """The rows of the layout dealt out to the worker processes."""
    rows = list(range(len(rs_codec.row_spans(dat_bytes, k))))
    return [part for part in (rows[w::workers] for w in range(workers)) if part]


def in_row_order(parts: list, answers: list) -> bytes:
    """What the workers gave back for their rows, joined in the order of the rows."""
    by_row = {r: a for part, answer in zip(parts, answers) for r, a in zip(part, answer)}
    return b"".join(by_row[r] for r in sorted(by_row))


def digests_of_files(base: str, dat_bytes: int, k: int, m: int, pool_map, workers: int) -> bytes:
    """A digest of every block of the k + m shard files `<base>.ecNN`: row after
    row, shard after shard."""
    parts = row_parts(dat_bytes, k, workers)
    return in_row_order(parts, pool_map(file_digests, [(base, dat_bytes, part, k, m) for part in parts]))


def reference_digests(store: dict, k: int, m: int, pool_map, workers: int) -> bytes:
    """The same digests of what the benchmark's own codec makes of the .dat."""
    parts = row_parts(store["dat_bytes"], k, workers)
    return in_row_order(parts, pool_map(codec_digests, [(store["dat"], part, k, m) for part in parts]))


def digests_differing(got: bytes, want: bytes) -> int:
    if len(got) != len(want):
        return max(len(got), len(want)) // DIGEST_BYTES
    a = np.frombuffer(got, dtype=np.uint8).reshape(-1, DIGEST_BYTES)
    b = np.frombuffer(want, dtype=np.uint8).reshape(-1, DIGEST_BYTES)
    return int(np.count_nonzero((a != b).any(axis=1)))


def digest(block) -> bytes:
    return hashlib.blake2b(block, digest_size=DIGEST_BYTES).digest()


def file_digests(job: tuple) -> list:
    base, dat_bytes, rows, k, m = job
    spans = rs_codec.row_spans(dat_bytes, k)
    fds = [os.open(f, os.O_RDONLY) for f in shard_paths(base, k + m)]
    try:
        return [b"".join(digest(os.pread(fd, spans[r][2], spans[r][1])) for fd in fds) for r in rows]
    finally:
        for fd in fds:
            os.close(fd)


def codec_digests(job: tuple) -> list:
    dat_path, rows, k, m = job
    codec = rs_codec.Codec(k, m)
    dat = np.memmap(dat_path, dtype=np.uint8, mode="r")
    spans = rs_codec.row_spans(len(dat), k)
    out = []
    for r in rows:
        dat_off, _shard_off, block = spans[r]
        data = rs_codec.data_rows(dat, dat_off, block, k)
        out.append(b"".join(digest(row.tobytes()) for row in (*data, *codec.encode(data))))
    return out


def compare_files(store: dict, bases: list, k: int, m: int, seed: int, pool_map, workers: int,
                  lost: list | None = None) -> tuple:
    """The shard files `<base>.ecNN` of each base against the benchmark's own
    codec on the store's .dat, every byte, and in one row drawn from the seed
    the data back from the k shards that are left when `lost` (m of them, drawn
    from the seed unless given) are taken away: (bytes differing, bytes
    compared, recovered bytes differing). The one comparison a run, the control
    and the tests make."""
    parts = row_parts(store["dat_bytes"], k, workers)
    rng = np.random.default_rng([seed, 0xEC])
    drawn = sorted(rng.choice(k + m, m, replace=False).tolist())
    recover_row = int(rng.integers(0, sum(len(p) for p in parts)))
    differing = compared = unrecovered = 0
    for d, c, u in pool_map(
        compare_rows,
        [(store["dat"], bases, part, k, m, lost or drawn, recover_row) for part in parts],
    ):
        differing, compared, unrecovered = differing + d, compared + c, unrecovered + u
    return differing, compared, unrecovered


def compare_rows(job: tuple) -> tuple:
    """For some rows of the template: the reference's k + m blocks against every
    base's shard files. One row in the run also proves the guarantee itself: the
    data blocks come back from k of the program's k + m shards."""
    dat_path, bases, rows, k, m, lost, recover_row = job
    codec = rs_codec.Codec(k, m)
    dat = np.memmap(dat_path, dtype=np.uint8, mode="r")
    spans = rs_codec.row_spans(len(dat), k)
    files = [[open(f, "rb") for f in shard_paths(b, k + m)] for b in bases]
    differing = compared = unrecovered = 0
    try:
        for r in rows:
            dat_off, shard_off, block = spans[r]
            data = rs_codec.data_rows(dat, dat_off, block, k)
            want = np.concatenate([data, codec.encode(data)])
            for handles in files:
                got = np.stack([
                    np.frombuffer(os.pread(f.fileno(), block, shard_off), dtype=np.uint8)
                    for f in handles
                ])
                compared += got.size
                if not np.array_equal(got, want):
                    differing += int(np.count_nonzero(got != want))
                if r == recover_row:
                    alive = {i: got[i] for i in range(k + m) if i not in lost}
                    back = codec.recover(alive, list(range(k)))
                    unrecovered += int(np.count_nonzero(back != data))
    finally:
        for handles in files:
            for f in handles:
                f.close()
    return differing, compared, unrecovered
