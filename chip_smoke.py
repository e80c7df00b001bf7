#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the volume server's device planes once, through the entry points a
user would call, and checks every answer against a plain reference:

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # the (vol, blk) mesh path, nothing else

One chip (the default). ONE child — `python -m seaweedfs_tpu server
-storageBackend tpu -index lsm -batchLookup arena` — owns the chip; this
parent never initialises a JAX backend and reads the device from the child's
/status. Phases: load a >= 1 GiB volume of >= 200,000 needles over
/dir/assign + HTTP; one 65,536-key BulkLookup RPC against the .idx log's
replay; concurrent GETs through the batching gate onto the device arena,
bodies compared; `ec.encode` in the shell, 14 shard files compared with the
numpy table codec run here on the same .dat; two shards removed, 1,000
needles read degraded, `ec.rebuild`, rebuilt shards compared with the
removed ones.

Four chips (`--chips 4`, run by hand): in THIS process,
write_ec_files_multi / rebuild_ec_files_multi over 8 volumes of 256 MiB with
mesh=make_mesh(4) against the same calls without a mesh, shard files
identical; sharded_verify == 0; one sharded_bulk_lookup; bytes held per
device.

Every earlier line is one JSON observation (seconds, bytes, counters — not
metrics: no rate here is a result). The LAST line is the verdict:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}} and
exit 0, or {"ok": false, ...} and exit 1 — for any phase that fails, any
comparison that differs, any kernel kind other than `device`, any device
other than a TPU. `--rehearse` runs every phase on whatever JAX finds (and
at any size) to find wrong paths without the chip; it never says ok.
"""

from __future__ import annotations

import argparse
import asyncio
import filecmp
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback
from collections import deque

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

DEPLOYMENT_VOLUME_MB = 30_000  # -volumeSizeLimitMB default (command/cli.py)
FULL_DAT_BYTES = 1 << 30
FULL_NEEDLES = 200_000
FLOOR_DAT_BYTES = 256 << 20
FLOOR_NEEDLES = 50_000
BULK_KEYS = 65_536
GET_ROUND = 4_096  # distinct fids per round of concurrent GETs
GET_CONCURRENCY = 768
DEGRADED_READS = 1_000
LOST_SHARDS = (3, 11)  # one data, one parity
POOL_BYTES = 32 << 20
SMALL_BLOCK = 1 << 20  # EC small block: shard of byte x = (x // 1 MiB) % k


def say(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


class Failed(Exception):
    """A phase did not hold; the run ends with ok=false."""


class Verdict:
    """Soft checks: a rehearsal keeps going to find the next wrong path,
    a real run stops at the first one."""

    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        if ok:
            return
        if not self.rehearse:
            raise Failed(what)
        self.failures.append(what)
        say("rehearsal_failure", what=what)


# ---------------------------------------------------------------- the child
class Server:
    """The one child: all-in-one master + volume server that owns the chip."""

    def __init__(self, root: str, master_port: int, volume_port: int):
        self.root = root
        self.master = f"127.0.0.1:{master_port}"
        self.volume = f"127.0.0.1:{volume_port}"
        self.data_dir = os.path.join(root, "data")
        self.log_path = os.path.join(root, "server.log")
        os.makedirs(self.data_dir)
        env = dict(os.environ)
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "seaweedfs_tpu", "server",
                "-dir", self.data_dir,
                "-port", str(master_port), "-volumePort", str(volume_port),
                "-storageBackend", "tpu", "-index", "lsm",
                "-batchLookup", "arena",
            ],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise Failed(
                f"server child exited with {self.proc.returncode}:\n"
                + self.log_tail()
            )

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def stop(self) -> None:
        if self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGTERM)
            try:
                self.proc.wait(15)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait()
        self._log.close()


def shell(master: str, commands: str) -> str:
    """`python -m seaweedfs_tpu shell`, one-shot. It computes nothing; it is
    pinned to the CPU so it can never reach for the child's chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master", master,
         commands],
        cwd=HERE, env=env, capture_output=True, text=True, timeout=900,
    )
    if out.returncode != 0:
        raise Failed(f"shell {commands!r} exited {out.returncode}: {out.stderr[-2000:]}")
    return out.stdout.strip()


class Client:
    """The repo's keep-alive HTTP client, asking again what the server
    shed: its admission gate answers 503 + Retry-After for what it will
    not queue (util/overload.py), and a client then backs off and retries.
    Sheds are counted and printed with the phase they fell in."""

    def __init__(self, pool: int):
        from seaweedfs_tpu.util.fasthttp import FastHTTPClient

        # the client's own circuit breaker would turn the first sheds
        # into refusals to ask at all
        os.environ["SEAWEEDFS_TPU_BREAKER"] = "0"
        self._http = FastHTTPClient(pool_per_host=pool)
        self.shed = 0

    async def request(self, method: str, hostport: str, target: str, **kw):
        for _attempt in range(400):
            st, body = await self._http.request(method, hostport, target, **kw)
            if st != 503:
                break
            self.shed += 1
            await asyncio.sleep(
                max(0.02, self._http.retry_after_remaining(hostport))
            )
        return st, body


async def each(items, workers: int, fn) -> None:
    """await fn(item) for every item, `workers` at a time."""
    todo = deque(items)

    async def worker() -> None:
        while todo:
            await fn(todo.popleft())

    await asyncio.gather(*(worker() for _ in range(min(workers, len(todo)))))


class Probe:
    """Reads the child's own pages: /status, /metrics, /debug/needle_map."""

    def __init__(self, http, server: Server):
        from seaweedfs_tpu.ops.proc_cluster import parse_prom, sum_metric

        self.http, self.server = http, server
        self._parse, self._sum = parse_prom, sum_metric

    async def get(self, target: str) -> bytes:
        st, body = await self.http.request("GET", self.server.volume, target)
        if st != 200:
            raise Failed(f"GET {target}: {st} {body[:200]!r}")
        return body

    async def status(self) -> dict:
        return json.loads(await self.get("/status"))

    async def metrics(self) -> dict:
        return self._parse((await self.get("/metrics")).decode())

    async def needle_map(self) -> dict:
        return json.loads(await self.get("/debug/needle_map"))

    async def compiles(self) -> dict:
        m = await self.metrics()
        cache = "seaweedfs_tpu_jax_compile_cache_total"
        return {
            "compiles": int(self._sum(m, "seaweedfs_tpu_jax_compiles_total")),
            "compile_s": self._sum(m, "seaweedfs_tpu_jax_compile_seconds_total"),
            "cache_hits": int(self._sum(m, cache, result="hit")),
            "cache_misses": int(self._sum(m, cache, result="miss")),
        }


class Phase:
    """Wall seconds and the child's compile counters around one phase."""

    def __init__(self, name: str, probe: Probe):
        self.name, self.probe = name, probe

    async def __aenter__(self):
        self.before = await self.probe.compiles()
        self.shed0 = self.probe.http.shed
        self.t0 = time.perf_counter()
        self.note: dict = {}
        return self.note

    async def __aexit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        after = await self.probe.compiles()
        say(
            self.name, wall_s=round(wall, 3),
            **{k: round(after[k] - self.before[k], 3) for k in after},
            shed_503=self.probe.http.shed - self.shed0,
            **self.note,
        )
        return False


# ---------------------------------------------------------------- the store
class Store:
    """What the loader wrote, regenerable from the seed: needle i's body is
    pool[start[i] : start[i] + size[i]]."""

    def __init__(self, seed: int, n_small: int):
        rng = np.random.default_rng(seed)
        self.rng = rng
        self.pool = rng.integers(0, 256, POOL_BYTES, dtype=np.uint8).tobytes()
        self.size = rng.integers(1024, 10 * 1024 + 1, n_small).tolist()
        self.start = rng.integers(0, POOL_BYTES - (1 << 20), n_small).tolist()
        self.fid: list = [None] * n_small
        self.deleted: set = set()
        self.vid = 0

    def body(self, i: int) -> bytes:
        return self.pool[self.start[i] : self.start[i] + self.size[i]]

    def add_large(self) -> int:
        self.size.append(1 << 20)
        self.start.append(int(self.rng.integers(0, POOL_BYTES - (1 << 20))))
        self.fid.append(None)
        return len(self.fid) - 1

    def keys(self) -> np.ndarray:
        from seaweedfs_tpu.storage.file_id import FileId

        return np.array(
            [FileId.parse(f).key for f in self.fid], dtype=np.uint64
        )


async def load(http, server: Server, store: Store, dat_target: int) -> dict:
    from seaweedfs_tpu.client.operation import AssignLease, http_assign
    from seaweedfs_tpu.util.fasthttp import build_multipart

    async def fetch(count: int):
        return await http_assign(http, server.master, count)

    # one volume, grown by hand: /dir/assign would grow seven at once
    # (topology/volume_growth.py) and spread the needles over them
    st, body = await http.request("GET", server.master, "/vol/grow?count=1")
    if st != 200:
        raise Failed(f"/vol/grow: {st} {body[:200]!r}")
    lease = AssignLease(fetch=fetch, batch=256)

    async def put(i: int) -> None:
        ar = await lease.take()
        payload, ctype = build_multipart("file", store.body(i))
        st, body = await http.request(
            "POST", ar.url, "/" + ar.fid, body=payload, content_type=ctype,
            timeout=120,
        )
        if st >= 300:
            raise Failed(f"PUT {ar.fid}: {st} {body[:200]!r}")
        store.fid[i] = ar.fid
        if not i % 4096:
            server.alive()

    async def delete(i: int) -> None:
        st, body = await http.request(
            "DELETE", server.volume, "/" + store.fid[i]
        )
        if st >= 300:
            raise Failed(f"DELETE {store.fid[i]}: {st} {body[:200]!r}")
        store.deleted.add(i)

    n_small = len(store.fid)
    await each(range(n_small), 64, put)
    vids = {f.split(",")[0] for f in store.fid}
    if len(vids) != 1:
        raise Failed(f"needles landed in volumes {sorted(vids)}, want one")
    store.vid = int(vids.pop())
    dat = os.path.join(server.data_dir, f"{store.vid}.dat")
    short = dat_target - os.path.getsize(dat)
    if short > 0:  # enough larger needles to reach the size
        await each(
            [store.add_large() for _ in range(short // (1 << 20) + 1)], 8, put
        )
    # a few thousand deletes, so lookups meet tombstones
    doomed = store.rng.choice(n_small, size=max(16, n_small // 100), replace=False)
    await each(doomed.tolist(), 32, delete)
    return {
        "needles": len(store.fid), "small": n_small,
        "large": len(store.fid) - n_small, "deleted": len(store.deleted),
        "dat_bytes": os.path.getsize(dat), "volume": store.vid,
    }


def replay_idx(idx_path: str) -> dict:
    """The plain reference for lookups: the .idx log replayed into a dict,
    newest entry wins, tombstones and zero offsets absent."""
    log = np.fromfile(
        idx_path, dtype=[("key", ">u8"), ("off", ">u4"), ("size", ">u4")]
    )
    table: dict = {}
    for key, off, size in zip(
        log["key"].tolist(), log["off"].tolist(), log["size"].tolist()
    ):
        if off == 0 or size == 0xFFFFFFFF:
            table.pop(key, None)
        else:
            table[key] = (off, size)
    return table


async def bulk_lookup(server: Server, store: Store, keys_of, live, table, verdict):
    from seaweedfs_tpu.pb import grpc_address
    from seaweedfs_tpu.pb.rpc import Stub

    rng = store.rng
    dead = np.array(sorted(store.deleted))
    n_present = BULK_KEYS * 5 // 8
    present = keys_of[rng.choice(live, min(n_present, len(live)), replace=False)]
    deleted = keys_of[dead[: BULK_KEYS // 8]]
    top = int(keys_of.max())
    absent = top + 1 + rng.integers(
        1, 1 << 40, BULK_KEYS - len(present) - len(deleted)
    ).astype(np.uint64)
    probes = np.concatenate([present, deleted, absent]).astype(np.uint64)
    rng.shuffle(probes)
    r = await Stub(grpc_address(server.volume), "volume").call(
        "BulkLookup",
        {"volume_id": store.vid, "keys": probes.astype("<u8").tobytes()},
        timeout=600,
    )
    if r.get("error"):
        raise Failed(f"BulkLookup: {r['error']}")
    offs = np.frombuffer(r["offsets"], dtype=r.get("offset_dtype", "<u4"))
    sizes = np.frombuffer(r["sizes"], dtype="<u4")
    found = np.frombuffer(r["found"], dtype=np.uint8).astype(bool)
    want = [table.get(k) for k in probes.tolist()]
    want_found = np.array([w is not None for w in want])
    verdict.check(
        len(found) == len(probes) and bool((found == want_found).all()),
        "BulkLookup found-flags differ from the idx replay",
    )
    hit = np.flatnonzero(want_found & found)
    verdict.check(
        all(
            (int(offs[j]), int(sizes[j])) == want[j] for j in hit.tolist()
        ),
        "BulkLookup (offset, size) differ from the idx replay",
    )
    return {
        "keys": len(probes), "present": len(present),
        "deleted": len(deleted), "absent": len(absent),
        "found": int(found.sum()),
    }


async def get_many(http, server: Server, store: Store, idxs, what: str, verdict):
    """GET every needle of `idxs` at GET_CONCURRENCY, each body compared."""
    bad: list = []

    async def get(i: int) -> None:
        st, body = await http.request(
            "GET", server.volume, "/" + store.fid[i], timeout=120
        )
        if st != 200 or body != store.body(i):
            bad.append((store.fid[i], st, len(body)))

    await each(idxs, GET_CONCURRENCY, get)
    verdict.check(not bad, f"{what}: {len(bad)} bodies differ, e.g. {bad[:3]}")
    return sum(store.size[i] for i in idxs)


def same_files(a_base: str, b_base: str, shard_ids, what: str, verdict) -> int:
    total = 0
    for i in shard_ids:
        a, b = f"{a_base}.ec{i:02d}", f"{b_base}.ec{i:02d}"
        verdict.check(
            os.path.exists(a) and os.path.exists(b)
            and filecmp.cmp(a, b, shallow=False),
            f"{what}: shard {i} differs ({a} vs {b})",
        )
        total += os.path.getsize(a) if os.path.exists(a) else 0
    return total


# ------------------------------------------------------------ the one-chip run
async def run_served(args, root: str, verdict: Verdict) -> dict:
    from seaweedfs_tpu import native
    from seaweedfs_tpu.ops.proc_cluster import free_port_pair, sum_metric
    from seaweedfs_tpu.pb import grpc_address
    from seaweedfs_tpu.pb.rpc import Stub, close_all_channels
    from seaweedfs_tpu.storage.erasure_coding import write_ec_files
    from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec

    say("host_codec", tier=native.tier())
    mp = free_port_pair()
    server = Server(root, mp, free_port_pair({mp, mp + 10000}))
    http = Client(pool=GET_CONCURRENCY + 64)
    probe = Probe(http, server)
    try:
        # --- start: the child says what it runs on
        t0 = time.perf_counter()
        while True:
            server.alive()
            try:
                status = await probe.status()
                break
            except (OSError, Failed, asyncio.TimeoutError):
                if time.perf_counter() - t0 > 180:
                    raise Failed("server not ready:\n" + server.log_tail())
                await asyncio.sleep(0.25)
        dev = status.get("Device") or {}
        device = {
            "platform": dev.get("platform"),
            "kind": dev.get("device_kind"),
            "count": dev.get("count"),
        }
        say("start", wall_s=round(time.perf_counter() - t0, 3), device=device)
        verdict.check(
            device["platform"] == "tpu",
            f"the server runs on {device}, not on a TPU",
        )
        verdict.check(device["count"] == 1, f"want one chip, got {device}")

        # --- load
        store = Store(args.seed, args.needles)
        async with Phase("load", probe) as note:
            note.update(await load(http, server, store, args.dat_bytes))
        base = os.path.join(server.data_dir, str(store.vid))
        dat_bytes = os.path.getsize(base + ".dat")
        verdict.check(
            dat_bytes >= args.dat_bytes and len(store.fid) >= args.needles,
            f"store too small: {dat_bytes} bytes, {len(store.fid)} needles",
        )
        keys_of = store.keys()
        table = replay_idx(base + ".idx")
        live = [i for i in range(len(store.fid)) if i not in store.deleted]
        verdict.check(
            len(table) == len(live)
            and all(int(keys_of[i]) in table for i in live[:: max(1, len(live) // 5000)]),
            f"idx replay holds {len(table)} live needles, loader wrote {len(live)}",
        )

        # --- lookup on the chip: one BulkLookup RPC
        async with Phase("bulk_lookup", probe) as note:
            note.update(
                await bulk_lookup(server, store, keys_of, live, table, verdict)
            )

        # --- lookup on the chip: concurrent GETs through the gate + arena
        small_live = [i for i in live if i < args.needles]
        order = store.rng.permutation(len(small_live)).tolist()
        async with Phase("gate_gets", probe) as note:
            rounds = got = 0
            nm: dict = {}
            while order and rounds < 8:
                batch = [small_live[j] for j in order[:GET_ROUND]]
                del order[:GET_ROUND]
                got += len(batch)
                note["bytes"] = note.get("bytes", 0) + await get_many(
                    http, server, store, batch, "gate GETs", verdict
                )
                rounds += 1
                nm = await probe.needle_map()
                if (
                    nm["gate"]["device_batches"] > 0
                    and nm["device"]["dispatches"] > 0
                    and got >= 512
                ):
                    break
            note.update(
                gets=got, rounds=rounds, gate=nm.get("gate"),
                arena=nm.get("device"),
                volume_runs=nm["volumes"].get(str(store.vid), {}).get("runs"),
            )
        gate, arena = nm["gate"], nm["device"]
        verdict.check(gate["device_batches"] > 0, f"no device batch: {gate}")
        verdict.check(gate["largest_batch"] >= 128, f"no wakeup >= 128: {gate}")
        verdict.check(
            arena["uploads"] > 0 and arena["dispatches"] > 0,
            f"arena never uploaded or dispatched: {arena}",
        )
        verdict.check(
            gate["device_error"] == 0 and arena["device_error"] == 0
            and gate["identity_mismatches"] == 0,
            f"device errors or identity mismatches: {gate} {arena}",
        )

        # --- encode on the chip
        ref_dir = os.path.join(root, "ref")
        os.makedirs(ref_dir)
        ref_base = os.path.join(ref_dir, str(store.vid))
        os.link(base + ".dat", ref_base + ".dat")  # ec.encode drops the .dat
        async with Phase("ec_encode", probe) as note:
            out = await asyncio.to_thread(
                shell, server.master,
                f"lock; ec.encode -volumeId {store.vid}; unlock",
            )
            m = await probe.metrics()
            name = "seaweedfs_tpu_ec_encoded_bytes_total"
            by_backend = {
                k: v for k, v in m.items() if k.startswith(name) and v
            }
            note.update(shell=out, bytes=dat_bytes, encoded_by_backend=by_backend)
        verdict.check("encoded" in out, f"ec.encode said: {out!r}")
        verdict.check(
            sum_metric(m, name, backend="device") == dat_bytes
            and sum_metric(m, name) == dat_bytes,
            f"want all {dat_bytes} bytes under backend=device: {by_backend}",
        )
        t0 = time.perf_counter()
        await asyncio.to_thread(write_ec_files, ref_base, CpuRSCodec())
        shard_bytes = same_files(base, ref_base, range(14), "ec.encode", verdict)
        say(
            "ec_encode_reference", codec="numpy tables (coder_cpu)",
            wall_s=round(time.perf_counter() - t0, 3), shard_bytes=shard_bytes,
        )

        # --- degraded read and rebuild on the chip
        stub = Stub(grpc_address(server.volume), "volume")
        for call in ("VolumeEcShardsUnmount", "VolumeEcShardsDelete"):
            r = await stub.call(
                call,
                {"volume_id": store.vid, "collection": "",
                 "shard_ids": list(LOST_SHARDS)},
            )
            if r.get("error"):
                raise Failed(f"{call}: {r['error']}")
        verdict.check(
            not any(os.path.exists(f"{base}.ec{i:02d}") for i in LOST_SHARDS),
            "the removed shard files are still there",
        )
        on_lost = [
            i for i in small_live
            if (table[int(keys_of[i])][0] * 8 // SMALL_BLOCK) % 10
            == LOST_SHARDS[0]
        ]
        picks = store.rng.choice(
            on_lost, min(DEGRADED_READS, len(on_lost)), replace=False
        ).tolist()
        async with Phase("degraded_read", probe) as note:
            before = sum_metric(
                await probe.metrics(), "seaweedfs_tpu_ec_reconstructions_total"
            )
            note["bytes"] = await get_many(
                http, server, store, picks, "degraded GETs", verdict
            )
            recon = sum_metric(
                await probe.metrics(), "seaweedfs_tpu_ec_reconstructions_total"
            ) - before
            note.update(gets=len(picks), reconstructions=int(recon))
        verdict.check(
            len(picks) >= min(DEGRADED_READS, args.needles // 20) and recon > 0,
            f"{len(picks)} degraded reads, {recon} reconstructions",
        )
        async with Phase("ec_rebuild", probe) as note:
            deadline = time.perf_counter() + 60
            while True:  # the master learns of the loss by heartbeat
                out = await asyncio.to_thread(
                    shell, server.master, "lock; ec.rebuild; unlock"
                )
                if "rebuilt" in out or time.perf_counter() > deadline:
                    break
                await asyncio.sleep(1.0)
            m = await probe.metrics()
            stage = "seaweedfs_tpu_ec_rebuild_stage_seconds"
            note.update(
                shell=out,
                rebuild_stages={
                    k: v for k, v in m.items()
                    if k.startswith(stage + "_sum") and v
                },
            )
        verdict.check("rebuilt" in out, f"ec.rebuild said: {out!r}")
        note_bytes = same_files(base, ref_base, LOST_SHARDS, "ec.rebuild", verdict)
        say("ec_rebuild_compare", shards=list(LOST_SHARDS), bytes=note_bytes)
        nm = await probe.needle_map()
        verdict.check(
            nm["gate"]["device_error"] == 0 and nm["device"]["device_error"] == 0,
            f"device errors by the end: {nm['gate']} {nm['device']}",
        )
        say("server_log_tail", text=server.log_tail(1500))
        return device
    finally:
        await close_all_channels()
        server.stop()


# ----------------------------------------------------------- the four-chip run
def run_mesh(args, root: str, verdict: Verdict) -> dict:
    from seaweedfs_tpu.util.device import describe

    import jax  # noqa: F401  (this process owns the chips)

    from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
    from seaweedfs_tpu.parallel import (
        make_mesh, sharded_bulk_lookup, sharded_ec, sharded_verify,
    )
    from seaweedfs_tpu.storage.erasure_coding import (
        rebuild_ec_files_multi, write_ec_files_multi,
    )
    from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec

    d = describe()
    device = {"platform": d["platform"], "kind": d["device_kind"], "count": d["count"]}
    say("start", device=device)
    verdict.check(d["platform"] == "tpu", f"runs on {device}, not on a TPU")
    verdict.check(d["count"] >= 4, f"want four chips, got {device}")
    mesh = make_mesh(4)
    say("mesh", shape=dict(mesh.shape))
    codec = TpuRSCodec()
    n_vol, vol_bytes = args.mesh_volumes, args.mesh_volume_bytes
    rng = np.random.default_rng(args.seed)
    dirs = {w: os.path.join(root, w) for w in ("one", "mesh")}
    for p in dirs.values():
        os.makedirs(p)
    t0 = time.perf_counter()
    for v in range(n_vol):
        one = os.path.join(dirs["one"], f"{v + 1}.dat")
        with open(one, "wb") as f:
            f.write(rng.integers(0, 256, vol_bytes, dtype=np.uint8).tobytes())
        os.link(one, os.path.join(dirs["mesh"], f"{v + 1}.dat"))
    bases = {
        w: [os.path.join(p, str(v + 1)) for v in range(n_vol)]
        for w, p in dirs.items()
    }
    say("make_volumes", volumes=n_vol, bytes_each=vol_bytes,
        wall_s=round(time.perf_counter() - t0, 3))

    def held() -> dict:
        out = {
            str(k): {"held": h, "zero_padding": z}
            for k, (h, z) in sorted(sharded_ec.DEVICE_BYTES.items())
        }
        sharded_ec.DEVICE_BYTES.clear()
        return out

    for what, fn in (("encode", write_ec_files_multi), ("rebuild", rebuild_ec_files_multi)):
        if what == "rebuild":
            for bs in bases.values():
                for b in bs:
                    for i in LOST_SHARDS:
                        os.replace(f"{b}.ec{i:02d}", f"{b}.lost{i:02d}")
        t0 = time.perf_counter()
        fn(bases["one"], codec=codec)
        t1 = time.perf_counter()
        fn(bases["mesh"], codec=codec, mesh=mesh)
        t2 = time.perf_counter()
        ids = range(14) if what == "encode" else LOST_SHARDS
        total = sum(
            same_files(a, b, ids, f"mesh {what}", verdict)
            for a, b in zip(bases["one"], bases["mesh"])
        )
        if what == "rebuild":
            for b in bases["mesh"]:
                for i in LOST_SHARDS:
                    verdict.check(
                        filecmp.cmp(f"{b}.ec{i:02d}", f"{b}.lost{i:02d}", shallow=False),
                        f"mesh rebuild: {b} shard {i} differs from the removed one",
                    )
        say(f"mesh_{what}", one_chip_wall_s=round(t1 - t0, 3),
            mesh_wall_s=round(t2 - t1, 3), compared_bytes=total,
            bytes_held_per_device=held())

    # the one-chip result is itself held to the numpy table codec
    width = min(4 << 20, os.path.getsize(bases["one"][0] + ".ec00"))
    rows = np.stack([
        np.fromfile(f"{bases['one'][0]}.ec{i:02d}", dtype=np.uint8, count=width)
        for i in range(14)
    ])
    verdict.check(
        np.array_equal(CpuRSCodec().encode(rows[:10]), rows[10:]),
        "one-chip parity differs from the numpy table codec",
    )
    shards = np.stack([
        np.stack([
            np.fromfile(f"{b}.ec{i:02d}", dtype=np.uint8, count=width)
            for i in range(14)
        ])
        for b in bases["mesh"]
    ])
    t0 = time.perf_counter()
    mism = sharded_verify(codec.parity_matrix, shards, mesh)
    say("mesh_verify", shape=list(shards.shape), mismatches=mism,
        wall_s=round(time.perf_counter() - t0, 3), bytes_held_per_device=held())
    verdict.check(mism == 0, f"sharded_verify found {mism} mismatches")

    m = 10_000_000
    keys = np.cumsum(rng.integers(1, 9, m, dtype=np.uint64)).astype(np.uint64)
    offs = rng.integers(1, 1 << 30, m, dtype=np.uint64).astype(np.uint32)
    sizes = rng.integers(1, 1 << 20, m, dtype=np.uint64).astype(np.uint32)
    pick = rng.integers(0, m, BULK_KEYS + 3)
    probes = keys[pick].copy()
    probes[:3] = keys[-1] + np.uint64(17)  # guaranteed misses
    t0 = time.perf_counter()
    off, size, found = sharded_bulk_lookup(keys, offs, sizes, probes, mesh)
    say("mesh_lookup", rows=m, probes=len(probes), found=int(found.sum()),
        wall_s=round(time.perf_counter() - t0, 3))
    verdict.check(
        not found[:3].any() and bool(found[3:].all())
        and np.array_equal(off[3:], offs[pick[3:]])
        and np.array_equal(size[3:], sizes[pick[3:]]),
        "sharded_bulk_lookup differs from the table",
    )
    return device


# ------------------------------------------------------------------------ main
def scratch_root() -> str:
    shm = "/dev/shm"
    if os.path.isdir(shm) and shutil.disk_usage(shm).free > (12 << 30):
        return tempfile.mkdtemp(prefix="chip_smoke_", dir=shm)
    return tempfile.mkdtemp(prefix="chip_smoke_")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--needles", type=int, default=FULL_NEEDLES)
    ap.add_argument("--dat-bytes", type=int, default=FULL_DAT_BYTES)
    ap.add_argument("--mesh-volumes", type=int, default=8)
    ap.add_argument("--mesh-volume-bytes", type=int, default=256 << 20)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="run every phase on whatever JAX finds, at any size; never ok",
    )
    args = ap.parse_args(argv)
    verdict = Verdict(args.rehearse)
    root = None
    try:
        from seaweedfs_tpu.util.device import setup_compile_cache

        placed = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        cache = setup_compile_cache()
        say(
            "compile_cache", dir=cache, placed_from_outside=placed,
            cold=not (cache and os.path.isdir(cache) and os.listdir(cache)),
        )
        if not args.rehearse and (
            args.needles < FLOOR_NEEDLES or args.dat_bytes < FLOOR_DAT_BYTES
        ):
            raise Failed(
                f"below the floor of {FLOOR_DAT_BYTES} bytes and "
                f"{FLOOR_NEEDLES} needles; rehearse with --rehearse"
            )
        root = scratch_root()
        if args.chips == 4:
            device = run_mesh(args, root, verdict)
        else:
            say(
                "cut", deployment_volume_mb=DEPLOYMENT_VOLUME_MB,
                smoke_dat_bytes=args.dat_bytes, smoke_needles=args.needles,
                full_dat_bytes=FULL_DAT_BYTES, full_needles=FULL_NEEDLES,
                note="one volume filled to dat_bytes, not to the 30,000 MB "
                "limit; EC rows are all 1 MiB small blocks at this size",
            )
            device = asyncio.run(run_served(args, root, verdict))
            if "jax" in sys.modules:
                raise Failed("the parent imported jax: it must stay off the chip")
        if args.rehearse:
            raise Failed(
                f"rehearsal only; {len(verdict.failures)} check(s) failed: "
                f"{verdict.failures}"
            )
    except BaseException as e:
        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    finally:
        if root is not None:
            shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
