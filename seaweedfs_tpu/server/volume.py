"""Volume server: HTTP data plane + gRPC admin + heartbeat loop.

HTTP (ref: weed/server/volume_server_handlers_{read,write}.go):
  GET/HEAD /{vid},{fid}[/name][.ext]  read (EC fallback when no volume)
  POST     /{vid},{fid}               write (+ synchronous replication fan-out,
                                      ref: weed/topology/store_replicate.go:20)
  DELETE   /{vid},{fid}               delete (+ replication fan-out)

gRPC "volume" service (ref: weed/server/volume_grpc_*.go): allocation,
vacuum, mount/unmount, copy streams, batch delete, and the EC suite
(see volume_ec.py).

Heartbeat loop (ref: weed/server/volume_grpc_client_to_master.go): bidi
stream to the master carrying full inventories at connect + deltas per tick;
EC full-state refresh every 17 pulses.
"""

from __future__ import annotations

import asyncio
import functools as _functools
import logging
import os
import time
from typing import Optional

import aiohttp
from aiohttp import web

from ..pb import grpc_address
from ..pb.rpc import Service, Stub, serve
from ..storage.erasure_coding import to_ext
from ..storage.file_id import FileId
from ..storage.needle import Needle, NotFoundError
from ..storage.store import Store
from ..storage.volume import AlreadyDeleted, CookieMismatch, NotFound, Volume
from ..storage import vacuum as vacuum_mod
from ..util import tenancy
from ..util.fasthttp import (
    DETACHED,
    FALLBACK,
    finish_detached,
    finish_detached_proxy,
    parse_multipart,
    render_response,
)
from ..util.metrics import (
    CHUNK_BATCH_PUT_SIZE,
    READ_CACHE_BYTES,
    READ_CACHE_EVICTIONS,
    READ_CACHE_HITS,
    READ_CACHE_MISSES,
    READ_STAGE_SECONDS,
    WRITE_STAGE_SECONDS,
    mark_startup,
    startup_line,
)
from .volume_ec import EcHandlers

logger = logging.getLogger(__name__)


_NEEDS_FULL_APP = object()  # needle shape the fast tier doesn't serve

# pre-assembled response head for the common read shape (no
# Last-Modified): one %-format replaces the 9-piece render_response
# join + etag()-hex-str round-trip, measurable at read QPS rates.
# %08x of the u32 checksum == u32_to_bytes(checksum).hex() (both BE).
_HEAD_200 = (
    b"HTTP/1.1 200 OK\r\n"
    b"Content-Type: %b\r\n"
    b"Content-Length: %d\r\n"
    b'Etag: "%08x"\r\n'
    b"Accept-Ranges: bytes\r\n"
    b"Connection: keep-alive\r\n\r\n"
)

# hot-needle cache sizing: capacity from the env (MB; 0 disables), entry
# bodies capped so one large blob cannot monopolize the LRU
READ_CACHE_BYTES_CAP = int(
    float(os.environ.get("SEAWEEDFS_TPU_READ_CACHE_MB", "64") or 0) * (1 << 20)
)
READ_CACHE_MAX_ENTRY = 128 * 1024


class HotNeedleCache:
    """Byte-bounded LRU of whole small needle responses keyed by
    (vid, key, cookie) — the serving read plane exploiting zipfian skew
    (the `DegradedIntervalCache` pattern from volume_ec.py applied to the
    hot path in front of the volume tier).

    Entries carry the pre-rendered wire response (status line + headers +
    body in ONE bytes object, the same zero-copy write shape the
    pre-rendered-head path produces) plus the (volume object, offset_units,
    size) the record was parsed from. A hit is served only while BOTH
    still hold:

    - the SAME Volume object is mounted (vacuum-commit, repair recopy and
      remounts swap the object, so their entries can never resurface), and
    - the live needle map still points the key at the same
      (offset_units, size): the .dat is append-only, so an unchanged
      location means unchanged bytes; any overwrite moves the entry to a
      new offset and any delete tombstones it.

    That makes hits byte-identical to uncached reads by construction —
    even for mutations that bypass the server layer entirely. The
    explicit invalidation hooks (overwrite/delete/vacuum-commit) exist on
    top so the LRU sheds dead entries instead of carrying them to
    eviction. TTL'd needles are never cached (expiry is a read-time
    decision the cache cannot replay)."""

    def __init__(self, capacity_bytes: int = READ_CACHE_BYTES_CAP,
                 max_entry: int = READ_CACHE_MAX_ENTRY):
        import threading
        import weakref
        from collections import OrderedDict

        self.capacity = capacity_bytes
        self.max_entry = max_entry
        # (vid, key) -> (vol_ref, cookie, offset_units, size, resp, head_len)
        # — one live record per needle key, so the cookie lives in the
        # entry (hit requires a match) and per-key invalidation is O(1)
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self._weakref = weakref.ref
        self._hits = READ_CACHE_HITS.child()
        self._misses = READ_CACHE_MISSES.child()
        self._served = READ_CACHE_BYTES.child()
        # plain ints alongside the registry counters: the bench reads the
        # hit rate without scraping /metrics (GIL-atomic increments)
        self.hits = 0
        self.misses = 0

    def get(self, v, vid: int, key: int, cookie: int, head_only: bool):
        """The response bytes for a cached needle, or None. `v` is the
        currently-mounted Volume the caller resolved for vid."""
        k = (vid, key)
        with self._lock:
            e = self._entries.get(k)
            if e is not None:
                self._entries.move_to_end(k)
        if e is None:
            self.misses += 1
            self._misses.inc()
            return None
        vol_ref, e_cookie, offset_units, size, resp, head_len = e
        if e_cookie != cookie:
            # wrong cookie is a REQUEST property, not staleness: the
            # uncached path owns the 404; the entry stays for valid reads
            self.misses += 1
            self._misses.inc()
            return None
        # freshness: same volume object AND the live map still points here
        if vol_ref() is not v or v.locate_live(key) != (offset_units, size):
            with self._lock:
                cur = self._entries.get(k)
                if cur is e:
                    del self._entries[k]
                    self._bytes -= len(resp)
            READ_CACHE_EVICTIONS.inc(reason="stale")
            self.misses += 1
            self._misses.inc()
            return None
        self.hits += 1
        self._hits.inc()
        out = resp[:head_len] if head_only else resp
        self._served.inc(len(out))
        return out

    def put(
        self, v, vid: int, n, offset_units: int, size: int, resp: bytes,
        head_len: int,
    ) -> None:
        """Admit one rendered response. Caller guarantees `resp` is the
        simple GET shape (pre-rendered head + raw body) parsed from
        (offset_units, size) of `v`'s .dat."""
        if len(resp) > self.max_entry or n.has_ttl():
            return
        k = (vid, n.id)
        entry = (
            self._weakref(v), n.cookie, offset_units, size, bytes(resp),
            head_len,
        )
        with self._lock:
            old = self._entries.pop(k, None)
            if old is not None:
                self._bytes -= len(old[4])
            self._entries[k] = entry
            self._bytes += len(resp)
            evicted = 0
            while self._bytes > self.capacity and self._entries:
                _k, e = self._entries.popitem(last=False)
                self._bytes -= len(e[4])
                evicted += 1
        if evicted:
            READ_CACHE_EVICTIONS.inc(evicted, reason="lru")

    def invalidate_key(self, vid: int, key: int, reason: str = "overwrite") -> None:
        """Drop one needle's entry (overwrite/delete hooks)."""
        with self._lock:
            e = self._entries.pop((vid, key), None)
            if e is not None:
                self._bytes -= len(e[4])
        if e is not None:
            READ_CACHE_EVICTIONS.inc(reason=reason)

    def invalidate_volume(self, vid: int, reason: str = "vacuum") -> int:
        """Drop every entry of a volume (vacuum-commit swap, repair
        recopy, unmount); returns how many entries were dropped."""
        with self._lock:
            doomed = [k for k in self._entries if k[0] == vid]
            for k in doomed:
                self._bytes -= len(self._entries.pop(k)[4])
        if doomed:
            READ_CACHE_EVICTIONS.inc(len(doomed), reason=reason)
        return len(doomed)

    def stats(self) -> dict:
        with self._lock:
            out = {"entries": len(self._entries), "bytes": self._bytes}
        out["hits"] = self.hits
        out["misses"] = self.misses
        total = self.hits + self.misses
        out["hit_rate"] = round(self.hits / total, 4) if total else 0.0
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


def _parse_fid_path_cached(path: str):
    """Pure fid-path parse, memoized for hot paths: serving re-reads the
    same fids, and the split/rpartition/FileId.parse chain is a measurable
    slice of a ~60µs request (FileId is frozen, so sharing is safe). Long
    paths bypass the cache — keys are attacker-controlled pre-auth, so an
    unbounded-length key would let 64KB request lines pin gigabytes."""
    if len(path) > 96:
        return _parse_fid_path_impl(path)
    return _parse_fid_path_lru(path)


@_functools.lru_cache(maxsize=131072)
def _parse_fid_path_lru(path: str):
    return _parse_fid_path_impl(path)


def _parse_fid_path_impl(path: str):
    parts = path.lstrip("/").split("/")
    fid_part = parts[0]
    if "," not in fid_part and len(parts) > 1:
        # /vid/fid[/filename] form
        fid_part = parts[0] + "," + parts[1]
        filename = parts[2] if len(parts) > 2 else ""
    else:
        filename = parts[1] if len(parts) > 1 else ""
    ext = ""
    if "." in fid_part:
        fid_part, _, tail = fid_part.rpartition(".")
        ext = "." + tail
    if not ext and "." in filename:
        ext = "." + filename.rsplit(".", 1)[1]
    return FileId.parse(fid_part), filename, ext


def _decode_keys(req: dict):
    """BulkLookup/BatchRead probe keys: <u8-LE bytes or list[int] -> u64[P]."""
    import numpy as np

    raw = req.get("keys", b"")
    if isinstance(raw, (bytes, bytearray)):
        return np.frombuffer(raw, dtype="<u8").astype(np.uint64)
    return np.asarray(raw, dtype=np.uint64)


def _make_needle_map_debug(store, arena=None, gate=None):
    """/debug/needle_map handler: per-volume + aggregate bloom-sidecar
    economics (LsmNeedleMap.bloom_stats) for every live volume whose map
    kind carries filters, plus — when the arena backend is on — the
    DeviceColumnArena's residency/eviction/dispatch stats and the gate's
    device-vs-fallback counters (the soak harness scrapes this to prove
    host fallback from OUTSIDE the process). Plain closures over leaf
    state, never the server object (cycle warning on
    serving_core._make_debug_middleware)."""

    async def handler(request):
        per_volume = {}
        agg = {"runs": 0, "runs_with_filter": 0, "probes": 0,
               "negatives": 0}
        for loc in store.locations:
            for vid, v in list(loc.volumes.items()):
                stats_fn = getattr(v.nm, "bloom_stats", None)
                if stats_fn is None:
                    continue
                st = stats_fn()
                per_volume[str(vid)] = st
                for k in agg:
                    agg[k] += st.get(k, 0)
        agg["filter_hit_rate"] = (
            round(agg["negatives"] / agg["probes"], 4)
            if agg["probes"] else 0.0
        )
        body = {
            "kind": store.needle_map_kind,
            "aggregate": agg,
            "volumes": per_volume,
        }
        if arena is not None:
            body["device"] = arena.stats()
        if gate is not None:
            body["gate"] = dict(gate.stats)
        return web.json_response(body)

    return handler


class VolumeServer(EcHandlers):
    def __init__(
        self,
        master: str,
        directories: list[str],
        host: str = "127.0.0.1",
        port: int = 8080,
        public_url: str = "",
        max_volume_counts: Optional[list[int]] = None,
        pulse_seconds: float = 1.0,
        data_center: str = "",
        rack: str = "",
        codec_backend: str = "cpu",
        jwt_signing_key: str = "",
        needle_map_kind: str = "memory",
        pprof: bool = False,
        white_list: tuple = (),
        batch_lookup: str = "off",
    ):
        self.jwt_signing_key = jwt_signing_key
        self.pprof = pprof
        from ..util.security import Guard

        # one guard for writes/deletes (ref guard.go wraps the public mux's
        # Post/Delete handlers, volume_server.go:74-90)
        self.guard = Guard(
            white_list=tuple(white_list), signing_key=jwt_signing_key
        )
        # seed master list with failover + leader-hint following
        # (ref volume_grpc_client_to_master.go:35-57)
        self.masters = [master] if isinstance(master, str) else list(master)
        self.master = self.masters[0]
        self.host = host
        self.port = port
        self.address = f"{host}:{port}"
        self.public_url = public_url or self.address
        self.pulse_seconds = pulse_seconds
        self.data_center = data_center
        self.rack = rack
        self.codec_backend = codec_backend
        self.store = Store(
            host,
            port,
            self.public_url,
            directories,
            max_volume_counts or [7] * len(directories),
            needle_map_kind=needle_map_kind,
        )
        self.store.load()
        mark_startup("store_load")
        self._http_runner: Optional[web.AppRunner] = None
        self._grpc_server = None
        self._heartbeat_task: Optional[asyncio.Task] = None
        self._http_client: Optional[aiohttp.ClientSession] = None
        self._shutdown = False
        self._codec = None
        # anti-entropy plane: background scrubber (rate-shaped by
        # SEAWEEDFS_TPU_SCRUB_MBPS; 0 = no background pass, scrubs run
        # only when forced via VolumeScrub / the volume.scrub command)
        self.scrub_mbps = float(
            os.environ.get("SEAWEEDFS_TPU_SCRUB_MBPS", "0") or 0
        )
        self.scrub_interval_seconds = float(
            os.environ.get("SEAWEEDFS_TPU_SCRUB_INTERVAL", "300") or 300
        )
        self._scrubber = None
        self._scrub_task: Optional[asyncio.Task] = None
        self._group_committers: dict[int, object] = {}
        self._replica_loc_cache: dict[int, tuple[float, list]] = {}
        # cross-request probe batching (north-star #2 serving path):
        # off | auto (bulk_lookup's device policy) | host | device |
        # arena (ISSUE 18: the whole wakeup as ONE ragged dispatch over
        # the HBM-resident column arena, host fallback when cold/absent)
        self.lookup_gate = None
        self.lookup_arena = None
        # what the device planes of THIS process run on (None: they are
        # off, JAX is never imported). `tpu` / `device` / `arena` are
        # requests for the chip and refuse to start without one.
        self.device: Optional[dict] = None
        if codec_backend not in ("cpu", "numpy") or batch_lookup in (
            "auto", "device", "arena",
        ):
            from ..util import device

            asked = {}
            if codec_backend == "tpu":
                asked["storageBackend"] = codec_backend
            if batch_lookup in ("device", "arena"):
                asked["batchLookup"] = batch_lookup
            if asked:
                device.require_chip(**asked)
            self.device = device.describe()
            device.watch_compiles()
            mark_startup("device")
            logger.warning(
                "volume server %s: device planes on platform=%s "
                "device_kind=%r count=%d (storageBackend=%s batchLookup=%s)",
                self.address, self.device["platform"],
                self.device["device_kind"], self.device["count"],
                codec_backend, batch_lookup,
            )
        if batch_lookup == "arena":
            from ..ops.ragged_lookup import get_default_arena
            from .lookup_gate import BatchLookupGate

            self.lookup_arena = get_default_arena()
            self.lookup_gate = BatchLookupGate(
                self.store, arena=self.lookup_arena
            )
        elif batch_lookup not in ("off", "", None):
            from .lookup_gate import BatchLookupGate

            self.lookup_gate = BatchLookupGate(
                self.store,
                use_device={"auto": None, "host": False, "device": True}[
                    batch_lookup
                ],
            )
        # hot-needle read cache (ISSUE 6): whole small responses in front
        # of the volume tier, byte-bounded by SEAWEEDFS_TPU_READ_CACHE_MB
        # (0 disables); correctness comes from the per-hit map validation,
        # not from the env default
        self.read_cache = (
            HotNeedleCache() if READ_CACHE_BYTES_CAP > 0 else None
        )
        # read-path stage attribution, pre-bound (tuple(sorted(labels))
        # per request was measurable at write QPS; reads are hotter)
        self._stage_cache_hit = READ_STAGE_SECONDS.child(stage="cache_hit")
        self._stage_read_render = READ_STAGE_SECONDS.child(
            stage="read_render"
        )
        self._stage_ec_read = READ_STAGE_SECONDS.child(stage="ec_read")
        mark_startup("index_build")

    def _group_committer(self, vid: int):
        gc = self._group_committers.get(vid)
        if gc is None:
            from ..storage.group_commit import GroupCommitWorker

            v = self.store.find_volume(vid)
            gc = GroupCommitWorker(v)
            gc.start()
            self._group_committers[vid] = gc
        return gc

    @property
    def codec(self):
        if self._codec is None:
            from ..tpu.coder import get_codec

            self._codec = get_codec(self.codec_backend)
        return self._codec

    # ---------------- lifecycle ----------------
    async def start(self) -> None:
        from ..util.http_timeouts import client_timeout

        self._http_client = aiohttp.ClientSession(timeout=client_timeout())
        app = web.Application(client_max_size=256 << 20)
        app.router.add_route("*", "/{tail:.*}", self._dispatch)
        # shared serving core (server/serving_core.py): full aiohttp
        # surface on an internal loopback port; the public port is owned
        # by the byte-level fast tier, which serves the hot data plane
        # itself and transparently proxies everything else (the
        # reference's thin Go handler loop equivalent,
        # volume_server_handlers_read.go)
        from .serving_core import ServingCore

        # pprof honors the ctor/-pprof opt-in: True forces the HTTP
        # profiling surface on, the default False falls back to the
        # SEAWEEDFS_TPU_PPROF env gate like every other server type
        self._core = ServingCore(
            "volume", self._fast_dispatch, self.host, self.port,
            pprof=True if self.pprof else None,
            tenant_fn=self._tenant_fn,
            # bloom-sidecar economics per live volume (closes over the
            # store, not the server — see ServingCore.debug_handlers):
            # multi-run LSM maps appear under sustained load, and the
            # soak harness scrapes this to disclose sidecar hit rates
            # from OUTSIDE the process
            debug_handlers={
                "/debug/needle_map": _make_needle_map_debug(
                    self.store,
                    arena=self.lookup_arena,
                    gate=self.lookup_gate,
                )
            },
        )
        await self._core.start(app)
        self._fast_server = self._core.fast_server
        self._http_runner = self._core._http_runner

        # the gRPC surface shares the HTTP gate's per-tenant quota
        # buckets: message bytes bill the same TenantQuota (ISSUE 13)
        svc = Service("volume", gate=self._core.gate)
        svc.unary("AllocateVolume")(self._grpc_allocate_volume)
        svc.unary("VolumeMount")(self._grpc_volume_mount)
        svc.unary("VolumeUnmount")(self._grpc_volume_unmount)
        svc.unary("VolumeDelete")(self._grpc_volume_delete)
        svc.unary("VolumeMarkReadonly")(self._grpc_volume_mark_readonly)
        svc.unary("VolumeMarkWritable")(self._grpc_volume_mark_writable)
        svc.unary("VolumeLifecycleCheck")(self._grpc_lifecycle_check)
        svc.unary("VolumeConfigure")(self._grpc_volume_configure)
        svc.unary("DeleteCollection")(self._grpc_delete_collection)
        svc.unary("VacuumVolumeCheck")(self._grpc_vacuum_check)
        svc.unary("VacuumVolumeCompact")(self._grpc_vacuum_compact)
        svc.unary("VacuumVolumeCommit")(self._grpc_vacuum_commit)
        svc.unary("VacuumVolumeCleanup")(self._grpc_vacuum_cleanup)
        svc.unary("BatchDelete")(self._grpc_batch_delete)
        svc.unary("BulkLookup")(self._grpc_bulk_lookup)
        svc.server_stream("BatchRead")(self._grpc_batch_read)
        svc.unary("VolumeServerStatus")(self._grpc_status)
        svc.server_stream("CopyFile")(self._grpc_copy_file)
        svc.unary("VolumeCopy")(self._grpc_volume_copy)
        svc.server_stream("VolumeIncrementalCopy")(self._grpc_incremental_copy)
        svc.unary("VolumeSyncStatus")(self._grpc_sync_status)
        svc.unary("VolumeScrub")(self._grpc_volume_scrub)
        svc.unary("VolumeTailSync")(self._grpc_volume_tail_sync)
        svc.unary("VolumeRepairCopy")(self._grpc_volume_repair_copy)
        svc.server_stream("Query")(self._grpc_query)
        svc.server_stream("VolumeTierMoveDatToRemote")(self._grpc_tier_to_remote)
        svc.server_stream("VolumeTierMoveDatFromRemote")(
            self._grpc_tier_from_remote
        )
        svc.unary("VolumeTierManifestKeys")(self._grpc_tier_manifest_keys)
        self.register_ec_rpcs(svc)
        self._grpc_server = await serve(grpc_address(self.address), svc)

        self._heartbeat_task = asyncio.ensure_future(self._heartbeat_loop())
        if self.scrub_mbps > 0:
            self._scrub_task = asyncio.ensure_future(self._scrub_loop())
        mark_startup("listening")
        logger.warning(
            "volume server %s ready: seconds since process start %s",
            self.address, startup_line(),
        )

    async def stop(self) -> None:
        self._shutdown = True
        if self._scrub_task is not None:
            self._scrub_task.cancel()
            try:
                await self._scrub_task
            except (asyncio.CancelledError, Exception):
                pass
        if self.lookup_gate is not None:
            self.lookup_gate.close()
        for gc in self._group_committers.values():
            await gc.stop()
        if self._heartbeat_task is not None:
            self._heartbeat_task.cancel()
            try:
                await self._heartbeat_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._grpc_server is not None:
            await self._grpc_server.stop(0.5)
        if getattr(self, "_fast_server", None) is not None:
            await self._fast_server.stop()
        if self._http_runner is not None:
            await self._http_runner.cleanup()
        if self._http_client is not None:
            await self._http_client.close()
        self.store.close()

    # ---------------- heartbeat (ref volume_grpc_client_to_master.go) ----------------
    async def _heartbeat_loop(self) -> None:
        while not self._shutdown:
            try:
                await self._heartbeat_once()
                # stream ended cleanly (e.g. follower redirect already
                # switched self.master) — redial after a pulse
                await asyncio.sleep(self.pulse_seconds / 2)
            except asyncio.CancelledError:
                return
            except Exception:
                # current master unreachable: rotate through the seed list
                # (ref volume_grpc_client_to_master.go master failover)
                if self.master in self.masters:
                    i = self.masters.index(self.master)
                    self.master = self.masters[(i + 1) % len(self.masters)]
                else:
                    self.master = self.masters[0]
                await asyncio.sleep(self.pulse_seconds)

    async def _heartbeat_once(self) -> None:
        import grpc

        stub = Stub(grpc_address(self.master), "master")
        call = stub.bidi_stream("SendHeartbeat")

        # responses are drained by a dedicated task: wrapping call.read() in
        # wait_for would CANCEL the whole RPC on timeout and tear the stream
        # down every quiet pulse
        async def reader() -> None:
            while True:
                resp = await call.read()
                if resp is grpc.aio.EOF or resp is None:
                    return
                if not isinstance(resp, dict):
                    continue
                if resp.get("volume_size_limit"):
                    self.store.volume_size_limit = int(resp["volume_size_limit"])
                if resp.get("storage_backends"):
                    # cold-tier backends pushed by the master (ISSUE 15
                    # satellite): register them locally so offload/
                    # recall/remote reads work with no per-process
                    # env/registry wiring (ref backend.go:77-95)
                    from ..storage.tier_backend import (
                        load_from_pb_storage_backends,
                    )

                    load_from_pb_storage_backends(
                        resp["storage_backends"]
                    )
                if "leader" in resp:
                    leader = resp.get("leader")
                    if leader and leader != self.master:
                        # follow the leader hint; the redial targets it
                        if leader not in self.masters:
                            self.masters.append(leader)
                        self.master = leader
                        return
                    if not leader:
                        # this master has no known leader (deposed or
                        # mid-election): rotate instead of re-dialing it
                        if self.master in self.masters:
                            i = self.masters.index(self.master)
                            self.master = self.masters[
                                (i + 1) % len(self.masters)
                            ]
                        return

        reader_task = asyncio.ensure_future(reader())
        try:
            hb = self.store.collect_heartbeat()
            hb["data_center"] = self.data_center
            hb["rack"] = self.rack
            hb.update(self.store.collect_ec_heartbeat())
            await call.write(hb)
            tick = 0
            while not self._shutdown:
                await asyncio.sleep(self.pulse_seconds)
                if reader_task.done():
                    break  # master closed the stream; reconnect
                tick += 1
                deltas = self.store.drain_deltas()
                hb = {"ip": self.host, "port": self.port}
                if any(deltas.values()):
                    hb.update({k: v for k, v in deltas.items() if v})
                if tick % 17 == 0:
                    # periodic full EC state (ref :121 — EC tick = 17 x pulse)
                    hb.update(self.store.collect_ec_heartbeat())
                if tick % 5 == 0:
                    # anti-entropy tick: slim digest/frontier refresh so the
                    # master compares CURRENT replica digests, not the ones
                    # frozen at stream connect (our extension)
                    hb["volume_digests"] = self.store.collect_volume_digests()
                    # lifecycle tick: EC read heat rides the same pulse so
                    # the re-inflation planner sees warm volumes turning
                    # hot within seconds, not at the ~17-tick EC refresh
                    hb["ec_heat"] = self.store.collect_ec_heat()
                await call.write(hb)
        finally:
            reader_task.cancel()
            try:
                call.cancel()
            except Exception:
                pass

    # ------------- fast-tier HTTP dispatch (server/serving_core.py) -------------
    def _tenant_fn(self, req):
        """Tenant principal for admission (ISSUE 12): the explicit
        header / collection query param first (the shared derivation —
        in-cluster hops from the filer carry the gateway's principal in
        the header), else the data-plane path's vid maps to the mounted
        volume's collection, so raw-tier reads of a tenant collection
        are attributed without the client saying anything."""
        t = tenancy.tenant_from_request(req)
        if t is not None:
            return t
        p = req.path
        comma = p.find(",")
        if comma > 1:
            try:
                vid = int(p[1:comma])
            except ValueError:
                return None
            v = self.store.find_volume(vid)
            if v is not None and v.collection:
                return v.collection
        return None

    async def _fast_dispatch(self, req):
        """Byte-level hot handlers for the data plane. Any request shape
        outside the fully-understood fast cases returns FALLBACK, which the
        protocol replays against the internal aiohttp app — semantics can
        never diverge, the fast tier only short-circuits what it completely
        covers. Reads may fall back at ANY point (no side effects); writes
        only before the needle append. A read of a plain volume and of an
        EC volume mounted here (healthy or degraded) is answered here;
        queries, ranges, manifests, compressed needles, tiered volumes and
        volumes held elsewhere go to the aiohttp app. Counting and the
        server-side fault seam live in the shared ServingCore; DETACHED
        responses count at their completion callback via _count_fast so a
        gated read that proxies to the full app is never double-counted."""
        method = req.method
        if method in ("GET", "HEAD"):
            return await self._fast_read(req)
        if method in ("POST", "PUT"):
            if req.path == "/!batch/put":
                return await self._fast_batch_put(req)
            return self._fast_write(req)
        return FALLBACK

    def _count_fast(self, method: str) -> None:
        self._core.count(method)

    async def _fast_read(self, req):
        if req.query or not req.path or req.path == "/" or "debug" in req.path:
            return FALLBACK
        head_only = req.method == "HEAD"
        h = req.headers
        if b"range" in h or b"if-range" in h:
            return FALLBACK
        try:
            fid, _filename, ext = self._parse_fid_path(req.path)
        except Exception:
            return FALLBACK  # /status, /ui, /metrics, bad fids...
        vid = fid.volume_id
        v = self.store.find_volume(vid)
        if v is None:
            ev = self.store.find_ec_volume(vid)
            if ev is None:
                return FALLBACK  # not here: the redirect through the master
            return await self._fast_read_ec(ev, fid, head_only)
        if v.has_remote_file:
            return FALLBACK  # tiered: blocking remote I/O, off the loop
        t0 = time.perf_counter()
        cache = self.read_cache
        if cache is not None:
            out = cache.get(v, vid, fid.key, fid.cookie, head_only)
            if out is not None:
                self._stage_cache_hit.observe(time.perf_counter() - t0)
                return out
        if self.lookup_gate is not None:
            # batched serving path (north-star #2): the index probe joins
            # the gate's micro-batch, and the WHOLE continuation (pread ->
            # render -> socket write) runs inside the flush callback — a
            # batch of N coalesced reads costs one event-loop callback,
            # zero per-request task resumes (DETACHED protocol mode)
            def done(loc, exc) -> None:
                out = self._render_gated(v, vid, fid, head_only, loc, exc)
                if out is None:  # complex needle: full app takes over
                    finish_detached_proxy(self._fast_server, req)
                else:
                    # gated misses are read_render too: gate wait + probe
                    # + pread + render, wall from request entry
                    self._stage_read_render.observe(
                        time.perf_counter() - t0
                    )
                    self._count_fast(req.method)
                    finish_detached(req, out)

            self.lookup_gate.lookup_cb(vid, fid.key, done)
            return DETACHED
        try:
            # direct volume read: v is already resolved, and the by-key
            # form skips the shell-needle + per-field merge of read_needle
            n, off_units, size = v.read_needle_by_key_located(fid.key)
        except (NotFound, NotFoundError, AlreadyDeleted, LookupError):
            return render_response(
                404, b'{"error": "not found"}', head_only=head_only
            )
        except Exception:
            return FALLBACK
        out = self._render_needle(n, fid, head_only)
        if out is _NEEDS_FULL_APP:
            return FALLBACK
        self._maybe_cache_fill(
            cache, v, vid, fid, n, off_units, size, out, head_only
        )
        self._stage_read_render.observe(time.perf_counter() - t0)
        return out

    async def _fast_read_ec(self, ev, fid, head_only):
        """A read of a locally mounted EC volume, healthy or degraded: the
        coroutine the aiohttp handler awaits, rendered as a plain volume's
        needle is. Whatever it raises goes to the aiohttp tier, which
        decides every status code the fast tier does not (a read has no
        side effect to repeat)."""
        t0 = time.perf_counter()
        try:
            n = await self.read_ec_needle(ev, fid.key)
        except Exception:
            return FALLBACK
        if n is None:
            out = render_response(
                404, b'{"error": "not found"}', head_only=head_only
            )
        else:
            out = self._render_needle(n, fid, head_only)
            if out is _NEEDS_FULL_APP:
                return FALLBACK
        self._stage_ec_read.observe(time.perf_counter() - t0)
        return out

    def _maybe_cache_fill(
        self, cache, v, vid, fid, n, off_units, size, out, head_only
    ) -> None:
        """Admit a just-rendered simple-shape GET response into the
        hot-needle cache. `out` must be the pre-rendered head + raw body
        join `_render_needle` produces for the no-Last-Modified shape;
        anything else (HEAD, TTL'd, cookie-mismatch 404s) is skipped."""
        if (
            cache is None
            or head_only
            or n.last_modified
            or n.cookie != fid.cookie
            or n.is_chunked_manifest()
            or n.is_compressed()
        ):
            return
        cache.put(v, vid, n, off_units, size, out, len(out) - len(n.data))

    def _render_gated(self, v, vid, fid, head_only, loc, exc) -> bytes:
        """Response bytes for a gated read, run inside the gate's flush."""
        try:
            if exc is not None:
                if isinstance(exc, LookupError):
                    return render_response(
                        404, b'{"error": "not found"}', head_only=head_only
                    )
                return render_response(
                    500, b'{"error": "lookup failed"}', head_only=head_only
                )
            if loc is None:
                return render_response(
                    404, b'{"error": "not found"}', head_only=head_only
                )
            offset_units, size = loc
            n = Needle(id=fid.key)
            stale = False
            try:
                if size > 0:
                    n = v.read_needle_at(offset_units, size)
                stale = size > 0 and n.cookie != fid.cookie
            except Exception:
                stale = True
            if stale:
                # vacuum may have rewritten the .dat between probe and
                # pread; the locked per-request path is atomic
                n = Needle(id=fid.key)
                self.store.read_volume_needle(vid, n)
            out = self._render_needle(n, fid, head_only)
            if out is _NEEDS_FULL_APP:
                return None
            if not stale:
                self._maybe_cache_fill(
                    self.read_cache, v, vid, fid, n, offset_units, size,
                    out, head_only,
                )
            return out
        except (NotFound, NotFoundError, AlreadyDeleted, LookupError):
            return render_response(
                404, b'{"error": "not found"}', head_only=head_only
            )
        except Exception:
            return render_response(
                500, b'{"error": "internal error"}', head_only=head_only
            )

    # the module-level pre-assembled head (see _HEAD_200 above)
    _HEAD_200 = _HEAD_200

    def _render_needle(self, n, fid, head_only):
        if n.cookie != fid.cookie:
            return render_response(
                404, b'{"error": "cookie mismatch"}',
                head_only=head_only,
            )
        if n.is_chunked_manifest() or n.is_compressed():
            # manifest resolution / content negotiation: full app territory
            return _NEEDS_FULL_APP
        ctype = bytes(n.mime) if n.mime else b"application/octet-stream"
        if not n.last_modified:
            head = self._HEAD_200 % (
                ctype, len(n.data), n.checksum & 0xFFFFFFFF
            )
            # n.data is a zero-copy view into the pread blob; the join is
            # the single copy that assembles the wire bytes
            return head if head_only else b"".join((head, n.data))
        extra = b'Etag: "%s"\r\nAccept-Ranges: bytes\r\n' % n.etag().encode()
        extra += b"Last-Modified-Ts: %d\r\n" % n.last_modified
        return render_response(
            200, n.data, content_type=ctype, extra=extra,
            head_only=head_only,
        )

    def _fast_write(self, req):
        if req.query:
            return FALLBACK  # ts/ttl/cm/fsync/type=replicate...
        try:
            fid, _, _ = self._parse_fid_path(req.path)
        except Exception:
            return FALLBACK
        if not self.guard.check_whitelist(req.peer):
            return FALLBACK  # replicate-membership exemption lives there
        if self.jwt_signing_key:
            auth = req.headers.get(b"authorization", b"").decode("latin1")
            if not self.guard.check_jwt(auth, str(fid)):
                return render_response(401, b'{"error": "unauthorized"}')
        vid = fid.volume_id
        v = self.store.find_volume(vid)
        if v is None:
            if self.store.has_volume(vid):
                return FALLBACK
            return render_response(
                404, (b'{"error": "volume %d not found"}' % vid)
            )
        if v.super_block.replica_placement.copy_count() > 1:
            return FALLBACK  # synchronous replication fan-out
        ct = req.headers.get(b"content-type", b"")
        if ct.startswith(b"multipart/form-data"):
            parsed = parse_multipart(req.body, ct)
            if parsed is None:
                return FALLBACK
            data, filename, mime = parsed
        else:
            # multipart-free POST/PUT body: the raw request body IS the
            # payload — handed to the needle append without a copy
            data, filename, mime = req.body, "", ct.decode("latin1")
        # zero-copy handoff: `data` is the request body (bytes) or a
        # memoryview into it (multipart part); the append serializer
        # writes straight from the buffer
        n = Needle(cookie=fid.cookie, id=fid.key, data=data)
        if filename:
            n.set_name(filename.encode())
        if mime and mime != "application/octet-stream":
            n.set_mime(mime.encode())
        import json as _json

        try:
            _off, size, _unchanged = self.store.write_volume_needle(vid, n)
        except Exception as e:
            # the append may or may not have landed: NEVER fall back (a
            # replay could double-write); report like the slow path does
            return render_response(
                500, _json.dumps({"error": str(e)}).encode()
            )
        if self.read_cache is not None:
            self.read_cache.invalidate_key(vid, fid.key, "overwrite")
        if filename and (
            '"' in filename or "\\" in filename or not filename.isprintable()
        ):
            body = _json.dumps(
                {"name": filename, "size": size, "eTag": n.etag()}
            ).encode()
        else:
            # common case: filename needs no JSON escaping, eTag is hex —
            # dumps() was measurable at write QPS rates
            body = (
                '{"name": "%s", "size": %d, "eTag": "%s"}'
                % (filename, size, n.etag())
            ).encode()
        return render_response(201, body)

    async def _fast_batch_put(self, req):
        """Batched multipart-free chunk PUT (POST /!batch/put): one
        request appends N needles — the write-side sibling of
        BatchLookupGate/BatchDelete, fed by the filer's chunk-upload
        gate so concurrent gateway PUTs amortize the per-request HTTP
        machinery instead of paying a full hop per chunk.

        Plain frame: [u32 count] then per item [u16 fid_len]
        [u32 body_len][fid][body]. Tenant-tagged frame (high bit of the
        count word, ISSUE 13): per item [u16 fid_len][u16 tenant_len]
        [u32 body_len][fid][tenant][body] — each member's bytes are
        re-attributed to its OWN principal (quota + heat) instead of
        whichever request scheduled the filer's flush. Bodies are
        handed to the needle append as memoryviews into the request
        body (zero-copy).

        The per-volume groups append through the GROUP-COMMIT worker as
        whole frames: each frame lands as ONE coalesced .dat extent +
        ONE .idx extent (Volume.write_needle_batch) inside a shared
        fsync batch — two pwrites + an amortized fsync per frame, not
        two pwrites per needle (the ~265µs/needle syscall floor that
        capped the 1M-key soak).

        Response: JSON list of {"f": fid, "s": size, "e": etag} or
        {"f": fid, "err": reason} — items this server can't serve on
        the fast path (missing volume, replicated placement, member
        over byte quota) report per-item errors and the CLIENT retries
        them through the single-needle path, so semantics never
        diverge."""
        import json as _json
        import struct as _struct

        if not self.guard.check_whitelist(req.peer):
            return render_response(403, b'{"error": "forbidden"}')
        if self.jwt_signing_key:
            # per-item tokens can't ride one batch request: the filer
            # never batches when the master signs uploads, and a stray
            # batch against a signing server must not bypass auth
            return render_response(401, b'{"error": "unauthorized"}')
        body = req.body
        mv = memoryview(body)
        out: list = []
        gate = self._core.gate if self._core is not None else None
        carrier = tenancy.current()
        # vid -> (group committer input) [(out_idx, fid, needle)]
        groups: dict[int, list] = {}
        try:
            (word,) = _struct.unpack_from("<I", body, 0)
            tagged = bool(word & 0x80000000)
            count = word & 0x7FFFFFFF
            pos = 4
            if count > 4096:
                raise ValueError("batch too large")
            for _ in range(count):
                if tagged:
                    fl, tl, bl = _struct.unpack_from("<HHI", body, pos)
                    pos += 8
                else:
                    fl, bl = _struct.unpack_from("<HI", body, pos)
                    tl = 0
                    pos += 6
                fid_s = bytes(mv[pos : pos + fl]).decode("latin1")
                pos += fl
                tenant = (
                    bytes(mv[pos : pos + tl]).decode("utf-8") or None
                    if tl
                    else None
                )
                pos += tl
                if pos + bl > len(body):
                    raise ValueError("truncated batch frame")
                payload = mv[pos : pos + bl]
                pos += bl
                slot = len(out)
                out.append({"f": fid_s, "err": "unprocessed"})
                try:
                    fid = FileId.parse(fid_s)
                    vid = fid.volume_id
                    v = self.store.find_volume(vid)
                    if v is None:
                        out[slot]["err"] = "no volume"
                        continue
                    if v.super_block.replica_placement.copy_count() > 1:
                        # replication fan-out is the aiohttp single
                        # path's job; the client retries item-wise
                        out[slot]["err"] = "replicated"
                        continue
                    if v.is_read_only():
                        out[slot]["err"] = "read only"
                        continue
                    # normalized compare: an item explicitly tagged
                    # "default" against a None carrier is the SAME
                    # principal — re-attributing it would charge the
                    # default bucket twice (admission + member) with
                    # the refund skipped as a self-transfer
                    if (
                        gate is not None
                        and tenant is not None
                        and (tenant or tenancy.DEFAULT_TENANT)
                        != (carrier or tenancy.DEFAULT_TENANT)
                        and not gate.charge_member_bytes(
                            tenant, bl, carrier=carrier
                        )
                    ):
                        # member over ITS byte quota: decline item-wise;
                        # the retry runs under the member's principal
                        out[slot]["err"] = "quota"
                        continue
                    n = Needle(
                        cookie=fid.cookie, id=fid.key, data=payload
                    )
                    groups.setdefault(vid, []).append((slot, fid, n))
                except Exception as e:
                    out[slot]["err"] = str(e)
        except Exception:
            return render_response(400, b'{"error": "bad batch frame"}')

        async def _write_group(vid: int, members: list) -> None:
            gc = self._group_committer(vid)
            try:
                results = await gc.write_many([n for _s, _f, n in members])
            except Exception as e:
                for slot, _fid, _n in members:
                    out[slot] = {"f": out[slot]["f"], "err": str(e)}
                return
            for (slot, fid, n), res in zip(members, results):
                if isinstance(res, Exception):
                    out[slot] = {"f": out[slot]["f"], "err": str(res)}
                    continue
                _off, size, _unchanged = res
                if self.read_cache is not None:
                    self.read_cache.invalidate_key(
                        vid, fid.key, "overwrite"
                    )
                out[slot] = {"f": out[slot]["f"], "s": size, "e": n.etag()}

        if groups:
            await asyncio.gather(
                *(_write_group(vid, m) for vid, m in groups.items())
            )
        CHUNK_BATCH_PUT_SIZE.observe(count)
        return render_response(200, _json.dumps(out).encode())

    # ---------------- HTTP dispatch ----------------
    async def _dispatch(self, request: web.Request) -> web.StreamResponse:
        import time as _time

        from ..util.metrics import REQUEST_COUNTER, REQUEST_HISTOGRAM

        path = request.path
        if path == "/status":
            return web.json_response(
                {
                    "Version": "seaweedfs-tpu",
                    "Volumes": [],
                    "Device": self.device,
                }
            )
        if path in ("/ui", "/ui/"):
            return self._ui_response()
        # /metrics and /debug/pprof (ref -pprof, util/grace/pprof.go) are
        # served by the shared ServingCore middleware before any route —
        # handlers here would be unreachable shadows
        t0 = _time.perf_counter()
        try:
            return await self._dispatch_inner(request)
        finally:
            REQUEST_COUNTER.inc(server="volume", operation=request.method)
            REQUEST_HISTOGRAM.observe(
                _time.perf_counter() - t0, server="volume", operation=request.method
            )

    def _ui_response(self) -> web.Response:
        """Minimal HTML status page (ref: weed/server/volume_server_ui/)."""
        from html import escape

        vol_rows = []
        ec_rows = []
        for loc in self.store.locations:
            for v in loc.volumes.values():
                # collection names are client-supplied — escape them
                vol_rows.append(
                    f"<tr><td>{v.id}</td>"
                    f"<td>{escape(v.collection) or '-'}</td>"
                    f"<td>{v.data_file_size():,}</td><td>{v.file_count()}</td>"
                    f"<td>{v.deleted_count()}</td>"
                    f"<td>{'ro' if v.is_read_only() else 'rw'}</td>"
                    f"<td>{escape(loc.directory)}</td></tr>"
                )
            for vid, ev in loc.ec_volumes.items():
                ec_rows.append(
                    f"<tr><td>{vid}</td><td>{escape(ev.collection) or '-'}</td>"
                    f"<td>{ev.shard_ids()}</td>"
                    f"<td>{ev.data_shards}.{ev.parity_shards}</td></tr>"
                )
        html = f"""<!doctype html><html><head><title>seaweedfs-tpu volume</title>
<style>body{{font-family:sans-serif;margin:2em}}table{{border-collapse:collapse;margin-bottom:1.5em}}
td,th{{border:1px solid #ccc;padding:4px 10px}}</style></head><body>
<h1>seaweedfs-tpu volume server {self.address}</h1>
<p>master: {escape(self.master)} &middot; rack: {escape(self.rack) or "-"} &middot;
dc: {escape(self.data_center) or "-"} &middot; codec: {self.codec_backend}</p>
<table><tr><th>volume</th><th>collection</th><th>size</th><th>files</th>
<th>deleted</th><th>mode</th><th>dir</th></tr>{"".join(vol_rows)}</table>
<table><tr><th>ec volume</th><th>collection</th><th>local shards</th>
<th>geometry</th></tr>{"".join(ec_rows)}</table>
<p><a href="/metrics">/metrics</a></p></body></html>"""
        return web.Response(text=html, content_type="text/html")

    async def _dispatch_inner(self, request: web.Request) -> web.StreamResponse:
        try:
            if request.method in ("GET", "HEAD"):
                return await self._handle_read(request)
            if request.method in ("POST", "PUT"):
                return await self._handle_write(request)
            if request.method == "DELETE":
                return await self._handle_delete(request)
        except (NotFound, NotFoundError, AlreadyDeleted, LookupError) as e:
            return web.json_response({"error": str(e)}, status=404)
        except ValueError as e:
            # unparsable file id (ref volume_server_handlers_read.go:35-39)
            return web.json_response({"error": str(e)}, status=400)
        except CookieMismatch as e:
            return web.json_response({"error": str(e)}, status=403)
        return web.json_response({"error": "method not allowed"}, status=405)

    def _parse_fid_path(self, path: str) -> tuple[FileId, str, str]:
        return _parse_fid_path_cached(path)

    # ---------------- read (ref volume_server_handlers_read.go) ----------------
    async def _handle_read(self, request: web.Request) -> web.StreamResponse:
        fid, _filename, ext = self._parse_fid_path(request.path)
        vid = fid.volume_id

        if self.store.has_volume(vid):
            n = Needle(id=fid.key)
            v = self.store.find_volume(vid)
            gated = (
                self.lookup_gate is not None
                and v is not None
                and not v.has_remote_file
            )
            if gated:
                # batched serving path: the index probe joins the gate's
                # current micro-batch (one vectorized bulk_lookup for all
                # concurrent requests) and only the pread stays per-request
                loc = await self.lookup_gate.lookup(vid, fid.key)
                if loc is None:
                    return web.json_response(
                        {"error": "not found"}, status=404
                    )
                offset_units, size = loc
                try:
                    if size > 0:
                        n = v.read_needle_at(offset_units, size)
                    stale = size > 0 and n.cookie != fid.cookie
                except Exception:
                    stale = True
                if stale:
                    # a vacuum commit may have rewritten the .dat between
                    # the batched probe and the pread — re-resolve through
                    # the locked per-request path, which is atomic
                    n = Needle(id=fid.key)
                    self.store.read_volume_needle(vid, n)
            elif v is not None and v.has_remote_file:
                # tiered volume: the backend does blocking remote I/O —
                # keep it off the event loop
                await asyncio.get_event_loop().run_in_executor(
                    None, self.store.read_volume_needle, vid, n
                )
            else:
                self.store.read_volume_needle(vid, n)
            if n.cookie != fid.cookie:
                return web.json_response({"error": "cookie mismatch"}, status=404)
            if n.is_chunked_manifest() and request.query.get("cm") != "false":
                return await self._chunked_manifest_response(request, n, ext)
            return self._needle_response(request, n, ext)

        ev = self.store.find_ec_volume(vid)
        if ev is not None:
            n = await self.read_ec_needle(ev, fid.key)
            if n is None:
                return web.json_response({"error": "not found"}, status=404)
            if n.cookie != fid.cookie:
                return web.json_response({"error": "cookie mismatch"}, status=404)
            if n.is_chunked_manifest() and request.query.get("cm") != "false":
                return await self._chunked_manifest_response(request, n, ext)
            return self._needle_response(request, n, ext)

        # not local: redirect via master lookup (ref :41-53)
        result = await self._lookup_volume(vid)
        if result:
            url = result[0]
            if url != self.address and url != self.public_url:
                raise web.HTTPMovedPermanently(
                    location=f"http://{url}{request.path_qs}"
                )
        return web.json_response({"error": "volume not found"}, status=404)

    # ---------------- chunked-file manifests ----------------
    @staticmethod
    def _load_manifest(n: Needle) -> dict:
        """Manifest JSON from a cm-flagged needle
        (ref: operation/chunked_file.go LoadChunkManifest)."""
        import json

        body = bytes(n.data)
        if n.is_compressed():
            import gzip

            body = gzip.decompress(body)
        m = json.loads(body)
        m["chunks"] = sorted(m.get("chunks", []), key=lambda c: c["offset"])
        return m

    async def _fetch_chunk(
        self, fid: str, start: int = 0, end: Optional[int] = None
    ) -> bytes:
        """Bytes [start, end] (inclusive; None = to the end) of one chunk
        needle — local store first, else via master lookup with the range
        forwarded so only the needed slice crosses the network."""
        f = FileId.parse(fid)
        v = self.store.find_volume(f.volume_id)
        if v is not None:
            n = Needle(id=f.key)
            if v.has_remote_file:
                # tiered: blocking remote I/O stays off the event loop
                await asyncio.get_event_loop().run_in_executor(
                    None, self.store.read_volume_needle, f.volume_id, n
                )
            else:
                self.store.read_volume_needle(f.volume_id, n)
            if n.cookie != f.cookie:
                raise LookupError(f"chunk {fid}: cookie mismatch")
            body = bytes(n.data)
            if n.is_compressed():
                import gzip

                body = gzip.decompress(body)
            return body[start : None if end is None else end + 1]
        locs = await self._lookup_volume(f.volume_id)
        if not locs:
            raise LookupError(f"chunk {fid}: volume not found")
        headers = {}
        if start != 0 or end is not None:
            headers["Range"] = f"bytes={start}-{'' if end is None else end}"
        async with self._http_client.get(
            f"http://{locs[0]}/{fid}", headers=headers
        ) as resp:
            if resp.status not in (200, 206):
                raise LookupError(f"chunk {fid}: status {resp.status}")
            body = await resp.read()
            if resp.status == 200 and headers:
                # server ignored the range; slice locally
                body = body[start : None if end is None else end + 1]
            return body

    async def _chunked_manifest_response(
        self, request: web.Request, n: Needle, ext: str = ""
    ) -> web.Response:
        """Resolve a chunk manifest into file bytes, honoring single ranges
        by fetching only the chunks they cover
        (ref: volume_server_handlers_read.go:170-207 tryHandleChunkedFile)."""
        try:
            manifest = self._load_manifest(n)
        except Exception:
            # unreadable manifest: fall back to serving the raw needle
            # (ref tryHandleChunkedFile returns false on load error)
            return self._needle_response(request, n, ext)
        total = int(manifest.get("size", 0))
        content_type = manifest.get("mime") or "application/octet-stream"
        headers = {
            "Accept-Ranges": "bytes",
            "X-File-Store": "chunked",
            "Etag": f'"{n.etag()}"',
        }
        if request.method == "HEAD":
            headers["Content-Length"] = str(total)
            headers["Content-Type"] = content_type
            return web.Response(status=200, headers=headers)

        span = self._parse_range(request.headers.get("Range", ""), total)
        if span == "invalid-range":
            return web.Response(
                status=416, headers={"Content-Range": f"bytes */{total}"}
            )
        start, end = span if span is not None else (0, total - 1)

        # stream chunk by chunk: memory stays bounded by one chunk no
        # matter how large the whole file is
        headers["Content-Type"] = content_type
        headers["Content-Length"] = str(max(end - start + 1, 0))
        if span is not None:
            headers["Content-Range"] = f"bytes {start}-{end}/{total}"
        resp = web.StreamResponse(
            status=206 if span is not None else 200, headers=headers
        )
        await resp.prepare(request)
        for c in manifest["chunks"]:
            c_start, c_size = int(c["offset"]), int(c["size"])
            c_end = c_start + c_size - 1
            if c_end < start or c_start > end:
                continue
            lo = max(start, c_start) - c_start
            hi = min(end, c_end) - c_start
            await resp.write(await self._fetch_chunk(c["fid"], lo, hi))
        await resp.write_eof()
        return resp

    async def _delete_manifest_chunks(self, n: Needle) -> None:
        """Fan out deletes of a manifest's chunk needles
        (ref: volume_server_handlers_write.go DeleteHandler + DeleteChunks)."""
        try:
            manifest = self._load_manifest(n)
        except Exception:
            return
        for c in manifest.get("chunks", []):
            try:
                f = FileId.parse(c["fid"])
                # always go through HTTP DELETE so the owning server's
                # replication fan-out runs (a direct store delete would
                # leave other replicas serving the chunk)
                locs = await self._lookup_volume(f.volume_id)
                if self.address in locs or self.public_url in locs:
                    target = self.address
                elif locs:
                    target = locs[0]
                elif self.store.has_volume(f.volume_id):
                    target = self.address
                else:
                    continue
                headers = {}
                if self.jwt_signing_key:
                    # the cascade is server-initiated: sign its own token
                    from ..util.security import gen_jwt

                    headers["Authorization"] = "Bearer " + gen_jwt(
                        self.jwt_signing_key, 10, c["fid"]
                    )
                async with self._http_client.delete(
                    f"http://{target}/{c['fid']}", headers=headers
                ):
                    pass
            except Exception:
                pass  # best-effort, matching the reference's async delete

    def _needle_response(
        self, request: web.Request, n: Needle, ext: str = ""
    ) -> web.Response:
        headers = {"Etag": f'"{n.etag()}"', "Accept-Ranges": "bytes"}
        if n.last_modified:
            headers["Last-Modified-Ts"] = str(n.last_modified)
        from .. import images

        width, height, mode, do_resize = images.should_resize(
            ext, request.query
        )

        body = bytes(n.data)
        if n.is_compressed():
            accept = request.headers.get("Accept-Encoding", "")
            # resize requires plaintext regardless of what the client
            # accepts (ref volume_server_handlers_read.go:210-238)
            if "gzip" in accept and not do_resize:
                headers["Content-Encoding"] = "gzip"
            else:
                import gzip as _gzip

                body = _gzip.decompress(body)
        content_type = (
            n.mime.decode() if n.mime else "application/octet-stream"
        )

        # on-read image resizing (ref volume_server_handlers_read.go:210-238)
        if do_resize:
            body, _, _ = images.resized(ext, body, width, height, mode)

        if request.method == "HEAD":
            headers["Content-Length"] = str(len(body))
            headers["Content-Type"] = content_type
            return web.Response(status=200, headers=headers)

        # single-range requests (ref writeResponseContent / http.ServeContent);
        # an unparsable Range header is ignored per RFC 9110. Never slice the
        # gzip representation: the ETag is shared with the identity variant,
        # so a ranged gzip body could be spliced into an identity download.
        if headers.get("Content-Encoding"):
            return web.Response(
                body=body, content_type=content_type, headers=headers
            )
        if_range = request.headers.get("If-Range", "")
        if if_range and if_range != headers["Etag"]:
            return web.Response(
                body=body, content_type=content_type, headers=headers
            )
        range_span = self._parse_range(request.headers.get("Range", ""), len(body))
        if range_span == "invalid-range":
            return web.Response(
                status=416,
                headers={"Content-Range": f"bytes */{len(body)}"},
            )
        if range_span is not None:
            start, end = range_span
            headers["Content-Range"] = f"bytes {start}-{end}/{len(body)}"
            return web.Response(
                status=206,
                body=body[start : end + 1],
                content_type=content_type,
                headers=headers,
            )
        return web.Response(body=body, content_type=content_type, headers=headers)

    @staticmethod
    def _parse_range(rng: str, total: int):
        """-> (start, end) | None (serve full body) | "invalid-range" (416)."""
        from ..util.http_range import parse_range

        return parse_range(rng, total)

    # ---------------- write (ref volume_server_handlers_write.go) ----------------
    async def _parse_upload(self, request: web.Request) -> tuple[bytes, str, str]:
        """-> (data, filename, mime)"""
        content_type = request.headers.get("Content-Type", "")
        if content_type.startswith("multipart/form-data"):
            reader = await request.multipart()
            async for part in reader:
                if part.name in ("file", "upload") or part.filename:
                    data = await part.read(decode=False)
                    return (
                        bytes(data),
                        part.filename or "",
                        part.headers.get("Content-Type", ""),
                    )
            return b"", "", ""
        return await request.read(), "", content_type

    async def _check_write_auth(self, request: web.Request, fid: str = ""):
        """Whitelist + JWT gate shared by writes and deletes; replicate
        traffic from registered cluster peers bypasses the whitelist (the
        reference puts replication on a separate admin mux) but never the
        JWT check — the primary forwards the client's token."""
        from ..util.security import real_remote

        remote = real_remote(request)
        if not self.guard.check_whitelist(remote):
            is_replicate = request.query.get("type") == "replicate"
            if not (is_replicate and await self._is_cluster_member(remote)):
                return web.json_response({"error": "forbidden"}, status=403)
        if self.jwt_signing_key:
            if not fid:
                # canonical form so the /vid/fid slash-URL variant compares
                # equal to the comma fid the token was minted for
                try:
                    fid = str(self._parse_fid_path(request.path)[0])
                except ValueError:
                    fid = request.path.lstrip("/").split("/")[0]
            if not self.guard.check_jwt(
                request.headers.get("Authorization", ""), fid
            ):
                return web.json_response({"error": "unauthorized"}, status=401)
        return None

    async def _handle_write(self, request: web.Request) -> web.Response:
        fid, _, _ = self._parse_fid_path(request.path)
        vid = fid.volume_id
        denied = await self._check_write_auth(request, str(fid))
        if denied is not None:
            return denied
        if not self.store.has_volume(vid):
            return web.json_response({"error": f"volume {vid} not found"}, status=404)

        data, filename, mime = await self._parse_upload(request)
        n = Needle(cookie=fid.cookie, id=fid.key, data=data)
        if filename:
            n.set_name(filename.encode())
        if mime and mime != "application/octet-stream":
            n.set_mime(mime.encode())
        ts = request.query.get("ts")
        if ts:
            n.set_last_modified(int(ts))
        ttl = request.query.get("ttl")
        if ttl:
            from ..storage.ttl import TTL

            n.set_ttl(TTL.read(ttl))
        if request.query.get("cm") == "true":
            # chunk manifest upload (ref needle_parse_upload.go:177)
            n.set_is_chunk_manifest()

        is_replicate = request.query.get("type") == "replicate"
        v = self.store.find_volume(vid)
        needs_fanout = (
            not is_replicate
            and v is not None
            and v.super_block.replica_placement.copy_count() > 1
        )
        rep_task = None
        # pipelined fan-out: replica POSTs are launched BEFORE the local
        # append so they overlap the local disk work instead of
        # serializing after it. Durability is unchanged — the 201 ack
        # still requires the local write AND every replica to succeed.
        # Deterministic local-failure preconditions (read-only volume,
        # size ceiling) are checked FIRST via Volume.can_accept: launching
        # the fan-out and then failing locally would land data on healthy
        # replicas the primary never wrote (the residual window is
        # mid-append I/O errors — the mirror image of the pre-existing
        # local-ok/replica-fail window, and equally un-acked).
        if needs_fanout and v.can_accept(len(n.data)):
            rep_task = asyncio.ensure_future(
                self._replicate(request, vid, "POST", await self._raw_body(n))
            )
        t0 = time.perf_counter()
        try:
            if request.query.get("fsync") == "true":
                # group-commit path: one fsync amortized over concurrent
                # writers
                offset, size, unchanged = await self._group_committer(
                    vid
                ).write(n)
            elif rep_task is not None:
                # run the local append off the loop so the replica POSTs
                # actually progress while it runs
                offset, size, unchanged = await asyncio.get_event_loop(
                ).run_in_executor(
                    None, self.store.write_volume_needle, vid, n
                )
            else:
                offset, size, unchanged = self.store.write_volume_needle(
                    vid, n
                )
        except BaseException:
            if rep_task is not None:
                rep_task.cancel()
            raise
        WRITE_STAGE_SECONDS.observe(
            time.perf_counter() - t0, stage="local_append"
        )
        if self.read_cache is not None:
            self.read_cache.invalidate_key(vid, fid.key, "overwrite")
        if rep_task is not None:
            t1 = time.perf_counter()
            err = await rep_task
            WRITE_STAGE_SECONDS.observe(
                time.perf_counter() - t1, stage="replicate_wait"
            )
            if err:
                return web.json_response({"error": err}, status=500)
        return web.json_response(
            {"name": filename, "size": size, "eTag": n.etag()}, status=201
        )

    async def _raw_body(self, n: Needle) -> bytes:
        return bytes(n.data)

    async def _handle_delete(self, request: web.Request) -> web.Response:
        fid, _, _ = self._parse_fid_path(request.path)
        vid = fid.volume_id
        is_replicate = request.query.get("type") == "replicate"
        denied = await self._check_write_auth(request, str(fid))
        if denied is not None:
            return denied

        if self.store.has_volume(vid):
            n = Needle(id=fid.key, cookie=fid.cookie)
            try:
                check = Needle(id=fid.key)
                self.store.read_volume_needle(vid, check)
                if check.cookie != fid.cookie:
                    return web.json_response({"error": "cookie mismatch"}, status=403)
            except (NotFound, AlreadyDeleted):
                return web.json_response({"size": 0}, status=404)
            if check.is_chunked_manifest() and not is_replicate:
                # deleting a manifest also deletes its chunk needles; only
                # the primary fans out, or every replica would re-issue the
                # whole cascade (ref volume_server_handlers_write.go)
                await self._delete_manifest_chunks(check)
            size = self.store.delete_volume_needle(vid, n)
            if self.read_cache is not None:
                self.read_cache.invalidate_key(vid, fid.key, "delete")
            if not is_replicate:
                await self._replicate(request, vid, "DELETE", b"")
            return web.json_response({"size": size}, status=202)

        ev = self.store.find_ec_volume(vid)
        if ev is not None:
            check = await self.read_ec_needle(ev, fid.key)
            if check is not None and check.cookie != fid.cookie:
                return web.json_response({"error": "cookie mismatch"}, status=403)
            if (
                check is not None
                and check.is_chunked_manifest()
                and not is_replicate
            ):
                # manifest on an EC volume still owns its chunk needles
                await self._delete_manifest_chunks(check)
            size = await self.delete_ec_needle(ev, fid.key)
            return web.json_response({"size": size}, status=202)
        return web.json_response({"error": "volume not found"}, status=404)

    async def _is_cluster_member(self, ip: str) -> bool:
        """True when ip belongs to a registered volume server — replicate
        traffic is only exempt from the whitelist for actual cluster peers
        (the reference puts replication on a separate admin port; sharing
        one port here means ?type=replicate must not be a free bypass)."""
        import time as _time

        now = _time.monotonic()
        cache = getattr(self, "_member_ips", None)
        if cache is None or now - cache[0] > 10.0:
            hosts: set[str] = set()
            try:
                stub = Stub(grpc_address(self.master), "master")
                resp = await stub.call("VolumeList", {})
                for dc in resp.get("topology_info", {}).get("data_centers", []):
                    for rack in dc.get("racks", []):
                        for dn in rack.get("data_nodes", []):
                            hosts.add(dn.get("url", "").rsplit(":", 1)[0])
            except Exception:
                if cache is not None:
                    return ip in cache[1]
                return False
            # registered hosts may be DNS names or other-interface
            # addresses — resolve them concurrently with a bound so a slow
            # resolver can't stall the triggering request for long
            ips: set[str] = set(hosts)
            loop = asyncio.get_event_loop()

            async def resolve(host: str) -> None:
                try:
                    infos = await asyncio.wait_for(
                        loop.getaddrinfo(host, None), timeout=2.0
                    )
                    for info in infos:
                        ips.add(info[4][0])
                except (OSError, asyncio.TimeoutError):
                    pass

            await asyncio.gather(*(resolve(h) for h in hosts))
            cache = (now, ips)
            self._member_ips = cache
        return ip in cache[1]

    # ---------------- replication (ref store_replicate.go:20-121) ----------------
    async def _lookup_volume(self, vid: int) -> list[str]:
        """Replica locations for vid, TTL-cached: a master RPC per
        replicated WRITE would put the master on every write's critical
        path (the reference serves this from wdclient's vid cache,
        ref store_replicate.go:100). Short TTL: topology changes
        (fix.replication, volume moves) must be picked up promptly."""
        cached = self._replica_loc_cache.get(vid)
        now = time.monotonic()  # wall-clock steps must not break the TTL
        if cached is not None and now - cached[0] < 2.0:
            return cached[1]
        locations: list[str] = []
        try:
            stub = Stub(grpc_address(self.master), "master")
            resp = await stub.call("LookupVolume", {"volume_ids": [str(vid)]})
            for r in resp.get("volume_id_locations", []):
                if int(r.get("volumeId", "0").split(",")[0]) == vid and r.get(
                    "locations"
                ):
                    locations = [l["url"] for l in r["locations"]]
                    break
        except Exception:
            # master unreachable: serve the stale entry only within a
            # bounded window — beyond it, stale locations would keep
            # routing writes/redirects to servers the volume left
            if cached is not None and now - cached[0] < 30.0:
                return cached[1]
            return []
        if not locations:
            # a transient empty answer (heartbeat lag) must cost one
            # request, not a 2s window of failed replication; empty
            # results are also what bogus client-supplied vids produce,
            # so not caching them keeps the dict scanner-proof
            self._replica_loc_cache.pop(vid, None)
            return []
        if len(self._replica_loc_cache) > 4096:  # runaway-vid backstop
            self._replica_loc_cache.clear()
        self._replica_loc_cache[vid] = (now, locations)
        return locations

    async def _replicate(
        self, request: web.Request, vid: int, method: str, body: bytes
    ) -> str:
        v = self.store.find_volume(vid)
        if v is None or v.super_block.replica_placement.copy_count() <= 1:
            return ""
        locations = await self._lookup_volume(vid)
        others = [u for u in locations if u not in (self.address, self.public_url)]
        if len(others) + 1 < v.super_block.replica_placement.copy_count():
            return f"replicating to {len(others)} replicas, need more"
        errs = []

        # forward the client's JWT so replicas can run the same auth check
        headers = {}
        auth = request.headers.get("Authorization", "")
        if auth:
            headers["Authorization"] = auth
        # cross-hop trace propagation: the fan-out rides aiohttp (not the
        # FastHTTPClient, whose inject seam would do this), so the header
        # is added here — each replica's server span parents to this hop
        from ..util import trace

        ctx = trace.current()
        if ctx is not None:
            headers["traceparent"] = trace.format_traceparent(ctx)

        async def one(url: str) -> None:
            target = f"http://{url}{request.path}?type=replicate"
            q = {k: v for k, v in request.query.items() if k != "type"}
            if q:
                target += "&" + "&".join(f"{k}={v}" for k, v in q.items())
            try:
                if method == "POST":
                    form = aiohttp.FormData()
                    form.add_field("file", body, filename="replica")
                    async with self._http_client.post(
                        target, data=form, headers=headers
                    ) as resp:
                        if resp.status >= 300:
                            errs.append(f"{url}: status {resp.status}")
                else:
                    async with self._http_client.delete(
                        target, headers=headers
                    ) as resp:
                        if resp.status >= 400 and resp.status != 404:
                            errs.append(f"{url}: status {resp.status}")
            except Exception as e:
                errs.append(f"{url}: {e}")

        with trace.span("volume.replicate", replicas=len(others)):
            await asyncio.gather(*(one(u) for u in others))
        return "; ".join(errs)

    # ---------------- gRPC admin ----------------
    async def _grpc_allocate_volume(self, req, context) -> dict:
        try:
            self.store.add_volume(
                int(req["volume_id"]),
                req.get("collection", ""),
                req.get("replication", "000") or "000",
                req.get("ttl", "") or "",
                int(req.get("preallocate", 0)),
            )
            return {}
        except Exception as e:
            return {"error": str(e)}

    async def _grpc_volume_mount(self, req, context) -> dict:
        vid = int(req["volume_id"])
        self.store.mount_volume(vid)
        if req.get("seed_read_heat") is not None:
            # lifecycle re-inflation: the freshly-decoded volume inherits
            # the heat the master aggregated across its EC shard holders.
            # Without this it would mount near-cold (only the decode
            # node's share persisted) and could immediately re-qualify
            # for EC — the exact flap the hysteresis exists to prevent.
            v = self.store.find_volume(vid)
            if v is not None:
                v.heat.seed(float(req["seed_read_heat"]))
        return {}

    async def _grpc_volume_unmount(self, req, context) -> dict:
        vid = int(req["volume_id"])
        self.store.unmount_volume(vid)
        if self.read_cache is not None:
            self.read_cache.invalidate_volume(vid, "unmount")
        return {}

    async def _grpc_volume_delete(self, req, context) -> dict:
        vid = int(req["volume_id"])
        # keep_ec_files: EC conversion retires the source volume but the
        # freshly-generated shards at the same base name still need the
        # .vif/.heat sidecars — the .dat/.idx are destroyed either way
        # (an unmount-then-delete sequence would no-op the delete and
        # leave a resurrectable .dat behind)
        self.store.delete_volume(
            vid, keep_ec_files=bool(req.get("keep_ec_files"))
        )
        if self.read_cache is not None:
            self.read_cache.invalidate_volume(vid, "volume_delete")
        return {}

    async def _grpc_volume_mark_readonly(self, req, context) -> dict:
        self.store.mark_volume_readonly(int(req["volume_id"]))
        return {}

    async def _grpc_volume_mark_writable(self, req, context) -> dict:
        """Undo VolumeMarkReadonly (ref volume_grpc_admin.go
        VolumeMarkWritable) — the lifecycle dispatcher's rollback when a
        conversion fails after sealing the source: a transient encode
        failure must not leave the volume read-only forever. Refuses
        quarantined volumes (scrub owns that flag) and sorted-map loads
        (structurally read-only)."""
        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            return {"error": f"volume {vid} not found"}
        if v.scrub_corrupt:
            return {"error": f"volume {vid} is quarantined"}
        if getattr(v, "needle_map_kind", "") == "sorted":
            return {"error": f"volume {vid} has a read-only sorted map"}
        v.no_write_or_delete = False
        return {}

    async def _grpc_lifecycle_check(self, req, context) -> dict:
        """Authoritative lifecycle re-check (the VacuumVolumeCheck
        analogue): live heat/size/flags for a normal volume, or the EC
        read heat for a local EC volume — consulted by the master's
        dispatcher before spending conversion I/O, so a stale heartbeat
        temperature costs one cheap probe, never a wasted conversion."""
        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is not None:
            return {
                "kind": "volume",
                "read_heat": v.heat.read_heat(),
                "write_heat": v.heat.write_heat(),
                "size": v.data_file_size(),
                "read_only": v.is_read_only(),
                "scrub_corrupt": v.scrub_corrupt,
                "is_compacting": v.is_compacting,
            }
        ev = self.store.find_ec_volume(vid)
        if ev is not None:
            return {
                "kind": "ec",
                "read_heat": ev.heat.read_heat(),
                # cold tier: the offload/recall dispatchers gate on the
                # live split, and the inflate dispatcher refuses a volume
                # whose shards are still remote (recall first)
                "local_shards": len(ev.shards),
                "offloaded_shards": len(ev.remote_shards),
            }
        return {"error": f"volume {vid} not found"}

    async def _grpc_volume_configure(self, req, context) -> dict:
        """Rewrite a live volume's replica placement in its super block
        (ref volume_grpc_admin.go VolumeConfigure, super_block byte 1);
        heartbeats then carry the new placement to the master."""
        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            return {"error": f"volume {vid} not found"}
        from ..storage.super_block import ReplicaPlacement, SuperBlock

        try:
            rp = ReplicaPlacement.parse(req.get("replication", ""))
        except ValueError as e:
            return {"error": str(e)}
        old_msg = self.store._volume_message(v)
        with v._lock:
            sb = v.super_block
            v.super_block = SuperBlock(
                version=sb.version,
                replica_placement=rp,
                ttl=sb.ttl,
                compaction_revision=sb.compaction_revision,
                extra=sb.extra,
            )
            v.data_backend.write_at(v.super_block.to_bytes(), 0)
            v.data_backend.sync()
        # steady-state propagation: the next heartbeat tick carries the
        # change as a deleted(old)+new(new) delta pair, moving the volume
        # between the master's VolumeLayouts without a stream reconnect
        self.store.note_volume_changed(old_msg, self.store._volume_message(v))
        return {}

    async def _grpc_delete_collection(self, req, context) -> dict:
        collection = req.get("collection", "")
        for loc in self.store.locations:
            for vid, v in list(loc.volumes.items()):
                if v.collection == collection:
                    loc.delete_volume(vid)
        return {}

    async def _grpc_vacuum_check(self, req, context) -> dict:
        v = self.store.find_volume(int(req["volume_id"]))
        if v is None:
            return {"error": "volume not found"}
        return {"garbage_ratio": v.garbage_level()}

    async def _grpc_vacuum_compact(self, req, context) -> dict:
        v = self.store.find_volume(int(req["volume_id"]))
        if v is None:
            return {"error": "volume not found"}
        loop = asyncio.get_event_loop()
        try:
            # the per-run report, NOT the module-global "last" snapshot:
            # concurrent compactions (vacuum_concurrency > 1) each get
            # their own numbers
            report = await loop.run_in_executor(
                None,
                lambda: vacuum_mod.compact2(
                    v,
                    route=req.get("route") or None,
                    verify=req.get("verify"),
                ),
            )
            return {
                "stages": report.get("stages", {}),
                "route": {
                    k: report[k]
                    for k in ("route", "extents", "records")
                    if k in report
                },
            }
        except Exception as e:
            return {"error": str(e)}

    async def _grpc_vacuum_commit(self, req, context) -> dict:
        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            return {"error": "volume not found"}
        loop = asyncio.get_event_loop()
        old_msg = self.store._volume_message(v)
        try:
            new_v = await loop.run_in_executor(None, vacuum_mod.commit_compact, v)
            for loc in self.store.locations:
                if loc.find_volume(vid) is not None:
                    loc.volumes[vid] = new_v
            # the swap rewrote the .dat: cached responses must not outlive
            # it (the per-hit volume-identity check would catch any that
            # did, but the LRU should shed them now, not at eviction)
            if self.read_cache is not None:
                self.read_cache.invalidate_volume(vid, "vacuum")
            # the garbage ratio (and digest) just changed: ride the next
            # heartbeat pulse so the master's vacuum queue prunes this
            # volume instead of re-dispatching off stale state
            self.store.note_volume_changed(
                old_msg, self.store._volume_message(new_v)
            )
            return {}
        except Exception as e:
            # commit_compact closed the volume before it failed (shadows
            # swept, old .dat/.idx intact): reload so the volume keeps
            # serving and a later vacuum retry can start clean
            try:
                reloaded = await loop.run_in_executor(
                    None,
                    lambda: Volume(
                        v.dir, v.collection, vid, create=False,
                        needle_map_kind=getattr(
                            v, "needle_map_kind", "memory"
                        ),
                    ),
                )
                for loc in self.store.locations:
                    if loc.find_volume(vid) is not None:
                        loc.volumes[vid] = reloaded
            except Exception:
                pass  # original error is the one worth reporting
            return {"error": str(e)}

    async def _grpc_vacuum_cleanup(self, req, context) -> dict:
        v = self.store.find_volume(int(req["volume_id"]))
        if v is not None:
            if v.is_compacting:
                # a cleanup racing an in-flight compact2 would unlink the
                # shadow mid-write and leave .cpx-without-.cpd on disk —
                # the state the load-time sweep treats as half-committed
                return {"error": "compaction in flight; not cleaning"}
            vacuum_mod.cleanup_compact(v)
        return {}

    async def _grpc_batch_delete(self, req, context) -> dict:
        results = []
        for fid_str in req.get("file_ids", []):
            try:
                fid = FileId.parse(fid_str)
                n = Needle(id=fid.key, cookie=fid.cookie)
                size = self.store.delete_volume_needle(fid.volume_id, n)
                if self.read_cache is not None:
                    self.read_cache.invalidate_key(
                        fid.volume_id, fid.key, "delete"
                    )
                results.append({"file_id": fid_str, "status": 202, "size": size})
            except Exception as e:
                results.append({"file_id": fid_str, "status": 500, "error": str(e)})
        return {"results": results}

    async def _grpc_bulk_lookup(self, req, context) -> dict:
        """Batched fid -> (offset, size) probes served from the
        device-resident index snapshot (the TPU read north star — the
        reference runs one CompactMap binary search per request,
        ref compact_map.go:145-172; this RPC has no Go equivalent).

        req:  {volume_id, keys: <u8-LE bytes | list[int]}
        resp: {offsets: <u4-LE bytes, sizes: <u4-LE bytes, found: u8 bytes}
        columns aligned with the probe order.
        """
        import numpy as np

        vid = int(req["volume_id"])
        keys = _decode_keys(req)
        v = self.store.find_volume(vid)
        loop = asyncio.get_event_loop()
        if v is not None:
            offsets, sizes, found = await loop.run_in_executor(
                None, v.bulk_lookup, keys
            )
        else:
            ev = self.store.find_ec_volume(vid)
            if ev is None:
                return {"error": f"volume {vid} not found"}
            offsets, sizes, found = await loop.run_in_executor(
                None, ev.bulk_locate, keys
            )
        # 5-byte-offset volumes need u64 columns on the wire
        off_dtype = "<u8" if offsets.dtype.itemsize > 4 else "<u4"
        return {
            "offsets": np.ascontiguousarray(offsets, dtype=off_dtype).tobytes(),
            "offset_dtype": off_dtype,
            "sizes": np.ascontiguousarray(sizes, dtype="<u4").tobytes(),
            "found": np.ascontiguousarray(found, dtype=np.uint8).tobytes(),
        }

    async def _grpc_batch_read(self, req, context):
        """Bulk needle reads: one device-batched index probe, then record
        preads. Yields {key, found[, cookie, data, size]} per probe in order.

        req: {volume_id, keys: <u8-LE bytes | list[int]}
        """
        vid = int(req["volume_id"])
        keys = _decode_keys(req)
        loop = asyncio.get_event_loop()
        v = self.store.find_volume(vid)
        if v is not None:
            offsets, sizes, found = await loop.run_in_executor(
                None, v.bulk_lookup, keys
            )

            def read_slice(idxs: list[int]) -> list:
                # one executor hop per slice of preads, not per needle; a
                # vacuum commit racing the stream surfaces as a per-key
                # miss, not a dead stream
                out = []
                for i in idxs:
                    try:
                        out.append(
                            v.read_needle_at(int(offsets[i]), int(sizes[i]))
                        )
                    except Exception as e:
                        out.append(e)
                return out

            # slices are capped by accumulated payload bytes AND key count
            # so neither large needles nor huge key lists can pile up
            # unbounded work before the first yield
            max_slice_bytes = 8 << 20
            max_slice_keys = 256
            lo = 0
            while lo < len(keys):
                hi = lo
                span_bytes = 0
                while (
                    hi < len(keys)
                    and hi - lo < max_slice_keys
                    and (
                        hi == lo
                        or span_bytes + int(sizes[hi]) <= max_slice_bytes
                    )
                ):
                    if found[hi]:
                        span_bytes += int(sizes[hi])
                    hi += 1
                idxs = [i for i in range(lo, hi) if found[i]]
                results = (
                    await loop.run_in_executor(None, read_slice, idxs)
                    if idxs
                    else []
                )
                by_idx = dict(zip(idxs, results))
                for i in range(lo, hi):
                    key = int(keys[i])
                    n = by_idx.get(i)
                    if n is None:
                        yield {"key": key, "found": False}
                    elif isinstance(n, Exception):
                        yield {"key": key, "found": False, "error": str(n)}
                    else:
                        yield {
                            "key": key,
                            "found": True,
                            "cookie": n.cookie,
                            "size": int(sizes[i]),
                            "data": bytes(n.data),
                        }
                lo = hi
            return
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            yield {"error": f"volume {vid} not found"}
            return
        offsets, sizes, found = await loop.run_in_executor(
            None, ev.bulk_locate, keys
        )
        for i, key in enumerate(keys):
            if not found[i]:
                yield {"key": int(key), "found": False}
                continue
            try:
                n = await self.read_ec_needle_at(
                    ev, int(key), int(offsets[i]), int(sizes[i])
                )
            except Exception as e:
                # one corrupt needle must not kill the whole stream
                yield {"key": int(key), "found": False, "error": str(e)}
                continue
            if n is None:
                yield {"key": int(key), "found": False}
                continue
            yield {
                "key": int(key),
                "found": True,
                "cookie": n.cookie,
                "size": len(n.data),
                "data": bytes(n.data),
            }

    async def _grpc_status(self, req, context) -> dict:
        return {
            "volumes": [
                self.store._volume_message(v)
                for loc in self.store.locations
                for v in loc.volumes.values()
            ],
        }

    async def _grpc_query(self, req, context):
        """S3-Select-style query over stored JSON/CSV objects
        (ref volume_grpc_query.go, volume_server.proto:86; the reference
        declares but never implements the CSV input — here it works).

        Either {selected_columns, where} (JSON only, legacy) or
        {expression: "SELECT ...", input_serialization: {format, csv_delimiter,
        csv_header}}.
        """
        from ..query import query_json, select_rows

        fields = req.get("selected_columns")
        where = req.get("where", "")
        expression = req.get("expression", "")
        input_cfg = req.get("input_serialization") or {}
        for fid_str in req.get("from_file_ids", []):
            try:
                fid = FileId.parse(fid_str)
                n = Needle(id=fid.key)
                self.store.read_volume_needle(fid.volume_id, n)
                if n.cookie != fid.cookie:
                    continue
                if expression:
                    rows = select_rows(
                        bytes(n.data),
                        expression,
                        input_format=input_cfg.get("format", "json"),
                        csv_delimiter=input_cfg.get("csv_delimiter", ","),
                        csv_header=input_cfg.get("csv_header", "NONE"),
                    )
                else:
                    rows = query_json(bytes(n.data), fields, where)
                for row in rows:
                    yield {"file_id": fid_str, "record": row}
            except Exception as e:
                yield {"file_id": fid_str, "error": str(e)}

    async def _grpc_incremental_copy(self, req, context):
        """Stream records appended after since_ns
        (ref volume_grpc_copy_incremental.go + volume_backup.go)."""
        vid = int(req["volume_id"])
        since_ns = int(req.get("since_ns", 0))
        v = self.store.find_volume(vid)
        if v is None:
            yield {"error": f"volume {vid} not found"}
            return
        from ..storage.volume_backup import incremental_changes

        for chunk in incremental_changes(v, since_ns):
            yield {"file_content": chunk}

    async def _grpc_sync_status(self, req, context) -> dict:
        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            return {"error": f"volume {vid} not found"}
        return {
            "volume_id": vid,
            "tail_offset": v.data_file_size(),
            "compact_revision": v.super_block.compaction_revision,
            "idx_file_size": v.index_file_size(),
            "last_append_at_ns": v.last_append_at_ns,
        }

    async def _charge_maintenance(self, n: int, plane: str = "repair") -> None:
        """Charge n bytes to the shared maintenance budget (no-op when
        SEAWEEDFS_TPU_MAINT_MBPS is unset). The blocking token wait runs in
        the executor so a throttled repair pull never stalls serving."""
        from ..storage.maintenance import plane_bucket

        bucket = plane_bucket(plane)
        if bucket is not None and n:
            await asyncio.get_event_loop().run_in_executor(
                None, bucket.consume, n
            )

    async def _pull_volume_files(
        self, vid: int, collection: str, source: str, base: str
    ) -> None:
        """Stream .dat/.idx/.vif from a source server into base.* (atomic
        per-file via .tmp+rename); shared by VolumeCopy and the repair
        re-copy path. Pull traffic is charged to the shared maintenance
        budget: a repair storm and a scrub pass together stay under the
        one configured background-I/O cap."""
        stub = Stub(grpc_address(source), "volume")
        for ext in (".dat", ".idx", ".vif"):
            tmp = base + ext + ".tmp"
            got_any = False
            with open(tmp, "wb") as f:
                async for msg in stub.server_stream(
                    "CopyFile",
                    {"volume_id": vid, "collection": collection, "ext": ext},
                    timeout=3600,
                ):
                    if msg.get("error"):
                        if ext == ".vif":
                            break
                        raise IOError(msg["error"])
                    chunk = msg.get("file_content", b"")
                    await self._charge_maintenance(len(chunk))
                    f.write(chunk)
                    got_any = True
            if got_any or ext != ".vif":
                os.replace(tmp, base + ext)
            else:
                os.remove(tmp)
        # the pulled .idx is a different log: a stale lsm needle-map
        # snapshot at this base (repair recopy over a previously mounted
        # volume) must not be consulted by the remount
        from ..storage.needle_map.lsm_map import invalidate_snapshot

        invalidate_snapshot(base)

    async def _grpc_volume_copy(self, req, context) -> dict:
        """Pull a whole volume (.dat/.idx/.vif) from a source server and
        mount it (ref volume_grpc_copy.go:23-116)."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        source = req["source_data_node"]
        if self.store.has_volume(vid):
            return {"error": f"volume {vid} already exists"}
        loc = max(
            self.store.locations,
            key=lambda l: l.max_volume_count - len(l.volumes),
        )
        from ..storage.volume import volume_base_name

        base = volume_base_name(loc.directory, collection, vid)
        try:
            await self._pull_volume_files(vid, collection, source, base)
            self.store.mount_volume(vid)
            return {}
        except Exception as e:
            return {"error": str(e)}

    # ---------------- anti-entropy plane ----------------
    @property
    def scrubber(self):
        if self._scrubber is None:
            from ..storage.scrub import Scrubber

            self._scrubber = Scrubber(
                self.store,
                rate_mbps=self.scrub_mbps,
                codec_for=self.codec_for,
            )
        return self._scrubber

    async def _scrub_loop(self) -> None:
        """Background scrub: one rate-shaped pass per interval. The token
        bucket bounds the I/O so verification coexists with serving load;
        the per-volume cursor makes restarts resume, not restart."""
        from ..util import trace

        loop = asyncio.get_event_loop()
        while not self._shutdown:
            try:
                await asyncio.sleep(self.scrub_interval_seconds)
                if self._shutdown:
                    return
                # background-plane root span (ISSUE 8): scrub passes show
                # up in the same flight recorder as the serving traces
                # they can interfere with
                with trace.span_root(
                    "scrub.pass", plane="scrub", addr=self.address
                ):
                    await loop.run_in_executor(
                        None, self.scrubber.run_pass
                    )
            except asyncio.CancelledError:
                return
            except Exception:
                # a broken volume must not kill the loop; findings (and
                # quarantines) from the partial pass already counted
                continue

    async def _grpc_volume_scrub(self, req, context) -> dict:
        """Forced scrub pass (shell `volume.scrub` / tests): walk the
        requested volume (or everything local), verify CRCs, extents and
        EC parity, apply the quarantine policy, return the full report."""
        volume_id = int(req.get("volume_id", 0) or 0)
        include_ec = bool(req.get("include_ec", True))
        scrubber = self.scrubber
        rate = req.get("rate_mbps")
        if rate:
            from ..storage.scrub import Scrubber

            scrubber = Scrubber(
                self.store, rate_mbps=float(rate), codec_for=self.codec_for
            )
        loop = asyncio.get_event_loop()
        try:
            report = await loop.run_in_executor(
                None,
                lambda: scrubber.run_pass(
                    volume_id=volume_id or None, include_ec=include_ec
                ),
            )
            return report
        except Exception as e:
            return {"error": str(e)}

    async def _grpc_volume_tail_sync(self, req, context) -> dict:
        """Catch-up resync of a stale replica: pull every record appended
        on the source after our local frontier through the incremental
        tail path (volume_backup.py) and replay it into the local volume.
        Dispatched by the master when replica digests diverge and our
        append frontier trails."""
        from ..storage.volume_backup import apply_incremental
        from ..util.metrics import ANTIENTROPY_RESYNCS

        vid = int(req["volume_id"])
        source = req["source_data_node"]
        v = self.store.find_volume(vid)
        if v is None:
            return {"error": f"volume {vid} not found"}
        since_ns = v.last_append_at_ns
        stub = Stub(grpc_address(source), "volume")
        chunks = []
        async for msg in stub.server_stream(
            "VolumeIncrementalCopy",
            {"volume_id": vid, "since_ns": since_ns},
            timeout=3600,
        ):
            if msg.get("error"):
                return {"error": msg["error"]}
            chunks.append(msg.get("file_content", b""))
        data = b"".join(chunks)
        if not data:
            return {"applied_records": 0, "applied_bytes": 0}
        loop = asyncio.get_event_loop()
        old_msg = self.store._volume_message(v)
        try:
            applied = await loop.run_in_executor(
                None, apply_incremental, v, data
            )
        except Exception as e:
            return {"error": f"apply incremental: {e}"}
        if self.read_cache is not None:
            # replayed records may overwrite cached keys
            self.read_cache.invalidate_volume(vid, "tail_sync")
        ANTIENTROPY_RESYNCS.inc(kind="tail_sync")
        # the digest changed: let the master see the converged state on
        # the next pulse instead of the next full reconnect
        self.store.note_volume_changed(old_msg, self.store._volume_message(v))
        return {"applied_records": applied, "applied_bytes": len(data)}

    async def _grpc_volume_repair_copy(self, req, context) -> dict:
        """Replace a scrub-quarantined replica with a fresh copy from a
        healthy peer: quarantine the damaged files aside as `.bad` (never
        deleted), pull .dat/.idx/.vif from the source, remount. The
        master dispatches this when a volume heartbeats `scrub_corrupt`
        while a clean replica exists."""
        from ..util.metrics import ANTIENTROPY_RESYNCS

        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        source = req["source_data_node"]
        v = self.store.find_volume(vid)
        if v is None:
            return {"error": f"volume {vid} not found"}
        if not v.scrub_corrupt and not req.get("force"):
            # idempotent skip: the master may re-dispatch while the healed
            # state is still riding a heartbeat back to it
            return {"repaired": False, "skipped": "not quarantined"}
        base = v.file_name()
        target_loc = None
        for loc in self.store.locations:
            if loc.find_volume(vid) is not None:
                target_loc = loc
                break
        old_msg = self.store._volume_message(v)
        # a group committer pinned to the old volume object would fsync a
        # closed fd after the swap — retire it first
        gc = self._group_committers.pop(vid, None)
        if gc is not None:
            await gc.stop()
        # unmount WITHOUT a deleted-delta: repair is an in-place swap, the
        # note_volume_changed below reports the healthy state
        with target_loc._lock:
            target_loc.volumes.pop(vid, None)
        v.close()
        for ext in (".dat", ".idx", ".vif"):
            try:
                os.replace(base + ext, base + ext + ".bad")
            except FileNotFoundError:
                pass
        try:
            await self._pull_volume_files(vid, collection, source, base)
        except Exception as e:
            # rollback: a transient copy failure must not convert a
            # corrupt-but-present replica into a missing one — put the
            # quarantined files back, remount, re-flag, retry later
            for ext in (".dat", ".idx", ".vif"):
                for leftover in (base + ext + ".tmp", base + ext):
                    try:
                        os.remove(leftover)  # partial pull artifacts
                    except FileNotFoundError:
                        pass
                try:
                    os.replace(base + ext + ".bad", base + ext)
                except FileNotFoundError:
                    pass
            target_loc.load_existing_volumes()
            restored = self.store.find_volume(vid)
            if restored is not None:
                restored.quarantine("restored after failed repair pull")
            return {"error": f"pull from {source}: {e}"}
        target_loc.load_existing_volumes()
        new_v = self.store.find_volume(vid)
        if new_v is None:
            return {"error": f"volume {vid} did not remount after repair"}
        if self.read_cache is not None:
            self.read_cache.invalidate_volume(vid, "repair")
        ANTIENTROPY_RESYNCS.inc(kind="recopy")
        self.store.note_volume_changed(
            old_msg, self.store._volume_message(new_v)
        )
        return {"repaired": True}

    async def _grpc_tier_to_remote(self, req, context):
        """Move a volume's .dat to a remote tier, streaming progress
        (ref volume_grpc_tier_upload.go VolumeTierMoveDatToRemote)."""
        from ..storage import tier_backend

        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            yield {"error": f"volume {vid} not found"}
            return
        if req.get("collection", "") != v.collection:
            yield {"error": f"existing collection '{v.collection}' unexpected"}
            return
        try:
            async for msg in self._run_tier_op(
                lambda fn: tier_backend.tier_upload(
                    v,
                    req["destination_backend_name"],
                    fn,
                    keep_local=bool(req.get("keep_local_dat_file")),
                )
            ):
                if "result" in msg:
                    key, size = msg["result"]
                    yield {"key": key, "size": size}
                else:
                    yield msg
        except (ValueError, OSError) as e:
            yield {"error": str(e)}

    async def _grpc_tier_from_remote(self, req, context):
        """Bring a tiered volume's .dat back local
        (ref volume_grpc_tier_download.go VolumeTierMoveDatFromRemote)."""
        from ..storage import tier_backend

        vid = int(req["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            yield {"error": f"volume {vid} not found"}
            return
        try:
            async for msg in self._run_tier_op(
                lambda fn: tier_backend.tier_download(v, fn)
            ):
                if "result" in msg:
                    yield {"size": msg["result"]}
                else:
                    yield msg
        except (ValueError, OSError) as e:
            yield {"error": str(e)}

    async def _grpc_tier_manifest_keys(self, req, context) -> dict:
        """Every remote object key this server's `.ctm` manifests (and
        tiered-volume .vif files) still name, grouped by backend — the
        orphan sweep's reference side (ISSUE 15 satellite)."""
        return {"backends": {
            name: sorted(keys)
            for name, keys in self.store.collect_tier_manifest_keys().items()
        }}

    async def _run_tier_op(self, op):
        """Run a blocking tier transfer in an executor, streaming throttled
        progress messages as they happen (ref the 1s-throttled stream.Send
        in volume_grpc_tier_upload.go:53-64). Yields {"processed":..,
        "processedPercentage":..} then {"result": <op return value>}."""
        import time as _time

        loop = asyncio.get_event_loop()
        queue: asyncio.Queue = asyncio.Queue()
        last_sent = [0.0]

        def progress(done: int, pct: float) -> None:
            now = _time.monotonic()
            if now - last_sent[0] < 1.0:
                return
            last_sent[0] = now
            loop.call_soon_threadsafe(
                queue.put_nowait, {"processed": done, "processedPercentage": pct}
            )

        fut = loop.run_in_executor(None, op, progress)
        while True:
            done_task = asyncio.ensure_future(queue.get())
            await asyncio.wait(
                {done_task, fut}, return_when=asyncio.FIRST_COMPLETED
            )
            if done_task.done():
                yield done_task.result()
                continue
            done_task.cancel()
            break
        while not queue.empty():
            yield queue.get_nowait()
        yield {"result": await fut}

    async def _grpc_copy_file(self, req, context):
        """Stream a volume file's bytes (ref volume_grpc_copy.go doCopyFile).

        req: {volume_id, collection, ext, compaction_revision,
              stop_offset, is_ec_volume}
        """
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        ext = req["ext"]
        from ..storage.volume import volume_base_name

        for loc in self.store.locations:
            base = volume_base_name(loc.directory, collection, vid)
            path = base + ext
            if os.path.exists(path):
                with open(path, "rb") as f:
                    while True:
                        chunk = f.read(1 << 20)
                        if not chunk:
                            return
                        yield {"file_content": chunk}
        yield {"error": f"{vid}{ext} not found"}
