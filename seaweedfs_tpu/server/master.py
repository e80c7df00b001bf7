"""Master server: volume -> location mapping and file-id assignment.

HTTP (data-plane control, ref: weed/server/master_server.go:112-130):
  /dir/assign /dir/lookup /dir/status /vol/grow /vol/vacuum /col/delete
  /{fileId} redirect
gRPC (ref: weed/server/master_grpc_server*.go):
  SendHeartbeat (bidi; full + delta volume/EC inventories),
  KeepConnected (vid-location push to clients), Assign, Statistics,
  LookupVolume, LookupEcVolume, CollectionList/Delete, VolumeList,
  LeaseAdminToken/ReleaseAdminToken.

Multi-master: RaftLite (server/raft.py) elects one leader; followers
proxy Assign/growth to it, redirect heartbeat + KeepConnected streams,
and freshly assigned volume ids are majority-committed before use
(ref: weed/server/raft_server.go, weed/topology/topology.go:115-122).
"""

from __future__ import annotations

import asyncio
import os
import random
import time
from typing import Optional

from aiohttp import web

from ..pb import grpc_address
from ..pb.rpc import Service, Stub, serve
from ..sequence import MemorySequencer
from ..storage.erasure_coding import DATA_SHARDS_COUNT, TOTAL_SHARDS_COUNT
from ..storage.erasure_coding.ec_volume import ShardBits
from ..storage.super_block import ReplicaPlacement
from ..storage.ttl import TTL
from ..topology import GrowOption, Topology, VolumeGrowth
from ..topology.placement import plan_ec_domain_spread, plan_replica_spread
from ..topology.repair import (
    RepairQueue,
    find_unresolved_divergence,
    plan_ec_repairs,
    plan_replica_repairs,
)
from ..topology.volume_growth import NoFreeSpaceError, grow_count_for_copy_level
from ..topology.vacuum_plan import plan_vacuums
from ..topology.lifecycle import (
    LifecycleConfig,
    plan_ec_conversions,
    plan_offloads,
    plan_recalls,
    plan_reinflations,
)
from ..util.metrics import (
    ANTIENTROPY_DIVERGED,
    LIFECYCLE_CONVERSIONS,
    LIFECYCLE_QUEUE_DEPTH,
    PLACEMENT_VIOLATIONS,
    REPAIR_SECONDS,
    VACUUM_QUEUE_DEPTH,
)


def _tier_key_vid(key: str):
    """(vid, collection) parsed from a cold-tier object key — the
    deterministic `{collection_}{vid}{ext}` layout of
    `tier_backend._tier_key` — or (None, "") for foreign keys (which
    the orphan sweep then treats by age alone)."""
    import re

    base = key.rsplit("/", 1)[-1]
    m = re.match(r"^(?:(.+)_)?(\d+)\.\w+$", base)
    if m is None:
        return None, ""
    return int(m.group(2)), m.group(1) or ""


def _ec_tier_bits(messages: list) -> dict:
    """{vid: (local_bits, offloaded_bits)} off an EC heartbeat/heat-tick
    message list. Older senders carry no split: their ec_index_bits count
    as local (nothing offloaded) — the planner stays backward-safe."""
    out = {}
    for m in messages:
        try:
            local = int(
                m.get("ec_local_bits", m.get("ec_index_bits", 0)) or 0
            )
            out[int(m["id"])] = (
                local,
                int(m.get("ec_offloaded_bits", 0) or 0),
            )
        except (KeyError, TypeError, ValueError):
            continue
    return out


class MasterServer:
    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 9333,
        volume_size_limit_mb: int = 30_000,
        default_replication: str = "000",
        garbage_threshold: float = 0.3,
        pulse_seconds: float = 5.0,
        jwt_signing_key: str = "",
        jwt_expires_seconds: int = 10,
        peers: Optional[list[str]] = None,
        admin_lease_seconds: float = 10.0,
        maintenance_scripts: str = "",
        maintenance_sleep_minutes: float = 17.0,
        maintenance_filer: str = "",
        sequencer_file: str = "",
        raft_state_file: str = "",
        auto_repair: Optional[bool] = None,
        repair_grace_seconds: Optional[float] = None,
        repair_concurrency: int = 2,
        auto_vacuum: Optional[bool] = None,
        vacuum_concurrency: int = 2,
        auto_lifecycle: Optional[bool] = None,
        lifecycle_concurrency: int = 1,
        lifecycle_config: Optional[LifecycleConfig] = None,
        lifecycle_ec_shards: str = "",
        storage_backends: Optional[list[dict]] = None,
    ):
        self.jwt_signing_key = jwt_signing_key
        self.jwt_expires_seconds = jwt_expires_seconds
        self.admin_lease_seconds = admin_lease_seconds
        self.maintenance_scripts = maintenance_scripts
        self.maintenance_sleep_minutes = maintenance_sleep_minutes
        self.maintenance_filer = maintenance_filer
        self._maintenance_task: Optional[asyncio.Task] = None
        self.host = host
        self.port = port
        self.address = f"{host}:{port}"
        self.default_replication = default_replication
        self.garbage_threshold = garbage_threshold
        self.pulse_seconds = pulse_seconds
        if sequencer_file:
            from ..sequence import FileSequencer

            sequencer = FileSequencer(sequencer_file)
        else:
            sequencer = MemorySequencer()
        self.topo = Topology(
            volume_size_limit=volume_size_limit_mb * 1024 * 1024,
            sequencer=sequencer,
        )
        self.growth = VolumeGrowth()
        from .raft import RaftLite

        self.raft = RaftLite(
            self.address,
            peers,
            get_max_volume_id=lambda: self.topo.max_volume_id,
            adjust_max_volume_id=self.topo.adjust_max_volume_id,
            state_file=raft_state_file,
        )
        # anti-entropy repair plane: heartbeat-driven failure detection ->
        # prioritized queue -> batched-rebuild dispatch. The background
        # loop is opt-in (SEAWEEDFS_TPU_AUTO_REPAIR / auto_repair=True);
        # run_anti_entropy_once() is always callable (shell, tests).
        if auto_repair is None:
            auto_repair = os.environ.get(
                "SEAWEEDFS_TPU_AUTO_REPAIR", ""
            ).lower() in ("1", "true", "on", "yes")
        self.auto_repair = auto_repair
        self.repair_grace_seconds = (
            repair_grace_seconds
            if repair_grace_seconds is not None
            else max(15.0, 4 * pulse_seconds)
        )
        self.repair_concurrency = repair_concurrency
        self.repair_queue = RepairQueue(rng=random.Random())
        self.repair_log: list[dict] = []  # last dispatch outcomes
        # latest anti-entropy scan's placement-policy findings (served
        # by PlacementStatus / geo.status)
        self.placement_violations: list[dict] = []
        self._repair_task: Optional[asyncio.Task] = None
        # vacuum plane: garbage ratios ride heartbeats; findings feed a
        # highest-garbage-first queue dispatched under a concurrency cap
        # with full-jitter backoff — the repair scheduler's shape applied
        # to compaction. Background loop opt-in (SEAWEEDFS_TPU_AUTO_VACUUM
        # / auto_vacuum=True); run_vacuum_once() is always callable
        # (/vol/vacuum, VacuumStatus -run, tests).
        if auto_vacuum is None:
            auto_vacuum = os.environ.get(
                "SEAWEEDFS_TPU_AUTO_VACUUM", ""
            ).lower() in ("1", "true", "on", "yes")
        self.auto_vacuum = auto_vacuum
        self.vacuum_concurrency = vacuum_concurrency
        self.vacuum_queue = RepairQueue(
            rng=random.Random(), depth_gauge=VACUUM_QUEUE_DEPTH
        )
        self.vacuum_log: list[dict] = []
        self._vacuum_task: Optional[asyncio.Task] = None
        self._vacuum_inflight: set[int] = set()
        # lifecycle plane (ISSUE 10): access heat rides heartbeats the way
        # garbage ratios do; cold+full volumes auto-EC into the warm tier,
        # hot EC volumes re-inflate — the Haystack→f4 arc as a background
        # scheduler in the vacuum/repair shape. Background loop opt-in
        # (SEAWEEDFS_TPU_AUTO_LIFECYCLE / auto_lifecycle=True);
        # run_lifecycle_once() is always callable (shell, tests, bench).
        if auto_lifecycle is None:
            auto_lifecycle = os.environ.get(
                "SEAWEEDFS_TPU_AUTO_LIFECYCLE", ""
            ).lower() in ("1", "true", "on", "yes")
        self.auto_lifecycle = auto_lifecycle
        self.lifecycle_concurrency = lifecycle_concurrency
        self.lifecycle_config = lifecycle_config or LifecycleConfig.from_env()
        # cold-tier backends pushed to volume servers via the heartbeat
        # response (ISSUE 15 satellite): an explicit list wins; None
        # snapshots whatever the master's own process registered at
        # START time — the master, not per-volume-server env, is the
        # single source of backend truth
        self._storage_backends = storage_backends
        self.orphan_sweep_log: list[dict] = []
        # conversion RS geometry "k.m" ("" = the volume servers' default)
        lifecycle_ec_shards = lifecycle_ec_shards or os.environ.get(
            "SEAWEEDFS_TPU_LIFECYCLE_SHARDS", ""
        )
        self.lifecycle_data_shards = self.lifecycle_parity_shards = 0
        if lifecycle_ec_shards:
            try:
                k, _, m = lifecycle_ec_shards.partition(".")
                if int(k) >= 1 and int(m) >= 1:
                    self.lifecycle_data_shards = int(k)
                    self.lifecycle_parity_shards = int(m)
            except ValueError:
                pass
        self.lifecycle_queue = RepairQueue(
            rng=random.Random(), depth_gauge=LIFECYCLE_QUEUE_DEPTH
        )
        self.lifecycle_log: list[dict] = []
        self._lifecycle_task: Optional[asyncio.Task] = None
        self._lifecycle_inflight: set[int] = set()
        # cold tier anti-flap: vid -> monotonic time its recall finished
        # (plan_offloads exempts these for cfg.offload_holddown_s)
        self._lifecycle_recall_at: dict[int, float] = {}
        self._clients: dict[str, asyncio.Queue] = {}
        self._option_cache: dict[tuple, GrowOption] = {}
        self._admin_token: Optional[tuple[int, float]] = None  # (token, ts)
        self._http_runner: Optional[web.AppRunner] = None
        self._grpc_server = None
        self._shutdown = False

    @property
    def leader(self) -> str:
        return self.raft.leader_address or self.address

    @property
    def known_leader(self) -> str:
        """The elected leader, or "" while none is known — a deposed or
        mid-election master must not hint clients back to itself."""
        if self.raft.is_leader:
            return self.address
        return self.raft.leader_address or ""

    @property
    def is_leader(self) -> bool:
        return self.raft.is_leader

    # ---------------- lifecycle ----------------
    async def start(self) -> None:
        if self._storage_backends is None:
            from ..storage.tier_backend import snapshot_backends_payload

            self._storage_backends = snapshot_backends_payload()
        app = web.Application()
        app.router.add_route("*", "/dir/assign", self._dir_assign)
        app.router.add_route("*", "/dir/lookup", self._dir_lookup)
        app.router.add_get("/dir/status", self._dir_status)
        app.router.add_route("*", "/vol/grow", self._vol_grow)
        app.router.add_route("*", "/vol/vacuum", self._vol_vacuum)
        app.router.add_route("*", "/col/delete", self._col_delete)
        app.router.add_get("/cluster/status", self._cluster_status)
        # /metrics and /debug/* are served by the ServingCore middleware
        # before routing — a route here would be an unreachable shadow
        app.router.add_get("/", self._ui)
        app.router.add_get("/ui", self._ui)
        app.router.add_get("/{file_id:[0-9]+,.+}", self._redirect)
        # shared serving core (server/serving_core.py): full app on an
        # internal loopback port; the public port is the byte-level fast
        # tier which serves /dir/assign and /dir/lookup itself and
        # proxies the rest here
        from .serving_core import ServingCore

        self._core = ServingCore(
            "master", self._fast_dispatch, self.host, self.port
        )
        await self._core.start(app)
        self._fast_server = self._core.fast_server
        self._http_runner = self._core._http_runner

        svc = Service("master", gate=self._core.gate)
        svc.bidi_stream("SendHeartbeat")(self._send_heartbeat)
        svc.bidi_stream("KeepConnected")(self._keep_connected)
        svc.unary("Assign")(self._grpc_assign)
        svc.unary("LookupVolume")(self._grpc_lookup_volume)
        svc.unary("LookupEcVolume")(self._grpc_lookup_ec_volume)
        svc.unary("Statistics")(self._grpc_statistics)
        svc.unary("CollectionList")(self._grpc_collection_list)
        svc.unary("CollectionDelete")(self._grpc_collection_delete)
        svc.unary("VolumeList")(self._grpc_volume_list)
        svc.unary("LeaseAdminToken")(self._grpc_lease_admin_token)
        svc.unary("ReleaseAdminToken")(self._grpc_release_admin_token)
        svc.unary("GetMasterConfiguration")(self._grpc_get_configuration)
        svc.unary("RepairStatus")(self._grpc_repair_status)
        svc.unary("PlacementStatus")(self._grpc_placement_status)
        svc.unary("VacuumStatus")(self._grpc_vacuum_status)
        svc.unary("LifecycleStatus")(self._grpc_lifecycle_status)
        svc.unary("TierOrphanSweep")(self._grpc_tier_orphan_sweep)
        svc.unary("RaftRequestVote")(self._grpc_raft_request_vote)
        svc.unary("RaftAppendEntries")(self._grpc_raft_append_entries)
        self._grpc_server = await serve(grpc_address(self.address), svc)
        self.raft.start()
        if self.maintenance_scripts.strip():
            self._maintenance_task = asyncio.ensure_future(
                self._maintenance_loop()
            )
        if self.auto_repair:
            self._repair_task = asyncio.ensure_future(self._anti_entropy_loop())
        if self.auto_vacuum:
            self._vacuum_task = asyncio.ensure_future(self._auto_vacuum_loop())
        if self.auto_lifecycle:
            self._lifecycle_task = asyncio.ensure_future(
                self._auto_lifecycle_loop()
            )

    async def _maintenance_loop(self) -> None:
        """Leader-only periodic admin scripts (ref: master_server.go:191-246
        startAdminScripts — [master.maintenance] scripts run through the
        same shell command table on a timer; lock/unlock are auto-wrapped
        when the script doesn't manage the lease itself)."""
        from ..shell import CommandEnv, run_command
        from ..util import log

        lines = [
            part.strip()
            for line in self.maintenance_scripts.splitlines()
            for part in line.split(";")
            if part.strip()
        ]
        if not any(line.split()[0] == "lock" for line in lines):
            lines = ["lock"] + lines + ["unlock"]
        while not self._shutdown:
            await asyncio.sleep(self.maintenance_sleep_minutes * 60)
            if not self.is_leader or self._shutdown:
                continue
            env = CommandEnv(self.address, filer=self.maintenance_filer)
            for line in lines:
                try:
                    out = await run_command(env, line)
                    log.info("maintenance %r: %s", line, out)
                except Exception as e:
                    log.info("maintenance %r failed: %s", line, e)
            await env.release_lock()

    async def stop(self) -> None:
        self._shutdown = True
        if getattr(self, "_fast_server", None) is not None:
            await self._fast_server.stop()
        if self._repair_task is not None:
            self._repair_task.cancel()
            try:
                await self._repair_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._vacuum_task is not None:
            self._vacuum_task.cancel()
            try:
                await self._vacuum_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._lifecycle_task is not None:
            self._lifecycle_task.cancel()
            try:
                await self._lifecycle_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._maintenance_task is not None:
            self._maintenance_task.cancel()
            try:
                await self._maintenance_task
            except (asyncio.CancelledError, Exception):
                pass
        await self.raft.stop()
        if self._grpc_server is not None:
            await self._grpc_server.stop(0.5)
        if self._http_runner is not None:
            await self._http_runner.cleanup()

    # ---------------- fast-tier HTTP dispatch (util/fasthttp.py) ----------------
    async def _fast_dispatch(self, req):
        """Hot client-facing lookups: /dir/assign and /dir/lookup with plain
        query parameters. Anything else (percent-encoded queries, form
        bodies, admin/UI/status routes) proxies to the full app."""
        from ..util.fasthttp import FALLBACK, render_response

        if req.method not in ("GET", "POST") or (
            req.method == "POST" and req.body
        ):
            return FALLBACK
        if req.path not in ("/dir/assign", "/dir/lookup"):
            return FALLBACK
        q = req.query
        if "%" in q or "+" in q:
            return FALLBACK  # encoded values: use the full URL parser
        params = {}
        if q:
            for pair in q.split("&"):
                k, _, v = pair.partition("=")
                params[k] = v
        import json as _json

        if req.path == "/dir/assign":
            if not params.keys() <= {
                "count", "collection", "replication", "ttl", "dataCenter",
            }:
                return FALLBACK
            result = await self._do_assign(params)
            # hand-formatted success body: fid/url are plain host:port and
            # hex strings (never need JSON escaping), and dumps() was
            # measurable at assign QPS rates. Exact expected-key check: any
            # field this formatter doesn't know (auth today, whatever
            # _do_assign grows tomorrow) falls through to the json tier
            # instead of being silently dropped
            if set(result) == {"fid", "url", "publicUrl", "count"}:
                return render_response(
                    200,
                    (
                        '{"fid": "%s", "url": "%s", "publicUrl": "%s", '
                        '"count": %d}'
                        % (
                            result["fid"],
                            result["url"],
                            result["publicUrl"],
                            result["count"],
                        )
                    ).encode(),
                )
        else:
            if not self.raft.is_leader:
                return FALLBACK  # follower: full app serves the leader gate
            result = self._do_lookup(
                params.get("volumeId", ""), params.get("collection", "")
            )
        return render_response(200, _json.dumps(result).encode())

    # ---------------- assignment core ----------------
    def _parse_option(self, params) -> GrowOption:
        # memoized: assigns repeat the same handful of option tuples, and
        # re-parsing replication/TTL strings per request showed up at QPS
        # rates. GrowOption is treated as immutable by all consumers.
        key = (
            params.get("collection", ""),
            params.get("replication", ""),
            params.get("ttl", ""),
            params.get("dataCenter", ""),
            params.get("rack", ""),
        )
        opt = self._option_cache.get(key)
        if opt is None:
            opt = GrowOption(
                collection=key[0],
                replica_placement=ReplicaPlacement.parse(
                    key[1] or self.default_replication
                ),
                ttl=TTL.read(key[2]),
                data_center=key[3],
                rack=key[4],
            )
            if len(self._option_cache) > 256:  # runaway-key backstop
                self._option_cache.clear()
            self._option_cache[key] = opt
        return opt

    async def _allocate_volume(self, vid: int, option: GrowOption, servers) -> bool:
        """AllocateVolume RPC to each chosen server (ref
        topology/allocate_volume.go)."""
        # the vid must reach a raft majority before any server uses it
        if not await self.raft.commit_max_volume_id(vid):
            return False
        ok = True
        for dn in servers:
            stub = Stub(grpc_address(dn.url), "volume")
            try:
                resp = await stub.call(
                    "AllocateVolume",
                    {
                        "volume_id": vid,
                        "collection": option.collection,
                        "replication": str(option.replica_placement),
                        "ttl": str(option.ttl),
                        "preallocate": option.preallocate,
                    },
                )
                ok = ok and not resp.get("error")
            except Exception:
                ok = False
        return ok

    async def _ensure_writable(self, option: GrowOption) -> None:
        layout = self.topo.get_volume_layout(
            option.collection, option.replica_placement, option.ttl
        )
        if layout.has_writable_volume():
            return
        count = grow_count_for_copy_level(option.replica_placement.copy_count())
        grown = await self.growth.grow_by_count(
            count, self.topo, option, self._allocate_volume
        )
        if grown == 0:
            raise NoFreeSpaceError("no free volumes left")
        # push the fresh vid locations to KeepConnected clients right away
        # (heartbeat deltas would also deliver them, but only a pulse later)
        for vid, locs in list(layout.vid_to_locations.items()):
            for dn in locs:
                self._broadcast_location(dn, new_vids=[vid], deleted_vids=[])

    async def _do_assign(self, params) -> dict:
        # Only the raft leader may assign/grow: followers proxy to the
        # leader so concurrent masters never allocate colliding volume
        # ids (ref master_server.go:159-189 proxy-to-leader wrapper).
        proxied = await self._proxy_to_leader("Assign", dict(params))
        if proxied is not None:
            return proxied
        try:
            # clamp the lease width: count=N reserves N sequential file
            # ids, and an unbounded client value could burn the shared
            # key space (or overflow derived-fid arithmetic) in one call
            count = min(max(int(params.get("count", 1) or 1), 1), 100_000)
            option = self._parse_option(params)
            await self._ensure_writable(option)
            fid, cnt, locations = self.topo.pick_for_write(
                count, option.collection, option.replica_placement, option.ttl
            )
        except (NoFreeSpaceError, LookupError, ValueError) as e:
            # ValueError: malformed replication/ttl params, or a placement
            # the byte encoding can't represent (e.g. "300") — an error
            # body, not a 500
            return {"error": str(e)}
        dn = locations[0]
        result = {
            "fid": fid,
            "url": dn.url,
            "publicUrl": dn.public_url,
            "count": cnt,
        }
        if self.jwt_signing_key:
            from ..util.security import gen_jwt

            result["auth"] = gen_jwt(
                self.jwt_signing_key, self.jwt_expires_seconds, fid
            )
        return result

    def _do_lookup(self, vid_str: str, collection: str = "") -> dict:
        try:
            vid = int(vid_str.split(",")[0])
        except ValueError:
            return {"volumeId": vid_str, "error": "unknown volumeId format"}
        locations = self.topo.lookup(collection, vid)
        if not locations:
            ec = self.topo.lookup_ec_shards(vid)
            if ec is not None:
                by_url = {}
                for locs in ec.locations:
                    for dn in locs:
                        by_url.setdefault(dn.url, dn)
                if by_url:
                    return {
                        "volumeId": vid_str,
                        "locations": [
                            {
                                "url": u,
                                "publicUrl": u,
                                "dataCenter": self._dc_of(by_url[u]),
                            }
                            for u in sorted(by_url)
                        ],
                    }
            return {"volumeId": vid_str, "error": "volume id not found"}
        return {
            "volumeId": vid_str,
            "locations": [
                {
                    "url": dn.url,
                    "publicUrl": dn.public_url,
                    "dataCenter": self._dc_of(dn),
                }
                for dn in locations
            ],
        }

    @staticmethod
    def _dc_of(dn) -> str:
        """The DC label clients use for read affinity (rides lookup
        responses and KeepConnected pushes)."""
        dc = getattr(dn, "data_center", None)
        return dc.id if dc is not None else ""

    def _leader_gate_http(self, request: web.Request) -> Optional[web.Response]:
        """None when this master may serve the request; otherwise a
        503 (no leader yet) — or raises a redirect to the leader
        (ref master_server.go:159-189 proxyToLeader)."""
        if self.is_leader:
            return None
        leader = self.raft.leader_address
        if not leader or leader == self.address:
            return web.json_response(
                {"error": "no leader elected yet"}, status=503
            )
        raise web.HTTPTemporaryRedirect(f"http://{leader}{request.path_qs}")

    # ---------------- HTTP handlers ----------------
    async def _dir_assign(self, request: web.Request) -> web.Response:
        params = dict(request.query)
        if request.method == "POST":
            params.update(dict(await request.post()))
        return web.json_response(await self._do_assign(params))

    async def _dir_lookup(self, request: web.Request) -> web.Response:
        gate = self._leader_gate_http(request)
        if gate is not None:
            return gate
        params = dict(request.query)
        if request.method == "POST":
            params.update(dict(await request.post()))
        vid = params.get("volumeId", "")
        return web.json_response(
            self._do_lookup(vid, params.get("collection", ""))
        )

    async def _dir_status(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"Topology": self.topo.to_info(), "Version": "seaweedfs-tpu 0.1"}
        )

    async def _vol_grow(self, request: web.Request) -> web.Response:
        gate = self._leader_gate_http(request)
        if gate is not None:
            return gate
        params = dict(request.query)
        try:
            option = self._parse_option(params)
            # force the representability check (parse accepts any digits,
            # e.g. "300", but the byte encoding can't store them)
            option.replica_placement.to_byte()
            count = int(params.get("count", 1) or 1)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        grown = await self.growth.grow_by_count(
            count, self.topo, option, self._allocate_volume
        )
        if grown == 0:
            return web.json_response({"error": "no free volumes left"}, status=404)
        return web.json_response({"count": grown})

    async def _vol_vacuum(self, request: web.Request) -> web.Response:
        gate = self._leader_gate_http(request)
        if gate is not None:
            return gate
        threshold = float(
            request.query.get("garbageThreshold", self.garbage_threshold)
        )
        results = await self.vacuum(threshold)
        return web.json_response({"Result": results})

    async def _col_delete(self, request: web.Request) -> web.Response:
        gate = self._leader_gate_http(request)
        if gate is not None:
            return gate
        collection = request.query.get("collection", "")
        for dn in self.topo.data_nodes():
            stub = Stub(grpc_address(dn.url), "volume")
            try:
                await stub.call("DeleteCollection", {"collection": collection})
            except Exception:
                pass
        self.topo.delete_collection(collection)
        return web.json_response({})

    async def _ui(self, request: web.Request) -> web.Response:
        """Minimal HTML status page (ref: weed/server/master_ui/)."""
        from html import escape

        info = self.topo.to_info()
        rows = []
        for dc in info["data_centers"]:
            for rack in dc["racks"]:
                for dn in rack["data_nodes"]:
                    # dc/rack/url strings come from heartbeats — escape them
                    url = escape(dn["url"], quote=True)
                    rows.append(
                        f"<tr><td>{escape(str(dc['id']))}</td>"
                        f"<td>{escape(str(rack['id']))}</td>"
                        f"<td><a href='http://{url}/ui'>{url}</a></td>"
                        f"<td>{len(dn.get('volumes', []))}</td>"
                        f"<td>{dn.get('max_volume_count', 0)}</td>"
                        f"<td>{len(dn.get('ec_shards', []))}</td></tr>"
                    )
        html = f"""<!doctype html><html><head><title>seaweedfs-tpu master</title>
<style>body{{font-family:sans-serif;margin:2em}}table{{border-collapse:collapse}}
td,th{{border:1px solid #ccc;padding:4px 10px}}</style></head><body>
<h1>seaweedfs-tpu master {self.address}</h1>
<p>leader: <b>{escape(str(self.leader or "-"))}</b> (this node is
{"the leader" if self.is_leader else "a follower"}) &middot;
peers: {escape(", ".join(self.raft.others()) or "none")}</p>
<p>volumes: {info["volume_count"]} / capacity {info["max_volume_count"]}
&middot; max volume id: {info["max_volume_id"]}
&middot; ec shards: {info["ec_shard_count"]}</p>
<table><tr><th>data center</th><th>rack</th><th>volume server</th>
<th>volumes</th><th>max</th><th>ec shards</th></tr>{"".join(rows)}</table>
<p><a href="/dir/status">/dir/status</a> &middot;
<a href="/cluster/status">/cluster/status</a> &middot;
<a href="/metrics">/metrics</a></p></body></html>"""
        return web.Response(text=html, content_type="text/html")

    async def _cluster_status(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "IsLeader": self.is_leader,
                "Leader": self.leader,
                "Peers": self.raft.others(),
            }
        )

    async def _redirect(self, request: web.Request) -> web.Response:
        gate = self._leader_gate_http(request)
        if gate is not None:
            return gate
        file_id = request.match_info["file_id"]
        result = self._do_lookup(file_id.split(",")[0])
        if "error" in result:
            return web.json_response(result, status=404)
        url = result["locations"][0]["publicUrl"]
        raise web.HTTPMovedPermanently(location=f"http://{url}/{file_id}")

    # ---------------- gRPC: heartbeats ----------------
    async def _send_heartbeat(self, request_iterator, context):
        """Bidi heartbeat stream from one volume server
        (ref: master_grpc_server.go:20-178)."""
        # Followers don't own topology state: hand the volume server the
        # leader's address and end the stream so it redials
        # (ref master_grpc_server.go heartbeat leader check).
        if not self.is_leader:
            yield {"leader": self.known_leader}
            return
        dn = None
        try:
            async for hb in request_iterator:
                if not self.is_leader:
                    # demoted mid-stream: hand over and end the stream so
                    # the volume server redials the new leader
                    yield {"leader": self.known_leader}
                    return
                if dn is None and hb.get("ip"):
                    dc = self.topo.get_or_create_data_center(
                        hb.get("data_center") or "DefaultDataCenter"
                    )
                    rack = dc.get_or_create_rack(hb.get("rack") or "DefaultRack")
                    dn = rack.get_or_create_data_node(
                        f"{hb['ip']}:{hb['port']}",
                        f"{hb['ip']}:{hb['port']}",
                        hb.get("public_url", ""),
                        int(hb.get("max_volume_count", 7)),
                    )
                if dn is None:
                    continue
                dn.last_seen = time.time()
                if hb.get("max_file_key"):
                    self.topo.sequence.set_max(int(hb["max_file_key"]))

                new_vids, deleted_vids = [], []
                if hb.get("volumes") is not None or hb.get("has_no_volumes"):
                    new_infos, deleted_infos, changed_infos = (
                        dn.update_volumes(hb.get("volumes") or [])
                    )
                    # an in-place layout change (volume.configure.replication)
                    # must move the volume between VolumeLayouts, or assigns
                    # keep serving the old placement forever
                    for old_info, _new_info in changed_infos:
                        self.topo.unregister_volume(old_info, dn)
                    for info in hb.get("volumes") or []:
                        self.topo.register_volume(info, dn)
                    for info in deleted_infos:
                        self.topo.unregister_volume(info, dn)
                    new_vids += [int(i["id"]) for i in new_infos]
                    deleted_vids += [int(i["id"]) for i in deleted_infos]
                # deletions first: a changed volume arrives as a
                # (deleted=old-info, new=new-info) pair and must leave its
                # old layout before (re)registering in the new one
                if hb.get("deleted_volumes"):
                    dn.delta_update_volumes([], hb["deleted_volumes"])
                    for info in hb["deleted_volumes"]:
                        self.topo.unregister_volume(info, dn)
                        deleted_vids.append(int(info["id"]))
                if hb.get("new_volumes"):
                    dn.delta_update_volumes(hb["new_volumes"], [])
                    for info in hb["new_volumes"]:
                        self.topo.register_volume(info, dn)
                        new_vids.append(int(info["id"]))

                if hb.get("ec_shards") is not None or hb.get("has_no_ec_shards"):
                    # full EC state doubles as a heat snapshot (lifecycle)
                    dn.ec_heat = {
                        int(m["id"]): float(m.get("read_heat", 0.0))
                        for m in hb.get("ec_shards") or []
                    }
                    dn.ec_tier = _ec_tier_bits(hb.get("ec_shards") or [])
                    new_ec, deleted_ec = dn.update_ec_shards(
                        hb.get("ec_shards") or []
                    )
                    for vid, collection, bits in new_ec:
                        self.topo.register_ec_shards(vid, collection, bits, dn)
                        new_vids.append(vid)
                    for vid, collection, bits in deleted_ec:
                        self.topo.unregister_ec_shards(vid, collection, bits, dn)
                        self.topo.forget_ec_volume_if_empty(vid)
                if hb.get("new_ec_shards"):
                    for m in hb["new_ec_shards"]:
                        bits = ShardBits(int(m["ec_index_bits"]))
                        dn.delta_update_ec_shards(
                            [(int(m["id"]), m.get("collection", ""), bits)], []
                        )
                        self.topo.register_ec_shards(
                            int(m["id"]), m.get("collection", ""), bits, dn
                        )
                        new_vids.append(int(m["id"]))
                if hb.get("deleted_ec_shards"):
                    for m in hb["deleted_ec_shards"]:
                        bits = ShardBits(int(m["ec_index_bits"]))
                        dn.delta_update_ec_shards(
                            [], [(int(m["id"]), m.get("collection", ""), bits)]
                        )
                        self.topo.unregister_ec_shards(
                            int(m["id"]), m.get("collection", ""), bits, dn
                        )
                        # explicit delete delta: a fully-emptied EC volume
                        # is genuinely retired (decode/lifecycle), not a
                        # silent node — drop the registration
                        self.topo.forget_ec_volume_if_empty(int(m["id"]))
                        if not dn.ec_shards.get(int(m["id"])):
                            deleted_vids.append(int(m["id"]))

                if hb.get("volume_digests"):
                    # anti-entropy tick: refresh digest/frontier/quarantine
                    # fields in place — layouts don't change, but replica
                    # comparison must see current values
                    for m in hb["volume_digests"]:
                        info = dn.volumes.get(int(m["id"]))
                        if info is None:
                            continue
                        for k in (
                            "content_digest",
                            "append_at_ns",
                            "read_only",
                            "scrub_corrupt",
                            "garbage_ratio",
                            "read_heat",
                            "write_heat",
                            "size",
                            "modified_at_second",
                        ):
                            if k in m:
                                info[k] = m[k]

                if hb.get("ec_heat") is not None:
                    # lifecycle tick: full snapshot of this node's EC read
                    # heat (an empty list clears it — the node holds no EC
                    # volumes any more); the cold-tier planners read the
                    # local/offloaded split off the same tick
                    dn.ec_heat = {
                        int(m["id"]): float(m.get("read_heat", 0.0))
                        for m in hb["ec_heat"]
                    }
                    dn.ec_tier = _ec_tier_bits(hb["ec_heat"])

                if new_vids or deleted_vids:
                    self._broadcast_location(
                        dn, new_vids=new_vids, deleted_vids=deleted_vids
                    )

                resp = {
                    "volume_size_limit": self.topo.volume_size_limit,
                    "leader": self.leader,
                    "metrics_interval_seconds": 15,
                }
                if self._storage_backends:
                    # registered cold-tier backends ride every pulse
                    # response (ref master_grpc_server.go StorageBackends;
                    # the payload is a few dicts, and re-registration is
                    # idempotent): volume servers need no per-process
                    # env/registry wiring — the master is the single
                    # source of backend truth, and a volume server that
                    # lost its registry (restart) heals on the next pulse
                    resp["storage_backends"] = self._storage_backends
                yield resp
        finally:
            if dn is not None:
                self._unregister_data_node(dn)

    def _unregister_data_node(self, dn) -> None:
        """Heartbeat stream broke: drop all its volumes/EC shards
        (ref master_grpc_server.go:24-52)."""
        deleted = []
        for info in list(dn.volumes.values()):
            self.topo.unregister_volume(info, dn)
            deleted.append(int(info["id"]))
        for vid, bits in list(dn.ec_shards.items()):
            self.topo.unregister_ec_shards(vid, "", bits, dn)
            deleted.append(vid)
        dn.update_volumes([])  # -> ([], all, []) clears the node
        dn.update_ec_shards([])
        if dn.parent:
            dn.parent.unlink_child(dn.id)
        if deleted:
            self._broadcast_location(dn, new_vids=[], deleted_vids=deleted)

    def _broadcast_location(self, dn, new_vids, deleted_vids) -> None:
        msg = {
            "url": dn.url,
            "public_url": dn.public_url,
            "data_center": self._dc_of(dn),
            "new_vids": sorted(set(new_vids)),
            "deleted_vids": sorted(set(deleted_vids)),
            "leader": self.leader,
        }
        for q in list(self._clients.values()):
            try:
                q.put_nowait(msg)
            except asyncio.QueueFull:
                pass

    # ---------------- gRPC: client push ----------------
    async def _keep_connected(self, request_iterator, context):
        """vid-location push stream (ref master_grpc_server.go:182-235)."""
        if not self.is_leader:
            # point the client at the leader and end the stream
            yield {"leader": self.known_leader}
            return
        first = await request_iterator.__anext__()
        client_name = f"{first.get('name', 'client')}@{id(context)}"
        queue: asyncio.Queue = asyncio.Queue(maxsize=10_000)
        self._clients[client_name] = queue

        # initial full state
        for dn in self.topo.data_nodes():
            vids = sorted(set(list(dn.volumes.keys()) + list(dn.ec_shards.keys())))
            if vids:
                yield {
                    "url": dn.url,
                    "public_url": dn.public_url,
                    "data_center": self._dc_of(dn),
                    "new_vids": vids,
                    "deleted_vids": [],
                    "leader": self.leader,
                }

        async def drain_requests():
            try:
                async for _ in request_iterator:
                    pass
            except Exception:
                pass

        drainer = asyncio.ensure_future(drain_requests())
        try:
            while not self._shutdown:
                if not self.is_leader:
                    yield {"leader": self.known_leader}  # demoted: hand over
                    return
                try:
                    msg = await asyncio.wait_for(queue.get(), timeout=1.0)
                    yield msg
                except asyncio.TimeoutError:
                    yield {"leader": self.leader}  # keepalive tick
        finally:
            drainer.cancel()
            self._clients.pop(client_name, None)

    # ---------------- gRPC: unary ----------------
    async def _grpc_assign(self, req, context) -> dict:
        return await self._do_assign(req)

    async def _proxy_to_leader(self, method: str, req) -> Optional[dict]:
        """Forward a unary gRPC call to the leader when this master is a
        follower; None means serve locally."""
        if self.is_leader:
            return None
        leader = self.raft.leader_address
        if not leader or leader == self.address:
            return {"error": "no leader elected yet"}
        try:
            return await Stub(grpc_address(leader), "master").call(
                method, dict(req), timeout=5.0
            )
        except Exception as e:
            return {"error": f"proxy to leader {leader} failed: {e}"}

    async def _grpc_lookup_volume(self, req, context) -> dict:
        proxied = await self._proxy_to_leader("LookupVolume", req)
        if proxied is not None:
            return proxied
        results = []
        for vid in req.get("volume_ids", []):
            results.append(self._do_lookup(str(vid), req.get("collection", "")))
        return {"volume_id_locations": results}

    async def _grpc_lookup_ec_volume(self, req, context) -> dict:
        """(ref master_grpc_server_volume.go LookupEcVolume)"""
        proxied = await self._proxy_to_leader("LookupEcVolume", req)
        if proxied is not None:
            return proxied
        vid = int(req["volume_id"])
        locs = self.topo.lookup_ec_shards(vid)
        if locs is None:
            return {"error": f"ec volume {vid} not found"}
        shard_locations = []
        for shard_id, nodes in enumerate(locs.locations):
            if nodes:
                shard_locations.append(
                    {
                        "shard_id": shard_id,
                        "locations": [
                            {"url": dn.url, "public_url": dn.public_url}
                            for dn in nodes
                        ],
                    }
                )
        return {"volume_id": vid, "shard_id_locations": shard_locations}

    async def _grpc_statistics(self, req, context) -> dict:
        proxied = await self._proxy_to_leader("Statistics", req)
        if proxied is not None:
            return proxied
        return {
            "used_size": sum(
                int(v.get("size", 0))
                for dn in self.topo.data_nodes()
                for v in dn.volumes.values()
            ),
        }

    async def _grpc_collection_list(self, req, context) -> dict:
        proxied = await self._proxy_to_leader("CollectionList", req)
        if proxied is not None:
            return proxied
        return {"collections": [{"name": c} for c in self.topo.collections]}

    async def _grpc_collection_delete(self, req, context) -> dict:
        proxied = await self._proxy_to_leader("CollectionDelete", req)
        if proxied is not None:
            return proxied
        name = req.get("name", "")
        for dn in self.topo.data_nodes():
            stub = Stub(grpc_address(dn.url), "volume")
            try:
                await stub.call("DeleteCollection", {"collection": name})
            except Exception:
                pass
        self.topo.delete_collection(name)
        return {}

    async def _grpc_volume_list(self, req, context) -> dict:
        proxied = await self._proxy_to_leader("VolumeList", req)
        if proxied is not None:
            return proxied
        return {
            "topology_info": self.topo.to_info(),
            "volume_size_limit_mb": self.topo.volume_size_limit // (1024 * 1024),
        }

    async def _grpc_lease_admin_token(self, req, context) -> dict:
        """Cluster-wide exclusive admin lock
        (ref master_grpc_server_admin.go:113-131)."""
        now = time.time()
        prev = int(req.get("previous_token", 0))
        if self._admin_token is not None:
            token, ts = self._admin_token
            if now - ts < self.admin_lease_seconds and token != prev:
                return {"error": "already locked"}
        token = int(now * 1e9) & 0x7FFFFFFFFFFFFFFF
        self._admin_token = (token, now)
        return {"token": token, "lock_ts_ns": int(now * 1e9)}

    async def _grpc_release_admin_token(self, req, context) -> dict:
        if self._admin_token and self._admin_token[0] == int(
            req.get("previous_token", 0)
        ):
            self._admin_token = None
        return {}

    async def _grpc_get_configuration(self, req, context) -> dict:
        return {
            "metrics_address": "",
            "metrics_interval_seconds": 15,
        }

    async def _grpc_raft_request_vote(self, req, context) -> dict:
        return await self.raft.handle_request_vote(req)

    async def _grpc_raft_append_entries(self, req, context) -> dict:
        return await self.raft.handle_append_entries(req)

    # ---------------- anti-entropy repair scheduler ----------------
    async def _anti_entropy_loop(self) -> None:
        """Leader-only background repair: scan heartbeat state every few
        pulses, queue findings, dispatch under the concurrency cap."""
        interval = max(self.pulse_seconds * 2, 1.0)
        while not self._shutdown:
            try:
                await asyncio.sleep(interval)
                if not self.is_leader or self._shutdown:
                    continue
                await self.run_anti_entropy_once()
            except asyncio.CancelledError:
                return
            except Exception:
                continue  # scheduler errors must never kill the master

    async def run_anti_entropy_once(self, max_dispatch: Optional[int] = None) -> dict:
        """One scan+dispatch round: detect (silent nodes, missing EC
        shards, quarantined/diverged replicas), merge findings into the
        prioritized queue (fewest-survivors-first), dispatch up to the
        concurrency cap, full-jitter backoff on failures. Returns a
        status dict; also the engine behind `ec.repair.status -run`."""
        if not self.is_leader:
            return {"error": "not leader"}
        live = {
            dn.url
            for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
        }
        ec_states = self.topo.ec_states(live)
        for st in ec_states:
            # expected_total is heartbeat-history and resets with the
            # master: a shard whose EVERY holder died before this leader's
            # first scan would stay invisible. The .vif geometry (cached
            # per vid once a holder answers) is the source of truth.
            total = await self._ec_expected_total(st)
            if total:
                st["total_shards"] = max(int(st["total_shards"]), total)
        replica_states = self.topo.replica_states(live)
        tasks = plan_ec_repairs(ec_states)
        tasks += plan_replica_repairs(replica_states)
        # placement policy (ISSUE 19): existing volumes/EC shards are
        # re-checked against the spread the growth solver promises; the
        # proposed moves queue BEHIND data-loss repairs (PLACEMENT_PRIORITY)
        candidates = self.topo.placement_candidates(live)
        spread_violations, spread_tasks = plan_replica_spread(
            self.topo.placement_states(live), candidates
        )
        ec_violations, ec_spread_tasks = plan_ec_domain_spread(
            ec_states, candidates
        )
        PLACEMENT_VIOLATIONS.set(
            len(spread_violations), kind="replica_spread"
        )
        PLACEMENT_VIOLATIONS.set(len(ec_violations), kind="ec_domain")
        self.placement_violations = spread_violations + ec_violations
        if self.placement_violations:
            from ..util import log

            log.info(
                "anti-entropy: %d placement-policy violation(s), "
                "%d repair move(s) planned",
                len(self.placement_violations),
                len(spread_tasks) + len(ec_spread_tasks),
            )
        tasks += spread_tasks + ec_spread_tasks
        diverged = find_unresolved_divergence(replica_states)
        ANTIENTROPY_DIVERGED.set(len(diverged))
        if diverged:
            from ..util import log

            log.warning(
                "anti-entropy: volumes %s have healthy replicas that "
                "disagree at EQUAL append frontiers — not auto-repairable "
                "(run volume.fsck / re-replicate)", diverged,
            )
        valid_keys = set()
        for t in tasks:
            valid_keys.add(t.key)
            self.repair_queue.offer(t)
        self.repair_queue.prune(valid_keys)
        now = time.monotonic()
        ready = self.repair_queue.pop_ready(
            now, max_dispatch or self.repair_concurrency
        )
        results: list[dict] = []
        ec_ready = [t for t in ready if t.kind == "ec_rebuild"]
        placement = [
            t for t in ready if t.kind in ("placement_move", "ec_placement")
        ]
        other = [
            t
            for t in ready
            if t.kind not in ("ec_rebuild", "placement_move", "ec_placement")
        ]

        # background-plane root span (ISSUE 8), only when the scan found
        # work; the tail-sync/recopy/rebuild RPCs inherit the context so
        # anti-entropy interference is visible next to serving traces
        from ..util import trace

        cm = (
            trace.span_root(
                "anti_entropy.dispatch", plane="repair", tasks=len(ready)
            )
            if ready
            else trace.NULL_SPAN
        )
        with cm:
            # EC: survivor pulls run CONCURRENTLY per task (the cap is how
            # many we popped), then ONE batched rebuild RPC per rebuilder
            # node (PR 3's VolumeEcShardsRebuildBatch fast path — same-loss-
            # pattern volumes share wide device dispatches there)
            t0s = {t.key: time.perf_counter() for t in ec_ready}
            prep = await asyncio.gather(
                *(self._prepare_ec_rebuild(t, live) for t in ec_ready),
                return_exceptions=True,
            )
            prepared: dict[tuple, list] = {}
            for t, outcome in zip(ec_ready, prep):
                if isinstance(outcome, BaseException):
                    REPAIR_SECONDS.observe(
                        time.perf_counter() - t0s[t.key],
                        kind="ec_rebuild", result="error",
                    )
                    self.repair_queue.reschedule_failure(t, time.monotonic())
                    results.append({**t.to_info(), "error": str(outcome)})
                else:
                    prepared.setdefault((outcome, t.collection), []).append(
                        (t, t0s[t.key])
                    )
            # group rebuilds and replica repairs all dispatch concurrently —
            # one slow rebuild must not stall an unrelated critical repair
            await asyncio.gather(
                *(
                    self._dispatch_ec_group(
                        rebuilder, collection, group, results
                    )
                    for (rebuilder, collection), group in prepared.items()
                ),
                *(self._dispatch_replica_task(t, results) for t in other),
                *(self._dispatch_placement_task(t, results) for t in placement),
            )

        self.repair_log = (self.repair_log + results)[-50:]
        return {
            "dispatched": results,
            "queue_depth": self.repair_queue.depth(),
            "live_nodes": sorted(live),
            "diverged_volumes": diverged,
            "placement_violations": self.placement_violations,
        }

    async def _ec_expected_total(self, st: dict) -> int:
        """Authoritative shard count (k+m) for one EC volume from a
        holder's .vif, cached per vid; 0 when no holder answers."""
        vid = int(st["vid"])
        cache = getattr(self, "_ec_geom_cache", None)
        if cache is None:
            cache = self._ec_geom_cache = {}
        if vid in cache:
            return cache[vid]
        holders = sorted({u for urls in st["holders"].values() for u in urls})
        for url in holders:
            try:
                r = await Stub(grpc_address(url), "volume").call(
                    "VolumeEcShardsInfo",
                    {"volume_id": vid, "collection": st.get("collection", "")},
                    timeout=10,
                )
            except Exception:
                continue
            if not r.get("error") and r.get("data_shards"):
                total = int(r["data_shards"]) + int(r.get("parity_shards", 0))
                if len(cache) > 65536:  # runaway-vid backstop
                    cache.clear()
                cache[vid] = total
                return total
        return 0

    async def _dispatch_ec_group(
        self, rebuilder: str, collection: str, group: list, results: list
    ) -> None:
        rstub = Stub(grpc_address(rebuilder), "volume")
        vids = [t.vid for t, _t0 in group]
        try:
            r = await rstub.call(
                "VolumeEcShardsRebuildBatch",
                {"volume_ids": vids, "collection": collection},
                timeout=3600,
            )
        except Exception as e:
            r = {"error": str(e)}
        for t, t0 in group:
            err = r.get("error") or r.get("errors", {}).get(str(t.vid))
            res = r.get("results", {}).get(str(t.vid)) or {}
            rebuilt = res.get("rebuilt_shard_ids", [])
            if not err:
                try:
                    await rstub.call(
                        "VolumeEcShardsMount",
                        {
                            "volume_id": t.vid,
                            "collection": t.collection,
                            "shard_ids": rebuilt,
                        },
                    )
                except Exception as e:
                    err = f"mount rebuilt shards: {e}"
            dt = time.perf_counter() - t0
            if err:
                REPAIR_SECONDS.observe(dt, kind="ec_rebuild", result="error")
                self.repair_queue.reschedule_failure(t, time.monotonic())
                results.append({**t.to_info(), "error": err})
            else:
                REPAIR_SECONDS.observe(dt, kind="ec_rebuild", result="ok")
                results.append(
                    {**t.to_info(), "rebuilder": rebuilder, "rebuilt": rebuilt}
                )

    async def _dispatch_replica_task(self, t, results: list) -> None:
        t0 = time.perf_counter()
        method = (
            "VolumeRepairCopy"
            if t.kind == "replica_recopy"
            else "VolumeTailSync"
        )
        try:
            r = await Stub(grpc_address(t.target), "volume").call(
                method,
                {
                    "volume_id": t.vid,
                    "collection": t.collection,
                    "source_data_node": t.source,
                },
                timeout=3600,
            )
            err = r.get("error")
        except Exception as e:
            err = str(e)
        dt = time.perf_counter() - t0
        if err:
            REPAIR_SECONDS.observe(dt, kind=t.kind, result="error")
            self.repair_queue.reschedule_failure(t, time.monotonic())
            results.append({**t.to_info(), "error": err})
        else:
            REPAIR_SECONDS.observe(dt, kind=t.kind, result="ok")
            results.append({**t.to_info(), "repaired": True})

    async def _dispatch_placement_task(self, t, results: list) -> None:
        """Execute one placement-policy move: replica volumes ride the
        volume.move RPC pair (copy to the better-placed node, then drop
        the source copy — full copy count at every intermediate state);
        EC shards ride the ec.balance move sequence (copy+mount on the
        target, unmount+delete on the source)."""
        t0 = time.perf_counter()
        try:
            if t.kind == "placement_move":
                r = await Stub(grpc_address(t.target), "volume").call(
                    "VolumeCopy",
                    {
                        "volume_id": t.vid,
                        "collection": t.collection,
                        "source_data_node": t.source,
                    },
                    timeout=3600,
                )
                err = r.get("error")
                if not err:
                    r2 = await Stub(grpc_address(t.source), "volume").call(
                        "VolumeDelete", {"volume_id": t.vid}, timeout=600
                    )
                    err = r2.get("error")
            else:  # ec_placement: move one shard out of the hot domain
                sid = int(t.missing[0])
                tstub = Stub(grpc_address(t.target), "volume")
                r = await tstub.call(
                    "VolumeEcShardsCopy",
                    {
                        "volume_id": t.vid,
                        "collection": t.collection,
                        "shard_ids": [sid],
                        "copy_ecx_file": True,
                        "source_data_node": t.source,
                    },
                    timeout=3600,
                )
                err = r.get("error")
                if not err:
                    r = await tstub.call(
                        "VolumeEcShardsMount",
                        {
                            "volume_id": t.vid,
                            "collection": t.collection,
                            "shard_ids": [sid],
                        },
                        timeout=600,
                    )
                    err = r.get("error")
                if not err:
                    sstub = Stub(grpc_address(t.source), "volume")
                    await sstub.call(
                        "VolumeEcShardsUnmount",
                        {"volume_id": t.vid, "shard_ids": [sid]},
                        timeout=600,
                    )
                    await sstub.call(
                        "VolumeEcShardsDelete",
                        {
                            "volume_id": t.vid,
                            "collection": t.collection,
                            "shard_ids": [sid],
                        },
                        timeout=600,
                    )
        except Exception as e:
            err = str(e)
        dt = time.perf_counter() - t0
        if err:
            REPAIR_SECONDS.observe(dt, kind=t.kind, result="error")
            self.repair_queue.reschedule_failure(t, time.monotonic())
            results.append({**t.to_info(), "error": err})
        else:
            REPAIR_SECONDS.observe(dt, kind=t.kind, result="ok")
            results.append({**t.to_info(), "repaired": True})

    async def _master_ec_geometry(
        self, vid: int, collection: str, holders: list[str]
    ) -> tuple[int, int]:
        """(data_shards, parity_shards) from a shard holder's .vif;
        standard 10.4 when nobody answers."""
        for url in holders:
            try:
                r = await Stub(grpc_address(url), "volume").call(
                    "VolumeEcShardsInfo",
                    {"volume_id": vid, "collection": collection},
                )
                if not r.get("error"):
                    return (
                        int(r.get("data_shards") or DATA_SHARDS_COUNT),
                        int(
                            r.get("parity_shards")
                            or TOTAL_SHARDS_COUNT - DATA_SHARDS_COUNT
                        ),
                    )
            except Exception:
                continue
        return DATA_SHARDS_COUNT, TOTAL_SHARDS_COUNT - DATA_SHARDS_COUNT

    async def _prepare_ec_rebuild(self, task, live: set) -> str:
        """Stage one EC rebuild: verify repairability, choose the live
        rebuilder holding the most shards (fewest pulls), and copy it the
        survivors it lacks. Returns the rebuilder url; raises on any
        blocker (the caller reschedules with backoff)."""
        locs = self.topo.lookup_ec_shards(task.vid)
        if locs is None:
            raise LookupError(f"ec volume {task.vid} no longer registered")
        holders: dict[int, list[str]] = {}
        for sid in range(locs.expected_total):
            urls = [dn.url for dn in locs.locations[sid] if dn.url in live]
            if urls:
                holders[sid] = urls
        all_urls = sorted({u for urls in holders.values() for u in urls})
        if not all_urls:
            raise LookupError(f"ec volume {task.vid}: no live holders")
        k, _m = await self._master_ec_geometry(
            task.vid, task.collection, all_urls
        )
        if len(holders) < k:
            raise RuntimeError(
                f"ec volume {task.vid} unrepairable: "
                f"{len(holders)} survivors < {k} data shards"
            )
        by_url: dict[str, set[int]] = {u: set() for u in all_urls}
        for sid, urls in holders.items():
            for u in urls:
                by_url[u].add(sid)
        rebuilder = max(all_urls, key=lambda u: len(by_url[u]))
        rstub = Stub(grpc_address(rebuilder), "volume")
        local = set(by_url[rebuilder])
        for url in all_urls:
            if url == rebuilder:
                continue
            pull = sorted(by_url[url] - local)
            if not pull:
                continue
            r = await rstub.call(
                "VolumeEcShardsCopy",
                {
                    "volume_id": task.vid,
                    "collection": task.collection,
                    "shard_ids": pull,
                    "copy_ecx_file": True,
                    "source_data_node": url,
                },
                timeout=3600,
            )
            if r.get("error"):
                raise IOError(
                    f"pull shards {pull} from {url}: {r['error']}"
                )
            local.update(pull)
        return rebuilder

    async def _grpc_repair_status(self, req, context) -> dict:
        """Repair-plane introspection for `ec.repair.status` (+ `-run` to
        force a scan/dispatch round)."""
        proxied = await self._proxy_to_leader("RepairStatus", req)
        if proxied is not None:
            return proxied
        ran = None
        if req.get("run"):
            ran = await self.run_anti_entropy_once(
                max_dispatch=int(req.get("max_dispatch", 0) or 0) or None
            )
        live = {
            dn.url
            for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
        }
        all_nodes = {dn.url for dn in self.topo.data_nodes()}
        return {
            "auto_repair": self.auto_repair,
            "grace_seconds": self.repair_grace_seconds,
            "queue_depth": self.repair_queue.depth(),
            "queue": self.repair_queue.snapshot(),
            "live_nodes": sorted(live),
            "silent_nodes": sorted(all_nodes - live),
            "recent": self.repair_log[-10:],
            **({"ran": ran} if ran is not None else {}),
        }

    async def _grpc_placement_status(self, req, context) -> dict:
        """Placement-policy introspection for `geo.status` (+ `run` to
        force a fresh anti-entropy scan, which re-plans placement)."""
        proxied = await self._proxy_to_leader("PlacementStatus", req)
        if proxied is not None:
            return proxied
        if req.get("run"):
            await self.run_anti_entropy_once(
                max_dispatch=int(req.get("max_dispatch", 0) or 0) or None
            )
        live = {
            dn.url
            for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
        }
        return {
            "violations": self.placement_violations,
            "nodes": self.topo.placement_candidates(live),
            "queued_moves": [
                t
                for t in self.repair_queue.snapshot()
                if t["kind"] in ("placement_move", "ec_placement")
            ],
        }

    # ---------------- vacuum scheduler (ref topology_vacuum.go, rebuilt in
    # the repair scheduler's shape: heartbeat-ranked queue, concurrency
    # cap, full-jitter backoff, opt-in background loop) ----------------
    async def _auto_vacuum_loop(self) -> None:
        """Leader-only background vacuum: rank candidates off heartbeat
        garbage ratios every few pulses, dispatch under the cap."""
        interval = max(self.pulse_seconds * 4, 2.0)
        while not self._shutdown:
            try:
                await asyncio.sleep(interval)
                if not self.is_leader or self._shutdown:
                    continue
                await self.run_vacuum_once()
            except asyncio.CancelledError:
                return
            except Exception:
                continue  # scheduler errors must never kill the master

    async def run_vacuum_once(
        self,
        garbage_threshold: Optional[float] = None,
        max_dispatch: Optional[int] = None,
        probe_all: bool = False,
    ) -> dict:
        """One scan+dispatch round: candidates from heartbeat-carried
        garbage ratios merge into the highest-garbage-first queue, up to
        the concurrency cap dispatch concurrently (authoritative
        VacuumVolumeCheck -> compact every replica -> commit or cleanup),
        failures back off with full jitter. probe_all enqueues every
        registered volume regardless of heartbeat ratio (forced sweeps:
        the per-replica check still gates the actual compaction)."""
        if not self.is_leader:
            return {"error": "not leader"}
        threshold = (
            self.garbage_threshold
            if garbage_threshold is None
            else garbage_threshold
        )
        if probe_all:
            # forced sweeps enumerate the LAYOUTS (registered at volume
            # allocation), not heartbeat-fed dn.volumes — a volume grown
            # moments ago must still be sweepable (the pre-scheduler
            # /vol/vacuum semantics)
            states = self._layout_vacuum_states()
        else:
            live = {
                dn.url
                for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
            }
            states = self.topo.replica_states(live)
        tasks = plan_vacuums(states, threshold, include_all=probe_all)
        valid_keys = set()
        for t in tasks:
            valid_keys.add(t.key)
            self.vacuum_queue.offer(t)
        # tasks mid-retry (a forced sweep's failure in backoff) survive
        # scans whose plan wouldn't re-justify them — the promised retry
        # must happen; a success or terminal skip removes them normally
        self.vacuum_queue.prune(valid_keys | self.vacuum_queue.retry_keys())
        now = time.monotonic()
        ready = self.vacuum_queue.pop_ready(
            now, max_dispatch or self.vacuum_concurrency
        )
        results: list[dict] = []
        # background-plane root span (ISSUE 8), only when the round
        # actually dispatches (idle scans every few pulses stay out of
        # the flight recorder); the compact/commit RPCs inherit the
        # context, so maintenance I/O lines up against serving traces
        from ..util import trace

        cm = (
            trace.span_root("vacuum.round", plane="vacuum", tasks=len(ready))
            if ready
            else trace.NULL_SPAN
        )
        with cm:
            await asyncio.gather(
                *(
                    self._dispatch_vacuum_task(t, threshold, results)
                    for t in ready
                )
            )
        self.vacuum_log = (self.vacuum_log + results)[-50:]
        return {
            "dispatched": results,
            "queue_depth": self.vacuum_queue.depth(),
            "threshold": threshold,
        }

    def _layout_vacuum_states(self) -> dict:
        """Every registered volume from the layout maps, in the
        `plan_vacuums` shape; garbage ratio pinned to 1.0 so include_all
        ordering is stable — the dispatcher's authoritative
        VacuumVolumeCheck supplies the real number. read_only /
        scrub_corrupt are carried over from the heartbeat-fed volume
        infos when known, so forced sweeps honor the planner's
        quarantine gate too (the volume server also refuses to compact a
        quarantined volume — defense in depth)."""
        states: dict = {}
        for collection in list(self.topo.collections.values()):
            for layout in collection.layouts():
                for vid, nodes in list(layout.vid_to_locations.items()):
                    replicas = []
                    for dn in nodes:
                        info = dn.volumes.get(int(vid), {})
                        replicas.append(
                            {
                                "url": dn.url,
                                "collection": collection.name,
                                "garbage_ratio": 1.0,
                                "read_only": bool(info.get("read_only")),
                                "scrub_corrupt": bool(
                                    info.get("scrub_corrupt")
                                ),
                            }
                        )
                    states[int(vid)] = replicas
        return states

    async def _dispatch_vacuum_task(
        self, t, threshold: float, results: list
    ) -> None:
        """check -> compact (all replicas, concurrently) -> commit/cleanup
        for one queued volume (ref topology_vacuum.go per-volume flow).
        An in-flight set spans all three dispatch paths (auto loop,
        /vol/vacuum, -run) so one master never double-dispatches a
        volume; the volume server's own is_compacting gate covers the
        rest (a refused compact/cleanup errors into backoff here).
        Mutual exclusion with the lifecycle plane is TWO-way: a volume
        mid-conversion must not be compacted (the compaction's
        os.replace of the .dat under a running EC encode would bake a
        mixed-generation shard set), just as the lifecycle dispatcher
        skips volumes mid-vacuum."""
        inflight = self._vacuum_inflight
        if t.vid in inflight or t.vid in self._lifecycle_inflight:
            results.append(
                {**t.to_info(), "skipped": "already dispatching"}
            )
            return
        inflight.add(t.vid)
        try:
            await self._dispatch_vacuum_task_inner(t, threshold, results)
        finally:
            inflight.discard(t.vid)

    async def _dispatch_vacuum_task_inner(
        self, t, threshold: float, results: list
    ) -> None:
        t0 = time.perf_counter()
        nodes = self.topo.lookup(t.collection, t.vid)
        if not nodes:
            results.append({**t.to_info(), "error": "volume not registered"})
            return  # prune/offer re-discovers it if it reappears
        urls = sorted({dn.url for dn in nodes})

        async def rpc(url: str, method: str, timeout: float = 600):
            r = await Stub(grpc_address(url), "volume").call(
                method, {"volume_id": t.vid}, timeout=timeout
            )
            if r.get("error"):
                raise IOError(f"{method} on {url}: {r['error']}")
            return r

        async def cleanup_all() -> None:
            # idempotent shadow sweep; a server with a compact still in
            # flight refuses (it must not lose its own shadow mid-write)
            await asyncio.gather(
                *(
                    Stub(grpc_address(u), "volume").call(
                        "VacuumVolumeCleanup", {"volume_id": t.vid}
                    )
                    for u in urls
                ),
                return_exceptions=True,
            )

        try:
            checks = await asyncio.gather(
                *(rpc(u, "VacuumVolumeCheck", 30) for u in urls)
            )
            ratio = min(float(c.get("garbage_ratio", 0)) for c in checks)
            if ratio < threshold:
                REPAIR_SECONDS.observe(
                    time.perf_counter() - t0, kind="vacuum", result="skipped"
                )
                results.append(
                    {
                        **t.to_info(),
                        "skipped": f"garbage {ratio:.3f} < {threshold}",
                    }
                )
                # a prior PARTIAL failure may have stranded shadows on the
                # replica that kept its garbage — sweep them on the way out
                await cleanup_all()
                return
            # settle EVERY compact before deciding: gather's first-error
            # fast path would fire cleanup while other replicas are still
            # mid-copy, unlinking their shadows under the writer
            compacts = await asyncio.gather(
                *(rpc(u, "VacuumVolumeCompact") for u in urls),
                return_exceptions=True,
            )
            failed = [e for e in compacts if isinstance(e, BaseException)]
            if failed:
                raise IOError("; ".join(str(e) for e in failed[:3]))
        except Exception as e:
            # compaction is all-or-nothing per volume: sweep the shadows
            # everywhere (now that every compact RPC has settled), back
            # off, retry later
            await cleanup_all()
            REPAIR_SECONDS.observe(
                time.perf_counter() - t0, kind="vacuum", result="error"
            )
            self.vacuum_queue.reschedule_failure(t, time.monotonic())
            results.append({**t.to_info(), "error": str(e)})
            return
        commit = await asyncio.gather(
            *(rpc(u, "VacuumVolumeCommit") for u in urls),
            return_exceptions=True,
        )
        errs = [str(e) for e in commit if isinstance(e, BaseException)]
        dt = time.perf_counter() - t0
        if errs:
            REPAIR_SECONDS.observe(dt, kind="vacuum", result="error")
            self.vacuum_queue.reschedule_failure(t, time.monotonic())
            results.append({**t.to_info(), "error": "; ".join(errs[:3])})
        else:
            REPAIR_SECONDS.observe(dt, kind="vacuum", result="ok")
            results.append(
                {
                    **t.to_info(),
                    "compacted": True,
                    "garbage_ratio": round(ratio, 4),
                    "nodes": urls,
                }
            )

    async def _grpc_vacuum_status(self, req, context) -> dict:
        """Vacuum-plane introspection for `volume.vacuum -status` (+ `-run`
        to force a scan/dispatch round), mirroring RepairStatus."""
        proxied = await self._proxy_to_leader("VacuumStatus", req)
        if proxied is not None:
            return proxied
        ran = None
        if req.get("run"):
            ran = await self.run_vacuum_once(
                garbage_threshold=(
                    float(req["garbage_threshold"])
                    if req.get("garbage_threshold") is not None
                    else None
                ),
                max_dispatch=int(req.get("max_dispatch", 0) or 0) or None,
                probe_all=bool(req.get("probe_all")),
            )
        return {
            "auto_vacuum": self.auto_vacuum,
            "garbage_threshold": self.garbage_threshold,
            "queue_depth": self.vacuum_queue.depth(),
            "queue": self.vacuum_queue.snapshot(),
            "recent": self.vacuum_log[-10:],
            **({"ran": ran} if ran is not None else {}),
        }

    # ---------------- lifecycle scheduler (ISSUE 10: the hot→warm plane in
    # the vacuum/repair shape — heartbeat-ranked queues, authoritative
    # per-dispatch re-check, concurrency cap, full-jitter backoff, opt-in
    # background loop; see docs/perf.md "Lifecycle plane") ----------------
    async def _auto_lifecycle_loop(self) -> None:
        """Leader-only background lifecycle: rank candidates off heartbeat
        heat every few pulses, dispatch under the cap."""
        interval = max(self.pulse_seconds * 4, 2.0)
        while not self._shutdown:
            try:
                await asyncio.sleep(interval)
                if not self.is_leader or self._shutdown:
                    continue
                await self.run_lifecycle_once()
            except asyncio.CancelledError:
                return
            except Exception:
                continue  # scheduler errors must never kill the master

    async def run_lifecycle_once(
        self,
        max_dispatch: Optional[int] = None,
        include_all: bool = False,
    ) -> dict:
        """One scan+dispatch round: cold+full healthy volumes queue for
        auto-EC (coldest first), hot EC volumes queue for re-inflation
        (hottest first); up to the concurrency cap dispatch concurrently,
        each behind an authoritative VolumeLifecycleCheck so a volume
        that reheated (or got quarantined) since its heartbeat sample is
        SKIPPED, never converted. Failures back off with full jitter.
        include_all waives the cold/full planner gates (forced sweeps) —
        the dispatcher's heat re-check still applies, and the quarantine
        gate is never waived."""
        if not self.is_leader:
            return {"error": "not leader"}
        cfg = self.lifecycle_config
        live = {
            dn.url
            for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
        }
        states = self.topo.replica_states(live)
        tasks = plan_ec_conversions(
            states, self.topo.volume_size_limit, cfg, include_all=include_all
        )
        ec_states = self.topo.ec_heat_states(live)
        tasks += plan_reinflations(ec_states, cfg)
        # cold tier (ISSUE 14): the coldest band descends to the remote
        # backend; sustained heat climbs back — same queue, same backoff.
        # Recently recalled volumes sit out the offload planner for the
        # holddown window (anti-flap), and entries past it are dropped so
        # the map stays bounded by the churn of one window.
        now_mono = time.monotonic()
        for vid in [
            v
            for v, ts in self._lifecycle_recall_at.items()
            if now_mono - ts >= cfg.offload_holddown_s
        ]:
            del self._lifecycle_recall_at[vid]
        tasks += plan_offloads(
            ec_states, cfg, self._lifecycle_recall_at, now_mono
        )
        tasks += plan_recalls(ec_states, cfg)
        valid_keys = set()
        for t in tasks:
            valid_keys.add(t.key)
            self.lifecycle_queue.offer(t)
        # a task mid-retry survives scans whose plan wouldn't re-justify
        # it (heat drifts between pulses); the promised retry must happen
        self.lifecycle_queue.prune(
            valid_keys | self.lifecycle_queue.retry_keys()
        )
        ready = self.lifecycle_queue.pop_ready(
            time.monotonic(), max_dispatch or self.lifecycle_concurrency
        )
        results: list[dict] = []
        from ..util import trace

        cm = (
            trace.span_root(
                "lifecycle.round", plane="lifecycle", tasks=len(ready)
            )
            if ready
            else trace.NULL_SPAN
        )
        with cm:
            await asyncio.gather(
                *(self._dispatch_lifecycle_task(t, results) for t in ready)
            )
        self.lifecycle_log = (self.lifecycle_log + results)[-50:]
        return {
            "dispatched": results,
            "queue_depth": self.lifecycle_queue.depth(),
            "thresholds": {
                "cold_read_heat": cfg.cold_read_heat,
                "cold_write_heat": cfg.cold_write_heat,
                "hot_read_heat": cfg.hot_read_heat,
                "full_fraction": cfg.full_fraction,
                "offload_read_heat": cfg.offload_read_heat,
                "recall_read_heat": cfg.recall_read_heat,
            },
            "cold_backend": cfg.cold_backend,
        }

    async def _dispatch_lifecycle_task(self, t, results: list) -> None:
        """One queued conversion, guarded by the in-flight sets: a volume
        being vacuumed or already converting is skipped (dropped — the
        next scan re-discovers it if still justified)."""
        if t.vid in self._lifecycle_inflight or t.vid in self._vacuum_inflight:
            results.append({**t.to_info(), "skipped": "already dispatching"})
            return
        self._lifecycle_inflight.add(t.vid)
        direction = {
            "lifecycle_ec": "ec",
            "lifecycle_inflate": "inflate",
            "lifecycle_offload": "offload",
            "lifecycle_recall": "recall",
        }.get(t.kind, "inflate")
        t0 = time.perf_counter()
        try:
            if t.kind == "lifecycle_ec":
                outcome = await self._dispatch_lifecycle_convert(t)
            elif t.kind == "lifecycle_offload":
                outcome = await self._dispatch_lifecycle_offload(t)
            elif t.kind == "lifecycle_recall":
                outcome = await self._dispatch_lifecycle_recall(t)
            else:
                outcome = await self._dispatch_lifecycle_inflate(t)
        except Exception as e:
            LIFECYCLE_CONVERSIONS.inc(direction=direction, result="error")
            REPAIR_SECONDS.observe(
                time.perf_counter() - t0, kind=t.kind, result="error"
            )
            self.lifecycle_queue.reschedule_failure(t, time.monotonic())
            results.append({**t.to_info(), "error": str(e)})
            return
        finally:
            self._lifecycle_inflight.discard(t.vid)
        dt = time.perf_counter() - t0
        if "skipped" in outcome:
            LIFECYCLE_CONVERSIONS.inc(direction=direction, result="skipped")
            REPAIR_SECONDS.observe(dt, kind=t.kind, result="skipped")
        else:
            LIFECYCLE_CONVERSIONS.inc(direction=direction, result="ok")
            REPAIR_SECONDS.observe(dt, kind=t.kind, result="ok")
        results.append({**t.to_info(), **outcome})

    def _lifecycle_gen_geometry(self) -> dict:
        if self.lifecycle_data_shards:
            return {
                "data_shards": self.lifecycle_data_shards,
                "parity_shards": self.lifecycle_parity_shards,
            }
        return {}

    async def _dispatch_lifecycle_convert(self, t) -> dict:
        """hot→warm: authoritative re-check -> seal -> encode on one
        holder -> spread+mount shards (balanced across live nodes) ->
        retire the source volume everywhere. All conversion I/O is tagged
        plane="lifecycle", so it draws from the shared MaintenanceBudget
        and yields under overload pressure."""
        nodes = self.topo.lookup(t.collection, t.vid)
        if not nodes:
            # already converted (the unregister delta is a pulse behind) or
            # deleted: drop the task — error/backoff would retry forever
            return {"skipped": "no longer registered"}
        live = {
            dn.url
            for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
        }
        urls = sorted({dn.url for dn in nodes if dn.url in live})
        if not urls:
            raise LookupError(f"volume {t.vid}: no live holders")
        cfg = self.lifecycle_config

        checks = {}
        for u in urls:
            r = await Stub(grpc_address(u), "volume").call(
                "VolumeLifecycleCheck", {"volume_id": t.vid}, timeout=30
            )
            if r.get("error"):
                if "not found" in r["error"]:
                    return {"skipped": f"gone on {u}"}
                raise IOError(f"lifecycle check on {u}: {r['error']}")
            if r.get("kind") != "volume":
                return {"skipped": "already erasure-coded"}
            checks[u] = r
        if any(c.get("scrub_corrupt") for c in checks.values()):
            return {"skipped": "quarantined"}  # never convert damage
        if any(c.get("is_compacting") for c in checks.values()):
            return {"skipped": "compacting"}
        total_heat = sum(
            float(c.get("read_heat", 0.0)) + float(c.get("write_heat", 0.0))
            for c in checks.values()
        )
        if total_heat > cfg.cold_read_heat + cfg.cold_write_heat:
            return {"skipped": f"actively hot ({total_heat:.2f})"}

        # seal every replica so no write can land mid-encode; remember
        # which were writable so a failed conversion can roll that back
        was_writable = [u for u in urls if not checks[u].get("read_only")]
        source = max(urls, key=lambda u: int(checks[u].get("size", 0)))
        sealed = []
        try:
            for u in urls:
                r = await Stub(grpc_address(u), "volume").call(
                    "VolumeMarkReadonly", {"volume_id": t.vid}
                )
                if r.get("error"):
                    raise IOError(f"seal on {u}: {r['error']}")
                if u in was_writable:
                    sealed.append(u)
            gen_req = {
                "volume_id": t.vid,
                "collection": t.collection,
                "plane": "lifecycle",
                **self._lifecycle_gen_geometry(),
            }
            r = await Stub(grpc_address(source), "volume").call(
                "VolumeEcShardsGenerate", gen_req, timeout=3600
            )
            if r.get("error"):
                raise IOError(f"generate on {source}: {r['error']}")
        except Exception:
            # rollback the seal: a transient failure must not leave the
            # volume read-only forever (retry re-seals)
            for u in sealed:
                try:
                    await Stub(grpc_address(u), "volume").call(
                        "VolumeMarkWritable", {"volume_id": t.vid}
                    )
                except Exception:
                    pass
            raise

        # spread + mount (balanced, like shell ec.encode); from here the
        # shards exist — failures go to backoff WITHOUT unsealing
        from ..shell.ec_common import EcNode, plan_balanced_spread
        from ..storage.erasure_coding import TOTAL_SHARDS_COUNT

        total = (
            self.lifecycle_data_shards + self.lifecycle_parity_shards
        ) or TOTAL_SHARDS_COUNT
        ec_nodes = [
            EcNode(
                url=dn.url,
                free_slots=max(dn.free_space(), 0) * TOTAL_SHARDS_COUNT,
                shards={
                    vid: bits for vid, bits in dn.ec_shards.items()
                },
            )
            for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
        ]
        assignment = plan_balanced_spread(
            ec_nodes, t.vid, list(range(total)), source
        )
        for target, shard_ids in assignment.items():
            tstub = Stub(grpc_address(target), "volume")
            if target != source:
                r = await tstub.call(
                    "VolumeEcShardsCopy",
                    {
                        "volume_id": t.vid,
                        "collection": t.collection,
                        "shard_ids": shard_ids,
                        "copy_ecx_file": True,
                        "source_data_node": source,
                        "plane": "lifecycle",
                    },
                    timeout=3600,
                )
                if r.get("error"):
                    raise IOError(f"copy to {target}: {r['error']}")
            r = await tstub.call(
                "VolumeEcShardsMount",
                {
                    "volume_id": t.vid,
                    "collection": t.collection,
                    "shard_ids": shard_ids,
                },
            )
            if r.get("error"):
                raise IOError(f"mount on {target}: {r['error']}")

        # retire the normal volume on every replica holder: delete WHILE
        # mounted so the .dat/.idx are genuinely destroyed (an unmount
        # first would no-op the delete and leave a stale .dat a later
        # mount scan could resurrect as a writable duplicate); the source
        # keeps its .vif/.heat sidecars for the EC volume at the same base
        for u in urls:
            await Stub(grpc_address(u), "volume").call(
                "VolumeDelete",
                {"volume_id": t.vid, "keep_ec_files": u == source},
            )
        own = assignment.get(source, [])
        await Stub(grpc_address(source), "volume").call(
            "VolumeEcShardsDelete",
            {
                "volume_id": t.vid,
                "collection": t.collection,
                "shard_ids": [i for i in range(total) if i not in own],
            },
        )
        return {
            "converted": "ec",
            "source": source,
            "spread": {u: s for u, s in assignment.items()},
        }

    async def _dispatch_lifecycle_inflate(self, t) -> dict:
        """warm→hot: authoritative heat re-check across shard holders ->
        collect shards on the best-provisioned holder -> decode back to a
        normal .dat/.idx volume -> retire the shards -> re-mount (heat
        seeded with the observed EC heat, so hysteresis survives the
        conversion)."""
        locs = self.topo.lookup_ec_shards(t.vid)
        if locs is None:
            return {"skipped": "no longer registered"}
        live = {
            dn.url
            for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
        }
        by_url: dict[str, set[int]] = {}
        for sid in range(max(locs.expected_total, 1)):
            for dn in locs.locations[sid]:
                if dn.url in live:
                    by_url.setdefault(dn.url, set()).add(sid)
        if not by_url:
            raise LookupError(f"ec volume {t.vid}: no live holders")
        holders = sorted(by_url)
        cfg = self.lifecycle_config

        total_heat = 0.0
        for u in holders:
            r = await Stub(grpc_address(u), "volume").call(
                "VolumeLifecycleCheck", {"volume_id": t.vid}, timeout=30
            )
            if not r.get("error") and r.get("kind") == "ec":
                if int(r.get("offloaded_shards", 0)):
                    # cold tier: decode needs local shard files — the
                    # recall dispatcher (triggered at a lower threshold)
                    # brings them back first, then inflate re-qualifies
                    return {"skipped": f"shards offloaded on {u}"}
                total_heat += float(r.get("read_heat", 0.0))
        if total_heat < cfg.hot_read_heat:
            return {"skipped": f"cooled ({total_heat:.2f})"}

        k, m = await self._master_ec_geometry(t.vid, t.collection, holders)
        target = max(holders, key=lambda u: len(by_url[u]))
        tstub = Stub(grpc_address(target), "volume")
        have = set(by_url[target])
        for u in holders:
            if u == target:
                continue
            pull = sorted(by_url[u] - have)
            if not pull:
                continue
            r = await tstub.call(
                "VolumeEcShardsCopy",
                {
                    "volume_id": t.vid,
                    "collection": t.collection,
                    "shard_ids": pull,
                    "copy_ecx_file": False,
                    "source_data_node": u,
                    "plane": "lifecycle",
                },
                timeout=3600,
            )
            if r.get("error"):
                raise IOError(f"collect shards from {u}: {r['error']}")
            have.update(pull)
        if len([s for s in have if s < k]) < k:
            # some data shard exists nowhere: rebuild it from parity
            r = await tstub.call(
                "VolumeEcShardsRebuild",
                {"volume_id": t.vid, "collection": t.collection},
                timeout=3600,
            )
            if r.get("error"):
                raise IOError(f"rebuild for decode: {r['error']}")
        r = await tstub.call(
            "VolumeEcShardsToVolume",
            {
                "volume_id": t.vid,
                "collection": t.collection,
                "plane": "lifecycle",
            },
            timeout=3600,
        )
        if r.get("error"):
            raise IOError(f"decode on {target}: {r['error']}")
        # retire the shards everywhere, then bring the volume online
        for u in holders:
            ustub = Stub(grpc_address(u), "volume")
            await ustub.call(
                "VolumeEcShardsUnmount",
                {"volume_id": t.vid, "shard_ids": sorted(by_url[u])},
            )
            await ustub.call(
                "VolumeEcShardsDelete",
                {
                    "volume_id": t.vid,
                    "collection": t.collection,
                    "shard_ids": list(range(k + m)),
                },
            )
        r = await tstub.call(
            "VolumeMount",
            {"volume_id": t.vid, "seed_read_heat": round(total_heat, 4)},
        )
        if r.get("error"):
            raise IOError(f"mount on {target}: {r['error']}")
        return {"converted": "volume", "target": target}

    async def _live_ec_holders(self, vid: int) -> Optional[list[str]]:
        """Live shard-holder urls of an EC volume, or None when it is no
        longer registered (the task should drop, not backoff-loop)."""
        locs = self.topo.lookup_ec_shards(vid)
        if locs is None:
            return None
        live = {
            dn.url
            for dn in self.topo.live_data_nodes(self.repair_grace_seconds)
        }
        holders = set()
        for sid in range(max(locs.expected_total, 1)):
            for dn in locs.locations[sid]:
                if dn.url in live:
                    holders.add(dn.url)
        return sorted(holders)

    async def _ec_holder_heat_check(
        self, vid: int, holders: list[str], field: str
    ):
        """Shared authoritative re-check of the offload/recall
        dispatchers: per-holder VolumeLifecycleCheck summed into
        (total_heat, holders whose `field` count is non-zero,
        skip_reason_or_None). A holder that lost the volume is ignored
        (others may still serve); a non-EC answer means the volume left
        the warm tier entirely."""
        total_heat = 0.0
        matching: list[str] = []
        for u in holders:
            r = await Stub(grpc_address(u), "volume").call(
                "VolumeLifecycleCheck", {"volume_id": vid}, timeout=30
            )
            if r.get("error"):
                if "not found" in r["error"]:
                    continue
                raise IOError(f"lifecycle check on {u}: {r['error']}")
            if r.get("kind") != "ec":
                return 0.0, [], "not erasure-coded any more"
            total_heat += float(r.get("read_heat", 0.0))
            if int(r.get(field, 0)):
                matching.append(u)
        return total_heat, matching, None

    async def _dispatch_lifecycle_offload(self, t) -> dict:
        """warm→cold: authoritative heat re-check across shard holders →
        every holder uploads its local shard files to the configured
        remote backend (crash-safe per-shard manifest on each holder).
        ROLLBACK on a mid-flight failure: holders that already offloaded
        are recalled (delete_remote included), so a transient backend
        failure leaves the volume uniformly local and the task retries
        from a clean state — never a half-cold volume wedged in backoff."""
        cfg = self.lifecycle_config
        if not cfg.cold_backend:
            return {"skipped": "no cold backend configured"}
        holders = await self._live_ec_holders(t.vid)
        if holders is None:
            return {"skipped": "no longer registered"}
        if not holders:
            raise LookupError(f"ec volume {t.vid}: no live holders")

        total_heat, with_local, skip = await self._ec_holder_heat_check(
            t.vid, holders, "local_shards"
        )
        if skip is not None:
            return {"skipped": skip}
        if total_heat > cfg.offload_read_heat:
            return {"skipped": f"warmed ({total_heat:.2f})"}
        if not with_local:
            return {"skipped": "already offloaded"}

        attempted: list[str] = []
        offloaded: dict = {}
        total_bytes = 0
        try:
            for u in with_local:
                # append BEFORE the call: a holder that fails mid-burst
                # may have offloaded a shard subset, and the rollback
                # must recall ITS partial progress too — not only the
                # holders that completed
                attempted.append(u)
                r = await Stub(grpc_address(u), "volume").call(
                    "VolumeEcShardsOffload",
                    {
                        "volume_id": t.vid,
                        "collection": t.collection,
                        "backend": cfg.cold_backend,
                        "plane": "lifecycle",
                    },
                    timeout=3600,
                )
                if r.get("error"):
                    raise IOError(f"offload on {u}: {r['error']}")
                offloaded[u] = r.get("offloaded_shard_ids", [])
                total_bytes += int(r.get("bytes", 0))
        except Exception:
            # rollback: bring every attempted holder back fully local so
            # the retry starts from a uniform state (recall is idempotent
            # and crash-safe per shard; a failed rollback leaves the
            # manifest pointing at valid remote copies — still no loss)
            for u in attempted:
                try:
                    await Stub(grpc_address(u), "volume").call(
                        "VolumeEcShardsRecall",
                        {
                            "volume_id": t.vid,
                            "collection": t.collection,
                            "plane": "lifecycle",
                        },
                        timeout=3600,
                    )
                except Exception:
                    pass
            raise
        return {
            "offloaded": offloaded,
            "backend": cfg.cold_backend,
            "bytes": total_bytes,
        }

    async def _dispatch_lifecycle_recall(self, t) -> dict:
        """cold→warm: authoritative heat re-check → every holder recalls
        its offloaded shards back to local disk (download + atomic rename
        + manifest uncommit + remote delete, per shard). Per-holder recall
        walls ride the outcome (and tier_recall_seconds), so the bench can
        disclose recall p99 — the latency a reheating volume pays before
        it serves at local-disk prices again."""
        cfg = self.lifecycle_config
        holders = await self._live_ec_holders(t.vid)
        if holders is None:
            return {"skipped": "no longer registered"}
        if not holders:
            raise LookupError(f"ec volume {t.vid}: no live holders")

        total_heat, with_remote, skip = await self._ec_holder_heat_check(
            t.vid, holders, "offloaded_shards"
        )
        if skip is not None:
            return {"skipped": skip}
        if not with_remote:
            return {"skipped": "already local"}
        if total_heat < cfg.recall_read_heat:
            return {"skipped": f"cooled ({total_heat:.2f})"}

        recalled: dict = {}
        walls: dict = {}
        total_bytes = 0
        for u in with_remote:
            r = await Stub(grpc_address(u), "volume").call(
                "VolumeEcShardsRecall",
                {
                    "volume_id": t.vid,
                    "collection": t.collection,
                    "plane": "lifecycle",
                },
                timeout=3600,
            )
            if r.get("error"):
                # shards already recalled stay local (strictly safer than
                # remote); the failed holder retries via backoff
                raise IOError(f"recall on {u}: {r['error']}")
            recalled[u] = r.get("recalled_shard_ids", [])
            walls[u] = float(r.get("recall_s", 0.0))
            total_bytes += int(r.get("bytes", 0))
        # anti-flap holddown: the bytes just moved hot-ward must not
        # immediately reverse when the heat pulse decays
        self._lifecycle_recall_at[t.vid] = time.monotonic()
        return {
            "recalled": recalled,
            "recall_s": walls,
            "bytes": total_bytes,
        }

    async def _grpc_lifecycle_status(self, req, context) -> dict:
        """Lifecycle-plane introspection for `volume.lifecycle -status`
        (+ `-run` to force a scan/dispatch round), mirroring
        VacuumStatus/RepairStatus."""
        proxied = await self._proxy_to_leader("LifecycleStatus", req)
        if proxied is not None:
            return proxied
        ran = None
        if req.get("run"):
            ran = await self.run_lifecycle_once(
                max_dispatch=int(req.get("max_dispatch", 0) or 0) or None,
                include_all=bool(req.get("include_all")),
            )
        cfg = self.lifecycle_config
        return {
            "auto_lifecycle": self.auto_lifecycle,
            "thresholds": {
                "cold_read_heat": cfg.cold_read_heat,
                "cold_write_heat": cfg.cold_write_heat,
                "hot_read_heat": cfg.hot_read_heat,
                "full_fraction": cfg.full_fraction,
                "offload_read_heat": cfg.offload_read_heat,
                "recall_read_heat": cfg.recall_read_heat,
            },
            "cold_backend": cfg.cold_backend,
            "queue_depth": self.lifecycle_queue.depth(),
            "queue": self.lifecycle_queue.snapshot(),
            "recent": self.lifecycle_log[-10:],
            **({"ran": ran} if ran is not None else {}),
        }

    # ---------------- cold-tier orphan sweep (ISSUE 15 satellite) --------
    async def run_tier_orphan_sweep(
        self,
        backend_name: str = "",
        grace_s: float = 3600.0,
        expected_holders: int = 0,
    ) -> dict:
        """Master-dispatched remote-orphan sweep: collect every remote
        key the live volume servers' `.ctm` manifests still name, list
        the cold backend, and delete objects nothing names — the bytes
        a crash between manifest uncommit and remote delete leaks
        (bytes, never data: an orphan is by construction a copy nothing
        routes reads to). `grace_s` protects in-flight offloads: an
        object younger than the grace window may belong to an upload
        whose manifest commit hasn't happened yet, so it is skipped;
        objects the backend cannot date are only eligible at an
        explicit grace_s<=0.

        Down-holder protection: a disconnected volume server's
        manifests cannot be consulted (its topo registration is gone
        too), so (a) `expected_holders` lets the operator require a
        minimum fleet size before anything is deleted, and (b) a
        candidate key whose volume id is still REGISTERED anywhere in
        the topology is never deleted — a partially-down EC volume's
        remote shards survive even when the manifest-holding node is
        the one that is down. A fully-unreachable volume's objects are
        only protected by grace + expected_holders; run sweeps with the
        fleet healthy."""
        from ..storage.tier_backend import get_backend

        name = backend_name or self.lifecycle_config.cold_backend
        if not name:
            return {"skipped": "no cold backend configured"}
        backend = get_backend(name)
        if backend is None:
            return {"error": f"backend {name!r} not registered"}

        referenced: set[str] = set()
        holders = 0
        data_nodes = self.topo.data_nodes()
        if expected_holders and len(data_nodes) < expected_holders:
            return {
                "error": (
                    f"only {len(data_nodes)} of {expected_holders} "
                    "expected holders connected — a down holder's "
                    "manifests cannot be consulted; refusing to sweep"
                )
            }
        for dn in data_nodes:
            try:
                r = await Stub(grpc_address(dn.url), "volume").call(
                    "VolumeTierManifestKeys", {}, timeout=30
                )
            except Exception as e:
                # an unreachable holder might name keys we cannot see:
                # deleting anything now could orphan ITS manifest —
                # refuse the whole sweep (retry when the node returns)
                return {"error": f"manifest collection from {dn.url}: {e}"}
            holders += 1
            for bname, keys in (r.get("backends") or {}).items():
                # manifests record the RESOLVED backend name
                # ("s3.default"); the operator may have configured the
                # bare-type alias ("s3") — match either, or the whole
                # manifest-reference protection silently nullifies
                if bname in (name, backend.name):
                    referenced.update(str(k) for k in keys)

        loop = asyncio.get_event_loop()
        try:
            listed = await loop.run_in_executor(None, backend.list_keys)
        except Exception as e:
            return {"error": f"backend list: {e}"}
        now = time.time()
        orphans = []
        skipped_young = 0
        skipped_registered = 0
        for obj in listed:
            key = obj.get("key", "")
            if not key or key in referenced:
                continue
            vid, collection = _tier_key_vid(key)
            if vid is not None and (
                self.topo.lookup(collection, vid)
                or self.topo.lookup_ec_shards(vid) is not None
            ):
                # the volume is still REGISTERED: the manifest naming
                # this key may live on a holder that is down right now
                # — never delete what a live volume might recall
                skipped_registered += 1
                continue
            mtime = obj.get("mtime")
            if grace_s > 0 and (mtime is None or now - mtime < grace_s):
                skipped_young += 1
                continue
            orphans.append(key)
        swept = 0
        for key in orphans:
            try:
                await loop.run_in_executor(None, backend.delete_file, key)
                swept += 1
            except Exception:
                pass  # still an orphan; the next sweep retries
        if swept:
            from ..util.metrics import TIER_ORPHANS_SWEPT

            TIER_ORPHANS_SWEPT.inc(swept)
        report = {
            "backend": name,
            "holders": holders,
            "listed": len(listed),
            "referenced": len(referenced),
            "orphans_swept": swept,
            "skipped_young": skipped_young,
            "skipped_registered": skipped_registered,
        }
        self.orphan_sweep_log = (self.orphan_sweep_log + [report])[-10:]
        return report

    async def _grpc_tier_orphan_sweep(self, req, context) -> dict:
        proxied = await self._proxy_to_leader("TierOrphanSweep", req)
        if proxied is not None:
            return proxied
        return await self.run_tier_orphan_sweep(
            backend_name=req.get("backend", ""),
            grace_s=float(req.get("grace_s", 3600.0)),
            expected_holders=int(req.get("expected_holders", 0) or 0),
        )

    # ---------------- vacuum driver (the /vol/vacuum HTTP entry point) ----
    async def vacuum(self, garbage_threshold: float) -> list[dict]:
        """Forced cluster sweep through the scheduler: every registered
        volume is enqueued, the authoritative per-replica check applies
        `garbage_threshold`, and the queue drains in vacuum_concurrency-
        sized waves — a forced sweep must not launch every volume's
        compaction at once (the background-interference storm the cap
        exists to prevent). Tasks a failure pushed into backoff are left
        queued for the background loop / a later call (the queue's
        retry_keys survive scan pruning). Deliberately NOT a loop over
        run_vacuum_once: that would RE-PLAN every wave, re-offering the
        tasks the previous wave already popped and skipped — the drain
        needs plan-once / pop-until-empty semantics."""
        if not self.is_leader:
            return []
        states = self._layout_vacuum_states()
        tasks = plan_vacuums(states, garbage_threshold, include_all=True)
        for t in tasks:
            self.vacuum_queue.offer(t)
        dispatched: list[dict] = []
        while True:
            ready = self.vacuum_queue.pop_ready(
                time.monotonic(), self.vacuum_concurrency
            )
            if not ready:
                break
            await asyncio.gather(
                *(
                    self._dispatch_vacuum_task(t, garbage_threshold, dispatched)
                    for t in ready
                )
            )
        self.vacuum_log = (self.vacuum_log + dispatched)[-50:]
        return [
            {
                "volume_id": d["volume_id"],
                "compacted": bool(d.get("compacted")),
            }
            for d in dispatched
            if "skipped" not in d
        ]
