"""The fast HTTP tier answers a read of a locally mounted EC volume itself
(ISSUE 31): `_fast_read` awaits the coroutine the aiohttp handler awaits and
renders the needle as it renders a plain volume's, so a GET is no longer
replayed against the aiohttp tier over a new loopback connection.

One volume server holds three volumes of the same needles: `healthy` (EC, all
14 shards mounted), `degraded` (EC, shard 0 deleted: every needle lies on it,
nobody holds it, each read reconstructs) and a plain one for the chunks of a
manifest. Every shape is asked of the public port (the fast tier) and of the
internal aiohttp listener, and the two answers are compared."""

import asyncio
import gzip
import json
import threading

import aiohttp
import pytest

from seaweedfs_tpu.client.operation import upload_data
from seaweedfs_tpu.pb import grpc_address
from seaweedfs_tpu.pb.rpc import Stub
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.storage.file_id import format_needle_id_cookie
from seaweedfs_tpu.storage.needle import Needle

from test_cluster import Cluster, assign_retry, free_port_pair
from test_stage_tracing import moved, scrape

COOKIE = 0xEC3100
PLAIN, NAMED, DATED, GONE, ZIPPED, MANIFEST, CHUNK = 1, 2, 3, 4, 5, 6, 7
UNKNOWN = 99
VOLUME_GET = dict(server="volume", operation="GET")
PROXIED = "seaweedfs_tpu_request_proxied_total"
COMPARED = ("Content-Length", "Content-Type", "Etag")


class Live:
    """The cluster on an event loop of its own thread, for the module's life."""

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, daemon=True)
        self.thread.start()
        self.vid = {}
        self.body = {}
        try:
            self.run(self._start(), timeout=240)
        except BaseException:
            self.close()
            raise

    def run(self, coro, timeout=60):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def close(self):
        try:
            self.run(self._stop(), timeout=60)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(10)

    async def _stop(self):
        if getattr(self, "session", None) is not None:
            await self.session.close()
        await self.cluster.stop()

    async def _start(self):
        self.cluster = Cluster(self.tmp_path, n_volume_servers=0)
        self.cluster.master = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await self.cluster.master.start()
        (self.tmp_path / "vol").mkdir()
        self.vs = vs = VolumeServer(
            master=self.cluster.master.address,
            directories=[str(self.tmp_path / "vol")],
            port=free_port_pair(), pulse_seconds=0.2, max_volume_counts=[20],
        )
        await vs.start()
        self.cluster.volume_servers.append(vs)
        for _ in range(100):
            if self.cluster.master.topo.data_nodes():
                break
            await asyncio.sleep(0.1)
        self.session = aiohttp.ClientSession()
        await assign_retry(self.cluster.master.address)  # grows the volumes
        vids = sorted(vs.store.locations[0].volumes)[:3]
        self.vid = dict(zip(("healthy", "degraded", "plain"), vids))
        self.public = vs.address
        self.internal = f"127.0.0.1:{vs._core.internal_port}"
        for name, vid in self.vid.items():
            await self._fill(vid)
            if name == "plain":
                continue
            lost = [0] if name == "degraded" else []
            stub = Stub(grpc_address(vs.address), "volume")
            for rpc, req in (
                ("VolumeMarkReadonly", {"volume_id": vid}),
                ("VolumeEcShardsGenerate", {"volume_id": vid}),
                ("VolumeEcShardsMount", {"volume_id": vid, "shard_ids": list(range(14))}),
                ("VolumeUnmount", {"volume_id": vid}),
                ("VolumeEcShardsUnmount", {"volume_id": vid, "shard_ids": lost}),
                ("VolumeEcShardsDelete", {"volume_id": vid, "shard_ids": lost}),
            ):
                reply = await stub.call(rpc, req, timeout=120)
                assert not reply.get("error"), (rpc, reply)
            # a key deleted after the encode is a tombstone in the .ecx
            async with self.session.delete(
                f"http://{self.public}/{self.fid(name, GONE)}"
            ) as resp:
                assert resp.status < 300, resp.status
        # the master knows both EC volumes before the first read asks it who
        # holds the lost shard
        for _ in range(100):
            if all(
                self.cluster.master.topo.lookup_ec_shards(self.vid[k]) is not None
                for k in ("healthy", "degraded")
            ):
                break
            await asyncio.sleep(0.1)

    def fid(self, volume: str, key: int, cookie: int = 0) -> str:
        return f"{self.vid[volume]},{format_needle_id_cookie(key, cookie or COOKIE + key)}"

    async def _fill(self, vid: int):
        def fid(key):
            return f"{vid},{format_needle_id_cookie(key, COOKIE + key)}"

        def data(key, size):
            return bytes((key * 31 + i * 7) % 251 for i in range(size))

        up = self.session, self.public
        self.body[PLAIN] = data(PLAIN, 3001)
        await upload_data(*up, fid(PLAIN), self.body[PLAIN])
        self.body[NAMED] = data(NAMED, 2002)
        await upload_data(*up, fid(NAMED), self.body[NAMED],
                          filename="a.txt", mime="text/plain")
        self.body[DATED] = data(DATED, 1003)
        await upload_data(*up, fid(DATED), self.body[DATED], params={"ts": 1700000000})
        await upload_data(*up, fid(GONE), data(GONE, 504))
        self.body[ZIPPED] = data(ZIPPED, 4005) * 3
        zipped = Needle(cookie=COOKIE + ZIPPED, id=ZIPPED,
                        data=gzip.compress(self.body[ZIPPED]))
        zipped.set_is_compressed()
        self.vs.store.write_volume_needle(vid, zipped)
        # the manifest's one chunk lives on the plain volume
        self.body[CHUNK] = self.body[MANIFEST] = data(CHUNK, 1507)
        await upload_data(*up, fid(CHUNK), self.body[CHUNK])
        manifest = {"name": "big", "mime": "application/x-big", "size": 1507, "chunks": [
            {"fid": f"{self.vid['plain']},{format_needle_id_cookie(CHUNK, COOKIE + CHUNK)}",
             "offset": 0, "size": 1507}]}
        await upload_data(*up, fid(MANIFEST), json.dumps(manifest).encode(),
                          params={"cm": "true"})

    async def ask(self, where: str, method: str, path: str, headers=None) -> tuple:
        async with self.session.request(
            method, f"http://{where}/{path}", headers=headers or {}
        ) as resp:
            return resp.status, await resp.read(), dict(resp.headers)

    async def both(self, method: str, path: str, headers=None) -> tuple:
        """-> (the public port's answer, the aiohttp listener's, how many
        requests the fast tier replayed to give its own)."""
        before = scrape()
        fast = await self.ask(self.public, method, path, headers)
        proxied = moved(before, scrape(), PROXIED, server="volume")
        return fast, await self.ask(self.internal, method, path, headers), proxied


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    served = Live(tmp_path_factory.mktemp("fast_ec"))
    yield served
    served.close()


def same_answer(fast: tuple, cold: tuple) -> None:
    assert fast[0] == cold[0], (fast[0], cold[0])
    assert fast[1] == cold[1]
    for header in COMPARED:
        a, b = fast[2].get(header), cold[2].get(header)
        if header == "Content-Type" and fast[0] != 200:
            # an error's JSON: aiohttp names the charset, the fast tier's
            # pre-rendered answers never have (a plain volume's 404 either)
            a, b = a.split(";")[0], b.split(";")[0]
        assert a == b, (header, a, b)


SHAPES = {
    # name: (method, key, cookie or 0 for the right one, status, the body's key)
    "get": ("GET", PLAIN, 0, 200, PLAIN),
    "head": ("HEAD", PLAIN, 0, 200, None),
    "name_and_mime": ("GET", NAMED, 0, 200, NAMED),
    "head_name_and_mime": ("HEAD", NAMED, 0, 200, None),
    "last_modified": ("GET", DATED, 0, 200, DATED),
    "wrong_cookie": ("GET", PLAIN, 0xBAD, 404, None),
    "unknown_key": ("GET", UNKNOWN, 0, 404, None),
    "tombstoned_key": ("GET", GONE, 0, 404, None),
}


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("volume", ["healthy", "degraded"])
def test_the_fast_tier_answers_as_the_aiohttp_tier_does(live, volume, shape):
    method, key, cookie, status, body_of = SHAPES[shape]
    fast, cold, proxied = live.run(live.both(method, live.fid(volume, key, cookie)))
    assert proxied == 0, "the fast tier replayed a read it should answer itself"
    assert fast[0] == status
    if body_of is not None:
        assert fast[1] == live.body[body_of]
    if shape == "name_and_mime":
        assert fast[2]["Content-Type"] == "text/plain"
    if shape == "last_modified":
        assert fast[2]["Last-Modified-Ts"] == cold[2]["Last-Modified-Ts"] == "1700000000"
    same_answer(fast, cold)


FALLBACKS = {
    # name: (key, query, request headers, status, the body's key, its slice)
    "range": (PLAIN, "", {"Range": "bytes=10-109"}, 206, PLAIN, slice(10, 110)),
    "cm_false_query": (PLAIN, "?cm=false", {}, 200, PLAIN, slice(None)),
    "compressed_needle": (ZIPPED, "", {}, 200, ZIPPED, slice(None)),
    "chunk_manifest": (MANIFEST, "", {}, 200, MANIFEST, slice(None)),
}


@pytest.mark.parametrize("shape", FALLBACKS)
@pytest.mark.parametrize("volume", ["healthy", "degraded"])
def test_what_the_fast_tier_does_not_cover_still_reaches_the_aiohttp_tier(live, volume, shape):
    key, query, headers, status, body_of, part = FALLBACKS[shape]
    fast, cold, proxied = live.run(live.both("GET", live.fid(volume, key) + query, headers))
    assert proxied == 1
    assert fast[0] == status and fast[1] == live.body[body_of][part]
    if shape == "range":
        assert fast[2]["Content-Range"] == f"bytes 10-109/{len(live.body[PLAIN])}"
    if shape == "chunk_manifest":
        assert fast[2]["X-File-Store"] == "chunked"
    same_answer(fast, cold)


def test_a_degraded_get_is_observed_once_and_replayed_never(live):
    before = scrape()
    status, body, _headers = live.run(live.ask(live.public, "GET", live.fid("degraded", PLAIN)))
    after = scrape()
    assert status == 200 and body == live.body[PLAIN]
    assert moved(before, after, PROXIED, server="volume") == 0
    assert moved(before, after, "seaweedfs_tpu_request_seconds_count", **VOLUME_GET) == 1
    assert moved(before, after, "seaweedfs_tpu_request_total", **VOLUME_GET) == 1
    assert moved(before, after, "seaweedfs_tpu_read_stage_seconds_count", stage="ec_read") == 1
    assert moved(before, after, "seaweedfs_tpu_read_stage_seconds_sum", stage="ec_read") > 0
    assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total") == 1


def test_an_exception_inside_the_read_gives_the_aiohttp_tiers_answer(live, monkeypatch):
    """A shard file that fails under the read (an `OSError` out of
    `_read_one_ec_interval`) is no case the fast tier understands: the request
    is replayed, and the aiohttp tier's status is what the client gets."""
    calls = []

    async def broken(ev, shard_id, offset, size, key, deadline=None, recovered=None):
        calls.append(shard_id)
        raise OSError("the shard's file is gone")

    monkeypatch.setattr(live.vs, "_read_one_ec_interval", broken)
    before = scrape()
    fast, cold, proxied = live.run(live.both("GET", live.fid("healthy", PLAIN)))
    after = scrape()
    assert proxied == 1 and len(calls) == 3  # the fast tier, its replay, the direct ask
    assert fast[0] == cold[0] == 500
    same_answer(fast, cold)
    assert moved(before, after, "seaweedfs_tpu_read_stage_seconds_count", stage="ec_read") == 0


def test_fewer_than_ten_survivors_is_a_404_on_both_tiers(live):
    """Short of survivors `read_ec_needle` returns None and raises nothing:
    the fast tier's own 404, equal to the aiohttp tier's."""

    async def scenario():
        vid, more = live.vid["degraded"], [1, 2, 3, 4]
        stub = Stub(grpc_address(live.vs.address), "volume")
        await stub.call("VolumeEcShardsUnmount", {"volume_id": vid, "shard_ids": more})
        live.vs._ec_degraded_cache().invalidate(vid)
        try:
            return await live.both("GET", live.fid("degraded", PLAIN))
        finally:
            await stub.call("VolumeEcShardsMount", {"volume_id": vid, "shard_ids": more})

    fast, cold, proxied = live.run(scenario(), timeout=120)
    assert proxied == 0 and fast[0] == 404
    same_answer(fast, cold)
    status, body, _headers = live.run(live.ask(live.public, "GET", live.fid("degraded", PLAIN)))
    assert status == 200 and body == live.body[PLAIN]  # remounted: served again


@pytest.mark.parametrize("client", ["reads_the_answer", "drops_mid_read"])
def test_the_admission_slot_is_released_after_a_fast_tier_ec_read(live, monkeypatch, client):
    gate = live.vs._core.gate
    assert gate is not None and gate.inflight == 0
    inside, go_on = threading.Event(), threading.Event()
    read = live.vs.read_ec_needle
    held = []

    async def slow(ev, key):
        held.append(gate.inflight)
        inside.set()
        while not go_on.is_set():
            await asyncio.sleep(0.01)
        return await read(ev, key)

    monkeypatch.setattr(live.vs, "read_ec_needle", slow)
    host, port = live.public.split(":")

    async def send():
        reader, writer = await asyncio.open_connection(host, int(port))
        path = live.fid("degraded", PLAIN)
        writer.write(f"GET /{path} HTTP/1.1\r\nHost: {live.public}\r\n\r\n".encode())
        await writer.drain()
        return reader, writer

    reader, writer = live.run(send())
    assert inside.wait(10)
    assert held == [1] and gate.inflight == 1  # the slot is held for the service

    async def finish():
        if client == "drops_mid_read":
            writer.close()
            await writer.wait_closed()
            for _ in range(500):
                if gate.inflight == 0:
                    break
                await asyncio.sleep(0.01)
            return None
        go_on.set()
        head = await reader.readuntil(b"\r\n\r\n")
        writer.close()
        return head

    limiter = gate.limiter
    samples = limiter._n
    try:
        head = live.run(finish())
    finally:
        go_on.set()
    assert gate.inflight == 0
    if client == "reads_the_answer":
        assert head.startswith(b"HTTP/1.1 200 OK")
        # its service wall is a sample of the AIMD limiter, as a plain read's is
        assert limiter._n == (samples + 1) % limiter.window
    else:
        assert limiter._n == samples  # a read nobody waited for is no sample


def test_a_sampled_degraded_get_is_one_root_with_the_ec_stages_under_it(live):
    """The flight recorder's root is the fast tier's (none is dropped for a
    replay, no `tier="cold"` span joins it) and the read's stages hang off it."""
    from seaweedfs_tpu.util import trace

    rec = trace.RECORDER
    live.vs._ec_degraded_cache().invalidate(live.vid["degraded"])
    rec.configure(enabled=True, sample=1.0)
    try:
        status, body, _headers = live.run(
            live.ask(live.public, "GET", live.fid("degraded", PLAIN)))
        spans = [s for s in rec.spans() if s["name"].startswith(("volume:", "ec.read."))]
    finally:
        rec.configure()
    assert status == 200 and body == live.body[PLAIN]
    roots = [s for s in spans if s["name"] == "volume:GET"]
    assert len(roots) == 1 and roots[0]["parent"] is None
    assert roots[0].get("tags", {}).get("tier") != "cold"
    stages = {s["name"]: s for s in spans if s["name"].startswith("ec.read.")}
    assert {"ec.read.remote_attempts", "ec.read.survivor_read", "ec.read.cache_put"} <= set(stages)
    by_id = {s["span"]: s for s in spans}
    for s in stages.values():
        assert s["trace"] == roots[0]["trace"]
        while s["parent"] != roots[0]["span"]:  # a leaf under a stage under the root
            s = by_id[s["parent"]]


def test_the_fault_seam_sees_a_fast_tier_ec_read_once(live):
    """`nth=2` of `http:GET` on the public address is the second GET: the
    plan is consulted once a request, on the fast tier, and never by a replay."""
    from seaweedfs_tpu.util import faults

    faults.install_plan(faults.FaultPlan(rules=[
        faults.FaultRule(op="http:GET", target=live.public, nth=2,
                         fault="http_error", status=507),
    ]))
    try:
        answers = [
            live.run(live.ask(live.public, "GET", live.fid("degraded", PLAIN)))[0]
            for _ in range(3)
        ]
    finally:
        faults.clear_plan()
    assert answers == [200, 507, 200]
