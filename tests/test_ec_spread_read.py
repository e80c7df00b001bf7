"""An EC volume spread over four volume servers by the shell's `ec.encode`,
held to the benchmark's plain reference (ISSUE 33): the spread against
`benchmarks/reference/ec_spread.py`, a read of a shard another server holds
(`VolumeEcShardRead`, outcome `served`), the reads after one holder is lost
(a reconstruct whose survivors cross gRPC, equal to the reference codec's
reconstruction from the ten survivors the plan names), the clean failure after
two are lost, a holder that answers a short span, and the counters and stages
of the remote survivor read against what the test counts by hand. The
survivors one holder lists ride ONE `VolumeEcShardRead` stream (ISSUE 34):
streams and spans counted apart, a holder that does not know `shard_ids`, a
shard of a group that does not arrive, a tombstone, and a request without
`shard_ids` answered message for message as the parent's handler answers it.

Three clusters for the module's life, each on an event loop of its own thread:
every server up, one holder stopped, two holders stopped. Every test awaits
its coroutine with a time limit of its own.
"""

import asyncio
import errno
import os
import random
import threading

import aiohttp
import numpy as np
import pytest

from benchmarks.lib import common, metrics as layer_metrics
from benchmarks.reference import ec_spread, rs_codec
from seaweedfs_tpu import native
from seaweedfs_tpu.client.operation import upload_data
from seaweedfs_tpu.pb import grpc_address
from seaweedfs_tpu.server import volume_ec
from seaweedfs_tpu.shell import CommandEnv, run_command
from seaweedfs_tpu.storage.erasure_coding import to_ext
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolumeShard, NeedleNotFound
from seaweedfs_tpu.storage.file_id import format_needle_id_cookie
from seaweedfs_tpu.types import TOMBSTONE_FILE_SIZE
from seaweedfs_tpu.util import trace

from test_cluster import Cluster, assign_retry
from test_degraded_read_cache import _Host, _make_ec_volume
from test_stage_tracing import moved, scrape

K, M = 10, 4
SERVERS = 4
NEEDLES = 16
COOKIE = 0xEC3200
SPAN = volume_ec.EC_REMOTE_SPAN  # a reconstruct with survivors on other servers reads this far ahead
IDX_ENTRY = np.dtype([("key", ">u8"), ("off", ">u4"), ("size", ">u4")])
READS = "seaweedfs_tpu_ec_remote_shard_reads_total"
READ_BYTES = "seaweedfs_tpu_ec_remote_shard_read_bytes_total"
STREAMS = "seaweedfs_tpu_ec_remote_shard_read_streams_total"
STAGE = "seaweedfs_tpu_ec_degraded_read_stage_seconds_total"
ATTEMPTS = "seaweedfs_tpu_ec_remote_attempts_total"
SERVED = "seaweedfs_tpu_request_seconds_count"
SERVED_BYTES = "seaweedfs_tpu_ec_shard_read_served_bytes_total"
COPY_BYTES = "seaweedfs_tpu_ec_shard_copy_bytes_total"
COPY_SECONDS = "seaweedfs_tpu_ec_shard_copy_seconds_total"
SHARD_READ = dict(server="volume", operation="VolumeEcShardRead")
LOCAL_READS = "seaweedfs_tpu_ec_reconstruct_local_reads_total"
FILLS = "seaweedfs_tpu_ec_reconstruct_fills_total"
SURVIVOR_BYTES = "seaweedfs_tpu_ec_reconstruct_survivor_bytes_total"


class Spread:
    """Four volume servers, one volume of 16 needles over every data shard,
    `ec.encode` through the shell, then `lose` holders stopped."""

    def __init__(self, tmp_path, lose: int, sizes=None):
        # `sizes`: {key: body bytes}; by default 16 needles of 0.7 MB
        self.tmp_path, self.lose = tmp_path, lose
        self.sizes = sizes or {key: 700_000 + 1_001 * key for key in range(1, NEEDLES + 1)}
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, daemon=True)
        self.thread.start()
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
        for vs in self.cluster.volume_servers:
            if vs not in self.stopped:
                await vs.stop()
        self.cluster.volume_servers = []
        await self.cluster.stop()

    def fid(self, key: int) -> str:
        return f"{self.vid},{format_needle_id_cookie(key, COOKIE + key)}"

    async def _start(self):
        self.stopped = []
        self.cluster = Cluster(self.tmp_path, n_volume_servers=SERVERS)
        await self.cluster.start()
        self.session = aiohttp.ClientSession()
        master = self.cluster.master
        ar = await assign_retry(master.address)
        self.vid = int(ar.fid.split(",")[0])
        self.source = self.cluster.server_for(ar.url)
        rng = random.Random(32)
        self.body = {key: rng.randbytes(size) for key, size in self.sizes.items()}
        for key, data in self.body.items():
            await upload_data(self.session, ar.url, self.fid(key), data)
        base = os.path.join(self.source.store.locations[0].directory, str(self.vid))
        self.dat_bytes = os.path.getsize(base + ".dat")
        # where each record starts: the .idx as written, read without the program
        idx = np.fromfile(base + ".idx", dtype=IDX_ENTRY)
        self.offset = {int(e["key"]): int(e["off"]) * 8 for e in idx}
        env = CommandEnv(master.address)
        for _ in range(100):
            nodes = await env.collect_data_nodes()
            if any(int(v["id"]) == self.vid for dn in nodes for v in dn.get("volumes", [])):
                break
            await asyncio.sleep(0.1)
        assert await run_command(env, "lock") == "locked"
        before = scrape()
        out = await run_command(env, f"ec.encode -volumeId {self.vid}")
        self.encode_moved = (before, scrape())
        assert "encoded" in out, out
        assert await run_command(env, "unlock") == "unlocked"
        self.dat_left = os.path.exists(base + ".dat")
        await self.until(lambda: len(self.holders()) == K + M, "a holder for every shard")
        self.spread = self.holders()
        self.lost_shards = set()
        for _ in range(self.lose):
            # the holder of the lowest data shard that is neither on the
            # source nor lost already
            shard = min(s for s in range(K) if self.spread[s] != [self.source.address]
                        and s not in self.lost_shards)
            vs = self.cluster.server_for(self.spread[shard][0])
            self.lost_shards |= {s for s, at in self.spread.items() if at == [vs.address]}
            await vs.stop()
            self.stopped.append(vs)
        await self.until(lambda: not self.lost_shards & set(self.holders()),
                         "answer of the master without the stopped servers")
        self.live = [vs for vs in self.cluster.volume_servers if vs not in self.stopped]
        for vs in self.live:  # the table after it refreshes, not up to a TTL later
            await vs._refresh_shard_locations(vs.store.find_ec_volume(self.vid), force=True)

    async def until(self, good, what: str, limit_s: float = 20):
        for _ in range(int(limit_s / 0.1)):
            if good():
                return
            await asyncio.sleep(0.1)
        raise AssertionError(f"no {what} within {limit_s} s")

    def holders(self) -> dict:
        """{shard: [url]} as the master's topology has it now."""
        locs = self.cluster.master.topo.lookup_ec_shards(self.vid)
        if locs is None:
            return {}
        return {s: [dn.url for dn in nodes] for s, nodes in enumerate(locs.locations) if nodes}

    def shards_on(self, vs) -> set:
        return {s for s, at in self.spread.items() if at == [vs.address]}

    def start_shard(self, key: int) -> int:
        return ec_spread.locate(self.offset[key], 1, self.dat_bytes, K)[0][0]

    def key_on(self, shards) -> int:
        """A needle whose record starts on one of `shards`."""
        return next(k for k in sorted(self.body) if self.start_shard(k) in shards)

    def shard_file(self, shard: int) -> str:
        vs = self.cluster.server_for(self.spread[shard][0])
        return os.path.join(vs.store.locations[0].directory, f"{self.vid}.ec{shard:02d}")

    async def get(self, vs, key: int) -> tuple:
        async with self.session.get(f"http://{vs.address}/{self.fid(key)}") as resp:
            return resp.status, await resp.read()


@pytest.fixture(scope="module")
def healthy(tmp_path_factory):
    cluster = Spread(tmp_path_factory.mktemp("spread0"), lose=0)
    yield cluster
    cluster.close()


@pytest.fixture(scope="module")
def one_lost(tmp_path_factory):
    cluster = Spread(tmp_path_factory.mktemp("spread1"), lose=1)
    yield cluster
    cluster.close()


@pytest.fixture(scope="module")
def two_lost(tmp_path_factory):
    cluster = Spread(tmp_path_factory.mktemp("spread2"), lose=2)
    yield cluster
    cluster.close()


# ------------------------------------------------------------------ the spread
def test_spread_is_the_references(healthy):
    nodes = [vs.address for vs in healthy.cluster.volume_servers]
    assert ec_spread.judge_spread(healthy.spread, nodes, K + M) == {
        "shards_unplaced": 0, "shards_doubled": 0, "spread_uneven": 0}
    counts = sorted((sum(at == [n] for at in healthy.spread.values()) for n in nodes),
                    reverse=True)
    assert counts == ec_spread.balanced_counts(K + M, SERVERS) == [4, 4, 3, 3]


def test_every_shard_is_mounted_once_and_the_dat_is_gone(healthy):
    assert not healthy.dat_left
    for shard in range(K + M):
        mounted = [vs.address for vs in healthy.cluster.volume_servers
                   if vs.store.find_ec_shard(healthy.vid, shard) is not None]
        assert mounted == healthy.spread[shard], shard
        assert os.path.exists(healthy.shard_file(shard))


def test_the_copy_is_counted_on_the_servers_that_pulled(healthy):
    before, after = healthy.encode_moved
    copied = [s for s in range(K + M) if healthy.spread[s] != [healthy.source.address]]
    shard_bytes = rs_codec.shard_size(healthy.dat_bytes, K)
    assert os.path.getsize(healthy.shard_file(copied[0])) == shard_bytes
    pulled = moved(before, after, COPY_BYTES)
    # the shard files, and an .ecx, .ecj and .vif for each of the three targets
    assert len(copied) * shard_bytes < pulled < len(copied) * shard_bytes + (1 << 20)
    assert moved(before, after, COPY_SECONDS) > 0


# ------------------------------------------------- every server up: `served`
@pytest.mark.parametrize("through", range(SERVERS))
def test_every_needle_reads_through_every_server(healthy, through):
    vs = healthy.cluster.volume_servers[through]

    async def body():
        before = scrape()
        for key, data in healthy.body.items():
            assert await healthy.get(vs, key) == (200, data), key
        return before, scrape()

    before, after = healthy.run(body(), timeout=120)
    # most needles lie on shards of other servers: their holders answered
    assert moved(before, after, ATTEMPTS, outcome="served") > 0
    assert moved(before, after, ATTEMPTS, outcome="failed") == 0
    assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total") == 0


def test_a_remote_interval_is_counted_where_it_is_served(healthy):
    vs = healthy.source
    key = healthy.key_on(set(range(K)) - healthy.shards_on(vs))
    record = ec_spread.locate(healthy.offset[key], len(healthy.body[key]), healthy.dat_bytes, K)
    remote = [length for shard, _off, length in record if shard not in healthy.shards_on(vs)]

    async def body():
        before = scrape()
        assert await healthy.get(vs, key) == (200, healthy.body[key])
        return before, scrape()

    before, after = healthy.run(body())
    # the body's bytes are inside the record; the record's header and tail
    # add at most one more interval
    assert len(remote) <= moved(before, after, ATTEMPTS, outcome="served") <= len(remote) + 1
    assert moved(before, after, SERVED, **SHARD_READ) == moved(before, after, ATTEMPTS, outcome="served")
    assert moved(before, after, SERVED_BYTES) >= sum(remote)
    # a healthy read asks for no survivor
    assert moved(before, after, READS) == 0


# -------------------------------------------------------------- one holder lost
def test_the_master_names_only_live_holders(one_lost):
    now = one_lost.holders()
    live = {vs.address for vs in one_lost.live}
    assert len(one_lost.lost_shards) in (3, 4) and len(one_lost.lost_shards) <= M
    assert not one_lost.lost_shards & set(now)
    assert all(set(at) <= live for at in now.values())
    assert len(now) == K + M - len(one_lost.lost_shards)


@pytest.mark.parametrize("through", range(SERVERS - 1))
def test_every_needle_reads_back_with_one_holder_lost(one_lost, through):
    vs = one_lost.live[through]

    async def body():
        vs._ec_degraded_cache().invalidate(one_lost.vid)
        before = scrape()
        for key, data in one_lost.body.items():
            assert await one_lost.get(vs, key) == (200, data), key
        return before, scrape()

    before, after = one_lost.run(body(), timeout=120)
    assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total", kind="cold") > 0
    assert moved(before, after, READS, outcome="ok") > 0  # survivors crossed gRPC
    assert moved(before, after, READS, outcome="failed") == 0
    assert moved(before, after, ATTEMPTS, outcome="no_holder") > 0


def plan(cluster, vs, missing: int) -> list:
    """The ten survivors the read path's plan names on `vs`: its own shards,
    then, of the shards the table lists a holder for, the lowest, as many
    as the decode still needs."""
    own = sorted(cluster.shards_on(vs) - {missing})
    listed = sorted(set(range(K + M)) - cluster.lost_shards - set(own) - {missing})
    return sorted(own + listed[: K - len(own)])


def streams_of(cluster, vs, missing: int) -> tuple:
    """(grouped, single): the streams one reconstruct on `vs` sends by hand —
    the planned survivors on other servers by their holder, two or more of
    one holder in one stream. `grouped` is {holder: [shards]}."""
    by_holder = {}
    for s in plan(cluster, vs, missing):
        if s not in cluster.shards_on(vs):
            by_holder.setdefault(cluster.spread[s][0], []).append(s)
    grouped = {url: shards for url, shards in by_holder.items() if len(shards) > 1}
    return grouped, len(by_holder) - len(grouped)


def test_a_reconstruct_is_the_reference_codecs(one_lost):
    vs = one_lost.source
    ev = vs.store.find_ec_volume(one_lost.vid)
    missing = min(one_lost.lost_shards)
    key = one_lost.key_on({missing})
    shard, offset, length = ec_spread.locate(one_lost.offset[key], 4096, one_lost.dat_bytes, K)[0]
    assert shard == missing

    async def body():
        vs._ec_degraded_cache().invalidate(one_lost.vid)
        return await vs._recover_one_interval(ev, missing, offset, length, key)

    got = one_lost.run(body())
    survivors = plan(one_lost, vs, missing)
    assert len(survivors) == K and not set(survivors) & one_lost.lost_shards

    def span(s):
        with open(one_lost.shard_file(s), "rb") as f:
            f.seek(offset)
            return np.frombuffer(f.read(length), dtype=np.uint8)

    want = rs_codec.Codec(K, M).recover({s: span(s) for s in survivors}, [missing])[0]
    assert got == want.tobytes()
    assert got == span(missing).tobytes()  # the stopped server's file is still on its disk


def test_local_and_remote_survivors_land_in_the_rows_of_one_array(one_lost, monkeypatch):
    """What the codec is handed for a reconstruct on a server that holds four
    of the ten survivors: each survivor's own span under its shard id, the
    four read from disk and the six that crossed gRPC alike, and the ten of
    them the consecutive rows of ONE array, lowest shard id first."""
    from seaweedfs_tpu.ops.rs_kernel import rows_of_one_array

    vs = one_lost.source
    ev = vs.store.find_ec_volume(one_lost.vid)
    missing = min(one_lost.lost_shards)
    survivors = plan(one_lost, vs, missing)
    codec = vs.codec_for(K, M)
    handed = []
    inner = codec.reconstruct_rows

    def reconstruct_rows(shards, wanted, *args, **kw):
        sub = rows_of_one_array([s for s in shards if s is not None])
        handed.append(([None if s is None else bytes(s) for s in shards], list(wanted),
                       None if sub is None else sub.shape))
        return inner(shards, wanted, *args, **kw)

    monkeypatch.setattr(codec, "reconstruct_rows", reconstruct_rows, raising=False)
    off = 7 * SPAN + 300

    async def body():
        vs._ec_degraded_cache().invalidate(one_lost.vid)
        return await vs._recover_one_interval(ev, missing, off, 1000, 1)

    before = scrape()
    got = one_lost.run(body())
    after = scrape()
    (shards, wanted, shape), = handed
    assert wanted == [missing] and shape is not None and shape[0] == K and shape[1] >= SPAN

    def span(s):
        with open(one_lost.shard_file(s), "rb") as f:
            f.seek(7 * SPAN)
            return f.read(SPAN)

    assert [s for s, b in enumerate(shards) if b is not None] == survivors
    for s in survivors:
        assert shards[s] == span(s), s
    assert got == span(missing)[300:1300]
    own = len(one_lost.shards_on(vs) - {missing})
    assert moved(before, after, LOCAL_READS, where="worker") == moved(before, after, LOCAL_READS) == own
    assert moved(before, after, SURVIVOR_BYTES, origin="local") == own * SPAN
    assert moved(before, after, SURVIVOR_BYTES, origin="remote") == (K - own) * SPAN
    # the benchmark's metric file, as a run evaluates it, and on the parent
    spec = common.load("layer_metrics", "ec_read.worker_read_share.json")
    assert layer_metrics.Observed(before, after, {}, {}, {}, {}, None, None, {}).value(spec) == 100.0
    parents = [{k: v for k, v in page.items() if not k.startswith(LOCAL_READS)} for page in (before, after)]
    assert layer_metrics.Observed(*parents, {}, {}, {}, {}, None, None, {}).value(spec) is None


def test_counters_and_stages_of_one_reconstruct_by_hand(one_lost):
    vs = one_lost.source
    ev = vs.store.find_ec_volume(one_lost.vid)
    missing = min(one_lost.lost_shards)
    own = one_lost.shards_on(vs)
    listed = K + M - len(own) - len(one_lost.lost_shards)
    asked = K - len(own)  # what the decode needs of others, none to spare
    ok, nobody = min(asked, listed), max(0, asked - listed)
    assert ok == ec_spread.least_remote_survivors(missing, own, one_lost.lost_shards) and not nobody

    async def body():
        vs._ec_degraded_cache().invalidate(one_lost.vid)
        root = trace.begin_request("volume:GET", None, server="volume")
        data = await vs._recover_one_interval(ev, missing, 0, 100, 1)
        root.finish()
        return data

    rec = trace.RECORDER
    rec.configure(enabled=True, sample=0.0)
    try:
        before = scrape()
        got = one_lost.run(body())
        after = scrape()
        spans = [s for s in rec.spans() if s["name"] == "ec.read.remote_read"]
    finally:
        rec.configure()
    with open(one_lost.shard_file(missing), "rb") as f:
        assert got == f.read(100)
    assert moved(before, after, READS, outcome="ok") == ok
    assert moved(before, after, READS, outcome="no_holder") == nobody
    assert moved(before, after, READS) == ok + nobody
    assert moved(before, after, READ_BYTES) == ok * SPAN
    assert moved(before, after, STAGE, stage="remote_read") > 0
    assert moved(before, after, STAGE, stage="survivor_read") > 0
    # one stream a holder (one a survivor where a holder serves one alone):
    # a span each, naming its shards and its holder
    grouped, single = streams_of(one_lost, vs, missing)
    assert len(grouped) == 2 and sum(map(len, grouped.values())) + single == ok
    named = {s["tags"]["holder"]: [int(x) for x in s["tags"]["shards"].split(",")] for s in spans}
    assert len(spans) == len(named) == len(grouped) + single == len(one_lost.live) - 1
    assert all(one_lost.spread[shard] == [holder] for holder, shards in named.items() for shard in shards)
    assert {h: g for h, g in named.items() if len(g) > 1} == grouped
    assert not {x for g in named.values() for x in g} & (one_lost.lost_shards | own)
    assert moved(before, after, STREAMS, shape="grouped") == len(grouped)
    assert moved(before, after, STREAMS, shape="single") == single
    assert moved(before, after, STREAMS) == len(spans)
    # and on the serving side: one stream a holder, every survivor's span in its bytes
    assert moved(before, after, SERVED, **SHARD_READ) == len(spans) < ok
    assert moved(before, after, SERVED_BYTES) == ok * SPAN


def test_a_holder_that_answers_short_is_not_used(one_lost, monkeypatch):
    vs = one_lost.source
    ev = vs.store.find_ec_volume(one_lost.vid)
    missing = min(one_lost.lost_shards)
    short = next(s for s in plan(one_lost, vs, missing) if s not in one_lost.shards_on(vs))
    holder = one_lost.cluster.server_for(one_lost.spread[short][0])
    target = holder.store.find_ec_shard(one_lost.vid, short)
    inner = EcVolumeShard.read_at
    monkeypatch.setattr(
        EcVolumeShard, "read_at",
        lambda self, size, offset: inner(self, size - 1 if self is target else size, offset))

    async def body():
        vs._ec_degraded_cache().invalidate(one_lost.vid)
        return await vs._recover_one_interval(ev, missing, SPAN, 4096, 1)

    before = scrape()
    got = one_lost.run(body())
    after = scrape()
    if len(one_lost.lost_shards) < M:
        with open(one_lost.shard_file(missing), "rb") as f:
            f.seek(SPAN)
            assert got == f.read(4096)  # right all the same: a second round asked the rest
    else:
        assert got is None  # four shards lost and a fifth unusable: no answer, not a wrong one
    assert moved(before, after, READS, outcome="short") == 1
    assert moved(before, after, READ_BYTES) == moved(before, after, READS, outcome="ok") * SPAN
    # counted short once, as a survivor, though two streams carried it short:
    # its group's, then one of its own (the others of its group were used)
    grouped, _single = streams_of(one_lost, vs, missing)
    was_grouped = any(short in shards for shards in grouped.values())
    assert moved(before, after, STREAMS, shape="grouped") == len(grouped)
    assert moved(before, after, STREAMS, shape="regrouped") == int(was_grouped)


def test_one_reconstruct_sends_one_stream_a_live_holder(one_lost):
    vs = one_lost.source
    ev = vs.store.find_ec_volume(one_lost.vid)
    missing = min(one_lost.lost_shards)
    survivors = plan(one_lost, vs, missing)
    asked = K - len(one_lost.shards_on(vs))
    off = 3 * SPAN + 17

    async def body():
        vs._ec_degraded_cache().invalidate(one_lost.vid)
        return await vs._recover_one_interval(ev, missing, off, 2000, 1)

    before = scrape()
    got = one_lost.run(body())
    after = scrape()

    def span(s):
        with open(one_lost.shard_file(s), "rb") as f:
            f.seek(3 * SPAN)
            return np.frombuffer(f.read(SPAN), dtype=np.uint8)

    want = rs_codec.Codec(K, M).recover({s: span(s) for s in survivors}, [missing])[0]
    assert got == want.tobytes()[17:2017]
    # two live holders, two streams: the spans are counted a survivor, the calls a holder
    assert moved(before, after, SERVED, **SHARD_READ) == 2
    assert moved(before, after, STREAMS, shape="grouped") == moved(before, after, STREAMS) == 2
    assert moved(before, after, READS, outcome="ok") == moved(before, after, READS) == asked >= 6
    assert moved(before, after, READ_BYTES) == moved(before, after, SERVED_BYTES) == asked * SPAN
    assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total", kind="cold") == 1
    # the benchmark's metric file, evaluated as a run evaluates it; a program
    # without the family (the parent) leaves the metric out of the line
    spec = common.load("layer_metrics", "ec_read.remote_streams_per_reconstruct.json")
    assert layer_metrics.Observed(before, after, {}, {}, {}, {}, None, None, {}).value(spec) == 2.0
    parents = [{k: v for k, v in page.items() if not k.startswith(STREAMS)} for page in (before, after)]
    assert layer_metrics.Observed(*parents, {}, {}, {}, {}, None, None, {}).value(spec) is None


async def _parents_shard_read(vs, req):
    """`_grpc_ec_shard_read` as the parent commit has it (4ce3058: it knows
    `shard_id` alone), less its cold-tier branch, which no shard here takes."""
    vid, shard_id = int(req["volume_id"]), int(req["shard_id"])
    offset, size = int(req.get("offset", 0)), int(req.get("size", 0))
    shard = vs.store.find_ec_shard(vid, shard_id)
    if shard is None:
        yield {"error": f"ec shard {vid}.{shard_id} not found"}
        return
    if req.get("file_key"):
        ev = vs.store.find_ec_volume(vid)
        if ev is not None:
            try:
                _, nsize = ev.find_needle_from_ecx(int(req["file_key"]))
                if nsize == TOMBSTONE_FILE_SIZE:
                    yield {"is_deleted": True}
                    return
            except NeedleNotFound:
                pass
    left, pos = size, offset
    while left > 0:
        chunk = shard.read_at(min(1 << 20, left), pos)
        if not chunk:
            break
        yield {"data": chunk}
        pos += len(chunk)
        left -= len(chunk)


class _Routed:
    """`volume_ec.Stub` for a test: a `VolumeEcShardRead` stream to a gRPC
    address in `handlers` is answered on the asking loop by that handler (an
    async generator of the request); every other call crosses the real wire."""

    def __init__(self, handlers: dict):
        self.handlers, self.real, self.sent = handlers, volume_ec.Stub, []

    def __call__(self, address, service, channel=None):
        stub = self.real(address, service, channel)
        handler = self.handlers.get(address)
        if handler is not None:
            def server_stream(method, request, timeout=None):
                assert method == "VolumeEcShardRead"
                self.sent.append(request)
                return handler(request)

            stub.server_stream = server_stream
        return stub


def test_holders_that_ignore_shard_ids_still_give_every_needle_back(one_lost, monkeypatch):
    vs = one_lost.source
    old = {grpc_address(h.address): (lambda req, h=h: _parents_shard_read(h, req))
           for h in one_lost.live if h is not vs}
    wire = _Routed(old)
    monkeypatch.setattr(volume_ec, "Stub", wire)

    async def body():
        vs._ec_degraded_cache().invalidate(one_lost.vid)
        before = scrape()
        for key, data in one_lost.body.items():
            assert await one_lost.get(vs, key) == (200, data), key
        return before, scrape()

    before, after = one_lost.run(body(), timeout=120)
    cold = moved(before, after, "seaweedfs_tpu_ec_reconstructions_total", kind="cold")
    grouped = moved(before, after, STREAMS, shape="grouped")
    assert cold > 0 and grouped == 2 * cold
    assert grouped == sum("shard_ids" in req for req in wire.sent)
    # an old holder serves the group's first shard; the others come each alone
    asked = K - len(one_lost.shards_on(vs))
    assert moved(before, after, STREAMS, shape="regrouped") == (asked - 2) * cold
    assert moved(before, after, READS, outcome="ok") == moved(before, after, READS) == asked * cold


@pytest.mark.parametrize("fault", ["error", "short"])
def test_a_shard_of_a_group_that_does_not_arrive_is_fetched_again_alone(one_lost, monkeypatch, fault):
    vs = one_lost.source
    ev = vs.store.find_ec_volume(one_lost.vid)
    missing = min(one_lost.lost_shards)
    grouped, single = streams_of(one_lost, vs, missing)
    url, group = sorted(grouped.items())[0]
    holder = one_lost.cluster.server_for(url)
    target = holder.store.find_ec_shard(one_lost.vid, group[1])
    # the fault strikes the group's stream alone: one look-up, or the two reads
    # of a stream's loop (a byte short, then nothing for the byte left)
    left = [1 if fault == "error" else 2]

    def struck(shard) -> bool:
        if shard is target and left[0]:
            left[0] -= 1
            return True
        return False

    if fault == "error":  # the holder does not find the shard: `{"shard_id": s, "error": ...}`
        inner = holder.store.find_ec_shard
        monkeypatch.setattr(
            holder.store, "find_ec_shard",
            lambda vid, s: None if struck(inner(vid, s)) else inner(vid, s))
    else:
        inner = EcVolumeShard.read_at
        monkeypatch.setattr(
            EcVolumeShard, "read_at",
            lambda self, size, offset: inner(self, size - struck(self), offset))

    async def body():
        vs._ec_degraded_cache().invalidate(one_lost.vid)
        return await vs._recover_one_interval(ev, missing, 5 * SPAN, 4096, 1)

    before = scrape()
    got = one_lost.run(body())
    after = scrape()
    assert not left[0]
    with open(one_lost.shard_file(missing), "rb") as f:
        f.seek(5 * SPAN)
        assert got == f.read(4096)
    asked = K - len(one_lost.shards_on(vs))
    # every survivor counted once and `ok`: the two that arrived whole were
    # used, the third came over a stream of its own, and no second round ran
    assert moved(before, after, READS, outcome="ok") == moved(before, after, READS) == asked
    assert moved(before, after, READ_BYTES) == asked * SPAN
    assert moved(before, after, STREAMS, shape="grouped") == len(grouped) == 2
    assert moved(before, after, STREAMS, shape="regrouped") == 1
    assert moved(before, after, STREAMS, shape="single") == single == 0
    assert moved(before, after, SERVED, **SHARD_READ) == 3


# ------------------------------------------------------------- two holders lost
@pytest.mark.parametrize("through", range(SERVERS - 2))
def test_with_two_holders_lost_a_read_fails_cleanly(two_lost, through):
    vs = two_lost.live[through]
    assert len(two_lost.lost_shards) > M
    on_lost = two_lost.key_on(two_lost.lost_shards)
    kept = two_lost.key_on(set(range(K)) - two_lost.lost_shards)

    async def body():
        before = scrape()
        lost = await two_lost.get(vs, on_lost)
        return lost, await two_lost.get(vs, kept), before, scrape()

    lost, fine, before, after = two_lost.run(body(), timeout=90)
    assert lost[0] in (404, 500) and two_lost.body[on_lost] not in lost[1]
    assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total") == 0
    # a needle none of whose intervals is lost still reads, if it lies whole
    # on shards that are left
    record = ec_spread.locate(two_lost.offset[kept], len(two_lost.body[kept]) + 64,
                              two_lost.dat_bytes, K)
    if not {shard for shard, _o, _l in record} & two_lost.lost_shards:
        assert fine == (200, two_lost.body[kept])


# ------------------------------------- how far a reconstruct reads ahead
# One volume server's read path alone (test_degraded_read_cache's harness: a
# real EC volume on disk, the remote path a seam that reads the shard files):
# the span follows where the survivors are, and the cache serves either.
WIDE = volume_ec.EC_DEGRADED_SPAN
DEAD = 3


def _one_server(tmp_path, mounted: int):
    """The read path of a server with `mounted` of the 13 survivors of shard
    DEAD on its own disk; the rest answer through the remote seam, which
    records what it was asked."""
    base, ev = _make_ec_volume(tmp_path)
    for shard in [s for s in range(K + M) if s != DEAD][:mounted]:
        ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, shard))
    host, asked = _Host(), []

    async def remote(_ev, shard_id, offset, size, key, deadline=None, sent=None):
        asked.append((shard_id, offset, size))
        with open(base + to_ext(shard_id), "rb") as f:
            f.seek(offset)
            return f.read(size)

    host._read_remote_shard_interval = remote
    # every shard that is not mounted has a holder in the table, each its
    # own: no two share a stream, so every survivor comes through the seam
    ev.shard_locations.update({s: [f"127.0.0.1:{s + 1}"] for s in range(K + M) if s != DEAD})
    ev.shard_locations_refresh_time = 1e18
    return base, ev, host, asked


def _spy_preads(monkeypatch) -> list:
    """[(offset, size)] of every survivor span a reconstruct reads from a
    shard file here from now on, and on `.shards` and `.threads` the shard
    and the thread of each; on `.fills` the `how` of each fill. The worker
    that decodes reads each span into its row of the decode's input: the
    first `data_shards` survivors in one `EcVolumeShard.fill_rows`, a spare
    after a read that failed with `read_into`."""

    class Preads(list):
        pass

    preads = Preads()
    preads.shards, preads.threads, preads.fills = [], [], []
    inner_fill, inner_read = EcVolumeShard.fill_rows, EcVolumeShard.read_into

    def note(shard, buf, offset):
        preads.append((offset, len(buf)))
        preads.shards.append(shard.shard_id)
        preads.threads.append(threading.get_ident())

    def fill_rows(rows, sources, offset):
        got = inner_fill(rows, sources, offset)
        preads.fills.append("row_by_row" if got is None else "one_call")
        if got is not None:
            for row, src in zip(rows, sources):
                if isinstance(src, EcVolumeShard):
                    note(src, row, offset)
        return got

    def read_into(self, buf, offset):
        note(self, buf, offset)
        return inner_read(self, buf, offset)

    monkeypatch.setattr(EcVolumeShard, "fill_rows", staticmethod(fill_rows))
    monkeypatch.setattr(EcVolumeShard, "read_into", read_into)
    return preads


def _recover(host, ev, offset: int, size: int, limit_s: float = 30):
    return asyncio.run(asyncio.wait_for(
        host._recover_one_interval(ev, DEAD, offset, size, 0), limit_s))


def _shard_bytes(base, offset: int, size: int) -> bytes:
    with open(base + to_ext(DEAD), "rb") as f:
        f.seek(offset)
        return f.read(size)


def _cached_spans(host) -> list:
    return sorted((key[2], len(span)) for key, span in host._ec_degraded_cache()._spans.items())


def test_with_every_survivor_local_the_span_is_the_wide_one(tmp_path, monkeypatch):
    base, ev, host, asked = _one_server(tmp_path, mounted=13)
    preads = _spy_preads(monkeypatch)
    off = 2 * WIDE + 3 * SPAN + 100
    before = scrape()
    got = _recover(host, ev, off, 2048)
    after = scrape()
    assert got == _shard_bytes(base, off, 2048)
    assert not asked and moved(before, after, READS) == 0
    # the ten lowest of the thirteen survivors, each once, and no spare; none
    # on the thread that runs the loop (here the test's own)
    assert set(preads) == {(2 * WIDE, WIDE)}
    assert preads.shards == [s for s in range(K + M) if s != DEAD][:K]
    assert threading.get_ident() not in preads.threads
    # in one native call
    assert preads.fills == ["one_call"]
    assert moved(before, after, FILLS, how="one_call") == moved(before, after, FILLS) == 1
    assert moved(before, after, LOCAL_READS, where="worker") == moved(before, after, LOCAL_READS) == K
    assert moved(before, after, SURVIVOR_BYTES, origin="local") == K * WIDE
    assert moved(before, after, SURVIVOR_BYTES, origin="remote") == 0
    assert _cached_spans(host) == [(2 * WIDE, WIDE)]
    ev.close()


@pytest.mark.parametrize("mounted", [9, 4, 0])
def test_with_a_survivor_on_another_server_the_span_is_the_narrow_one(tmp_path, monkeypatch, mounted):
    base, ev, host, asked = _one_server(tmp_path, mounted)
    preads = _spy_preads(monkeypatch)
    off = 2 * WIDE + 3 * SPAN + 100
    before = scrape()
    got = _recover(host, ev, off, 2048)
    after = scrape()
    assert got == _shard_bytes(base, off, 2048)
    # with no shard of its own the server does not know a shard's size, and
    # reads the interval alone (a span past the end would come back short)
    narrow = (2 * WIDE + 3 * SPAN, SPAN) if mounted else (off, 2048)
    # as many of the others as the decode needs, none to spare, each the span
    assert len(asked) == K - mounted and {a[1:] for a in asked} == {narrow}
    assert set(preads) <= {narrow} and len(preads) == mounted
    assert threading.get_ident() not in preads.threads
    # the local reads and the fetched spans' copies in one native call
    assert preads.fills == ["one_call"]
    assert moved(before, after, FILLS, how="one_call") == moved(before, after, FILLS) == 1
    assert moved(before, after, LOCAL_READS, where="worker") == moved(before, after, LOCAL_READS) == mounted
    assert moved(before, after, SURVIVOR_BYTES, origin="local") == mounted * narrow[1]
    assert moved(before, after, SURVIVOR_BYTES, origin="remote") == (K - mounted) * narrow[1]
    assert moved(before, after, READS, outcome="ok") == K - mounted
    assert moved(before, after, READ_BYTES) == (K - mounted) * narrow[1]
    assert _cached_spans(host) == [narrow]
    ev.close()


@pytest.mark.parametrize("fault", ["short", "raises"])
@pytest.mark.parametrize("spare", ["local", "remote", "none"])
def test_a_local_survivor_that_cannot_be_read_is_replaced_by_the_next_one(
        tmp_path, monkeypatch, fault, spare):
    """The lowest survivor's read comes a byte short, or raises: its row goes
    to the next local spare, read the same way; with no spare on this server
    the survivors not asked yet are (the second round), and with none of
    those either there is no answer, not a wrong one."""
    mounted = 13 if spare == "local" else K
    base, ev, host, asked = _one_server(tmp_path, mounted)
    if spare == "none":
        async def nobody(*a, **kw):
            return None

        host._read_remote_shard_interval = nobody
    preads = _spy_preads(monkeypatch)
    spied_fill, spied_read = EcVolumeShard.fill_rows, EcVolumeShard.read_into
    bad = min(s for s in range(K + M) if s != DEAD)

    def fill_rows(rows, sources, offset):
        # the bad shard's row a byte short, so that its read comes short;
        # or its read done and then failed, as a read that raises is
        rows = [row[:-1] if getattr(src, "shard_id", None) == bad and fault == "short" else row
                for row, src in zip(rows, sources)]
        got = spied_fill(rows, sources, offset)
        if got is None:
            return None
        return [-errno.EIO if getattr(src, "shard_id", None) == bad and fault == "raises" else n
                for n, src in zip(got, sources)]

    def read_into(self, buf, offset):
        if self.shard_id != bad:
            return spied_read(self, buf, offset)
        if fault == "raises":
            preads.shards.append(bad)
            raise OSError(5, "injected")
        return spied_read(self, buf[:-1], offset)

    monkeypatch.setattr(EcVolumeShard, "fill_rows", staticmethod(fill_rows))
    monkeypatch.setattr(EcVolumeShard, "read_into", read_into)
    off = 4 * WIDE + 777
    before = scrape()
    got = _recover(host, ev, off, 3000)
    after = scrape()
    survivors = [s for s in range(K + M) if s != DEAD]
    if spare == "none":
        assert got is None and not _cached_spans(host)
        assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total") == 0
    else:
        assert got == _shard_bytes(base, off, 3000)
        assert _cached_spans(host) == [(4 * WIDE, WIDE)]
        assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total", kind="cold") == 1
    if spare == "local":
        # eleven reads: the ten lowest, the bad one among them, in one call,
        # and one spare after it
        assert not asked and preads.shards == survivors[: K + 1]
        assert preads.fills == ["one_call"]
    else:
        # the ten local ones, then the three others, then the local ones again
        assert preads.shards == survivors[:K] * 2
        assert len(asked) == (3 if spare == "remote" else 0)
        assert preads.fills == ["one_call"] * 2
    assert moved(before, after, FILLS, how="one_call") == len(preads.fills)
    assert threading.get_ident() not in preads.threads
    short = (len(preads.shards) // K) * (WIDE - 1) if fault == "short" else 0
    good = sum(s != bad for s in preads.shards) * WIDE
    assert moved(before, after, SURVIVOR_BYTES, origin="local") == good + short
    assert moved(before, after, LOCAL_READS, where="worker") == len(preads.shards)
    assert moved(before, after, LOCAL_READS, where="loop") == 0
    ev.close()


@pytest.mark.parametrize("mounted", [13, 4])
def test_without_the_native_library_the_rows_are_filled_one_by_one(tmp_path, monkeypatch, mounted):
    """A library without `fill_rows`, or none: the same survivors are read
    and copied a row at a time, on the worker, into rows byte for byte
    those the one native call fills, and the fill is counted `row_by_row`."""
    base, ev, host, asked = _one_server(tmp_path, mounted)
    codec_type, decoded = type(host.codec), []

    def reconstruct_rows(self, shards, missing, _inner=codec_type.reconstruct_rows):
        decoded.append({i: bytes(row) for i, row in enumerate(shards) if row is not None})
        return _inner(self, shards, missing)

    monkeypatch.setattr(codec_type, "reconstruct_rows", reconstruct_rows)
    preads = _spy_preads(monkeypatch)
    off = 6 * WIDE + 5 * SPAN + 33
    want = _shard_bytes(base, off, 1500)
    assert _recover(host, ev, off, 1500) == want
    host._ec_degraded_cache().invalidate(ev.volume_id)
    monkeypatch.setattr(native, "load", lambda: None)
    before = scrape()
    assert _recover(host, ev, off, 1500) == want
    after = scrape()
    assert preads.fills == ["one_call", "row_by_row"]
    assert len(decoded) == 2 and decoded[0] == decoded[1]
    local = min(mounted, K)
    # each planned survivor read once a fill, the second fill's on the worker too
    assert preads.shards == [s for s in range(K + M) if s != DEAD][:local] * 2
    assert threading.get_ident() not in preads.threads
    assert len(asked) == 2 * (K - local)
    assert moved(before, after, FILLS, how="row_by_row") == moved(before, after, FILLS) == 1
    assert moved(before, after, LOCAL_READS, where="worker") == moved(before, after, LOCAL_READS) == local
    ev.close()


@pytest.mark.parametrize("cached_under", ["wide", "narrow"])
def test_a_span_cached_under_one_alignment_serves_a_read_of_the_other(tmp_path, cached_under):
    # the first read caches its span; then the server's shards change, so that
    # the second read would have chosen the other alignment: it is a hit
    first, then = (13, 4) if cached_under == "wide" else (4, 13)
    base, ev, host, asked = _one_server(tmp_path, first)
    off = 5 * WIDE + 2 * SPAN + 700
    assert _recover(host, ev, off, 1024) \
        == _shard_bytes(base, off, 1024)
    want = (5 * WIDE, WIDE) if cached_under == "wide" else (5 * WIDE + 2 * SPAN, SPAN)
    assert _cached_spans(host) == [want]
    survivors = [s for s in range(K + M) if s != DEAD]
    for shard in survivors[then:first]:
        ev.delete_shard(shard)
    for shard in survivors[first:then]:
        ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, shard))
    assert sum(ev.find_shard(s) is not None for s in survivors) == then
    del asked[:]
    before = scrape()
    near = off + 1024  # a neighbour inside the narrow span, so inside the wide one too
    assert _recover(host, ev, near, 512) \
        == _shard_bytes(base, near, 512)
    after = scrape()
    assert not asked and _cached_spans(host) == [want]
    assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total", kind="cache_hit") == 1
    assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total", kind="cold") == 0
    ev.close()


# ------------------------------------- the serving side of `VolumeEcShardRead`
# One holder's handler alone, on a real EC volume with one needle (key 7) in
# its .ecx: what it answers with and without `shard_ids`.
HELD = [0, 1, 4, 9]


class _ShardStore:
    def __init__(self, ev):
        self.ev = ev

    def find_ec_volume(self, vid):
        return self.ev

    def find_ec_shard(self, vid, shard_id):
        return self.ev.find_shard(shard_id)


def _holder(tmp_path):
    base, ev = _make_ec_volume(tmp_path)
    for shard in HELD:
        ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, shard))
    return base, ev, _Host(store=_ShardStore(ev))


def _answer(handler, req: dict, limit_s: float = 30) -> list:
    async def body():
        return [msg async for msg in handler(req)]

    return asyncio.run(asyncio.wait_for(body(), limit_s))


@pytest.mark.parametrize("case", ["held", "not_held", "past_the_end", "tombstoned", "tombstoned_not_held"])
def test_a_request_without_shard_ids_is_answered_as_the_parent_answers_it(tmp_path, case):
    base, ev, host = _holder(tmp_path)
    shard_bytes = os.path.getsize(base + to_ext(1))
    req = {"volume_id": 1, "shard_id": 2 if "not_held" in case else 1, "offset": 4096,
           "size": shard_bytes if case == "past_the_end" else 3000, "file_key": 7}
    if "tombstoned" in case:
        ev.delete_needle_from_ecx(7)
    before = scrape()
    got = _answer(lambda r: host._grpc_ec_shard_read(r, None), req)
    after = scrape()
    assert got == _answer(lambda r: _parents_shard_read(host, r), req)
    assert all(set(msg) <= {"data", "error", "is_deleted"} for msg in got)  # no tag where none was asked for
    assert [set(msg) for msg in got] == {
        "held": [{"data"}], "not_held": [{"error"}], "past_the_end": [{"data"}],
        "tombstoned": [{"is_deleted"}], "tombstoned_not_held": [{"error"}]}[case]
    want = {"held": 3000, "past_the_end": shard_bytes - 4096}.get(case, 0)
    assert moved(before, after, SERVED_BYTES) == sum(len(msg.get("data", b"")) for msg in got) == want
    assert moved(before, after, SERVED, **SHARD_READ) == 1
    ev.close()


def test_a_grouped_request_is_answered_shard_by_shard_on_one_stream(tmp_path):
    base, ev, host = _holder(tmp_path)
    req = {"volume_id": 1, "shard_id": 1, "shard_ids": [1, 2, 9], "offset": SPAN, "size": SPAN, "file_key": 7}
    before = scrape()
    got = _answer(lambda r: host._grpc_ec_shard_read(r, None), req)
    after = scrape()

    def span(shard):
        with open(base + to_ext(shard), "rb") as f:
            f.seek(SPAN)
            return f.read(SPAN)

    # shard 2 is not here: an error of its own, and the stream goes on
    assert got == [{"shard_id": 1, "data": span(1)},
                   {"shard_id": 2, "error": "ec shard 1.2 not found"},
                   {"shard_id": 9, "data": span(9)}]
    assert moved(before, after, SERVED_BYTES) == 2 * SPAN
    assert moved(before, after, SERVED, **SHARD_READ) == 1  # one observation a stream
    ev.close()


@pytest.mark.parametrize("shape", ["grouped", "single"])
def test_a_tombstone_ends_a_grouped_stream_as_it_ends_a_single_one(tmp_path, monkeypatch, shape):
    base, ev, host = _holder(tmp_path)
    ev.delete_needle_from_ecx(7)
    shards = [1, 4, 9] if shape == "grouped" else [1]
    req = {"volume_id": 1, "shard_id": shards[0], "offset": 0, "size": SPAN, "file_key": 7}
    if shape == "grouped":
        req["shard_ids"] = shards
    assert _answer(lambda r: host._grpc_ec_shard_read(r, None), req) == [{"is_deleted": True}]
    # and the asking side takes it as the holder's last word on every shard
    # of the stream: none is asked for again, each is counted `failed`
    url = "127.0.0.1:7"
    wire = _Routed({grpc_address(url): lambda r: host._grpc_ec_shard_read(r, None)})
    monkeypatch.setattr(volume_ec, "Stub", wire)
    ev.shard_locations.update({s: [url] for s in shards})
    asker = _Host()
    before = scrape()
    if shape == "grouped":
        got = asyncio.run(asyncio.wait_for(
            asker._read_remote_survivor_group(ev, url, shards, 0, SPAN, 7, None), 30))
    else:
        got = {1: asyncio.run(asyncio.wait_for(
            asker._read_remote_survivor(ev, 1, 0, SPAN, 7, None), 30))}
    after = scrape()
    assert got == dict.fromkeys(shards)
    assert wire.sent == [req]
    assert moved(before, after, READS, outcome="failed") == moved(before, after, READS) == len(shards)
    assert moved(before, after, STREAMS, shape=shape) == moved(before, after, STREAMS) == 1
    assert moved(before, after, READ_BYTES) == 0
    ev.close()
