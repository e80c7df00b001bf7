"""Chunk needles of megabytes on an EC volume spread over four volume servers,
one of them lost (ISSUE 40): every needle reads back byte for byte through
every live server, its intervals counted by source as the benchmark's plain
reference counts them (benchmarks/reference/ec_sources.py over ec_locate.py),
the intervals that are not local asked for at once and joined in order
whichever arrives first, a holder that fails in the middle of a needle
replaced by a reconstruct, a span handed through as the bytes that were
served, and the holder's side serving what `EcVolumeShard.read_at` reads (the
seam the benchmark's span_byte control plants its fault in).

One cluster for the module's life (tests/test_ec_spread_read.py's `Spread`,
with chunk needles of 2.2-3.8 MiB), on an event loop of its own thread. Counts,
bytes and order are checked, never a time."""

import asyncio
import os

import numpy as np
import pytest

from benchmarks.lib import common, metrics as layer_metrics
from benchmarks.lib import http_chunk_sources_client_proc as sources_client
from benchmarks.lib import peer_child
from benchmarks.lib.stores import sealed_chunks
from benchmarks.reference import ec_locate, ec_sources
from seaweedfs_tpu.pb import grpc_address
from seaweedfs_tpu.server import volume_ec
from seaweedfs_tpu.storage.erasure_coding import to_ext
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolumeShard
from seaweedfs_tpu.storage.needle import get_actual_size
from seaweedfs_tpu.types import to_actual_offset

from test_degraded_read_cache import _Host
from test_ec_spread_read import K, M, Spread, _Routed, _answer, _holder
from test_stage_tracing import CHUNK_SPREAD_CELL, CHUNK_SPREAD_METRICS, moved, scrape

MB = 1 << 20
INTERVALS = "seaweedfs_tpu_ec_read_intervals_total"
NEEDLES = "seaweedfs_tpu_ec_needle_reads_total"
ATTEMPTS = "seaweedfs_tpu_ec_remote_attempts_total"
STAGES = "seaweedfs_tpu_ec_read_stage_seconds_total"
REMOTE_SECONDS = "seaweedfs_tpu_ec_remote_interval_seconds_total"
REMOTE_BYTES = "seaweedfs_tpu_ec_remote_interval_bytes_total"
SIZES = {key: int(size) for key, size in enumerate(
    np.random.default_rng(40).integers(2_300_000, 4 * MB - 4096, 12), start=1)}


@pytest.fixture(scope="module")
def healthy(tmp_path_factory):
    cluster = Spread(tmp_path_factory.mktemp("chunk_spread0"), lose=0, sizes=SIZES)
    yield cluster
    cluster.close()


@pytest.fixture(scope="module")
def lost(tmp_path_factory):
    cluster = Spread(tmp_path_factory.mktemp("chunk_spread"), lose=1, sizes=SIZES)
    yield cluster
    cluster.close()


def intervals_of(cluster, vs, key: int) -> list:
    """The plain rule's intervals of a needle, from where `vs`'s .ecx says its
    record is: the look-up is the program's, the arithmetic is not."""
    ev = vs.store.find_ec_volume(cluster.vid)
    offset_units, size = ev.find_needle_from_ecx(key)
    return ec_locate.locate(to_actual_offset(offset_units), get_actual_size(size, ev.version),
                            K * ev.shard_size())


def reference(cluster, vs) -> dict:
    """Over one read of every needle through `vs`: intervals by source and
    the needles that meet a lost shard, by ec_sources."""
    held = cluster.shards_on(vs)
    total = np.zeros(5, dtype=np.int64)
    for key in cluster.body:
        total += ec_sources.tally(intervals_of(cluster, vs, key), held, cluster.lost_shards)
    return dict(zip(("intervals", "local", "served", "rebuilt", "degraded"), total.tolist()))


def read_all(cluster, vs) -> tuple:
    async def body():
        vs._ec_degraded_cache().invalidate(cluster.vid)
        before = scrape()
        got = {key: await cluster.get(vs, key) for key in cluster.body}
        return got, before, scrape()

    return cluster.run(body(), timeout=180)


@pytest.fixture(scope="module")
def recorded(lost):
    """The /metrics pair round one read of every needle through the server
    that holds the most shards, as a benchmark run's window would give it."""
    vs = max(lost.live, key=lambda v: len(lost.shards_on(v)))
    got, before, after = read_all(lost, vs)
    assert all(got[key] == (200, body) for key, body in lost.body.items())
    return vs, before, after


# ---------------------------------------------------------------- the reads
def test_the_cluster_is_the_cells_shape(lost):
    assert len(lost.lost_shards) in (3, 4) and len(lost.lost_shards) <= M
    for key, body in lost.body.items():
        ivs = intervals_of(lost, lost.source, key)
        assert 3 <= len(ivs) <= 5 and sum(n for _s, _o, n in ivs) > len(body)
    counts = reference(lost, lost.source)
    assert counts["local"] and counts["served"] and counts["rebuilt"]


@pytest.mark.parametrize("through", range(3))
def test_every_chunk_reads_back_and_its_intervals_are_the_references(lost, through):
    """Through each live server: every body byte for byte, and the program's
    intervals by source and degraded needles equal the plain reference's."""
    vs = lost.live[through]
    got, before, after = read_all(lost, vs)
    for key, body in lost.body.items():
        assert got[key] == (200, body), key
    want = reference(lost, vs)
    assert moved(before, after, INTERVALS) == want["intervals"]
    assert moved(before, after, INTERVALS, source="local") == want["local"]
    assert moved(before, after, INTERVALS, source="remote") == want["served"]
    assert (moved(before, after, INTERVALS, source="reconstructed")
            + moved(before, after, INTERVALS, source="cache")) == want["rebuilt"]
    assert moved(before, after, NEEDLES, kind="degraded") == want["degraded"]
    assert moved(before, after, NEEDLES, kind="healthy") == len(lost.body) - want["degraded"]
    # every served interval's wall and bytes, and a needle's fetch beside its intervals'
    assert moved(before, after, ATTEMPTS, outcome="served") == want["served"]
    assert moved(before, after, REMOTE_SECONDS) > 0
    served_bytes = sum(n for key in lost.body for s, _o, n in intervals_of(lost, vs, key)
                       if ec_sources.source(s, lost.shards_on(vs), lost.lost_shards) == "served")
    assert moved(before, after, REMOTE_BYTES) == served_bytes
    assert 0 < moved(before, after, STAGES, stage="fetch") <= moved(before, after, STAGES, stage="fetch_interval")


@pytest.mark.parametrize("slow", ["first", "last"])
def test_a_later_interval_that_arrives_first_is_joined_in_order(lost, monkeypatch, slow):
    """The intervals that are not local are asked for at once: hold back the
    first (or the last) of a needle's and the others arrive before it; the
    body is still the needle's, its intervals joined in order."""
    vs = lost.source
    ev = vs.store.find_ec_volume(lost.vid)
    held = lost.shards_on(vs)
    key = next(k for k in lost.body
               if sum(s not in held for s, _o, _n in intervals_of(lost, vs, k)) >= 2)
    far = [s for s, _o, _n in intervals_of(lost, vs, key) if s not in held]
    held_back = far[0] if slow == "first" else far[-1]
    inner, arrived = vs._read_one_ec_interval, []

    async def read_one(ev_, shard_id, *args, **kw):
        if shard_id == held_back:
            await asyncio.sleep(0.3)
        data = await inner(ev_, shard_id, *args, **kw)
        if shard_id not in held:  # the local ones are read in turn, before
            arrived.append(shard_id)
        return data

    monkeypatch.setattr(vs, "_read_one_ec_interval", read_one)

    async def body():
        vs._ec_degraded_cache().invalidate(lost.vid)
        n = await vs.read_ec_needle(ev, key)
        return bytes(n.data)

    assert lost.run(body()) == lost.body[key]
    assert sorted(arrived) == sorted(far) and arrived[-1] == held_back
    if slow == "first":
        assert arrived != far  # a later interval came in before the first


@pytest.mark.parametrize("fault", ["not_found", "raises"])
def test_a_holder_that_fails_in_the_middle_of_a_needle_is_replaced_by_a_reconstruct(
        healthy, monkeypatch, fault):
    """One holder stops serving one shard while a needle's other intervals
    are in flight: that interval is rebuilt from survivors (the holder's
    other shards among them), the rest served, the body right. Every server
    is up, so that one shard more than the parities is never lost."""
    lost = healthy
    vs = lost.source
    ev = vs.store.find_ec_volume(lost.vid)
    held = lost.shards_on(vs)
    key, shard = next((k, s) for k in lost.body for s, _o, _n in intervals_of(lost, vs, k)
                      if ec_sources.source(s, held, lost.lost_shards) == "served"
                      and sum(x not in held for x, _o, _n in intervals_of(lost, vs, k)) >= 2)
    holder = lost.cluster.server_for(lost.spread[shard][0])
    target = holder.store.find_ec_shard(lost.vid, shard)
    if fault == "not_found":
        inner = holder.store.find_ec_shard
        monkeypatch.setattr(holder.store, "find_ec_shard",
                            lambda vid, s: None if s == shard else inner(vid, s))
    else:
        read_at = EcVolumeShard.read_at

        def failing(self, size, offset):
            if self is target:
                raise OSError(5, "injected")
            return read_at(self, size, offset)

        monkeypatch.setattr(EcVolumeShard, "read_at", failing)

    async def body():
        vs._ec_degraded_cache().invalidate(lost.vid)
        before = scrape()
        n = await vs.read_ec_needle(ev, key)
        return bytes(n.data), before, scrape()

    got, before, after = lost.run(body(), timeout=120)
    assert got == lost.body[key]
    assert moved(before, after, ATTEMPTS, outcome="failed") == 1
    rebuilt = sum(s in lost.lost_shards for s, _o, _n in intervals_of(lost, vs, key)) + 1
    assert moved(before, after, INTERVALS, source="reconstructed") == rebuilt
    assert moved(before, after, NEEDLES, kind="degraded") == 1


# ------------------------------------------------ a span as it was served
@pytest.mark.parametrize("messages", [1, 3])
@pytest.mark.parametrize("shape", ["single", "grouped"])
def test_a_span_is_the_bytes_served_and_one_message_is_handed_through(tmp_path, shape, messages):
    """What the asking side returns for a span is the bytes the holder sent,
    joined once where they came in several messages; a span that came in one
    message is that message's bytes object itself, not a copy."""
    sent = {s: [os.urandom(1000 + 7 * j + s) for j in range(messages)] for s in (1, 4)}

    async def handler(req):
        for s in req.get("shard_ids") or [req["shard_id"]]:
            for part in sent[s]:
                yield {"shard_id": s, "data": part} if "shard_ids" in req else {"data": part}

    url = "127.0.0.1:9"
    wire = _Routed({grpc_address(url): handler})
    from test_degraded_read_cache import _make_ec_volume

    _base, ev = _make_ec_volume(tmp_path)
    ev.shard_locations.update({1: [url], 4: [url]})
    asker = _Host()
    size = {s: sum(map(len, parts)) for s, parts in sent.items()}
    real_stub = volume_ec.Stub
    volume_ec.Stub = wire
    try:
        if shape == "single":
            got = {1: asyncio.run(asker._read_remote_shard_once(ev, url, 1, 0, size[1], 7, None))}
        else:
            assert size[1] != size[4] or messages == 1
            got = asyncio.run(asker._read_remote_survivor_group(ev, url, [1, 4], 0, size[1], 7, None))
            got = {s: b for s, b in got.items() if size[s] == size[1]}
    finally:
        volume_ec.Stub = real_stub
        ev.close()
    for s, b in got.items():
        assert b == b"".join(sent[s])
        if messages == 1:
            assert b is sent[s][0]


@pytest.mark.parametrize("fault", [False, True])
@pytest.mark.parametrize("shape", ["single", "grouped"])
def test_a_holder_serves_what_read_at_reads(tmp_path, monkeypatch, shape, fault):
    """The serving side of VolumeEcShardRead reads each span through
    `EcVolumeShard.read_at`, so the fault benchmarks/lib/peer_child.py plants
    there (controls_spread.py's span_byte) reaches every span a peer serves:
    one byte in 256 altered, the length unchanged."""
    base, ev, host = _holder(tmp_path)
    if fault:
        monkeypatch.setattr(EcVolumeShard, "read_at", EcVolumeShard.read_at)  # restored after
        peer_child.fault_ec_span_byte()
    shards = [1, 4] if shape == "grouped" else [1]
    req = {"volume_id": 1, "shard_id": shards[0], "offset": 4096, "size": 3000, "file_key": 7}
    if shape == "grouped":
        req["shard_ids"] = shards
    got = _answer(lambda r: host._grpc_ec_shard_read(r, None), req)
    ev.close()
    assert len(got) == len(shards)
    for s, msg in zip(shards, got):
        with open(base + to_ext(s), "rb") as f:
            f.seek(4096)
            want = f.read(3000)
        span = msg["data"]
        assert len(span) == len(want)
        wrong = [i for i in range(len(span)) if span[i] != want[i]]
        assert wrong == (list(range(0, 3000, 256)) if fault else [])


@pytest.mark.parametrize("case", ["all_local", "one_of_each", "two_lost", "parity_lost_only"])
def test_the_references_sources_by_hand(case):
    held, lost = {0, 4, 8, 12}, {3, 7, 11}
    intervals = {
        "all_local": [(0, 10, 5), (4, 0, 5)],
        "one_of_each": [(2, 9, 1), (3, 0, 1), (4, 0, 1)],
        "two_lost": [(3, 0, 1), (5, 0, 1), (6, 0, 1), (7, 0, 1), (8, 0, 1)],
        "parity_lost_only": [(9, 0, 1), (0, 1, 1), (1, 1, 1)],
    }[case]
    want = {"all_local": (2, 2, 0, 0, 0), "one_of_each": (3, 1, 1, 1, 1),
            "two_lost": (5, 1, 2, 2, 1), "parity_lost_only": (3, 1, 2, 0, 0)}[case]
    assert ec_sources.tally(intervals, held, lost) == want


@pytest.mark.parametrize("seed", [1, 2147490011, 3_000_000_401])
def test_the_load_clients_tally_is_the_chunk_clients_and_more(seed):
    """The new client's five columns per needle: intervals, and rebuilt ones
    and degraded needles as http_chunk_client_proc counts them with the same
    lost shards; local + served + rebuilt is every interval."""
    recipe = dict(common.load("configs", "warm-rs10.4-filer4m-spread4.json")["store"])
    recipe.update(common.load("configs", "warm-rs10.4-filer4m-spread4.json")["rehearse"])
    pick = {"volume": 1, "lost_shards": [3, 7, 11], "held_shards": [0, 4, 8, 12], "data_shards": K}
    reader = sealed_chunks.Reader({"recipe": recipe}, seed, pick)
    five = sources_client.tallies(reader, pick)
    three = reader.tallies()
    assert five.shape == (reader.count, 5) and reader.count > 5
    assert np.array_equal(five[:, [0, 3, 4]], three)
    assert np.array_equal(five[:, 1] + five[:, 2] + five[:, 3], five[:, 0])


# ------------------------------------------------ the cell's per-layer metrics
@pytest.mark.parametrize("name", CHUNK_SPREAD_METRICS)
def test_each_new_metric_file_reads_what_the_program_wrote(lost, recorded, name):
    """Evaluated as a run evaluates them against the /metrics pair recorded
    here; on a program without the new families (the parent) they are left
    out of the line, never 0."""
    vs, before, after = recorded
    spec = common.load("layer_metrics", name + ".json")
    entry = next(e for e in common.benchmark_json()["per_layer"] if e["name"] == name)
    assert entry["workloads"] == [CHUNK_SPREAD_CELL]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    client = {"gets_good": len(lost.body), "peer_cpu_s": 0.5, "peer_served_bytes": 250e6}
    value = layer_metrics.Observed(before, after, {}, {}, client, {}, None, None, {}).value(spec)
    want = reference(lost, vs)
    if name == "ec_read.remote_interval_ms":
        assert value == 1000 * moved(before, after, REMOTE_SECONDS) / want["served"]
    elif name == "ec_read.remote_interval_mb_per_get":
        assert value == moved(before, after, REMOTE_BYTES) / 1e6 / len(lost.body)
    elif name == "ec_read.interval_overlap":
        assert value >= 1.0
    else:  # the peers' CPU over the bytes they served, in ms a MB
        assert value == pytest.approx(2.0)
    gone = ("seaweedfs_tpu_ec_remote_interval_", 'seaweedfs_tpu_ec_read_stage_seconds_total{stage="fetch')
    parents = [{k: v for k, v in page.items() if not k.startswith(gone)} for page in (before, after)]
    parent = layer_metrics.Observed(*parents, {}, {}, client, {}, None, None, {})
    if name == "peers.cpu_ms_per_mb":  # the peers' counter and CPU are the parent's too
        assert parent.value(spec) == value
    else:
        assert parent.value(spec) is None
