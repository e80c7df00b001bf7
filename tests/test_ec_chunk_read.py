"""Chunk needles of megabytes read from an EC volume (ISSUE 35): a needle that
lies over five 1 MiB blocks is five intervals read one after another, one of
them rebuilt where its shard is lost, and the program counts and stages what
that costs: `ec_read_intervals_total{source}`,
`ec_needle_reads_total{kind}`, `ec_reconstruct_survivor_bytes_total{origin}`,
the stages `ec.read.local_interval` and `ec.read.assemble`.

The yardstick is the benchmark's own plain reference
(benchmarks/reference/ec_locate.py, rs_codec.py), which imports nothing of the
program. One volume server holds one EC volume of ~24 MiB: seeded needles of
1.5-4 MiB and one of 1,000 bytes, all 14 shard files on disk; a test unmounts
the shards it wants lost and mounts them again. Everything runs on the CPU:
counts, bytes and names are checked, never a time."""

import asyncio
import json
import threading

import aiohttp
import numpy as np
import pytest

from seaweedfs_tpu.ops.gf256 import DEFAULT_BLOCK_ROWS, LANE
from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
from seaweedfs_tpu.pb import grpc_address
from seaweedfs_tpu.pb.rpc import Stub
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.server.volume_ec import EC_DEGRADED_SPAN
from seaweedfs_tpu.storage.erasure_coding import to_ext
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolumeShard
from seaweedfs_tpu.storage.erasure_coding.locate import locate_data
from seaweedfs_tpu.storage.file_id import format_needle_id_cookie
from seaweedfs_tpu.storage.needle import Needle, get_actual_size
from seaweedfs_tpu.types import to_actual_offset
from seaweedfs_tpu.util import trace

from benchmarks.lib import common, metrics as layer_metrics
from benchmarks.reference import ec_locate, rs_codec
from test_cluster import Cluster, assign_retry, free_port_pair
from test_stage_tracing import CHUNK_CELL, CHUNK_METRICS, host_event_names, moved, scrape

MB = 1 << 20
GB = 1 << 30
COOKIE = 0xC4A200
SMALL_KEY = 1  # the one-interval needle; keys 2.. are the chunk needles
INTERVALS = "seaweedfs_tpu_ec_read_intervals_total"
NEEDLES = "seaweedfs_tpu_ec_needle_reads_total"
STAGES = "seaweedfs_tpu_ec_read_stage_seconds_total"
SURVIVOR_BYTES = "seaweedfs_tpu_ec_reconstruct_survivor_bytes_total"
COLD = "seaweedfs_tpu_ec_reconstructions_total"
LOCAL_READS = "seaweedfs_tpu_ec_reconstruct_local_reads_total"
RS_SECONDS = "seaweedfs_tpu_rs_dispatch_seconds_total"
GET_CELLS = ["warm-rs10.4.degraded-get-c16", "warm-rs10.4-spread4.server-lost-get-c16", CHUNK_CELL]
PROXIED = "seaweedfs_tpu_request_proxied_total"
GRANULE = DEFAULT_BLOCK_ROWS * LANE * 4  # bytes a row the Pallas kernel pads to


# --------------------------------------------------- locate, against the rule
def program_intervals(large, small, dat, offset, size, k=10):
    return [
        iv.to_shard_id_and_offset(large, small) + (iv.size,)
        for iv in locate_data(large, small, dat, offset, size, data_shards=k)
    ]


def seeded_cases(seed, large, small, k=10):
    """(dat size, offset, size) whose spans cross a small block's end, a large
    block's end, the last large row's end and the volume's end. The .dat is
    kept out of the narrow window below a whole number of large rows in which
    upstream's reader and its own encoder disagree (locate_data's docstring),
    also once it is rounded up to whole rows, as the program is handed it."""
    rng = np.random.default_rng([seed, 35])
    cases = []
    for _ in range(40):
        n_large = int(rng.integers(0, 3))
        rest = int(rng.integers(2 * small, large * k - 2 * k * small))
        dat = n_large * large * k + rest
        edges = [dat - 1, n_large * large * k]  # the volume's end; where small rows begin
        edges += [int(rng.integers(1, dat // small + 1)) * small]
        if n_large:
            edges += [int(rng.integers(1, n_large * k + 1)) * large]
        for edge in edges:
            size = int(rng.integers(1, 5 * small))
            offset = max(0, min(edge - int(rng.integers(0, size + 1)), dat - size))
            if size <= dat:
                cases.append((dat, offset, size))
    return cases


def shard_bytes_of(dat, large, small, k=10):
    """`rs_codec.shard_size` at any block sizes."""
    n_large = ec_locate.large_rows(dat, k, large)
    rest = dat - n_large * large * k
    return n_large * large + -(-rest // (small * k)) * small


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_locate_data_is_the_plain_rule_over_small_and_large_blocks(seed):
    """Block sizes of 1 KiB and 16 KiB, so that a few hundred seeded spans
    meet every boundary the layout has."""
    large, small = 16 << 10, 1 << 10
    cases = seeded_cases(seed, large, small)
    assert len(cases) > 100
    crossed_large = crossed_into_small = 0
    for dat, offset, size in cases:
        want = ec_locate.locate(offset, size, dat, 10, large, small)
        assert program_intervals(large, small, dat, offset, size) == want, (dat, offset, size)
        assert sum(n for _s, _o, n in want) == size
        # the program is handed k * shard size, not the .dat's own length
        padded = 10 * shard_bytes_of(dat, large, small)
        assert program_intervals(large, small, padded, offset, size) == want
        n_large = ec_locate.large_rows(dat, 10, large)
        crossed_large += offset < n_large * large * 10 and len(want) > 1
        crossed_into_small += offset < n_large * large * 10 <= offset + size - 1
    assert crossed_large > 5 and crossed_into_small > 5


@pytest.mark.parametrize(
    "dat,offset,size,want",
    [
        # the benchmark's volume: 1 GiB and a little, rows of 1 MiB blocks only;
        # a 4 MiB chunk's record is five intervals on five consecutive shards
        (1_100_000_000, 8, 4 * MB + 40,
         [(0, 8, MB - 8), (1, 0, MB), (2, 0, MB), (3, 0, MB), (4, 0, 48)]),
        # from the last block of a row into the next row
        (1_100_000_000, 9 * MB + 100, 2 * MB,
         [(9, 100, MB - 100), (0, MB, MB), (1, MB, 100)]),
        # a record that ends within its own tail of a block's end is six
        (1_100_000_000, 23 * MB - 16, 4 * MB + 40,
         [(2, 3 * MB - 16, 16), (3, 2 * MB, MB), (4, 2 * MB, MB), (5, 2 * MB, MB),
          (6, 2 * MB, MB), (7, 2 * MB, 24)]),
        # 12 GiB: one row of 1 GiB blocks, then small rows after it in every shard
        (12 * GB, 3 * GB - 2 * MB, 4 * MB, [(2, GB - 2 * MB, 2 * MB), (3, 0, 2 * MB)]),
        (12 * GB, 10 * GB - MB, 2 * MB, [(9, GB - MB, MB), (0, GB, MB)]),
    ],
)
def test_locate_data_at_the_real_block_sizes_by_hand(dat, offset, size, want):
    assert ec_locate.locate(offset, size, dat) == want
    assert program_intervals(GB, MB, dat, offset, size) == want


# ------------------------------------------- the decode's four padded widths
@pytest.mark.parametrize("rows", ["separate", "one_array", "one_array_upload_wide"])
@pytest.mark.parametrize("blocks", [1, 2, 3, 4])
def test_each_padded_decode_width_gives_the_reference_codecs_row(blocks, rows):
    """A lost interval is widened to 128 KiB and a decode's row padded to the
    kernel's 256 KiB: spans of 128 KiB to 1 MiB land on four widths. Each,
    through the Pallas kernel (interpreted), is the plain codec's lost row,
    whatever form the survivors come in: arrays of their own (rebuild, scrub:
    stacked as ever), the rows of one array (not stacked), or the starts of
    the rows of one array that is already as wide as the upload, with
    anything at all past the survivors' width (a degraded read: neither
    stacked nor padded). The bytes counted real and padded are the same."""
    width = blocks * GRANULE - EC_DEGRADED_SPAN  # 128, 384, 640, 896 KiB
    rng = np.random.default_rng(blocks)
    data = rng.integers(0, 256, (10, width), dtype=np.uint8)
    plain = rs_codec.Codec(10, 4)
    full = np.concatenate([data, plain.encode(data)])
    codec = TpuRSCodec(force_pallas=True, interpret=True)
    assert codec.row_granule() == GRANULE
    used = [i for i in range(14) if i not in (3, 11)][:10]
    shards = [None] * 14
    if rows == "separate":
        for i in used:
            shards[i] = full[i].copy()
    else:
        wide = blocks * GRANULE if rows == "one_array_upload_wide" else width
        one = np.full((10, wide), 0xA5, dtype=np.uint8)
        for j, i in enumerate(used):
            one[j, :width] = full[i]
            shards[i] = one[j, :width]
    before = scrape()
    got = codec.reconstruct_rows(shards, [3])[0]
    after = scrape()
    assert got.shape == (width,) and np.array_equal(got, data[3])
    survivors = {i: full[i] for i in used}
    assert np.array_equal(got, plain.recover(survivors, [3])[0])
    labels = dict(op="decode", backend="device_emulated")
    family = "seaweedfs_tpu_rs_dispatch_bytes_total"
    assert moved(before, after, family, kind="real", **labels) == 11 * width
    assert moved(before, after, family, kind="padded", **labels) == 11 * EC_DEGRADED_SPAN
    stacked = moved(before, after, RS_SECONDS, op="decode", stage="stack")
    assert (stacked > 0) == (rows == "separate")


# ------------------------------------------------------------ the live volume
class Live:
    """Master + one volume server on an event loop of its own thread."""

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, daemon=True)
        self.thread.start()
        self.body = {}
        try:
            self.run(self._start(), timeout=240)
        except BaseException:
            self.close()
            raise

    def run(self, coro, timeout=120):
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
        self.vid = vid = sorted(vs.store.locations[0].volumes)[0]
        rng = np.random.default_rng(35)
        sizes = [1000] + [4 * MB] * 3 + rng.integers(3 * MB // 2, 4 * MB, 4).tolist()
        for key, size in enumerate(sizes, start=SMALL_KEY):
            self.body[key] = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            vs.store.write_volume_needle(
                vid, Needle(cookie=COOKIE + key, id=key, data=self.body[key])
            )
        self.stub = Stub(grpc_address(vs.address), "volume")
        for rpc, req in (
            ("VolumeMarkReadonly", {"volume_id": vid}),
            ("VolumeEcShardsGenerate", {"volume_id": vid}),
            ("VolumeEcShardsMount", {"volume_id": vid, "shard_ids": list(range(14))}),
            ("VolumeUnmount", {"volume_id": vid}),
        ):
            reply = await self.stub.call(rpc, req, timeout=120)
            assert not reply.get("error"), (rpc, reply)
        for _ in range(100):
            if self.cluster.master.topo.lookup_ec_shards(vid) is not None:
                break
            await asyncio.sleep(0.1)
        self.ev = vs.store.find_ec_volume(vid)
        self.shard_bytes = self.ev.shard_size()
        self.base = str(self.tmp_path / "vol" / str(vid))

    def fid(self, key: int) -> str:
        return f"{self.vid},{format_needle_id_cookie(key, COOKIE + key)}"

    def reference_intervals(self, key: int) -> list:
        """The plain rule's intervals of a needle, from where the .ecx says its
        record is: the look-up is the program's, the arithmetic is not."""
        offset_units, size = self.ev.find_needle_from_ecx(key)
        return ec_locate.locate(
            to_actual_offset(offset_units), get_actual_size(size, self.ev.version),
            10 * self.shard_bytes,
        )

    def shard_span(self, shard: int, offset: int, length: int) -> bytes:
        with open(self.base + to_ext(shard), "rb") as f:
            f.seek(offset)
            return f.read(length)

    async def lose(self, shards: list):
        """The shards unmounted (their files stay) and the span cache emptied."""
        reply = await self.stub.call(
            "VolumeEcShardsUnmount", {"volume_id": self.vid, "shard_ids": shards})
        assert not reply.get("error"), reply
        self.vs._ec_degraded_cache().invalidate(self.vid)

    async def mount(self, shards: list):
        reply = await self.stub.call(
            "VolumeEcShardsMount", {"volume_id": self.vid, "shard_ids": shards})
        assert not reply.get("error"), reply
        self.vs._ec_degraded_cache().invalidate(self.vid)

    async def read_all_with(self, lost: list) -> tuple:
        """Every needle read whole with `lost` unmounted: the bodies, and the
        /metrics pair around the reads."""
        await self.lose(lost)
        try:
            before = scrape()
            got = {}
            for key in self.body:
                n = await self.vs.read_ec_needle(self.ev, key)
                got[key] = None if n is None else bytes(n.data)
            return got, before, scrape()
        finally:
            await self.mount(lost)

    def reference_tally(self, lost: list) -> tuple:
        """(intervals, bytes of them) healthy and on a lost shard, and the
        needles that meet one, over one read of every needle."""
        healthy, rebuilt, needles = [0, 0], [0, 0], 0
        for key in self.body:
            ivs = self.reference_intervals(key)
            for shard, _off, n in ivs:
                side = rebuilt if shard in lost else healthy
                side[0] += 1
                side[1] += n
            needles += any(shard in lost for shard, _o, _n in ivs)
        return healthy, rebuilt, needles


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    served = Live(tmp_path_factory.mktemp("ec_chunks"))
    yield served
    served.close()


def test_a_chunk_needle_of_4_mib_is_five_intervals(live):
    assert live.shard_bytes == 3 * MB  # ~24 MiB of records: three rows of 1 MiB blocks
    for key, body in live.body.items():
        ivs = live.reference_intervals(key)
        if len(body) == 4 * MB:
            assert len(ivs) == 5 and [s for s, _o, _n in ivs] == [
                (ivs[0][0] + j) % 10 for j in range(5)]
    assert len(live.reference_intervals(SMALL_KEY)) == 1


@pytest.mark.parametrize("lost", [[s] for s in range(10)] + [[3, 11]],
                         ids=[f"shard{s}" for s in range(10)] + ["shards3and11"])
def test_every_needle_reads_back_byte_for_byte_with_a_shard_lost(live, lost):
    """Whichever data shard is lost, each multi-interval needle is its healthy
    intervals and the rebuilt one joined in order; the counts by source and
    kind are the plain reference's."""
    got, before, after = live.run(live.read_all_with(lost))
    for key, body in live.body.items():
        assert got[key] == body, key
    healthy, rebuilt, needles = live.reference_tally(lost)
    assert rebuilt[0] > 0  # three rows of ten blocks: every data shard holds a needle's bytes
    assert moved(before, after, INTERVALS, source="local") == healthy[0]
    assert (moved(before, after, INTERVALS, source="reconstructed")
            + moved(before, after, INTERVALS, source="cache")) == rebuilt[0]
    assert moved(before, after, INTERVALS) == healthy[0] + rebuilt[0]
    assert moved(before, after, INTERVALS, source="remote") == 0
    assert moved(before, after, INTERVALS, source="cold_tier") == 0
    assert moved(before, after, NEEDLES, kind="degraded") == needles
    assert moved(before, after, NEEDLES, kind="healthy") == len(live.body) - needles
    # a cold reconstruct reads the same span of the ten survivors the decode
    # uses and of no spare (thirteen or twelve are mounted), each on the worker
    # that decodes and none on the loop's thread
    cold = moved(before, after, COLD, kind="cold")
    assert cold == moved(before, after, INTERVALS, source="reconstructed") > 0
    read = moved(before, after, SURVIVOR_BYTES, origin="local")
    assert moved(before, after, SURVIVOR_BYTES, origin="remote") == 0
    assert read % 10 == 0 and rebuilt[1] <= read // 10 <= cold * MB
    assert moved(before, after, LOCAL_READS, where="worker") == 10 * cold
    assert moved(before, after, LOCAL_READS) == 10 * cold


def test_a_rebuilt_interval_is_the_reference_codecs_bytes(live):
    """What `_recover_one_interval` gives for a chunk's interval on the lost
    shard: the bytes the plain codec rebuilds from ten survivors' files, which
    are the bytes of the lost shard's own file."""
    lost = 3
    key, (shard, offset, length) = next(
        (key, iv) for key in live.body if len(live.body[key]) == 4 * MB
        for iv in live.reference_intervals(key) if iv[0] == lost and iv[2] == MB)

    async def rebuilt():
        await live.lose([lost, 11])
        try:
            return await live.vs._recover_one_interval(live.ev, shard, offset, length, key)
        finally:
            await live.mount([lost, 11])

    got = live.run(rebuilt())
    survivors = [s for s in range(14) if s not in (lost, 11)]
    want = ec_locate.rebuild(rs_codec.Codec(10, 4), live.shard_span, lost, offset, length, survivors)
    assert got == want == live.shard_span(lost, offset, length)


def test_a_one_interval_needle_moves_the_new_counters_once(live):
    async def read():
        before = scrape()
        n = await live.vs.read_ec_needle(live.ev, SMALL_KEY)
        return bytes(n.data), before, scrape()

    body, before, after = live.run(read())
    assert body == live.body[SMALL_KEY]
    (shard, _off, length), = live.reference_intervals(SMALL_KEY)
    assert moved(before, after, INTERVALS) == moved(before, after, INTERVALS, source="local") == 1
    assert length == ec_locate.record_bytes(1000)
    assert moved(before, after, NEEDLES) == moved(before, after, NEEDLES, kind="healthy") == 1
    assert moved(before, after, STAGES, stage="local_interval") > 0
    assert moved(before, after, STAGES, stage="assemble") > 0
    assert moved(before, after, SURVIVOR_BYTES) == 0 and moved(before, after, COLD) == 0


@pytest.mark.parametrize("lost", [[], [3, 11]], ids=["healthy", "degraded"])
def test_the_fast_tier_answers_a_4_mib_chunk_itself(live, lost):
    """A GET of a 4 MiB chunk on the public port: 200, the right
    Content-Length and body, and nothing replayed against the aiohttp tier."""
    key = next(k for k, body in live.body.items() if len(body) == 4 * MB
               and any(s == 3 for s, _o, _n in live.reference_intervals(k)))

    async def get():
        await live.lose(lost)
        try:
            before = scrape()
            async with live.session.get(f"http://{live.vs.address}/{live.fid(key)}") as resp:
                return resp.status, dict(resp.headers), await resp.read(), before, scrape()
        finally:
            await live.mount(lost)

    status, headers, body, before, after = live.run(get())
    assert status == 200 and headers["Content-Length"] == str(4 * MB)
    assert body == live.body[key]
    assert moved(before, after, PROXIED, server="volume") == 0
    assert moved(before, after, "seaweedfs_tpu_read_stage_seconds_count", stage="ec_read") == 1
    assert moved(before, after, NEEDLES, kind="degraded" if lost else "healthy") == 1
    assert moved(before, after, INTERVALS) == 5


def test_the_new_stages_are_child_spans_of_a_sampled_request(live):
    """Under a sampled request the flight recorder holds one
    `ec.read.local_interval` an interval read from a local shard, tagged with
    its shard and bytes, and one `ec.read.assemble` tagged with the needle's
    intervals and bytes, all children of the request's root."""
    key = next(k for k, body in live.body.items() if len(body) == 4 * MB)
    rec = trace.RECORDER

    async def read():
        rec.configure(enabled=True, sample=0.0)
        try:
            root = trace.begin_request("volume:GET", None, server="volume")
            await live.vs.read_ec_needle(live.ev, key)
            root.finish()
            return rec.spans()
        finally:
            rec.configure()

    spans = live.run(read())
    root = next(s for s in spans if s["name"] == "volume:GET")
    local = [s for s in spans if s["name"] == "ec.read.local_interval"]
    assemble, = [s for s in spans if s["name"] == "ec.read.assemble"]
    want = live.reference_intervals(key)
    assert [(s["tags"]["shard"], s["tags"]["bytes"]) for s in local] == [
        (shard, n) for shard, _off, n in want]
    assert assemble["tags"] == {"intervals": 5, "bytes": sum(n for _s, _o, n in want)}
    assert {s["parent"] for s in local + [assemble]} == {root["span"]}


def test_no_survivor_is_read_on_the_loops_thread(live, monkeypatch):
    """Ten `read_into`s a cold reconstruct, each on an executor thread; the
    loop's thread does the healthy intervals' `read_at`s and nothing else."""
    key = next(k for k, body in live.body.items() if len(body) == 4 * MB
               and any(s == 3 for s, _o, _n in live.reference_intervals(k)))
    seen = {"read_into": [], "read_at": []}
    for name in seen:
        def spy(self, *args, _inner=getattr(EcVolumeShard, name), _name=name):
            seen[_name].append((self.shard_id, threading.get_ident()))
            return _inner(self, *args)

        monkeypatch.setattr(EcVolumeShard, name, spy)

    async def read():
        await live.lose([3, 11])
        try:
            before = scrape()
            n = await live.vs.read_ec_needle(live.ev, key)
            return bytes(n.data), before, scrape()
        finally:
            await live.mount([3, 11])

    body, before, after = live.run(read())
    assert body == live.body[key]
    loop_thread = live.thread.ident
    assert [s for s, _t in seen["read_into"]] == [0, 1, 2, 4, 5, 6, 7, 8, 9, 10]
    assert loop_thread not in {t for _s, t in seen["read_into"]}
    assert {t for _s, t in seen["read_at"]} == {loop_thread} and len(seen["read_at"]) == 4
    assert moved(before, after, LOCAL_READS, where="worker") == 10
    assert moved(before, after, LOCAL_READS, where="loop") == 0
    # the cell's metric file, as a run evaluates it, and on a tree without the family
    spec = common.load("layer_metrics", "ec_read.worker_read_share.json")
    entry = next(e for e in common.benchmark_json()["per_layer"] if e["name"] == spec["name"])
    assert entry["workloads"] == GET_CELLS
    for field in ("unit", "better", "source", "layer", "moves"):
        assert entry[field] == spec[field], field
    assert layer_metrics.Observed(before, after, {}, {}, {}, {}, None, None, {}).value(spec) == 100.0
    parents = [{k: v for k, v in page.items() if not k.startswith(LOCAL_READS)} for page in (before, after)]
    assert layer_metrics.Observed(*parents, {}, {}, {}, {}, None, None, {}).value(spec) is None


# ------------------- ISSUE 37: the residues of a degraded GET, as stages
DEGRADED_STAGES = "seaweedfs_tpu_ec_degraded_read_stage_seconds_total"
COLD_SECONDS = "seaweedfs_tpu_ec_degraded_read_seconds_sum"
WORKER_SECONDS = "seaweedfs_tpu_ec_degraded_read_worker_seconds_total"


def a_chunk_on_shard_3(live) -> int:
    return next(k for k, body in live.body.items() if len(body) == 4 * MB
                and any(s == 3 for s, _o, _n in live.reference_intervals(k)))


def test_a_cold_reconstructs_stages_add_up_to_its_histogram(live):
    """`loop_resume` (the worker's last line to the coroutine's first after
    the hop) is what the records subtracted as "the rest": with it the
    stages of a cold reconstruct are its histogram's sum. The sums are of
    wall clocks read between statements, and a thread of a busy machine is
    put off for milliseconds at any of them: the best of three reconstructs
    is held to the clocks' limits, every one of them to the counts."""
    key = a_chunk_on_shard_3(live)

    async def read():
        await live.lose([3, 11])  # the unmount empties the degraded-read cache
        try:
            before = scrape()
            n = await live.vs.read_ec_needle(live.ev, key)
            return bytes(n.data), before, scrape()
        finally:
            await live.mount([3, 11])

    for _reading in range(3):
        body, before, after = live.run(read())
        assert body == live.body[key]
        assert moved(before, after, COLD, kind="cold") == 1
        stages = {
            stage: moved(before, after, DEGRADED_STAGES, stage=stage)
            for stage in ("survivor_read", "executor_wait", "decode", "loop_resume", "cache_put")
        }
        assert all(v > 0 for v in stages.values()), stages
        whole = moved(before, after, COLD_SECONDS, result="cold")
        # the worker's wall is its two stages, and its CPU is inside its wall
        wall = moved(before, after, WORKER_SECONDS, clock="wall")
        cpu = moved(before, after, WORKER_SECONDS, clock="cpu")
        clocks = (whole, stages, wall, cpu)
        if (abs(whole - sum(stages.values())) <= max(0.10 * whole, 0.0005)
                and wall >= stages["survivor_read"] + stages["decode"] > 0.9 * wall
                and 0 < cpu <= wall * 1.05 + 0.001):
            break
    else:
        pytest.fail(f"three reconstructs, the last: {clocks}")


def test_a_reconstructs_with_blocks_are_child_spans_of_a_sampled_get(live):
    """Under a sampled request the stages that are `with` blocks are child
    spans of the GET, the worker's too (it runs in the request's context);
    the two waits that cross threads (`executor_wait`, `loop_resume`) are
    counters alone."""
    key = a_chunk_on_shard_3(live)
    rec = trace.RECORDER

    async def read():
        await live.lose([3, 11])
        rec.configure(enabled=True, sample=0.0)
        try:
            root = trace.begin_request("volume:GET", None, server="volume")
            await live.vs.read_ec_needle(live.ev, key)
            root.finish()
            return rec.spans()
        finally:
            rec.configure()
            await live.mount([3, 11])

    spans = live.run(read())
    root = next(s for s in spans if s["name"] == "volume:GET")
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    for name in ("ec.read.locate", "ec.read.survivor_read", "ec.read.decode",
                 "ec.read.cache_put", "ec.read.assemble"):
        assert len(by_name[name]) == 1, name
        assert by_name[name][0]["parent"] == root["span"], name
    assert len(by_name["ec.read.pread"]) == 10
    assert "ec.read.loop_resume" not in by_name and "ec.read.executor_wait" not in by_name


def test_parse_locate_and_write_are_once_a_get_and_in_a_profiler_trace(live, tmp_path):
    """One GET on the public port: `http.parse`, `ec.read.locate` and
    `http.write` each once, their counters moved, their events on the
    loop's line of a profiler trace taken round it."""
    import jax

    key = a_chunk_on_shard_3(live)
    volume = dict(server="volume")

    async def get():
        async with live.session.get(f"http://{live.vs.address}/{live.fid(key)}") as resp:
            return resp.status, await resp.read()

    assert live.run(get())[0] == 200  # the connection is open, the codec is warm
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace_dir = str(tmp_path / "trace")
    before = scrape()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        status, body = live.run(get())
    finally:
        jax.profiler.stop_trace()
    after = scrape()
    assert status == 200 and body == live.body[key]
    assert moved(before, after, "seaweedfs_tpu_response_writes_total", **volume) == 1
    sent = moved(before, after, "seaweedfs_tpu_response_bytes_total", **volume)
    assert 4 * MB < sent < 4 * MB + 1024  # the head and the body in one write
    buffered = moved(before, after, "seaweedfs_tpu_response_buffered_bytes_total", **volume)
    assert 0 <= buffered <= sent
    assert moved(before, after, "seaweedfs_tpu_response_write_seconds_total", **volume) > 0
    assert moved(before, after, "seaweedfs_tpu_request_parse_seconds_total", **volume) > 0
    assert moved(before, after, STAGES, stage="locate") > 0
    assert moved(before, after, NEEDLES) == 1
    events = host_event_names(trace_dir)
    for name in ("http.parse", "ec.read.locate", "http.write"):
        assert events.count(name) == 1, (name, events.count(name))


def test_debug_pprof_device_takes_a_trace_with_the_reads_stages_in_it(live, monkeypatch):
    """`/debug/pprof/device?seconds=S` under the opt-in of the other pprof
    pages: 403 without it; with it a `.xplane.pb` in the directory the
    answer names, holding the `ec.*` events of a degraded read made
    meanwhile; 409 for a second one while the first runs."""
    import shutil

    key = a_chunk_on_shard_3(live)
    url = f"http://{live.vs.address}/debug/pprof/device"

    async def refused():
        async with live.session.get(url + "?seconds=0.1") as resp:
            return resp.status

    monkeypatch.delenv("SEAWEEDFS_TPU_PPROF", raising=False)
    assert live.run(refused()) == 403
    monkeypatch.setenv("SEAWEEDFS_TPU_PPROF", "1")

    async def traced_read():
        await live.lose([3, 11])
        try:
            async def ask(seconds):
                async with live.session.get(f"{url}?seconds={seconds}") as resp:
                    return resp.status, await resp.read()

            first = asyncio.ensure_future(ask(1.0))
            await asyncio.sleep(0.3)  # the trace is on
            second = await ask(0.1)
            n = await live.vs.read_ec_needle(live.ev, key)
            return await first, second, bytes(n.data)
        finally:
            await live.mount([3, 11])

    (status, answer), (second_status, _), body = live.run(traced_read())
    assert status == 200 and second_status == 409
    assert body == live.body[key]
    trace_dir = json.loads(answer)["dir"]
    try:
        names = set(host_event_names(trace_dir))
        # this server decodes on the host codec: the read's own stages
        assert {"ec.read.pread", "ec.read.locate", "ec.read.cache_put"} <= names, sorted(names)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def test_a_decode_altered_as_the_benchmarks_control_alters_it_fails_the_crc(live, monkeypatch):
    """`benchmarks/lib/server_child.py --fault ec_decode_byte` lays a wrapper
    over `TpuRSCodec.reconstruct_rows`; the read path still decodes through
    that method, so a needle with a rebuilt interval fails its CRC and a
    needle without one reads back."""
    from benchmarks.lib import server_child

    monkeypatch.setattr(live.vs, "_codec", TpuRSCodec())
    monkeypatch.setattr(TpuRSCodec, "reconstruct_rows", TpuRSCodec.reconstruct_rows)
    server_child.fault_ec_decode_byte()
    on_lost = [k for k in live.body if any(s == 3 for s, _o, _n in live.reference_intervals(k))]
    healthy = [k for k in live.body if k not in on_lost]
    assert on_lost and healthy

    async def read():
        await live.lose([3, 11])
        try:
            before = scrape()
            got = {}
            for key in (on_lost[0], healthy[0]):
                try:
                    n = await live.vs.read_ec_needle(live.ev, key)
                    got[key] = None if n is None else bytes(n.data)
                except Exception as e:
                    got[key] = e
            return got, before, scrape()
        finally:
            await live.mount([3, 11])

    got, before, after = live.run(read())
    assert isinstance(got[on_lost[0]], Exception) and "CRC" in str(got[on_lost[0]]).upper()
    assert got[healthy[0]] == live.body[healthy[0]]
    assert moved(before, after, "seaweedfs_tpu_rs_dispatches_total", op="decode") == 1


# ------------------------------------------------ the cell's per-layer metrics
@pytest.mark.parametrize("name", CHUNK_METRICS[:-1])
def test_each_chunk_metric_file_reads_what_the_program_wrote(live, name):
    """The cell's own metrics but the roofline (a device trace's), evaluated as
    a run evaluates them against a /metrics pair recorded here; on a tree
    without the counters they are absent, never 0."""
    _got, before, after = live.run(live.read_all_with([3, 11]))
    spec = common.load("layer_metrics", name + ".json")
    entry = next(e for e in common.benchmark_json()["per_layer"] if e["name"] == name)
    assert entry["workloads"] == [CHUNK_CELL]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    client = {"gets_good": len(live.body), "window_s": 2.0,
              "bytes_good": sum(map(len, live.body.values()))}
    seen = layer_metrics.Observed(before, after, {}, {}, client, {}, None, None, {})
    value = seen.value(spec)
    healthy, rebuilt, needles = live.reference_tally([3, 11])
    if name == "ec_read.intervals_per_get":
        assert value == (healthy[0] + rebuilt[0]) / len(live.body)
    elif name == "ec_read.degraded_get_share":
        assert value == 100.0 * needles / len(live.body)
    elif name == "ec_read.survivor_mb_per_reconstruct":
        assert 10 * EC_DEGRADED_SPAN / 1e6 <= value <= 10 * MB / 1e6
    elif name == "http.body_mb_per_s":
        assert value == client["bytes_good"] / 2.0 / 1e6
    else:
        assert value > 0, value
    nothing = layer_metrics.Observed({}, {}, {}, {}, {}, {}, None, None, {})
    assert nothing.value(spec) is None
