"""Degraded-read fast path (ISSUE 3): concurrent survivor fetches, the
reconstructed-interval cache, its .ecj-delete invalidation, and the
cold-vs-cache-hit split of seaweedfs_tpu_ec_reconstructions_total; and the
survivors read into the decode's own input array (ISSUE 36): a rebuilt span
against tests/ec_oracle.py at every shape a span can have, under every codec.

The harness drives EcHandlers._recover_one_interval directly against a
real on-disk EC volume; "remote" shard holders are a fault-injection seam
that reads the real shard bytes after an injected latency."""

import asyncio
import time

import numpy as np
import pytest

from ec_oracle import oracle_shards
from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
from seaweedfs_tpu.server import volume_ec
from seaweedfs_tpu.server.volume_ec import (
    DegradedIntervalCache,
    EC_DEGRADED_SPAN,
    EC_REMOTE_SPAN,
    EcHandlers,
)
from seaweedfs_tpu.storage.erasure_coding import to_ext, write_ec_files
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (
    EcVolume,
    EcVolumeShard,
)
from seaweedfs_tpu.storage.idx import entry_to_bytes
from seaweedfs_tpu.util.metrics import EC_RECONSTRUCTIONS


class _Host(EcHandlers):
    """Just enough VolumeServer surface for the degraded-read path."""

    address = "127.0.0.1:0"
    public_url = "localhost:0"
    codec = CpuRSCodec()
    codec_backend = "numpy"

    def __init__(self, store=None):
        self.store = store


class _Store:
    def __init__(self, ev):
        self._ev = ev

    def find_ec_volume(self, vid):
        return self._ev


def _reconstruction_counts() -> dict:
    with EC_RECONSTRUCTIONS._lock:
        return {
            dict(k).get("kind", ""): v
            for k, v in EC_RECONSTRUCTIONS._values.items()
        }


def _make_ec_volume(tmp_path, vid=1, needle_key=7):
    """Real shard files + a 1-entry .ecx so EcVolume loads and deletes."""
    base = str(tmp_path / str(vid))
    rng = np.random.default_rng(5)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size=1 << 20, dtype=np.uint8).tobytes())
    write_ec_files(base)
    with open(base + ".ecx", "wb") as f:
        f.write(entry_to_bytes(needle_key, 1, 100))
    ev = EcVolume(str(tmp_path), "", vid)
    return base, ev


def test_survivor_fetches_are_concurrent(tmp_path):
    """Fault-injected latency on every remote survivor read: the recover
    wall must track the SLOWEST survivor, not the sum of 13 of them."""
    base, ev = _make_ec_volume(tmp_path)
    host = _Host()
    delay = 0.05
    calls = []

    async def injected_remote_read(ev_, shard_id, offset, size, key, deadline=None, sent=None):
        calls.append(shard_id)
        await asyncio.sleep(delay)  # injected network latency
        with open(base + to_ext(shard_id), "rb") as f:
            f.seek(offset)
            return f.read(size)

    host._read_remote_shard_interval = injected_remote_read

    async def body():
        t0 = time.perf_counter()
        out = await host._recover_one_interval(ev, 3, 4096, 1024, 0)
        return out, time.perf_counter() - t0

    out, wall = asyncio.run(body())
    with open(base + to_ext(3), "rb") as f:
        f.seek(4096)
        assert out == f.read(1024)
    # remote fetch amplification is trimmed: only k holders are asked (none
    # to spare: the gather waits for the slowest of those asked, ISSUE 33),
    # in ONE gather — not all 13 candidates
    assert len(calls) == ev.data_shards
    # serial would be >= 11 * delay = 0.55s; concurrent ~= one delay
    assert wall < 7 * delay, f"survivor fetches look serialized: {wall:.3f}s"
    ev.close()


def test_degraded_cache_hit_and_counters(tmp_path):
    """Repeat reads of a dead shard come from the interval cache with the
    same bytes as a cold reconstruct, and the reconstruction counter
    distinguishes the two kinds."""
    base, ev = _make_ec_volume(tmp_path)
    # mount every shard EXCEPT the dead one locally
    dead = 2
    for i in range(14):
        if i != dead:
            ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, i))
    host = _Host()

    async def no_remote(*a, **kw):
        return None

    host._read_remote_shard_interval = no_remote
    before = _reconstruction_counts()

    off, size = 3 * EC_DEGRADED_SPAN + 513, 2048
    cold = asyncio.run(host._recover_one_interval(ev, dead, off, size, 0))
    with open(base + to_ext(dead), "rb") as f:
        f.seek(off)
        assert cold == f.read(size)
    mid = _reconstruction_counts()
    assert mid.get("cold", 0) == before.get("cold", 0) + 1

    hit = asyncio.run(host._recover_one_interval(ev, dead, off, size, 0))
    assert hit == cold
    # readahead: a neighbouring interval in the same span is a hit too
    near = asyncio.run(host._recover_one_interval(ev, dead, off + size, 512, 0))
    with open(base + to_ext(dead), "rb") as f:
        f.seek(off + size)
        assert near == f.read(512)
    after = _reconstruction_counts()
    assert after.get("cold", 0) == mid.get("cold", 0)  # no new cold decode
    assert after.get("cache_hit", 0) == before.get("cache_hit", 0) + 2
    ev.close()


def test_ecj_delete_invalidates_cache(tmp_path):
    """A blob delete (tombstone -> .ecj) drops the volume's cached spans:
    the next degraded read pays a cold reconstruct again."""
    base, ev = _make_ec_volume(tmp_path, needle_key=7)
    dead = 5
    for i in range(14):
        if i != dead:
            ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, i))
    host = _Host(store=_Store(ev))

    async def no_remote(*a, **kw):
        return None

    host._read_remote_shard_interval = no_remote
    asyncio.run(host._recover_one_interval(ev, dead, 0, 1024, 0))
    assert len(host._ec_degraded_cache()) == 1

    asyncio.run(
        host._grpc_ec_blob_delete({"volume_id": 1, "file_key": 7}, None)
    )
    assert len(host._ec_degraded_cache()) == 0
    before = _reconstruction_counts()
    asyncio.run(host._recover_one_interval(ev, dead, 0, 1024, 0))
    assert (
        _reconstruction_counts().get("cold", 0) == before.get("cold", 0) + 1
    )
    ev.close()


def test_interval_cache_capacity_bounded():
    cache = DegradedIntervalCache(capacity_bytes=4 * EC_DEGRADED_SPAN)
    span = bytes(EC_DEGRADED_SPAN)
    for i in range(32):
        cache.put(1, 0, i * EC_DEGRADED_SPAN, span)
        assert len(cache) <= 4
    # most-recent spans survive
    assert (
        cache.get(1, 0, 31 * EC_DEGRADED_SPAN, 16) == span[:16]
    )
    assert cache.get(1, 0, 0, 16) is None


def test_interval_cache_span_alignment():
    # unknown shard size: exact span, no readahead
    assert DegradedIntervalCache.span_for(1000, 64, None) == (1000, 64)
    # aligned span within the shard
    start, size = DegradedIntervalCache.span_for(
        EC_DEGRADED_SPAN + 5, 64, 10 * EC_DEGRADED_SPAN
    )
    assert start == EC_DEGRADED_SPAN and size == EC_DEGRADED_SPAN
    # tail capped at shard size
    start, size = DegradedIntervalCache.span_for(
        9 * EC_DEGRADED_SPAN + 5, 64, 9 * EC_DEGRADED_SPAN + 100
    )
    assert start + size == 9 * EC_DEGRADED_SPAN + 100


def test_interval_cache_finds_a_span_of_the_remote_alignment():
    # a reconstruct whose survivors cross gRPC reads ahead to EC_REMOTE_SPAN
    off = 3 * EC_DEGRADED_SPAN + 2 * EC_REMOTE_SPAN + 100
    start, size = DegradedIntervalCache.span_for(
        off, 64, 10 * EC_DEGRADED_SPAN, EC_REMOTE_SPAN
    )
    assert (start, size) == (off - 100, EC_REMOTE_SPAN)
    # a record over the boundary takes both sides of it
    assert DegradedIntervalCache.span_for(
        off, EC_REMOTE_SPAN, 10 * EC_DEGRADED_SPAN, EC_REMOTE_SPAN
    ) == (start, 2 * EC_REMOTE_SPAN)
    cache = DegradedIntervalCache(capacity_bytes=4 * EC_DEGRADED_SPAN)
    span = bytes(range(256)) * (EC_REMOTE_SPAN // 256)
    cache.put(1, 3, start, span)
    assert cache.get(1, 3, off, 64) == span[100:164]
    assert cache.get(1, 3, start + EC_REMOTE_SPAN - 8, 8) == span[-8:]
    # past the span's end, or in the wide span's other narrow spans: a miss
    assert cache.get(1, 3, start + EC_REMOTE_SPAN - 8, 9) is None
    assert cache.get(1, 3, start - EC_REMOTE_SPAN, 8) is None
    assert cache.get(1, 3, 3 * EC_DEGRADED_SPAN, 8) is None


# ------------------------------- survivors read into the decode's input array
CODECS = {
    "numpy": CpuRSCodec,  # rows of any width
    "jnp": TpuRSCodec,  # rows of whole packed words: 4 bytes
    # rows of the Pallas kernel's block, 256 KiB (interpreted here)
    "pallas": lambda: TpuRSCodec(force_pallas=True, interpret=True),
}
# (offset, size, the shard size the volume reports or None for its own, shards
# mounted here) -> the span the read path rebuilds
SHAPES = {
    # two aligned spans: as wide as the kernel's block, nothing to pad
    "block_wide": (3 * EC_DEGRADED_SPAN - 10, 20, None, 13),
    # one aligned span: half a block
    "half_a_block": (3 * EC_DEGRADED_SPAN + 513, 2048, None, 13),
    # the span's end cut off at the shard's end, at an odd width
    "clipped": (7 * EC_DEGRADED_SPAN + 5, 64, (1 << 20) - 1001, 13),
    # no shard here: no shard size, so the interval alone, every survivor fetched
    "interval_alone": (4099, 1001, None, 0),
    # four here and six fetched, as on a 4/4/3/3 spread
    "mixed": (5 * EC_REMOTE_SPAN + 77, 900, None, 4),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("codec", sorted(CODECS))
def test_a_rebuilt_span_is_the_oracles_whatever_its_width(tmp_path, monkeypatch, codec, shape):
    """The worker's rows hold whatever the last decode left (here 0xA5) past
    the span's width, and the codec computes on it: none of that reaches the
    caller or the span cache."""
    off, size, shard_size, mounted = SHAPES[shape]
    dead = 3
    base, ev = _make_ec_volume(tmp_path)
    for i in [s for s in range(14) if s != dead][:mounted]:
        ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, i))
    if shard_size is not None:
        monkeypatch.setattr(ev, "shard_size", lambda: shard_size)
    host = _Host()
    host.codec = CODECS[codec]()

    async def remote(_ev, shard_id, offset, length, key, deadline=None, sent=None):
        with open(base + to_ext(shard_id), "rb") as f:
            f.seek(offset)
            return f.read(length)

    host._read_remote_shard_interval = remote
    widths = []
    inner = volume_ec._survivor_rows

    def stale_rows(k, width):
        rows = inner(k, width)
        rows[:] = 0xA5
        widths.append(width)
        return rows

    monkeypatch.setattr(volume_ec, "_survivor_rows", stale_rows)
    got = asyncio.run(asyncio.wait_for(host._recover_one_interval(ev, dead, off, size, 0), 120))
    want = oracle_shards(base + ".dat")[dead]
    assert got == want[off : off + size]
    (key, span), = host._ec_degraded_cache()._spans.items()
    align = EC_DEGRADED_SPAN if mounted >= 10 else EC_REMOTE_SPAN
    start, length = DegradedIntervalCache.span_for(off, size, ev.shard_size() or None, align)
    assert key == (1, dead, start) and span == want[start : start + length]
    # the rows are as wide as this codec uploads them: the span rounded up
    granule = host.codec.row_granule()
    assert widths == [-(-length // granule) * granule]
    assert (widths[0] > length) == {
        "numpy": False, "jnp": length % 4 != 0,
        "pallas": shape != "block_wide"}[codec]
    ev.close()


def test_a_workers_rows_are_its_own_and_used_again():
    """One array a thread, grown to the widest span it has rebuilt and handed
    out again; a span wider than a small block gets an array that is not kept."""
    import threading

    a = volume_ec._survivor_rows(10, 1 << 17)
    b = volume_ec._survivor_rows(10, 1 << 16)
    assert a.shape == (10, 1 << 17) and b.shape == (10, 1 << 16)
    assert a.flags.c_contiguous and b.flags.c_contiguous and np.shares_memory(a, b)
    wide = volume_ec._survivor_rows(10, (1 << 20) + 4)
    assert not np.shares_memory(wide, a) and np.shares_memory(volume_ec._survivor_rows(10, 8), a)
    other = []
    t = threading.Thread(target=lambda: other.append(volume_ec._survivor_rows(10, 1 << 17)))
    t.start()
    t.join(30)
    assert not np.shares_memory(other[0], a)
