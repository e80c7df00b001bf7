"""Repair-plane fast path (ISSUE 3): missing-rows-only decode, pipelined
rebuild with atomic outputs, the degraded-read interval cache, and the
tier-1 guards for the rebuild stage breakdown and the decode-matrix LRU
bound.
"""

import os
import random

import numpy as np
import pytest

from seaweedfs_tpu.storage.erasure_coding import (
    rebuild_ec_files,
    rebuild_ec_files_multi,
    to_ext,
    write_ec_files,
)
from seaweedfs_tpu.storage.erasure_coding import encoder as enc
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec
from seaweedfs_tpu.storage.erasure_coding.galois import (
    DECODE_ROWS_CACHE,
    DecodeRowsCache,
    compose_decode_rows,
)


def _codecs():
    yield CpuRSCodec()
    try:
        from seaweedfs_tpu.storage.erasure_coding.coder_native import (
            NativeRSCodec,
        )

        yield NativeRSCodec()
    except (RuntimeError, OSError):
        pass


# ---------------- reconstruct_rows == reconstruct (property) ----------------


def test_reconstruct_rows_matches_full_reconstruct_property():
    """For every sampled (survivor set, wanted rows): reconstruct_rows is
    byte-identical to the full reconstruct on those ids — data rows, parity
    rows, and pass-through of already-present shards alike."""
    rng = np.random.default_rng(0)
    r = random.Random(42)
    for codec in _codecs():
        k, total = codec.data_shards, codec.total_shards
        data = rng.integers(0, 256, size=(k, 2048), dtype=np.uint8)
        shards = codec.encode_all(data)
        for _trial in range(40):
            keep = r.sample(range(total), r.randint(k, total))
            slots = [shards[i] if i in keep else None for i in range(total)]
            wanted = r.sample(range(total), r.randint(1, total))
            full = codec.reconstruct(list(slots))
            got = codec.reconstruct_rows(list(slots), wanted)
            for w, g in zip(wanted, got):
                assert np.array_equal(np.asarray(g), np.asarray(full[w])), (
                    type(codec).__name__,
                    sorted(keep),
                    wanted,
                    w,
                )


def test_reconstruct_rows_out_buffer_matches():
    """The recycled-out-buffer path returns the same bytes and actually
    lands them in the caller's buffer."""
    rng = np.random.default_rng(1)
    for codec in _codecs():
        k, total = codec.data_shards, codec.total_shards
        data = rng.integers(0, 256, size=(k, 512), dtype=np.uint8)
        shards = codec.encode_all(data)
        missing = [0, 3, total - 1]
        slots = [
            shards[i] if i not in missing else None for i in range(total)
        ]
        out = np.zeros((len(missing), 512), dtype=np.uint8)
        got = codec.reconstruct_rows(list(slots), missing, out=out)
        full = codec.reconstruct(list(slots))
        for r_i, w in enumerate(missing):
            assert np.array_equal(np.asarray(got[r_i]), np.asarray(full[w]))
            assert np.array_equal(out[r_i], np.asarray(full[w]))


def test_reconstruct_rows_too_few_survivors_raises():
    codec = CpuRSCodec()
    slots = [None] * codec.total_shards
    slots[0] = np.zeros(64, dtype=np.uint8)
    with pytest.raises(ValueError):
        codec.reconstruct_rows(slots, [1])


# ---------------- decode-matrix LRU ----------------


def test_decode_rows_cache_bounded_under_survivor_churn():
    """Tier-1 guard: randomized survivor/wanted churn cannot grow the LRU
    past its bound, and cached entries stay equal to a fresh composition."""
    cache = DecodeRowsCache(maxsize=32)
    codec = CpuRSCodec()
    r = random.Random(7)
    k, total = codec.data_shards, codec.total_shards
    for _ in range(500):
        survivors = sorted(r.sample(range(total), k))
        wanted = sorted(r.sample(range(total), r.randint(1, 4)))
        rows = cache.rows_for(codec.matrix, survivors, wanted)
        assert len(cache) <= 32
        if r.random() < 0.05:  # spot-check correctness of a cached entry
            fresh = compose_decode_rows(codec.matrix, survivors, wanted)
            assert np.array_equal(rows, fresh)
    assert len(cache) <= 32
    # the shared process-wide instance is bounded too
    assert len(DECODE_ROWS_CACHE) <= DECODE_ROWS_CACHE.maxsize


# ---------------- rebuild oracle + torn outputs ----------------


def _make_volume(tmp_path, size, seed=0):
    base = str(tmp_path / "1")
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size=size, dtype=np.uint8).tobytes())
    write_ec_files(base)
    originals = {}
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            originals[i] = f.read()
    return base, originals


def test_rebuild_vs_reencode_oracle_random_survivors(tmp_path):
    """Rebuild from random survivor subsets must reproduce the freshly
    encoded shards byte-for-byte, across routes and loss patterns."""
    base, originals = _make_volume(tmp_path, (2 << 20) + 12345)
    r = random.Random(3)
    routes = ["pread", "mmap", "onepass"]
    for trial in range(4):
        missing = sorted(r.sample(range(14), r.randint(1, 4)))
        for i in missing:
            os.remove(base + to_ext(i))
        rebuilt = rebuild_ec_files(
            base, route=routes[trial % len(routes)], chunk=256 * 1024
        )
        assert sorted(rebuilt) == missing
        for i in range(14):
            with open(base + to_ext(i), "rb") as f:
                assert f.read() == originals[i], (trial, i)


def test_rebuild_failure_leaves_no_torn_outputs(tmp_path):
    """A rebuild that dies mid-flight (truncated survivor) must leave
    neither a truncated .ecNN nor a stale .ecNN.tmp behind — a torn output
    counting as a 'present' survivor later would corrupt the volume."""
    base, originals = _make_volume(tmp_path, (2 << 20) + 999)
    os.remove(base + to_ext(4))
    # truncate a survivor: the upfront size survey must refuse
    with open(base + to_ext(7), "r+b") as f:
        f.truncate(12345)
    with pytest.raises((IOError, OSError)):
        rebuild_ec_files(base)
    assert not os.path.exists(base + to_ext(4))
    assert not os.path.exists(base + to_ext(4) + ".tmp")
    # restore the survivor: rebuild succeeds and is byte-identical
    with open(base + to_ext(7), "wb") as f:
        f.write(originals[7])
    assert rebuild_ec_files(base) == [4]
    with open(base + to_ext(4), "rb") as f:
        assert f.read() == originals[4]


def test_rebuild_sweeps_stale_tmp_outputs(tmp_path):
    """Leftover .ecNN.tmp from a crashed rebuild is removed, never treated
    as a survivor, and the rebuild still produces correct bytes."""
    base, originals = _make_volume(tmp_path, 1 << 20)
    os.remove(base + to_ext(2))
    with open(base + to_ext(2) + ".tmp", "wb") as f:
        f.write(b"torn garbage")
    assert rebuild_ec_files(base) == [2]
    assert not os.path.exists(base + to_ext(2) + ".tmp")
    with open(base + to_ext(2), "rb") as f:
        assert f.read() == originals[2]


def test_rebuild_multi_volume_batches(tmp_path):
    """rebuild_ec_files_multi repairs several volumes (host route) with
    byte-identical output, including mixed loss patterns."""
    vols = []
    for v in range(3):
        d = tmp_path / str(v)
        d.mkdir()
        vols.append(_make_volume(d, (1 << 20) + v * 4097, seed=v))
    losses = [[0, 13], [5], [1, 2, 10, 11]]
    for (base, _orig), missing in zip(vols, losses):
        for i in missing:
            os.remove(base + to_ext(i))
    res = rebuild_ec_files_multi([b for b, _o in vols])
    for (base, originals), missing in zip(vols, losses):
        assert res[base] == missing
        for i in range(14):
            with open(base + to_ext(i), "rb") as f:
                assert f.read() == originals[i], (base, i)


def test_rebuild_multi_volume_mesh_leg(tmp_path):
    """The multi-chip leg: rebuild_ec_files_multi(mesh=...) routes shared
    decode batches through sharded_reconstruct_padded and stays
    byte-identical (virtual host mesh — the same path a TPU mesh takes)."""
    jax = pytest.importorskip("jax")
    from seaweedfs_tpu.parallel.sharded_ec import make_mesh
    from seaweedfs_tpu.tpu.coder import get_codec

    codec = get_codec("numpy")
    vols = []
    for v in range(2):
        d = tmp_path / str(v)
        d.mkdir()
        vols.append(_make_volume(d, (1 << 20) + 321 + v, seed=10 + v))
    for base, _orig in vols:
        for i in (1, 12):
            os.remove(base + to_ext(i))
    mesh = make_mesh(devices=jax.devices("cpu"))
    res = rebuild_ec_files_multi(
        [b for b, _o in vols], codec=codec, chunk=256 * 1024, mesh=mesh
    )
    for base, originals in vols:
        assert res[base] == [1, 12]
        for i in range(14):
            with open(base + to_ext(i), "rb") as f:
                assert f.read() == originals[i], (base, i)


def test_rebuild_stage_breakdown_nonzero(tmp_path):
    """Tier-1 guard: every rebuild publishes a stage breakdown whose
    components are non-zero (fused routes disclose fused_s instead)."""
    base, _originals = _make_volume(tmp_path, (1 << 20) + 54321)
    for i in (0, 11):
        os.remove(base + to_ext(i))
    rebuild_ec_files(base, route="pread", chunk=128 * 1024)
    st = enc.LAST_REBUILD_STAGES
    assert st["total_s"] > 0
    assert st["read_s"] > 0 and st["decode_s"] > 0 and st["write_s"] > 0
    assert enc.LAST_REBUILD_ROUTE["route"] == "pread"
