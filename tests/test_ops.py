import random

import numpy as np
import pytest

from seaweedfs_tpu.ops.gf256 import gf_matmul_bytes
from seaweedfs_tpu.ops.index_kernel import IndexSnapshot
from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec
from seaweedfs_tpu.storage.needle_map import CompactMap


@pytest.mark.parametrize("n", [4096, 100_001])
def test_gf_matmul_jnp_matches_cpu_oracle(n):
    cpu = CpuRSCodec()
    rng = np.random.default_rng(n)
    data = rng.integers(0, 256, size=(10, n)).astype(np.uint8)
    want = cpu.encode(data)
    got = np.asarray(gf_matmul_bytes(cpu.parity_matrix, data, force_pallas=False))
    assert np.array_equal(got, want)


def test_gf_matmul_pallas_interpret_matches():
    # pallas interpret mode runs the real kernel logic on CPU
    cpu = CpuRSCodec()
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(10, 70_000)).astype(np.uint8)
    want = cpu.encode(data)
    got = np.asarray(
        gf_matmul_bytes(cpu.parity_matrix, data, force_pallas=True, interpret=True)
    )
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (12, 4)])
def test_tpu_codec_matches_cpu(k, m):
    cpu = CpuRSCodec(k, m)
    tpu = TpuRSCodec(k, m)  # falls back to jnp path on CPU
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(k, 10_000)).astype(np.uint8)
    assert np.array_equal(tpu.encode(data), cpu.encode(data))

    shards = cpu.encode_all(data)
    assert tpu.verify(shards)

    for kill_count in (1, m):
        killed = random.sample(range(k + m), kill_count)
        partial = [None if i in killed else shards[i] for i in range(k + m)]
        full = tpu.reconstruct(partial)
        for i in range(k + m):
            assert np.array_equal(full[i], shards[i]), f"shard {i}"


def test_tpu_codec_data_only_reconstruct():
    cpu = CpuRSCodec()
    tpu = TpuRSCodec()
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, size=(10, 5000)).astype(np.uint8)
    shards = cpu.encode_all(data)
    partial = [None if i in (0, 11) else shards[i] for i in range(14)]
    full = tpu.reconstruct(partial, data_only=True)
    assert np.array_equal(full[0], shards[0])
    assert full[11] is None  # parity not rebuilt when data_only


def test_index_snapshot_lookup():
    cm = CompactMap()
    keys = sorted(random.sample(range(1, 2**45), 5000))
    for key in keys:
        cm.set(key, key % 2**30, (key % 1000) + 1)
    for key in keys[::7]:
        cm.delete(key)
    snap = IndexSnapshot.from_map(cm)

    live = [k for i, k in enumerate(keys) if i % 7 != 0]
    probes = np.array(
        live[:100] + [3, 5, 7] + keys[:14:7], dtype=np.uint64
    )  # hits + misses + tombstoned
    off, size, found = snap.lookup(probes)
    for i, k in enumerate(live[:100]):
        assert found[i]
        assert off[i] == k % 2**30
        assert size[i] == (k % 1000) + 1
    assert not found[100] and not found[101] and not found[102]
    assert not found[103] and not found[104]  # deleted keys miss


def test_index_snapshot_empty():
    cm = CompactMap()
    snap = IndexSnapshot.from_map(cm)
    off, size, found = snap.lookup(np.array([1, 2], dtype=np.uint64))
    assert not found.any()


def test_index_snapshot_high_bits():
    # keys above 2^32 exercise the (hi, lo) split
    cm = CompactMap()
    keys = [2**63 + 5, 2**40, 2**32, 2**32 - 1, 12]
    for k in keys:
        cm.set(k, 1, 2)
    snap = IndexSnapshot.from_map(cm)
    off, size, found = snap.lookup(np.array(sorted(keys) + [2**50], dtype=np.uint64))
    assert found[:5].all()
    assert not found[5]


def test_write_ec_files_with_tpu_codec_byte_identical(tmp_path):
    """The EC file pipeline with the TPU codec produces byte-identical shard
    files to the CPU codec (storage.backend=tpu parity gate)."""
    import os

    from seaweedfs_tpu.storage.erasure_coding import to_ext, write_ec_files
    from seaweedfs_tpu.storage.erasure_coding.encoder import DEFAULT_CHUNK

    rng = np.random.default_rng(11)
    payload = rng.integers(0, 256, size=777_777, dtype=np.uint8).tobytes()

    for sub, codec in (("cpu", CpuRSCodec()), ("tpu", TpuRSCodec())):
        d = tmp_path / sub
        d.mkdir()
        base = str(d / "1")
        with open(base + ".dat", "wb") as f:
            f.write(payload)
        write_ec_files(base, codec=codec, large_block_size=10000, small_block_size=100)

    for i in range(14):
        with open(str(tmp_path / "cpu" / "1") + to_ext(i), "rb") as f:
            cpu_bytes = f.read()
        with open(str(tmp_path / "tpu" / "1") + to_ext(i), "rb") as f:
            tpu_bytes = f.read()
        assert cpu_bytes == tpu_bytes, f"shard {i} differs between backends"


def test_write_ec_files_pipelined_many_chunks_byte_identical(tmp_path):
    """The overlapped pipeline (several chunks in flight on the worker pool)
    writes the shard bytes the tests' oracle works out, including odd block
    tails, from the numpy codec and from the device codec."""
    from ec_oracle import oracle_shards
    from seaweedfs_tpu.storage.erasure_coding import to_ext, write_ec_files

    rng = np.random.default_rng(13)
    payload = rng.integers(0, 256, size=654_321, dtype=np.uint8).tobytes()

    for sub, codec in (("numpy", CpuRSCodec()), ("pipe", TpuRSCodec())):
        d = tmp_path / sub
        d.mkdir()
        base = str(d / "1")
        with open(base + ".dat", "wb") as f:
            f.write(payload)
        write_ec_files(
            base,
            codec=codec,
            large_block_size=40_000,
            small_block_size=1_000,
            chunk=4_096,  # forces many in-flight chunks per block
        )
        expected = oracle_shards(base + ".dat", 10, 4, 40_000, 1_000)
        for i in range(14):
            with open(base + to_ext(i), "rb") as f:
                assert f.read() == expected[i], f"{sub}: shard {i} differs"


def test_native_codec_matches_oracle():
    from seaweedfs_tpu import native

    if not native.available():
        pytest.skip("no C++ toolchain")
    from seaweedfs_tpu.storage.erasure_coding.coder_native import NativeRSCodec

    for k, m in ((10, 4), (6, 3)):
        cpu = CpuRSCodec(k, m)
        nat = NativeRSCodec(k, m)
        rng = np.random.default_rng(k)
        data = rng.integers(0, 256, size=(k, 100_003)).astype(np.uint8)
        assert np.array_equal(nat.encode(data), cpu.encode(data))
        shards = cpu.encode_all(data)
        killed = random.sample(range(k + m), m)
        partial = [None if i in killed else shards[i] for i in range(k + m)]
        full = nat.reconstruct(partial)
        for i in range(k + m):
            assert np.array_equal(full[i], shards[i])


def test_gf_matmul_bitsliced_matches_packed():
    """The MXU bit-slice prototype (GF(2) matmul over bit planes) must be
    byte-identical to the shipping packed formulation, including the
    xtime-chain math it replaces."""
    from seaweedfs_tpu.ops.gf256 import (
        gf_matmul_bitsliced,
        gf_matmul_packed,
        pack_bytes_host,
    )
    from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec

    cpu = CpuRSCodec()
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(10, 2048), dtype=np.uint8)
    packed = pack_bytes_host(data)
    a = np.asarray(gf_matmul_packed(cpu.parity_matrix, packed))
    b = np.asarray(gf_matmul_bitsliced(cpu.parity_matrix, packed))
    assert (a == b).all()
