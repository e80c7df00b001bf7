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


@pytest.mark.parametrize(
    "n", [16 << 10, 128 << 10, 256 << 10, 1 << 20, 100_001],
    ids=["16k", "128k", "256k", "1m", "ragged"],
)
def test_every_codec_call_through_the_kernel_is_the_oracles(n):
    """encode, reconstruct_rows (survivors as separate arrays, and as rows
    a caller filled into an array a whole number of the kernel's granule
    wide, with garbage past the span) and apply_matrix, through the Pallas
    kernel in interpret mode: byte for byte the numpy codec's."""
    cpu = CpuRSCodec()
    tpu = TpuRSCodec(force_pallas=True, interpret=True)
    rng = np.random.default_rng(n)
    data = rng.integers(0, 256, size=(10, n), dtype=np.uint8)
    shards = cpu.encode_all(data)
    assert np.array_equal(tpu.encode(data), shards[10:])

    lost = (3, 11)
    partial = [None if i in lost else shards[i] for i in range(14)]
    for i, row in zip(lost, tpu.reconstruct_rows(partial, list(lost))):
        assert np.array_equal(row, shards[i]), i

    granule = tpu.row_granule()
    wide = -(-n // granule) * granule
    filled = rng.integers(0, 256, size=(10, wide), dtype=np.uint8)
    survivors = [i for i in range(14) if i not in lost][:10]
    for j, i in enumerate(survivors):
        filled[j, :n] = shards[i]
    given = [None] * 14
    for j, i in enumerate(survivors):
        given[i] = filled[j, :n]
    for i, row in zip(lost, tpu.reconstruct_rows(given, list(lost))):
        assert np.array_equal(row, shards[i]), i

    rebuild = cpu.matrix[list(lost)]
    assert np.array_equal(tpu.apply_matrix(rebuild, data), shards[list(lost)])


def test_the_words_go_up_as_a_view_and_come_back_as_one(monkeypatch):
    """The jitted kernel is handed the packed words as a [C, S, 128] view
    of the caller's granule-wide rows (nothing copied on the host) and
    uploads them itself; it is the one program launched, and the rows
    returned are a view of its fetched result."""
    from seaweedfs_tpu.ops import gf256

    seen = {"given": []}
    kernel = gf256._gf_matmul_pallas

    def spy(key, words, *args):
        seen["given"].append(words)
        seen["out"] = kernel(key, words, *args)
        return seen["out"]

    cpu = CpuRSCodec()
    data = np.random.default_rng(5).integers(
        0, 256, size=(10, 2 * gf256.row_granule(interpret=True)), dtype=np.uint8
    )
    monkeypatch.setattr(gf256, "_gf_matmul_pallas", spy)
    got = gf256.gf_matmul_bytes(cpu.parity_matrix, data, interpret=True)
    assert np.array_equal(got, cpu.encode(data))
    [words] = seen["given"]
    assert isinstance(words, np.ndarray) and words.shape == (
        10, data.shape[1] // 4 // gf256.LANE, gf256.LANE
    )
    assert np.shares_memory(words, data)
    fetched = np.asarray(seen["out"])
    assert fetched.shape == (4,) + words.shape[1:]
    assert np.shares_memory(got, fetched)


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
