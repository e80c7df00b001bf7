"""The program's codecs and EC shard files, byte for byte against the
benchmark's own plain Reed-Solomon codec (benchmarks/reference/rs_codec.py),
the yardstick every cell's `correct` relies on. The reference imports
nothing of the program, and every expected byte here comes from it (and
from `ec_locate.large_rows` for the layout at reduced block sizes).

The device codec runs its Pallas kernel interpreted on the CPU; "cpu" is
the native library's codec, or the numpy one where the library cannot be
built. Sizes are small: the whole file takes seconds.
"""

import functools
import os

import numpy as np
import pytest

from benchmarks.reference import ec_locate, rs_codec
from seaweedfs_tpu.storage.erasure_coding import (
    rebuild_ec_files,
    to_ext,
    write_ec_files,
)
from seaweedfs_tpu.storage.erasure_coding.galois import build_matrix
from seaweedfs_tpu.tpu.coder import get_codec

GEOMETRIES = [(10, 4), (6, 3), (12, 4)]
BACKENDS = ["numpy", "cpu", "tpu"]
# {3, 11}: the shards the chip smoke and the degraded cells lose
LOST_SETS = [(3,), (11,), (3, 11), (0, 5, 10, 13)]
SMALL_ROW = 10 << 20  # one row of RS(10,4) small blocks


def _ids(cases):
    return ["-".join(str(x) for x in c) for c in cases]


@functools.lru_cache(maxsize=None)
def reference(k: int, m: int) -> rs_codec.Codec:
    return rs_codec.Codec(k, m)


def codec(backend: str, k: int, m: int):
    return get_codec(backend, k, m, interpret=backend == "tpu")


def random_bytes(seed: int, *shape) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, shape, dtype=np.uint8)


@pytest.mark.parametrize("k,m", GEOMETRIES, ids=[f"{k}.{m}" for k, m in GEOMETRIES])
def test_parity_matrix_is_the_references(k, m):
    assert np.array_equal(build_matrix(k, k + m), reference(k, m).matrix)


@pytest.mark.parametrize("k,m", GEOMETRIES, ids=[f"{k}.{m}" for k, m in GEOMETRIES])
@pytest.mark.parametrize("backend", BACKENDS)
def test_encode_is_the_references(backend, k, m):
    data = random_bytes(k * 100 + m, k, 4099)
    got = np.asarray(codec(backend, k, m).encode(data))
    assert np.array_equal(got, reference(k, m).encode(data))


@pytest.mark.parametrize("lost", LOST_SETS, ids=_ids(LOST_SETS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_reconstruct_is_the_references(backend, lost):
    """Each backend's served decode entry, `reconstruct_rows`, against the
    reference's `recover` from the same survivors."""
    ref = reference(10, 4)
    data = random_bytes(sum(lost), 10, 5003)
    full = np.concatenate([data, ref.encode(data)])
    given = [None if i in lost else full[i] for i in range(14)]
    got = codec(backend, 10, 4).reconstruct_rows(given, list(lost))
    survivors = {i: full[i] for i in range(14) if i not in lost}
    want = ref.recover(survivors, list(lost))
    for row, w in zip(got, want):
        assert np.array_equal(np.asarray(row), w)


def write_dat(base: str, size: int) -> np.ndarray:
    dat = random_bytes(size, size)
    dat.tofile(base + ".dat")
    return dat


def reference_shards(dat: np.ndarray, spans: list, k: int = 10, m: int = 4) -> list:
    """Shard i is block i of every row, in order: a data block, or a block
    of the parity the reference computes for that row."""
    shards = [[] for _ in range(k + m)]
    for dat_off, block in spans:
        rows = rs_codec.data_rows(dat, dat_off, block, k)
        for i, row in enumerate(np.concatenate([rows, reference(k, m).encode(rows)])):
            shards[i].append(row.tobytes())
    return [b"".join(s) for s in shards]


def reduced_spans(size: int, large: int, small: int, k: int = 10) -> list:
    """[(offset in the .dat, block size)] per row at any block sizes."""
    n_large = ec_locate.large_rows(size, k, large)
    rest = size - n_large * large * k
    n_small = -(-rest // (small * k)) if rest > 0 else 0
    return [(r * large * k, large) for r in range(n_large)] + [
        (n_large * large * k + r * small * k, small) for r in range(n_small)
    ]


def shard_files(base: str, k: int = 10, m: int = 4) -> list:
    out = []
    for i in range(k + m):
        with open(base + to_ext(i), "rb") as f:
            out.append(f.read())
    return out


LARGE, SMALL = 64 << 10, 4 << 10  # reduced blocks: 640 KiB large rows
# (id, .dat bytes, (large, small) block sizes or None for the real ones,
# rows of large blocks the layout has)
SHARD_FILE_CASES = [
    # real block sizes (1 GiB large, 1 MiB small): small rows only
    ("0", 0, None, 0),
    ("1", 1, None, 0),
    ("small-row-less-1", SMALL_ROW - 1, None, 0),
    ("small-row", SMALL_ROW, None, 0),
    ("2-small-rows-and-1", 2 * SMALL_ROW + 1, None, 0),
    # reduced block sizes, so that rows of large blocks come first; a .dat
    # of exactly one large row is small rows alone
    ("1-large-row", 10 * LARGE, (LARGE, SMALL), 0),
    ("1-large-row-and-1", 10 * LARGE + 1, (LARGE, SMALL), 1),
    ("3-large-rows-and-tail", 30 * LARGE + 25 * SMALL + 7, (LARGE, SMALL), 3),
]


@pytest.mark.parametrize(
    "size,blocks,n_large", [c[1:] for c in SHARD_FILE_CASES], ids=[c[0] for c in SHARD_FILE_CASES]
)
def test_shard_files_are_the_references(tmp_path, size, blocks, n_large):
    base = str(tmp_path / "1")
    dat = write_dat(base, size)
    if blocks is None:
        write_ec_files(base)
        spans = [(dat_off, block) for dat_off, _shard_off, block in rs_codec.row_spans(size, 10)]
        assert sum(block for _o, block in spans) == rs_codec.shard_size(size, 10)
        large = rs_codec.LARGE_BLOCK
    else:
        large, small = blocks
        write_ec_files(base, large_block_size=large, small_block_size=small)
        spans = reduced_spans(size, large, small)
    assert sum(block == large for _o, block in spans) == n_large
    want = reference_shards(dat, spans)
    got = shard_files(base)
    for i in range(14):
        assert got[i] == want[i], i


@pytest.mark.parametrize("lost", [(3,), (3, 11), (0, 5, 10, 13)], ids=["3", "3-11", "0-5-10-13"])
def test_rebuilt_shards_are_the_references(tmp_path, lost):
    """A .dat of one small row and a part: the shards rebuilt after the
    loss are the reference's own."""
    size = SMALL_ROW + 12345
    base = str(tmp_path / "1")
    dat = write_dat(base, size)
    write_ec_files(base)
    for i in lost:
        os.remove(base + to_ext(i))
    assert sorted(rebuild_ec_files(base)) == list(lost)
    spans = [(dat_off, block) for dat_off, _shard_off, block in rs_codec.row_spans(size, 10)]
    want = reference_shards(dat, spans)
    got = shard_files(base)
    for i in range(14):
        assert got[i] == want[i], i
