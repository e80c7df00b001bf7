"""The native codec in the encode pipeline, with and without the
kernel-side data splice, and the numpy codec beside it — all byte-identical
to the tests' oracle (ec_oracle; ref semantics:
weed/storage/erasure_coding/ec_encoder.go) — and the adaptive codec choice.
"""

import errno
import os

import numpy as np
import pytest

from ec_oracle import oracle_shards
from seaweedfs_tpu.storage.erasure_coding import to_ext, write_ec_files
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec

native = pytest.importorskip("seaweedfs_tpu.native")
if not native.available():
    pytest.skip("native gf256 library unavailable", allow_module_level=True)

from seaweedfs_tpu.storage.erasure_coding.coder_native import NativeRSCodec

LARGE, SMALL = 8192, 1024  # scaled-down 1GB/1MB geometry


def _write_dat(path: str, size: int) -> None:
    data = np.random.default_rng(size).integers(0, 256, size, dtype=np.uint8)
    with open(path, "wb") as f:
        f.write(data.tobytes())


def _read_shards(base: str) -> list:
    out = []
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            out.append(f.read())
    return out


# sizes hitting: large rows + small rows + EOF mid-block + EOF mid-row
SIZES = [LARGE * 10 * 2 + SMALL * 10 * 3 + 700, SMALL * 4 + 17, 0, SMALL * 10]


@pytest.mark.parametrize("size", SIZES)
def test_mmap_and_splice_match_oracle(tmp_path, size):
    _write_dat(str(tmp_path / "1.dat"), size)
    golden = oracle_shards(str(tmp_path / "1.dat"), 10, 4, LARGE, SMALL)

    for label, codec, kw in [
        ("auto", NativeRSCodec(), {}),  # the splice where the fs allows it
        ("no-splice", NativeRSCodec(), {"splice_data": False}),
        ("numpy codec", CpuRSCodec(), {}),
    ]:
        d = tmp_path / label
        d.mkdir()
        os.link(str(tmp_path / "1.dat"), str(d / "1.dat"))
        run = write_ec_files(
            str(d / "1"), codec=codec,
            large_block_size=LARGE, small_block_size=SMALL, **kw,
        )
        assert _read_shards(str(d / "1")) == golden, (label, size)
        assert run.route["route"] == "pipeline", label
        assert not (run.route["spliced"] and label == "no-splice")


def test_encode_rows_pointer_api_matches_stacked():
    c = NativeRSCodec()
    oracle = CpuRSCodec()
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    rows = [np.ascontiguousarray(r) for r in data]
    assert np.array_equal(c.encode_rows(rows), oracle.encode(data))
    # read-only views (the mmap case) must work too
    ro = [r.copy() for r in rows]
    for r in ro:
        r.flags.writeable = False
    assert np.array_equal(c.encode_rows(ro), oracle.encode(data))


def test_adaptive_codec_raises_on_poisoned_device(monkeypatch):
    """With a TPU attached, a failing device probe is an error to see —
    never a silent switch to the host codec."""
    from seaweedfs_tpu.tpu import coder
    from seaweedfs_tpu.util import device

    coder.reset_adaptive_cache()

    def boom(*a, **k):
        raise RuntimeError("device backend poisoned")

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    monkeypatch.setattr(coder, "probe_roundtrip_seconds", boom)
    try:
        with pytest.raises(RuntimeError, match="poisoned"):
            coder.adaptive_codec()
    finally:
        coder.reset_adaptive_cache()


def test_adaptive_codec_cpu_platform_short_circuits(monkeypatch):
    from seaweedfs_tpu.tpu import coder
    from seaweedfs_tpu.util import device

    coder.reset_adaptive_cache()
    monkeypatch.setattr(device, "platform", lambda: "cpu")

    def no_probe(*a, **k):  # must not be consulted on the cpu platform
        raise AssertionError("probe should not run")

    monkeypatch.setattr(coder, "probe_roundtrip_seconds", no_probe)
    try:
        c = coder.adaptive_codec()
        assert isinstance(c, CpuRSCodec)
        assert coder.adaptive_codec() is c  # cached
    finally:
        coder.reset_adaptive_cache()


def _rows_of_a_strided_view(rng, k: int, width: int, block: int) -> np.ndarray:
    """k rows `block` apart in one buffer, as the encode views a .dat."""
    flat = rng.integers(0, 256, k * block, dtype=np.uint8)
    return np.lib.stride_tricks.as_strided(flat, shape=(k, width), strides=(block, 1))


@pytest.mark.parametrize("with_library", [True, False], ids=["native", "without"])
def test_write_all_appends_each_row_to_its_file(tmp_path, monkeypatch, with_library):
    """Two chunks of rows, each row appended whole to the file beside it:
    the files hold the rows in order, through the one native call or,
    where the library is missing, one `write` a row."""
    if not with_library:
        monkeypatch.setattr(native, "load", lambda: None)
    rng = np.random.default_rng(7)
    chunks = [_rows_of_a_strided_view(rng, 5, 3000, 4096) for _ in range(2)]
    files = [open(tmp_path / f"s{i}", "wb") for i in range(5)]
    for rows in chunks:
        native.write_all(files, [rows[i, :2999] for i in range(5)])
    for f in files:
        f.close()
    for i in range(5):
        want = b"".join(rows[i, :2999].tobytes() for rows in chunks)
        assert (tmp_path / f"s{i}").read_bytes() == want


def test_write_all_names_the_file_whose_write_failed(tmp_path):
    """A full disk is the run's OSError, with its errno and the file's name;
    the rows before it are written."""
    ok = open(tmp_path / "ok", "wb")
    full = open("/dev/full", "wb")
    rows = [np.full(4096, 3, np.uint8), np.full(4096, 4, np.uint8)]
    with pytest.raises(OSError) as raised:
        native.write_all([ok, full], rows)
    assert raised.value.errno == errno.ENOSPC
    assert raised.value.filename == "/dev/full"
    ok.close()
    full.close()
    assert (tmp_path / "ok").read_bytes() == rows[0].tobytes()


def _a_file(tmp_path, size: int) -> tuple:
    """(bytes, an open descriptor) of a file of `size` random bytes."""
    data = np.random.default_rng(size).integers(0, 256, size, dtype=np.uint8).tobytes()
    path = tmp_path / f"f{size}"
    path.write_bytes(data)
    return data, os.open(path, os.O_RDONLY)


def test_fill_rows_reads_a_whole_span_into_each_row(tmp_path):
    """Rows of one array, each read from its own file at the one offset."""
    files = [_a_file(tmp_path, 50_000 + i) for i in range(3)]
    rows = np.zeros((3, 4096), np.uint8)
    try:
        got = native.fill_rows(list(rows), [fd for _d, fd in files], 12_345)
    finally:
        for _d, fd in files:
            os.close(fd)
    assert got == [4096] * 3
    for row, (data, _fd) in zip(rows, files):
        assert row.tobytes() == data[12_345:12_345 + 4096]


def test_fill_rows_stops_a_read_at_the_end_of_the_file(tmp_path):
    """A span past the end comes back short, by what the file held; the
    rest of its row is left as it was."""
    data, fd = _a_file(tmp_path, 10_000)
    row = np.full(4096, 7, np.uint8)
    try:
        assert native.fill_rows([row], [fd], 8_000) == [2_000]
        assert native.fill_rows([row], [fd], 20_000) == [0]
    finally:
        os.close(fd)
    assert row[:2_000].tobytes() == data[8_000:]
    assert (row[2_000:] == 7).all()


def test_fill_rows_gives_errno_for_a_bad_descriptor_and_does_the_others(tmp_path):
    """A closed descriptor is -EBADF for its row alone; the rows after it
    are still read."""
    data, fd = _a_file(tmp_path, 9_000)
    _d, closed = _a_file(tmp_path, 9_001)
    os.close(closed)
    rows = np.zeros((3, 1000), np.uint8)
    try:
        got = native.fill_rows(list(rows), [fd, closed, fd], 100)
    finally:
        os.close(fd)
    assert got == [1000, -errno.EBADF, 1000]
    assert rows[0].tobytes() == rows[2].tobytes() == data[100:1100]
    assert not rows[1].any()


def test_fill_rows_copies_buffers_beside_the_reads(tmp_path):
    """A buffer is copied into its row, its first bytes where it is longer,
    in the same call as the reads; one shorter than its row, or a row the
    call cannot write straight through, is refused before anything is done."""
    data, fd = _a_file(tmp_path, 30_000)
    fetched = [bytes(range(256)) * 8, np.arange(3000, dtype=np.uint8) * 3]
    rows = np.zeros((4, 2048), np.uint8)
    try:
        got = native.fill_rows(list(rows), [fetched[0], fd, fetched[1], fd], 5_000)
        with pytest.raises(ValueError):
            native.fill_rows([np.zeros(4096, np.uint8)], [fetched[0]], 0)
        for bad in (np.zeros(4096, np.uint8)[::2], np.zeros(512, np.uint32),
                    np.frombuffer(bytes(2048), np.uint8)):
            with pytest.raises(ValueError):
                native.fill_rows([bad], [fd], 0)
    finally:
        os.close(fd)
    assert got == [2048] * 4
    assert rows[0].tobytes() == fetched[0]
    assert rows[2].tobytes() == fetched[1][:2048].tobytes()
    assert rows[1].tobytes() == rows[3].tobytes() == data[5_000:7_048]


def test_fill_rows_reads_each_row_at_its_own_offset(tmp_path):
    """An offset a row (the rows of several reconstructs in one call): reads
    from two files at offsets of their own, copies beside them, and a read
    in the middle that the file's end cuts short, which leaves the rows
    after it whole; the int form still reads every row at the one offset."""
    data, fd = _a_file(tmp_path, 40_000)
    other, fd2 = _a_file(tmp_path, 40_001)
    fetched = bytes(range(256)) * 4
    rows = np.zeros((5, 1024), np.uint8)
    offsets = [0, 39_500, 7, 31_000, 12_345]
    try:
        got = native.fill_rows(list(rows), [fd, fd2, fetched, fd2, fd], offsets)
        with pytest.raises(ValueError):
            native.fill_rows(list(rows), [fd] * 5, offsets[:4])
        same = np.zeros((2, 1024), np.uint8)
        assert native.fill_rows(list(same), [fd, fd], 3_000) == [1024, 1024]
    finally:
        os.close(fd)
        os.close(fd2)
    assert got == [1024, 501, 1024, 1024, 1024]
    assert rows[0].tobytes() == data[:1024]
    assert rows[1, :501].tobytes() == other[39_500:] and not rows[1, 501:].any()
    assert rows[2].tobytes() == fetched
    assert rows[3].tobytes() == other[31_000:32_024]
    assert rows[4].tobytes() == data[12_345:13_369]
    assert same[0].tobytes() == same[1].tobytes() == data[3_000:4_024]


def test_fill_rows_of_no_rows_is_an_empty_answer(monkeypatch):
    """Nothing to fill is an empty list; without the library, None."""
    assert native.fill_rows([], [], 0) == []
    monkeypatch.setattr(native, "load", lambda: None)
    assert native.fill_rows([], [], 0) is None
