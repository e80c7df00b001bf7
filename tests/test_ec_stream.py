"""Streamed EC pipeline (ISSUE 17): the depth-N double-buffered encode
must be byte-identical to the one-shot reference route across geometries,
chunk sizes, and ragged final extents — and a mid-stream crash must leave
only sweepable .ecNN.tmp files, never a torn shard that looks complete."""

import errno
import os
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
from seaweedfs_tpu.storage.erasure_coding import to_ext, write_ec_files
from seaweedfs_tpu.storage.erasure_coding import encoder as enc
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec
from seaweedfs_tpu.storage.erasure_coding.encoder import rebuild_ec_files

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LARGE = 1 << 16  # shrunk geometry: same row structure, test-sized blocks
SMALL = 1 << 12


def _write_dat(base, size, seed):
    data = np.random.default_rng(seed).integers(0, 256, size, dtype=np.uint8)
    with open(base + ".dat", "wb") as f:
        f.write(data.tobytes())
    return data


def _read_shards(base, total):
    return [
        open(base + to_ext(i), "rb").read() for i in range(total)
    ]


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (12, 4)])
@pytest.mark.parametrize(
    "size_rows,tail,chunk",
    [
        (3, 12345, 1 << 14),      # ragged non-chunk-aligned final extent
        (1, 0, 1 << 14),          # exactly one large row
        (0, 7, 1 << 14),          # sub-small-block file (zero-padded row)
        (2, 4097, 12289),         # odd (non-power-of-two) chunk
        (2, SMALL + 1, 1 << 20),  # chunk larger than every row
    ],
)
def test_streamed_matches_oneshot(tmp_path, k, m, size_rows, tail, chunk):
    """Seeded property: pipeline=True (streamed, mmap-view input) produces
    the same k+m shard bytes as the synchronous pread one-shot route, for
    every geometry x extent x chunk combination."""
    size = size_rows * LARGE * k + tail
    seed = hash((k, m, size, chunk)) & 0xFFFF

    ref_base = str(tmp_path / "ref")
    _write_dat(ref_base, size, seed)
    write_ec_files(
        ref_base, codec=CpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, pipeline=False, splice_data=False,
        mmap_input=False, onepass=False,
    )
    expected = _read_shards(ref_base, k + m)

    got_base = str(tmp_path / "streamed")
    _write_dat(got_base, size, seed)
    write_ec_files(
        got_base, codec=TpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, chunk=chunk, pipeline=True,
    )
    assert enc.LAST_ROUTE["route"] == "pipeline"
    got = _read_shards(got_base, k + m)
    for i, (e, g) in enumerate(zip(expected, got)):
        assert e == g, f"shard {to_ext(i)} diverged ({k}.{m}, {size}B)"
    assert not any(
        name.endswith(".tmp") for name in os.listdir(tmp_path)
    )


def test_streamed_pread_staging_route_matches(tmp_path, monkeypatch):
    """The copy-staging (pread) input route — what the pipeline falls back
    to when calibration rules out the mmap fault path — is byte-identical
    too, including the grouped small-row items mmap never exercises."""
    monkeypatch.setattr(enc, "_HOST_ROUTE", "sync")
    k, m = 10, 4
    size = 2 * LARGE * k + 3 * SMALL * k + 517

    ref_base = str(tmp_path / "ref")
    _write_dat(ref_base, size, 99)
    write_ec_files(
        ref_base, codec=CpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, pipeline=False, splice_data=False,
        mmap_input=False, onepass=False,
    )
    got_base = str(tmp_path / "streamed")
    _write_dat(got_base, size, 99)
    write_ec_files(
        got_base, codec=TpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, chunk=1 << 14, pipeline=True,
    )
    assert enc.LAST_ROUTE["input"] == "pread"
    assert _read_shards(ref_base, k + m) == _read_shards(got_base, k + m)


def _with_writers(monkeypatch, writers, depth=2):
    """Let the pipeline see just the CPUs that give `writers` writing
    threads (the main thread, the pool's `depth` workers, and those), on
    a host where that many are worth having."""
    monkeypatch.setattr(
        "seaweedfs_tpu.util.available_cpus", lambda: 1 + depth + writers
    )
    monkeypatch.setattr(enc, "_STREAM_WRITERS_MOST", 14)


@pytest.mark.parametrize("writers", [1, 2, 5])
def test_streamed_matches_oneshot_from_any_number_of_writers(
    tmp_path, monkeypatch, writers
):
    """The 14 shard files are the one-shot encode's byte for byte whether
    one thread writes them or several do, each its own files: a .dat of
    large rows, small rows and a tail that straddles EOF mid-row."""
    k, m = 10, 4
    size = 2 * LARGE * k + 3 * SMALL * k + 2 * SMALL + 517
    ref_base = str(tmp_path / "ref")
    _write_dat(ref_base, size, 27)
    write_ec_files(
        ref_base, codec=CpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, pipeline=False, splice_data=False,
        mmap_input=False, onepass=False,
    )
    _with_writers(monkeypatch, writers)
    threads_before = set(threading.enumerate())
    got_base = str(tmp_path / "streamed")
    _write_dat(got_base, size, 27)
    run = write_ec_files(
        got_base, codec=TpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, chunk=1 << 14, pipeline=True,
        splice_data=False,
    )
    assert run.route["writers"] == writers
    assert _read_shards(ref_base, k + m) == _read_shards(got_base, k + m)
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))
    # every thread of the run is gone with it
    assert not [
        t.name for t in set(threading.enumerate()) - threads_before
        if t.name.startswith("ec-stream-writer")
    ]


class _FailingShard:
    """A shard file whose second write finds the disk full."""

    def __init__(self, f, raised_on: list):
        self._f = f
        self._writes = 0
        self._raised_on = raised_on

    def write(self, data):
        self._writes += 1
        if self._writes == 2:
            self._raised_on.append(threading.current_thread().name)
            raise OSError(errno.ENOSPC, "No space left on device")
        return self._f.write(data)

    def flush(self):
        self._f.flush()

    def close(self):
        self._f.close()


@pytest.mark.parametrize("shard", [11, 6], ids=["parity", "data"])
def test_a_helper_threads_write_error_ends_the_run(
    tmp_path, monkeypatch, shard
):
    """5 writers: shards 1, 6 and 11 are the first helper's. Its OSError
    is the run's, the ring keeps turning till the stream is consumed (no
    deadlock: a time limit of this test's own), and nothing is left but
    the .dat."""
    k, m = 10, 4
    base = str(tmp_path / "v")
    _write_dat(base, 4 * LARGE * k + 999, 5)
    _with_writers(monkeypatch, 5)
    raised_on: list = []
    target = base + to_ext(shard) + ".tmp"

    def opening(path, *a, **kw):
        f = open(path, *a, **kw)
        return _FailingShard(f, raised_on) if path == target else f

    monkeypatch.setattr(enc, "open", opening, raising=False)
    outcome: list = []

    def encode():
        try:
            write_ec_files(
                base, codec=TpuRSCodec(k, m), large_block_size=LARGE,
                small_block_size=SMALL, chunk=1 << 14, pipeline=True,
                splice_data=False,
            )
            outcome.append(None)
        except BaseException as e:
            outcome.append(e)

    t = threading.Thread(target=encode, daemon=True)
    t.start()
    t.join(120)
    assert not t.is_alive(), "the pipeline deadlocked on a failed writer"
    assert isinstance(outcome[0], OSError), outcome
    assert outcome[0].errno == errno.ENOSPC
    assert raised_on == ["ec-stream-writer-1"]
    assert os.listdir(tmp_path) == ["v.dat"]


def test_streamed_rebuild_roundtrip(tmp_path):
    """Streamed rebuild regenerates missing shards byte-identically."""
    k, m = 10, 4
    base = str(tmp_path / "v")
    _write_dat(base, 2 * LARGE * k + 31, 7)
    write_ec_files(
        base, codec=TpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, pipeline=True,
    )
    originals = _read_shards(base, k + m)
    for i in (0, 3, 11, 13):
        os.remove(base + to_ext(i))
    generated = rebuild_ec_files(base, pipeline=True)
    assert sorted(generated) == [0, 3, 11, 13]
    assert _read_shards(base, k + m) == originals
    assert not any(
        name.endswith(".tmp") for name in os.listdir(tmp_path)
    )


_KILL_CHILD = """
import sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
from seaweedfs_tpu.storage.erasure_coding import write_ec_files

class SlowCodec(TpuRSCodec):
    def pipeline_encode(self, data):
        print("CHUNK", flush=True)
        time.sleep(0.4)  # hold the stream open so the parent kills mid-run
        return super().pipeline_encode(data)

write_ec_files(
    {base!r}, codec=SlowCodec(), large_block_size={large},
    small_block_size={small}, chunk={large}, pipeline=True,
    splice_data=False,
)
print("DONE", flush=True)
"""


def test_kill_mid_stream_leaves_only_tmp(tmp_path):
    """Kill-point: SIGKILL the encode after the second chunk dispatch. No
    finally-cleanup runs, so the crash site must hold only .ecNN.tmp files
    (the next run's sweep target) and never a final-named shard; a fresh
    encode over the crash site then succeeds byte-identically with no .tmp
    leftovers."""
    k, m = 10, 4
    base = str(tmp_path / "v")
    _write_dat(base, 4 * LARGE * k + 999, 21)

    code = _KILL_CHILD.format(
        repo=REPO, base=base, large=LARGE, small=SMALL
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
    )
    try:
        markers = 0
        for line in proc.stdout:
            if line.strip() == b"DONE":
                pytest.fail("encode finished before the kill point")
            if line.strip() == b"CHUNK":
                markers += 1
                if markers == 2:
                    break
        assert markers == 2, "child died before reaching the kill point"
        proc.send_signal(signal.SIGKILL)
    finally:
        proc.wait(timeout=60)

    names = set(os.listdir(tmp_path))
    finals = [to_ext(i) for i in range(k + m) if f"v{to_ext(i)}" in names]
    assert not finals, f"crash left final-named shards: {finals}"
    assert any(n.endswith(".tmp") for n in names), names

    # recovery: the next encode sweeps the torn .tmp and rebuilds clean
    write_ec_files(
        base, codec=TpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, pipeline=True,
    )
    got = _read_shards(base, k + m)
    ref_base = str(tmp_path / "ref")
    _write_dat(ref_base, 4 * LARGE * k + 999, 21)
    write_ec_files(
        ref_base, codec=CpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, pipeline=False, splice_data=False,
        mmap_input=False, onepass=False,
    )
    assert got == _read_shards(ref_base, k + m)
    assert not any(
        n.endswith(".tmp") for n in os.listdir(tmp_path)
    )
