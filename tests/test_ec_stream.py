"""Streamed EC pipeline (ISSUE 17; the only encode route since ISSUE 30):
the depth-N double-buffered encode must be byte-identical to the tests'
own oracle (ec_oracle: rows laid out in memory, CpuRSCodec.encode) across
codecs, geometries, chunk sizes, and ragged final extents — and a
mid-stream crash must leave only sweepable .ecNN.tmp files, never a torn
shard that looks complete."""

import errno
import os
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

from ec_oracle import oracle_shards
from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
from seaweedfs_tpu.storage.erasure_coding import (
    to_ext,
    write_ec_files,
    write_ec_files_multi,
)
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


def _oracle(base, k=10, m=4):
    return oracle_shards(base + ".dat", k, m, LARGE, SMALL)


def _native_codec(k=10, m=4):
    native = pytest.importorskip("seaweedfs_tpu.native")
    if not native.available():
        pytest.skip("native gf256 library unavailable")
    from seaweedfs_tpu.storage.erasure_coding.coder_native import NativeRSCodec

    return NativeRSCodec(k, m)


CODECS = {"device": TpuRSCodec, "numpy": CpuRSCodec, "native": _native_codec}


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
    """Seeded property: the streamed route (mmap-view input) produces the
    k+m shard bytes the oracle works out, for every geometry x extent x
    chunk combination."""
    size = size_rows * LARGE * k + tail
    seed = hash((k, m, size, chunk)) & 0xFFFF

    got_base = str(tmp_path / "streamed")
    _write_dat(got_base, size, seed)
    expected = _oracle(got_base, k, m)
    run = write_ec_files(
        got_base, codec=TpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, chunk=chunk,
    )
    assert run.route["route"] == "pipeline" and run.route["input"] == "mmap"
    got = _read_shards(got_base, k + m)
    for i, (e, g) in enumerate(zip(expected, got)):
        assert e == g, f"shard {to_ext(i)} diverged ({k}.{m}, {size}B)"
    assert not any(
        name.endswith(".tmp") for name in os.listdir(tmp_path)
    )


@pytest.mark.parametrize("codec", sorted(CODECS))
def test_streamed_pread_staging_route_matches(tmp_path, monkeypatch, codec):
    """The copy-staging (pread) input — what the pipeline falls back to
    when the .dat cannot be mapped — is byte-identical too, including the
    grouped small-row items mmap never exercises."""
    import mmap

    def refuses(*_a, **_kw):
        raise OSError(errno.ENODEV, "this file system maps nothing")

    monkeypatch.setattr(mmap, "mmap", refuses)
    k, m = 10, 4
    got_base = str(tmp_path / "streamed")
    _write_dat(got_base, 2 * LARGE * k + 3 * SMALL * k + 517, 99)
    run = write_ec_files(
        got_base, codec=CODECS[codec](k, m), large_block_size=LARGE,
        small_block_size=SMALL, chunk=1 << 14,
    )
    assert run.route["route"] == "pipeline" and run.route["input"] == "pread"
    assert _oracle(got_base) == _read_shards(got_base, k + m)


@pytest.mark.parametrize("codec", sorted(CODECS))
def test_an_empty_dat_gives_fourteen_empty_shards(tmp_path, codec):
    """Nothing to map, nothing to dispatch: the pread fallback with no
    item, and still a commit by rename."""
    base = str(tmp_path / "v")
    _write_dat(base, 0, 1)
    run = write_ec_files(
        base, codec=CODECS[codec](), large_block_size=LARGE,
        small_block_size=SMALL,
    )
    assert run.route["route"] == "pipeline" and run.route["input"] == "pread"
    assert _read_shards(base, 14) == [b""] * 14 == _oracle(base)
    assert sorted(os.listdir(tmp_path)) == sorted(
        ["v.dat"] + ["v" + to_ext(i) for i in range(14)]
    )


@pytest.mark.parametrize(
    "fn,switch",
    [
        (write_ec_files, "pipeline"),
        (write_ec_files, "mmap_input"),
        (write_ec_files, "onepass"),
        (write_ec_files_multi, "workers"),
    ],
    ids=lambda v: getattr(v, "__name__", v),
)
def test_the_route_switches_are_gone(tmp_path, fn, switch):
    """One pipeline: nothing left to choose between (ISSUE 30)."""
    base = str(tmp_path / "v")
    _write_dat(base, 100, 1)
    arg = base if fn is write_ec_files else [base]
    with pytest.raises(TypeError, match=switch):
        fn(arg, codec=CpuRSCodec(), **{switch: False})
    assert os.listdir(tmp_path) == ["v.dat"]


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
    _with_writers(monkeypatch, writers)
    threads_before = set(threading.enumerate())
    got_base = str(tmp_path / "streamed")
    _write_dat(got_base, size, 27)
    run = write_ec_files(
        got_base, codec=TpuRSCodec(k, m), large_block_size=LARGE,
        small_block_size=SMALL, chunk=1 << 14, splice_data=False,
    )
    assert run.route["writers"] == writers
    assert _oracle(got_base) == _read_shards(got_base, k + m)
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
                small_block_size=SMALL, chunk=1 << 14, splice_data=False,
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
        small_block_size=SMALL,
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
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from {module} import {cls}
from seaweedfs_tpu.storage.erasure_coding import write_ec_files

class SlowCodec({cls}):
    def {dispatch}(self, data):
        # one write a marker: the pool's two workers dispatch at once, and
        # print() sends the word and its newline apart
        os.write(1, b"CHUNK\\n")
        time.sleep(0.4)  # hold the stream open so the parent kills mid-run
        return super().{dispatch}(data)

write_ec_files(
    {base!r}, codec=SlowCodec(), large_block_size={large},
    small_block_size={small}, chunk={large}, splice_data=False,
)
print("DONE", flush=True)
"""

# the codec's class, and the method the pipeline dispatches a chunk to
_KILL_CODECS = {
    "device": ("seaweedfs_tpu.ops.rs_kernel", "TpuRSCodec", "pipeline_encode"),
    "numpy": (
        "seaweedfs_tpu.storage.erasure_coding.coder_cpu", "CpuRSCodec",
        "encode",
    ),
    "native": (
        "seaweedfs_tpu.storage.erasure_coding.coder_native", "NativeRSCodec",
        "encode",
    ),
}


@pytest.mark.parametrize("codec", sorted(_KILL_CODECS))
def test_kill_mid_stream_leaves_only_tmp(tmp_path, codec):
    """Kill-point: SIGKILL the encode after the second chunk dispatch. No
    finally-cleanup runs, so the crash site must hold only .ecNN.tmp files
    (the next run's sweep target) and never a final-named shard; a fresh
    encode over the crash site then succeeds byte-identically with no .tmp
    leftovers. Whatever the codec: there is one pipeline."""
    k, m = 10, 4
    base = str(tmp_path / "v")
    _write_dat(base, 4 * LARGE * k + 999, 21)
    again = CODECS[codec](k, m)  # skips here where there is no native library
    module, cls, dispatch = _KILL_CODECS[codec]

    code = _KILL_CHILD.format(
        repo=REPO, base=base, large=LARGE, small=SMALL,
        module=module, cls=cls, dispatch=dispatch,
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
        base, codec=again, large_block_size=LARGE, small_block_size=SMALL,
    )
    assert _read_shards(base, k + m) == _oracle(base)
    assert not any(
        n.endswith(".tmp") for n in os.listdir(tmp_path)
    )
