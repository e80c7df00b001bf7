"""The native codec in the encode pipeline, with and without the
kernel-side data splice, and the numpy codec beside it — all byte-identical
to the tests' oracle (ec_oracle; ref semantics:
weed/storage/erasure_coding/ec_encoder.go) — and the adaptive codec choice.
"""

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
