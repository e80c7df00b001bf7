"""What the chip bring-up promises without a chip: chip_smoke.py fails
where there is no TPU, the compile cache can be placed from outside, a
request for the chip is refused on another backend unless JAX_PLATFORMS
itself says cpu, the server says what it runs on, a device failure is
counted as one, and a cluster hands the chip to one child."""

import asyncio
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from seaweedfs_tpu.util import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- chip_smoke.py
def test_chip_smoke_fails_without_a_chip():
    """The whole script under JAX_PLATFORMS=cpu: the child starts (cpu was
    said outright), says platform=cpu in /status, and the run ends there."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "not on a TPU" in last["error"]


def test_chip_smoke_fails_when_a_phase_fails(monkeypatch, capsys):
    import chip_smoke

    async def broken(args, root, verdict):
        raise chip_smoke.Failed("ec.encode: shard 7 differs")

    monkeypatch.setattr(chip_smoke, "run_served", broken)
    monkeypatch.setattr(device, "setup_compile_cache", lambda: None)
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": False, "error": "Failed: ec.encode: shard 7 differs"}


def test_chip_smoke_real_run_has_a_size_floor(monkeypatch, capsys):
    import chip_smoke

    monkeypatch.setattr(device, "setup_compile_cache", lambda: None)
    assert chip_smoke.main(["--needles", "100"]) == 1
    assert "below the floor" in capsys.readouterr().out


# ----------------------------------------------------------- compile cache
@pytest.fixture
def cache_calls(monkeypatch):
    calls = []
    monkeypatch.setattr(device, "_configure", lambda k, v: calls.append((k, v)))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    return calls


def test_compile_cache_leaves_an_outside_dir_alone(cache_calls, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device.setup_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in dict(cache_calls)
    # the thresholds still drop, or the sub-second GF kernels are not kept
    assert dict(cache_calls) == {
        "jax_persistent_cache_min_compile_time_secs": 0,
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }


def test_compile_cache_picks_the_same_in_checkout_path_twice(
    cache_calls, monkeypatch
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = device.setup_compile_cache()
    assert first == device.setup_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert cache_calls.count(("jax_compilation_cache_dir", first)) == 2


def test_compile_cache_stays_off_a_process_told_to_use_the_cpu(
    cache_calls, monkeypatch
):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.setup_compile_cache() is None and not cache_calls


def test_configure_uses_the_environment_until_jax_is_imported(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(os, "environ", dict(os.environ))
    device._configure("jax_compilation_cache_dir", "/x/.jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/x/.jax_cache"


# ------------------------------------------------- a request for the chip
def _free_port() -> int:
    for p in range(22100, 22900):
        try:
            with socket.socket() as a, socket.socket() as b:
                a.bind(("127.0.0.1", p))
                b.bind(("127.0.0.1", p + 10000))
            return p
        except OSError:
            continue
    raise RuntimeError("no free port pair")


def _volume_server(tmp_path, **kw):
    from seaweedfs_tpu.server.volume import VolumeServer

    return VolumeServer(
        master="127.0.0.1:1", directories=[str(tmp_path)], port=_free_port(),
        **kw,
    )


@pytest.mark.parametrize(
    "kw,flag",
    [
        ({"codec_backend": "tpu"}, "-storageBackend tpu"),
        ({"batch_lookup": "arena"}, "-batchLookup arena"),
        ({"batch_lookup": "device"}, "-batchLookup device"),
    ],
)
def test_request_for_the_chip_is_refused_on_a_cpu_backend(
    tmp_path, monkeypatch, kw, flag
):
    """The backend IS the CPU here, but nobody said so outright."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError) as e:
        _volume_server(tmp_path, **kw)
    assert flag in str(e.value) and "JAX_PLATFORMS" in str(e.value)
    assert "'cpu'" in str(e.value)


@pytest.mark.parametrize(
    "kw", [{"codec_backend": "cpu"}, {"codec_backend": "numpy", "batch_lookup": "host"}]
)
def test_host_planes_never_ask_what_the_device_is(tmp_path, monkeypatch, kw):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(
        device, "describe", lambda: pytest.fail("host planes touched JAX")
    )
    vs = _volume_server(tmp_path, **kw)
    assert vs.device is None
    vs.store.close()


def test_status_carries_the_device(tmp_path):
    """JAX_PLATFORMS=cpu (tests/conftest.py) lets `tpu` + `arena` start;
    /status then says what they run on."""
    import aiohttp

    from seaweedfs_tpu.pb.rpc import close_all_channels

    async def body():
        vs = _volume_server(tmp_path, codec_backend="tpu", batch_lookup="arena")
        await vs.start()
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://{vs.address}/status") as r:
                    page = await r.json()
        finally:
            await vs.stop()
            await close_all_channels()
        assert page["Device"] == {
            "platform": "cpu", "device_kind": "cpu", "count": 8,
        }
        assert set(page) == {"Version", "Volumes", "Device"}

    asyncio.run(body())


# ------------------------------------------ a device failure is counted as one
class _Vol:
    def __init__(self, nm):
        self.nm = nm


class _Store:
    def __init__(self, vols):
        self.vols = vols

    def find_volume(self, vid):
        return self.vols.get(vid)


def _lsm_volume(tmp_path, n=3000):  # runs seal every 1,024 entries
    from seaweedfs_tpu.storage.needle_map.lsm_map import LsmNeedleMap

    nm = LsmNeedleMap(str(tmp_path / "v1.idx"), memtable_bytes=1)
    for k in range(1, n + 1):
        nm.put(k, k, 100 + k % 50)
    return nm


def test_arena_that_raises_is_a_device_error_not_a_cold_arena(
    tmp_path, monkeypatch
):
    from seaweedfs_tpu.ops.ragged_lookup import DeviceColumnArena
    from seaweedfs_tpu.server import lookup_gate as lg
    from seaweedfs_tpu.util.metrics import NEEDLE_MAP_DEVICE_FALLBACKS

    monkeypatch.setattr(lg, "_ARENA_MIN_WAKEUP", 8)
    nm = _lsm_volume(tmp_path)
    arena = DeviceColumnArena()
    gate = lg.BatchLookupGate(_Store({1: _Vol(nm)}), arena=arena)

    def fallbacks(reason):
        return NEEDLE_MAP_DEVICE_FALLBACKS._values.get((("reason", reason),), 0)

    cold0, err0 = fallbacks("arena_cold"), fallbacks("device_error")

    async def wakeup():
        got = await asyncio.gather(*(gate.lookup(1, k) for k in range(1, 41)))
        assert got == [(k, 100 + k % 50) for k in range(1, 41)]  # host-served

    def refuse(groups, timings=None):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    async def body():
        await wakeup()  # still uploading: really cold
        assert gate.stats["device_error"] == 0
        assert fallbacks("arena_cold") == cold0 + 1
        monkeypatch.setattr(arena, "probe_groups", refuse)
        await wakeup()
        assert gate.stats["device_error"] == 1
        assert fallbacks("device_error") == err0 + 1
        assert fallbacks("arena_cold") == cold0 + 1  # not counted as cold

    try:
        asyncio.run(body())
    finally:
        gate.close()
        arena.close()
        nm.close()


def test_failed_arena_upload_is_a_device_error(tmp_path, monkeypatch):
    from seaweedfs_tpu.ops import ragged_lookup as rl

    def refuse(gen_id, segments):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setattr(rl, "_Generation", refuse)
    arena = rl.DeviceColumnArena()
    keys = np.arange(1, 5000, dtype=np.uint64)
    seg = rl.ArenaSegment(keys, keys.astype(np.uint32), keys.astype(np.uint32))
    try:
        assert arena.ensure([seg]) is None
        arena.refresh_sync()
        st = arena.stats()
        assert st["device_error"] >= 1 and st["uploads"] == 0
        assert st["platform"] == "cpu"
    finally:
        arena.close()


def test_encoded_bytes_are_counted_by_the_kernel_that_ran(tmp_path):
    """VolumeEcShardsGenerate feeds seaweedfs_tpu_ec_encoded_bytes_total
    with the `kernel` of the run's own route: on this CPU the `tpu` codec's
    streamed pipeline runs the host stand-in and says so."""
    from seaweedfs_tpu.storage.volume import Volume
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.util.metrics import EC_ENCODE_BYTES

    vs = _volume_server(tmp_path, codec_backend="tpu")
    v = Volume(str(tmp_path), "", 7)
    for k in range(1, 40):
        v.write_needle(Needle(id=k, cookie=1, data=os.urandom(3000)))
    v.close()
    dat = os.path.getsize(tmp_path / "7.dat")
    label = (("backend", vs.codec.pipeline_dispatch_kind),)
    before = dict(EC_ENCODE_BYTES._values)

    async def body():
        return await vs._grpc_ec_generate({"volume_id": 7}, None)

    try:
        assert asyncio.run(body()) == {}
    finally:
        vs.store.close()
    grew = {
        k: val - before.get(k, 0)
        for k, val in EC_ENCODE_BYTES._values.items()
        if val != before.get(k, 0)
    }
    assert label[0][1] != "device" and grew == {label: dat}


# --------------------------------------------------- one process per chip
def test_proc_cluster_hands_the_chip_to_one_child(tmp_path, monkeypatch):
    from seaweedfs_tpu.ops.proc_cluster import ProcCluster

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    cluster = ProcCluster(str(tmp_path), volumes=3, filers=1)
    assert cluster.chip_child == "volume-0"
    assert "JAX_PLATFORMS" not in cluster._child_env("volume-0", "volume")
    for name, role in (
        ("volume-1", "volume"), ("volume-2", "volume"),
        ("master", "master"), ("filer-0", "filer"),
    ):
        assert cluster._child_env(name, role)["JAX_PLATFORMS"] == "cpu"
    other = ProcCluster(str(tmp_path), volumes=2, chip_child="volume-1")
    assert "JAX_PLATFORMS" not in other._child_env("volume-1", "volume")
    assert other._child_env("volume-0", "volume")["JAX_PLATFORMS"] == "cpu"


def test_native_library_is_named_after_its_flag_set():
    from seaweedfs_tpu import native

    assert native._lib_path(["-mgfni", "-mavx512f", "-mavx512bw", "-mavx2"]).endswith(
        "libgf256_gfni-avx512f-avx512bw-avx2.so"
    )
    assert native._lib_path(["-mavx2"]).endswith("libgf256_avx2.so")
    assert native._lib_path([]).endswith("libgf256_scalar.so")
    if native.available():
        assert os.path.exists(
            os.path.join(os.path.dirname(native.__file__), f"libgf256_{native.tier()}.so")
        )
    else:
        assert native.tier() == "numpy"
