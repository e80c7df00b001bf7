"""The master's maintenance pass (ISSUE 28): `ec.encode -fullPercent=95
-quietFor=1h` selects as upstream's collectVolumeIdsForEcEncode does, a
volume's modification time survives a restart, and the batch the selection
sends (VolumeEcShardsGenerateBatch -> write_ec_files_multi) gives what the
one-volume route gives: bytes counted by backend, stages on /metrics, shard
files committed by rename, a fallback that is counted and reports the right
id.

Everything here runs on the CPU at small sizes: counts and bytes are checked,
never a time."""

import asyncio
import os
import subprocess
import sys
import time

import aiohttp
import numpy as np
import pytest

from ec_oracle import oracle_shards
from seaweedfs_tpu.shell import commands
from seaweedfs_tpu.shell.ec_common import select_volumes_for_ec_encode
from seaweedfs_tpu.storage.erasure_coding import (
    to_ext,
    write_ec_files,
    write_ec_files_multi,
)
from seaweedfs_tpu.storage.erasure_coding import encoder as enc
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec
from seaweedfs_tpu.util import metrics as m

from benchmarks.reference import ec_selection

MB = 1024 * 1024
NOW = 1_800_000_000
LARGE, SMALL = 8192, 1024


# ------------------------------------------------------------- the selection
def _vol(vid, collection="c", size=0, modified=0, **more):
    return {"id": vid, "collection": collection, "size": size,
            "modified_at_second": modified, **more}


LIMIT = 1024  # MB
FULL = int(0.95 * LIMIT * MB)  # the boundary itself: 95 % to the byte
OLD = NOW - 7200
SELECTION_TABLE = [
    # name, volumes, collection, percent, quiet, the ids worked out by hand
    ("full_and_quiet", [_vol(1, size=LIMIT * MB, modified=OLD)], "c", 95, "1h", [1]),
    ("not_full", [_vol(1, size=24 * MB, modified=OLD)], "c", 95, "1h", []),
    ("at_the_boundary_is_not_over_it", [_vol(1, size=FULL, modified=OLD)], "c", 95, "1h", []),
    ("one_byte_over_the_boundary", [_vol(1, size=FULL + 1, modified=OLD)], "c", 95, "1h", [1]),
    ("written_a_minute_ago", [_vol(1, size=LIMIT * MB, modified=NOW - 60)], "c", 95, "1h", []),
    ("quiet_to_the_second_is_not_quiet", [_vol(1, size=LIMIT * MB, modified=NOW - 3600)], "c", 95, "1h", []),
    ("quiet_one_second_longer", [_vol(1, size=LIMIT * MB, modified=NOW - 3601)], "c", 95, "1h", [1]),
    ("quiet_for_zero", [_vol(1, size=LIMIT * MB, modified=NOW - 1)], "c", 95, "0s", [1]),
    ("another_collection", [_vol(1, "d", LIMIT * MB, OLD), _vol(2, "", LIMIT * MB, OLD)], "c", 95, "1h", []),
    ("the_empty_collection", [_vol(1, "d", LIMIT * MB, OLD), _vol(2, "", LIMIT * MB, OLD)], "", 95, "1h", [2]),
    ("a_lower_percentage", [_vol(1, size=600 * MB, modified=OLD)], "c", 50, "30m", [1]),
    ("a_replica_counts_once", [_vol(3, size=LIMIT * MB, modified=OLD)] * 2, "c", 95, "1h", [3]),
    ("modified_at_as_loaded_from_disk",  # 0 would pass any quiet period: the fault this PR repairs
     [_vol(1, size=LIMIT * MB, modified=NOW - 5), _vol(2, size=LIMIT * MB, modified=1_700_000_000)],
     "c", 95, "1h", [2]),
    ("a_whole_collection", [
        _vol(1, size=LIMIT * MB, modified=OLD), _vol(2, size=LIMIT * MB + 7, modified=OLD - 9),
        _vol(3, size=24 * MB, modified=OLD), _vol(4, size=LIMIT * MB, modified=NOW),
        _vol(5, "c2", LIMIT * MB, OLD)], "c", 95, "1h", [1, 2]),
]


@pytest.mark.parametrize("name,volumes,collection,percent,quiet,want", SELECTION_TABLE,
                         ids=[row[0] for row in SELECTION_TABLE])
def test_selection_is_upstreams_and_the_plain_references(name, volumes, collection, percent, quiet, want):
    quiet_s = commands.parse_go_duration(quiet)
    got = select_volumes_for_ec_encode(volumes, collection, LIMIT, percent, quiet_s, NOW)
    plain = ec_selection.select(
        [(v["id"], v["collection"], v["size"], v["modified_at_second"]) for v in volumes],
        collection, LIMIT, percent, ec_selection.duration_seconds(quiet), NOW)
    assert sorted(got) == plain == want


@pytest.mark.parametrize("text,seconds", [
    ("1h", 3600), ("30m", 1800), ("45s", 45), ("0s", 0), ("0", 0), ("1h30m", 5400),
    ("1.5h", 5400), ("500ms", 0.5), ("2h45m10s", 9910),
])
def test_quiet_for_takes_gos_durations(text, seconds):
    assert commands.parse_go_duration(text) == pytest.approx(seconds)
    assert ec_selection.duration_seconds(text) == pytest.approx(seconds)


@pytest.mark.parametrize("text", ["", "1", "h", "1 h", "1d", "-1h", "1hh", "abc"])
def test_quiet_for_refuses_what_go_refuses(text):
    with pytest.raises(ValueError):
        commands.parse_go_duration(text)
    with pytest.raises(ValueError):
        ec_selection.duration_seconds(text)


def test_help_names_the_selection_flags():
    out = asyncio.run(commands.run_command(None, "help ec.encode"))
    for flag in ("-collection", "-fullPercent", "-quietFor", "-volumeId"):
        assert flag in out
    assert "ec.encode" in asyncio.run(commands.run_command(None, "help"))
    assert "unknown command" in asyncio.run(commands.run_command(None, "help nothing.such"))


# ---------------------------------------- a volume's modification time, kept
@pytest.mark.parametrize("kind", ["memory", "lsm"])
@pytest.mark.parametrize("last", ["write", "write_with_ts", "delete"])
def test_modification_time_is_the_same_after_a_reload(tmp_path, kind, last):
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.storage.volume import Volume

    v = Volume(str(tmp_path), "c", 3, needle_map_kind=kind)
    assert v.last_modified_ts_seconds == 0
    for key in range(1, 6):
        v.write_needle(Needle(id=key, cookie=9, data=os.urandom(300)))
    if last == "write_with_ts":
        n = Needle(id=6, cookie=9, data=b"dated by the client")
        n.set_last_modified(int(time.time()) + 50)
        v.write_needle(n)
    elif last == "delete":
        v.delete_needle(Needle(id=2, cookie=9))
    before = v.last_modified_ts_seconds
    assert abs(before - time.time()) < 60  # not 0: a volume taking writes is not quiet
    v.close()
    again = Volume(str(tmp_path), "c", 3, create=False, needle_map_kind=kind)
    try:
        assert again.last_modified_ts_seconds == before
    finally:
        again.close()
    # and that is what the master is told, at connect and on every digest tick
    store = Store("127.0.0.1", 1, "", [str(tmp_path)], [8], needle_map_kind=kind)
    store.load()
    try:
        assert [vm["modified_at_second"] for vm in store.collect_heartbeat()["volumes"]] == [before]
        assert [d["modified_at_second"] for d in store.collect_volume_digests()] == [before]
    finally:
        store.close()


# ------------------------------------------------- the batch, as a library
class _DeviceLike(CpuRSCodec):
    """The numpy codec with a device codec's preferences and a name of its
    own for the kernel that ran."""

    is_device = True
    preferred_chunk = 16 * 1024
    pipeline_dispatch_kind = "device_like"


def _mk_dat(path: str, size: int) -> None:
    data = np.random.default_rng(size + 7).integers(0, 256, size, dtype=np.uint8)
    with open(path, "wb") as f:
        f.write(data.tobytes())


def _shards(base: str) -> list:
    out = []
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            out.append(f.read())
    return out


def _oracle(base: str) -> list:
    return oracle_shards(base + ".dat", 10, 4, LARGE, SMALL)


def _stage_seconds() -> dict:
    return {dict(k)["stage"]: v for k, v in m.EC_ENCODE_STAGE_SECONDS._values.items()}


BATCH_SIZES = [LARGE * 10 * 2 + SMALL * 10 * 2 + 333, SMALL * 10 * 5, SMALL * 3 + 17]


@pytest.mark.parametrize("kind", ["device_like", "host"])
def test_batch_of_three_is_each_volume_alone_and_writes_the_one_volume_routes_stages(tmp_path, kind):
    batch = []
    for j, size in enumerate(BATCH_SIZES):
        d = tmp_path / f"b{j}"
        d.mkdir()
        _mk_dat(str(d / "1.dat"), size)
        batch.append(str(d / "1"))
    stages0, pieces0 = _stage_seconds(), sum(m.EC_ENCODE_BATCH_PIECES._values.values())
    calls0 = dict(m.EC_ENCODE_STAGE_CALLS._values)
    codec = _DeviceLike() if kind == "device_like" else CpuRSCodec()
    runs = write_ec_files_multi(batch, codec=codec, large_block_size=LARGE, small_block_size=SMALL)
    for b, size in zip(batch, BATCH_SIZES):
        assert _shards(b) == _oracle(b), size
    assert len({id(r) for r in runs}) == 3  # each volume its own run, in the order given
    # the stages are the one-volume route's, by name: no second set
    moved = {k for k, v in _stage_seconds().items() if v > stages0.get(k, 0)}
    assert set(_stage_seconds()) <= {"splice", "read", "slot_wait", "submit", "kernel", "parity_wait",
                                     "write", "write_thread", "sync"}
    pieces = sum(m.EC_ENCODE_BATCH_PIECES._values.values()) - pieces0
    assert all(r.route["route"] == "pipeline" and r.route["kernel"] == kind for r in runs)
    assert moved >= {"read", "slot_wait", "submit", "kernel", "parity_wait", "write", "write_thread", "sync"}
    items = sum(v - calls0.get(k, 0) for k, v in m.EC_ENCODE_STAGE_CALLS._values.items()
                if dict(k)["stage"] == "submit") - 3  # each volume's set-up adds one `since`
    assert pieces == items > 0  # one piece an item: nothing is batched into a dispatch
    assert not [n for n in os.listdir(tmp_path / "b0") if n.endswith(".tmp")]


def test_one_volume_counts_one_piece_an_item(tmp_path):
    _mk_dat(str(tmp_path / "1.dat"), SMALL * 10 * 4)
    pieces0 = sum(m.EC_ENCODE_BATCH_PIECES._values.values())
    run = write_ec_files(str(tmp_path / "1"), codec=CpuRSCodec(), large_block_size=LARGE,
                         small_block_size=SMALL, chunk=SMALL)
    assert run.route["route"] == "pipeline"
    assert sum(m.EC_ENCODE_BATCH_PIECES._values.values()) - pieces0 == 4  # one a row


KILLED_BATCH = """
import os, sys
sys.path.insert(0, {root!r})
from seaweedfs_tpu.storage.erasure_coding import write_ec_files_multi
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec

class Dies(CpuRSCodec):
    is_device = True
    preferred_chunk = 16 * 1024
    calls = 0
    def pipeline_encode(self, data):
        Dies.calls += 1
        if Dies.calls == {kill_at}:
            os._exit(9)  # the process is gone: no `finally` runs
        return self.encode(data)

write_ec_files_multi({bases!r}, codec=Dies(), large_block_size={large}, small_block_size={small})
"""


@pytest.mark.parametrize("kill_at,whole", [(3, 0), (9, 1)], ids=["in_the_first_volume", "in_the_second"])
def test_a_batch_killed_half_way_leaves_no_torn_final_name_and_the_next_run_sweeps(tmp_path, kill_at, whole):
    bases = []
    for j in range(3):
        d = tmp_path / f"v{j}"
        d.mkdir()
        _mk_dat(str(d / "1.dat"), SMALL * 10 * 6 + j)  # 6 rows + a tail: 7 dispatches a volume
        bases.append(str(d / "1"))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c",
         KILLED_BATCH.format(root=root, bases=bases, large=LARGE, small=SMALL, kill_at=kill_at)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=120)
    assert done.returncode == 9, done.stderr[-2000:]
    for j, base in enumerate(bases):
        names = os.listdir(os.path.dirname(base))
        final = [n for n in names if n[-5:-2] == ".ec"]
        tmp = [n for n in names if n.endswith(".tmp")]
        if j < whole:  # committed before the kill: whole, under its final names
            assert len(final) == 14 and not tmp, names
        else:  # nothing looks finished; the torn files of the volume the kill met are temporaries
            assert not final and bool(tmp) == (j == whole), names
    # the next run sweeps them and finishes; so does a one-volume run
    write_ec_files_multi(bases[:2], codec=_DeviceLike(), large_block_size=LARGE, small_block_size=SMALL)
    write_ec_files(bases[2], codec=_DeviceLike(), large_block_size=LARGE, small_block_size=SMALL)
    for base in bases:
        names = os.listdir(os.path.dirname(base))
        assert not [n for n in names if n.endswith(".tmp")], names
        assert _shards(base) == _oracle(base)


@pytest.mark.parametrize("fail_at,whole", [(2, 0), (9, 1)], ids=["in_the_first_volume", "in_the_second"])
def test_a_batch_that_fails_in_this_process_sweeps_and_says_what_is_whole(tmp_path, fail_at, whole):
    class Fails(_DeviceLike):
        calls = 0

        def pipeline_encode(self, data):
            Fails.calls += 1
            if Fails.calls == fail_at:
                raise RuntimeError("the device is gone")
            return self.encode(data)

    bases = []
    for j in range(2):
        d = tmp_path / f"v{j}"
        d.mkdir()
        _mk_dat(str(d / "1.dat"), SMALL * 10 * 6 + 1)
        bases.append(str(d / "1"))
    with pytest.raises(RuntimeError, match="the device is gone") as failed:
        write_ec_files_multi(bases, codec=Fails(), large_block_size=LARGE, small_block_size=SMALL)
    # the runs of the volumes before the failure: a fallback need not convert them again
    assert [r.route["route"] for r in failed.value.encoded] == ["pipeline"] * whole
    for j, base in enumerate(bases):
        names = sorted(os.listdir(os.path.dirname(base)))
        assert names == (["1.dat"] + [f"1.ec{i:02d}" for i in range(14)] if j < whole else ["1.dat"])


def test_a_host_codecs_batch_that_fails_says_what_is_whole_before_the_failure(tmp_path):
    """A host codec's volumes go at once. The one that fails is swept, the
    others finish, and `encoded` holds the runs up to the first failure, which
    is the prefix `_ec_generate_batch` takes as done."""
    mark = bytes(range(200, 208))

    class FailsOnTheMarkedVolume(CpuRSCodec):  # no is_device: a host codec
        def encode(self, data):
            if bytes(data[0, :8]) == mark:
                raise RuntimeError("this volume cannot be encoded")
            return super().encode(data)

    bases = []
    for j in range(3):
        d = tmp_path / f"v{j}"
        d.mkdir()
        _mk_dat(str(d / "1.dat"), SMALL * 10 * 6 + j)
        bases.append(str(d / "1"))
    with open(bases[1] + ".dat", "r+b") as f:
        f.write(mark)
    with pytest.raises(RuntimeError, match="cannot be encoded") as failed:
        write_ec_files_multi(bases, codec=FailsOnTheMarkedVolume(), large_block_size=LARGE, small_block_size=SMALL)
    assert [r.route["kernel"] for r in failed.value.encoded] == ["host"]
    whole = ["1.dat"] + [f"1.ec{i:02d}" for i in range(14)]
    assert [sorted(os.listdir(os.path.dirname(b))) for b in bases] == [whole, ["1.dat"], whole]
    for b in (bases[0], bases[2]):
        assert _shards(b) == _oracle(b)


# ---------------------------------------------------- the batch, as the RPC
def _server_with_volumes(tmp_path, vids, needles=40):
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume
    from test_bringup import _volume_server

    for vid in vids:
        v = Volume(str(tmp_path), "c", vid)
        for key in range(1, needles + vid):
            v.write_needle(Needle(id=key, cookie=1, data=os.urandom(3000)))
        v.close()
    return _volume_server(tmp_path, codec_backend="tpu")


def _counter(counter) -> dict:
    return {tuple(sorted(dict(k).items())): v for k, v in counter._values.items()}


def _grew(counter, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _counter(counter).items() if v != before.get(k, 0)}


def test_generate_batch_counts_bytes_by_backend_and_its_own_wall(tmp_path):
    vids = [7, 8, 9]
    vs = _server_with_volumes(tmp_path, vids)
    sizes = {vid: os.path.getsize(tmp_path / f"c_{vid}.dat") for vid in vids}
    label = (("backend", vs.codec.pipeline_dispatch_kind),)
    before = {c: _counter(c) for c in (m.EC_ENCODE_BYTES, m.EC_GENERATE_SECONDS,
                                        m.EC_ENCODE_BATCH_FALLBACKS, m.EC_ENCODE_BATCH_PIECES)}
    stages0 = _stage_seconds()
    try:
        reply = asyncio.run(vs._grpc_ec_generate_batch({"volume_ids": vids, "collection": "c"}, None))
    finally:
        vs.store.close()
    assert reply == {"errors": {}}
    assert label[0][1] != "device"  # a CPU encoded, and says so
    assert _grew(m.EC_ENCODE_BYTES, before[m.EC_ENCODE_BYTES]) == {label: sum(sizes.values())}
    wall = _grew(m.EC_GENERATE_SECONDS, before[m.EC_GENERATE_SECONDS])
    assert set(wall) == {(("rpc", "batch"),)} and wall[(("rpc", "batch"),)] > 0
    assert _grew(m.EC_ENCODE_BATCH_FALLBACKS, before[m.EC_ENCODE_BATCH_FALLBACKS]) == {}
    assert {k for k, v in _stage_seconds().items() if v > stages0.get(k, 0)} >= {
        "read", "submit", "kernel", "write", "write_thread", "sync"}
    for vid in vids:  # byte for byte what the one-volume route gives
        alone = str(tmp_path / f"alone{vid}")
        os.link(tmp_path / f"c_{vid}.dat", alone + ".dat")
        write_ec_files(alone, codec=CpuRSCodec())
        assert _shards(str(tmp_path / f"c_{vid}")) == _shards(alone)
        assert os.path.exists(tmp_path / f"c_{vid}.ecx") and os.path.exists(tmp_path / f"c_{vid}.vif")


def test_generate_single_counts_its_own_wall(tmp_path):
    vs = _server_with_volumes(tmp_path, [7])
    before = _counter(m.EC_GENERATE_SECONDS)
    try:
        assert asyncio.run(vs._grpc_ec_generate({"volume_id": 7, "collection": "c"}, None)) == {}
    finally:
        vs.store.close()
    assert set(_grew(m.EC_GENERATE_SECONDS, before)) == {(("rpc", "single"),)}


@pytest.mark.parametrize("fault,reason", [("dat_gone", "io"), ("codec_raises", "codec")])
def test_a_failed_batch_counts_a_fallback_and_reports_the_right_id(tmp_path, monkeypatch, fault, reason):
    vids = [7, 8, 9]
    vs = _server_with_volumes(tmp_path, vids)
    sizes = {vid: os.path.getsize(tmp_path / f"c_{vid}.dat") for vid in vids}
    if fault == "dat_gone":
        os.unlink(tmp_path / "c_8.dat")  # volume 8 is broken; 7 and 9 are not
    else:
        real = type(vs.codec).pipeline_encode
        state = {"calls": 0}

        def once(self, data):  # the batch's first dispatch fails; the volumes alone do not
            state["calls"] += 1
            if state["calls"] == 1:
                raise RuntimeError("the device hiccuped")
            return real(self, data)

        monkeypatch.setattr(type(vs.codec), "pipeline_encode", once)
    fallbacks0, bytes0 = _counter(m.EC_ENCODE_BATCH_FALLBACKS), _counter(m.EC_ENCODE_BYTES)
    from seaweedfs_tpu.server import volume_ec

    retried = []  # what the fallback converted one by one

    def one_by_one(base, **kw):
        retried.append(int(base.rsplit("_", 1)[1]))
        return write_ec_files(base, **kw)

    monkeypatch.setattr(volume_ec, "write_ec_files", one_by_one)
    try:
        reply = asyncio.run(vs._grpc_ec_generate_batch({"volume_ids": vids, "collection": "c"}, None))
    finally:
        vs.store.close()
    assert _grew(m.EC_ENCODE_BATCH_FALLBACKS, fallbacks0) == {(("reason", reason),): 1}
    # the volumes the batch had finished are not converted twice
    assert retried == ([8, 9] if fault == "dat_gone" else vids)
    good = [7, 9] if fault == "dat_gone" else vids
    assert sorted(reply["errors"]) == [str(v) for v in vids if v not in good]
    counted = sum(_grew(m.EC_ENCODE_BYTES, bytes0).values())
    assert counted == sum(sizes[v] for v in good)
    for vid in good:  # the neighbours finished
        assert all(os.path.exists(tmp_path / f"c_{vid}{to_ext(i)}") for i in range(14))
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    if fault == "dat_gone":
        assert not [n for n in os.listdir(tmp_path) if n.startswith("c_8.ec")]


# ----------------------------------- the command, through a master, end to end
def test_ec_encode_without_volume_id_leaves_alone_what_is_not_full_or_not_quiet(tmp_path, monkeypatch):
    from seaweedfs_tpu.client.operation import upload_data
    from seaweedfs_tpu.pb.rpc import close_all_channels
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.shell import CommandEnv, run_command
    from seaweedfs_tpu.storage.file_id import format_needle_id_cookie
    from test_cluster import assign_retry, free_port_pair

    async def body():
        master = MasterServer(port=free_port_pair(), pulse_seconds=0.2, volume_size_limit_mb=1)
        await master.start()
        vs = VolumeServer(master=master.address, directories=[str(tmp_path)], port=free_port_pair(),
                          pulse_seconds=0.2, max_volume_counts=[20])
        await vs.start()
        try:
            async with aiohttp.ClientSession() as session:
                ar = await assign_retry(master.address, collection="c")
                vid = int(ar.fid.split(",")[0])
                full, small = vid, (vid - 1 if vid > 1 else vid + 1)
                for key in range(1, 12):  # 1.1 MB: over 95 % of the master's 1 MB
                    await upload_data(session, ar.url, f"{full},{format_needle_id_cookie(key, 0xAB00 + key)}",
                                      os.urandom(100_000))
                await upload_data(session, ar.url, f"{small},{format_needle_id_cookie(1, 0xCD01)}", b"x" * 900)
                env = CommandEnv(master.address)
                for _ in range(100):  # until the digest tick has told the master
                    seen = {int(v["id"]): v for dn in await env.collect_data_nodes() for v in dn["volumes"]}
                    if seen.get(full, {}).get("size", 0) > MB and seen[full].get("modified_at_second"):
                        break
                    await asyncio.sleep(0.1)
                assert abs(seen[full]["modified_at_second"] - time.time()) < 60
                assert (await run_command(env, "lock")) == "locked"
                # the documented line: the volume was written a moment ago, so nothing is selected
                out = await run_command(env, "ec.encode -collection c -fullPercent=95 -quietFor=1h")
                assert out == "no volumes to encode"
                assert await run_command(env, "ec.encode -collection c") == "no volumes to encode"
                assert "bad duration" in await run_command(env, "ec.encode -collection c -quietFor=soon")
                # quiet for no time at all, a second later: the full volume alone
                monkeypatch.setattr(commands.time, "time", lambda real=time.time: real() + 2)
                out = await run_command(env, "ec.encode -collection c -fullPercent=95 -quietFor=0s")
                assert out.startswith(f"volume {full}: encoded") and out.count("volume ") == 1, out
                assert os.path.exists(tmp_path / f"c_{full}.ec00") and not os.path.exists(tmp_path / f"c_{full}.dat")
                assert os.path.exists(tmp_path / f"c_{small}.dat")
                assert not [n for n in os.listdir(tmp_path) if n.startswith(f"c_{small}.ec")]
                assert not vs.store.find_volume(small).is_read_only()
                await env.release_lock()
        finally:
            await vs.stop()
            await master.stop()
            await close_all_channels()

    asyncio.run(body())


def test_a_meshs_volumes_go_through_the_same_pipeline(tmp_path, monkeypatch):
    """write_ec_files_multi(mesh=...) computes each dispatch's parity over the
    mesh (virtual host mesh — the path a TPU mesh takes) and leaves the rest
    to the one pipeline: byte-identical, its stages, a commit by rename, and
    it says which kernel ran."""
    jax = pytest.importorskip("jax")
    from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
    from seaweedfs_tpu.parallel.sharded_ec import make_mesh

    batch = []
    for j, size in enumerate(BATCH_SIZES):
        d = tmp_path / f"b{j}"
        d.mkdir()
        _mk_dat(str(d / "1.dat"), size)
        batch.append(str(d / "1"))
    renamed = []
    real_replace = os.replace
    monkeypatch.setattr(enc.os, "replace", lambda a, b: (renamed.append((a, b)), real_replace(a, b))[1])
    runs = write_ec_files_multi(batch, codec=TpuRSCodec(), large_block_size=LARGE, small_block_size=SMALL,
                                mesh=make_mesh(devices=jax.devices("cpu")))
    assert len({id(r) for r in runs}) == 3
    for run in runs:
        assert run.route["route"] == "pipeline" and run.route["kernel"] == "mesh"
        assert {"read_s", "slot_wait_s", "kernel_s", "parity_wait_s", "write_s", "sync_s"} <= set(run.stages())
    for b, size in zip(batch, BATCH_SIZES):
        assert _shards(b) == _oracle(b), size
        assert not [n for n in os.listdir(os.path.dirname(b)) if n.endswith(".tmp")]
    # every shard file was written under a temporary name and renamed when its volume was whole
    assert renamed == [(b + to_ext(i) + ".tmp", b + to_ext(i)) for b in batch for i in range(14)]
