"""Batched multi-volume EC encode (BASELINE config #3): write_ec_files_multi
byte-parity vs the per-volume pipeline, and the VolumeEcShardsGenerateBatch
RPC end-to-end (ref per-volume semantics: ec_encoder.go:57,120-136)."""

import asyncio
import os
import random

import aiohttp
import numpy as np
import pytest

from ec_oracle import oracle_shards
from seaweedfs_tpu.storage.erasure_coding import (
    to_ext,
    write_ec_files_multi,
)
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec

LARGE, SMALL = 8192, 1024


def _mk_dat(path: str, size: int) -> None:
    data = np.random.default_rng(size + 7).integers(
        0, 256, size, dtype=np.uint8
    )
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


@pytest.mark.parametrize("route", ["in_turn", "mesh"])
def test_multi_device_batch_path_matches_oracle(tmp_path, route):
    """A device codec's batch — its volumes in turn through the streamed
    pipeline, on one device or with each dispatch over a mesh — must be
    byte-identical to the oracle across mixed geometries."""
    import jax

    from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
    from seaweedfs_tpu.parallel.sharded_ec import make_mesh

    mesh = make_mesh(devices=jax.devices("cpu")) if route == "mesh" else None

    sizes = [
        LARGE * 10 * 2 + SMALL * 10 * 2 + 333,
        SMALL * 10 * 5,
        SMALL * 3 + 17,
        0,
        LARGE * 10 + 1,
    ]
    multis = []
    for j, size in enumerate(sizes):
        d = tmp_path / f"dm{j}"
        d.mkdir()
        _mk_dat(str(d / "1.dat"), size)
        multis.append(str(d / "1"))
    runs = write_ec_files_multi(
        multis, codec=TpuRSCodec(),
        large_block_size=LARGE, small_block_size=SMALL, mesh=mesh,
    )
    for m, size in zip(multis, sizes):
        assert _shards(m) == _oracle(m), size
    assert [r.route["route"] for r in runs] == ["pipeline"] * len(sizes)
    if route == "mesh":
        assert {r.route["kernel"] for r in runs} == {"mesh"}


def test_multi_matches_per_volume_oracle(tmp_path):
    # varied geometries: large+small rows, small-only, sub-row tail, empty
    sizes = [
        LARGE * 10 * 2 + SMALL * 10 * 2 + 333,
        SMALL * 10 * 5,
        SMALL * 3 + 17,
        0,
        LARGE * 10 + 1,
    ]
    multis = []
    for j, size in enumerate(sizes):
        d = tmp_path / f"m{j}"
        d.mkdir()
        _mk_dat(str(d / "1.dat"), size)
        multis.append(str(d / "1"))
    write_ec_files_multi(
        multis, codec=CpuRSCodec(),
        large_block_size=LARGE, small_block_size=SMALL,
    )
    for m, size in zip(multis, sizes):
        assert _shards(m) == _oracle(m), size


def test_multi_with_native_codec(tmp_path):
    native = pytest.importorskip("seaweedfs_tpu.native")
    if not native.available():
        pytest.skip("native gf256 library unavailable")
    from seaweedfs_tpu.storage.erasure_coding.coder_native import NativeRSCodec

    sizes = [SMALL * 10 * 3 + 100, SMALL * 10 * 3 + 100, SMALL * 2]
    multis = []
    for j, size in enumerate(sizes):
        d = tmp_path / f"m{j}"
        d.mkdir()
        _mk_dat(str(d / "1.dat"), size)
        multis.append(str(d / "1"))
    write_ec_files_multi(
        multis, codec=NativeRSCodec(),
        large_block_size=LARGE, small_block_size=SMALL,
    )
    for m in multis:
        assert _shards(m) == _oracle(m)


def test_shell_ec_encode_batches_colocated_volumes(tmp_path):
    """`ec.encode -volumeId a,b` with both volumes on one node goes through
    VolumeEcShardsGenerateBatch, then spreads and serves reads as usual."""
    from seaweedfs_tpu.pb.rpc import close_all_channels
    from seaweedfs_tpu.shell import CommandEnv, run_command
    from seaweedfs_tpu.storage.file_id import format_needle_id_cookie

    from tests.test_cluster import Cluster
    from seaweedfs_tpu.client import assign
    from seaweedfs_tpu.client.operation import read_url, upload_data

    async def body():
        cluster = Cluster(tmp_path, n_volume_servers=1)
        await cluster.start()
        try:
            async with aiohttp.ClientSession() as session:
                from tests.test_cluster import assign_retry
                ar0 = await assign_retry(cluster.master.address)
                url = ar0.url
                vid0 = int(ar0.fid.split(",")[0])
                # a second volume that is KNOWN to exist on the (single)
                # server: assign may hand out the highest-numbered volume,
                # where vid0 + 1 was never grown
                vids = [vid0, vid0 - 1 if vid0 > 1 else vid0 + 1]
                payloads = {}
                for vid in vids:
                    for i in range(1, 6):
                        fid = f"{vid},{format_needle_id_cookie(i, 0xEE00 + i)}"
                        data = random.randbytes(1200 + 17 * i)
                        await upload_data(session, url, fid, data)
                        payloads[fid] = data

                env = CommandEnv(cluster.master.address)
                for _ in range(100):
                    nodes = await env.collect_data_nodes()
                    have = {
                        int(v["id"])
                        for dn in nodes
                        for v in dn.get("volumes", [])
                    }
                    if set(vids) <= have:
                        break
                    await asyncio.sleep(0.1)
                assert (await run_command(env, "lock")) == "locked"
                out = await run_command(
                    env, f"ec.encode -volumeId {vids[0]},{vids[1]}"
                )
                assert out.count("encoded") == 2, out

                for fid, want in payloads.items():
                    got = await read_url(session, f"http://{url}/{fid}")
                    assert got == want, fid
        finally:
            await cluster.stop()
            await close_all_channels()

    asyncio.run(body())


def test_generate_batch_rpc_and_read_back(tmp_path):
    from seaweedfs_tpu.pb import grpc_address
    from seaweedfs_tpu.pb.rpc import Stub, close_all_channels
    from seaweedfs_tpu.storage.file_id import format_needle_id_cookie

    from tests.test_cluster import Cluster
    from seaweedfs_tpu.client import assign
    from seaweedfs_tpu.client.operation import read_url, upload_data

    async def body():
        cluster = Cluster(tmp_path, n_volume_servers=1)
        await cluster.start()
        try:
            async with aiohttp.ClientSession() as session:
                from tests.test_cluster import assign_retry
                ar0 = await assign_retry(cluster.master.address)
                url = ar0.url
                vid0 = int(ar0.fid.split(",")[0])
                # see test_shell_ec_encode_batches_colocated_volumes: vid0+1
                # need not exist when assign picked the highest-grown volume
                vids = [vid0, vid0 - 1 if vid0 > 1 else vid0 + 1]
                payloads = {}
                for vid in vids:
                    for i in range(1, 8):
                        fid = f"{vid},{format_needle_id_cookie(i, 0xCD00 + i)}"
                        data = random.randbytes(1500 + 31 * i)
                        await upload_data(session, url, fid, data)
                        payloads[fid] = data

                stub = Stub(grpc_address(url), "volume")
                for vid in vids:
                    await stub.call("VolumeMarkReadonly", {"volume_id": vid})
                r = await stub.call(
                    "VolumeEcShardsGenerateBatch",
                    {"volume_ids": vids},
                    timeout=120,
                )
                assert not r.get("error"), r
                assert not r.get("errors"), r

                # serve from EC shards only: mount, drop the plain volumes
                for vid in vids:
                    r = await stub.call(
                        "VolumeEcShardsMount",
                        {"volume_id": vid, "shard_ids": list(range(14))},
                    )
                    assert not r.get("error"), r
                    await stub.call("VolumeUnmount", {"volume_id": vid})
                    await stub.call("VolumeDelete", {"volume_id": vid})
                for fid, want in payloads.items():
                    got = await read_url(session, f"http://{url}/{fid}")
                    assert got == want, fid
        finally:
            await cluster.stop()
            await close_all_channels()

    asyncio.run(body())
