"""Tier-1 guard for the serving write-path budget instrumentation: the
in-process cluster write path must record every leg of a write (assign
RPC, client-side build, upload RPC) for every request, lease its file ids
in batches, take its serving samples, and the fsync tier must commit in
groups. Runs small (hundreds of writes) to stay inside the tier-1 wall
clock.
"""

import asyncio
import socket
import time


def free_port_pair() -> int:
    for _ in range(50):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        if p + 10000 > 65535:
            continue
        try:
            with socket.socket() as s:
                s.bind(("127.0.0.1", p + 10000))
            return p
        except OSError:
            continue
    raise RuntimeError("no free port pair")


def _run_write_phase(tmp_path, num_files=240, concurrency=8):
    """Mini cluster + instrumented write phase -> run_benchmark stats."""
    from seaweedfs_tpu.command.benchmark import run_benchmark
    from seaweedfs_tpu.pb.rpc import close_all_channels
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer

    s: dict = {}

    async def body():
        ms = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await ms.start()
        vs = VolumeServer(
            master=ms.address,
            directories=[str(tmp_path)],
            port=free_port_pair(),
            pulse_seconds=0.2,
            max_volume_counts=[10],
        )
        await vs.start()
        try:
            for _ in range(100):
                if ms.topo.data_nodes():
                    break
                await asyncio.sleep(0.1)
            await run_benchmark(
                ms.address,
                num_files=num_files,
                concurrency=concurrency,
                stats_out=s,
                do_read=False,
                assign_batch=32,
            )
        finally:
            await vs.stop()
            await ms.stop()
            await close_all_channels()

    asyncio.run(body())
    return s


def _group_commit(tmp_path, n=600, conc=16) -> tuple[dict, list]:
    """`conc` writers through one GroupCommitWorker -> (its stats, each
    write's enqueue-to-fsync'd wall in seconds)."""
    from seaweedfs_tpu.storage.group_commit import GroupCommitWorker
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    v = Volume(str(tmp_path), "", 11, create=True)
    waits: list = []

    async def run() -> dict:
        gc = GroupCommitWorker(v)
        gc.start()
        seq = [0]

        async def writer() -> None:
            while seq[0] < n:
                seq[0] += 1
                nd = Needle(cookie=1, id=seq[0], data=b"x" * 1024)
                t0 = time.perf_counter()
                await gc.write(nd)
                waits.append(time.perf_counter() - t0)

        await asyncio.gather(*(writer() for _ in range(conc)))
        await gc.stop()
        return gc.stats

    try:
        return asyncio.run(run()), waits
    finally:
        v.close()


def test_write_budget_components_emitted_and_nonzero(tmp_path):
    (tmp_path / "cluster").mkdir()
    (tmp_path / "gc").mkdir()
    s = _run_write_phase(tmp_path / "cluster")
    assert s["write_failed"] == 0, "instrumented write phase had failures"
    assert s["write_qps"] > 0
    stats = s["write_stats"]
    assert stats.percentile(50) > 0

    # the client-side leg partition must be populated for every write
    legs = s["write_legs"]
    for key in ("assign_stats", "build_stats", "upload_stats"):
        leg = legs[key]
        assert leg.completed == stats.completed, key
        assert leg._sum_ms > 0, f"{key} not measured"
    # batched assigns actually amortized: far fewer RPCs than writes
    assert legs["assign_rpcs"] < stats.completed / 4
    assert legs["assign_batch"] == 32

    # early + final serving samples (VERDICT §7)
    samples = s["write_samples"]
    assert len(samples) == 2
    assert all(x["qps"] > 0 for x in samples)

    # fsync tier: adaptive group commit measured, batching actually >1
    gc, waits = _group_commit(tmp_path / "gc")
    assert gc["requests"] == len(waits) == 600
    assert sorted(waits)[len(waits) // 2] > 0
    assert gc["requests"] / max(gc["batches"], 1) > 1.5, gc


def test_group_commit_put_fast_path_and_fsync(tmp_path):
    """PUT rides the fast write tier and fsync=true rides group commit —
    both must store bytes readable back through the same stack."""
    import aiohttp

    from seaweedfs_tpu.client import assign
    from seaweedfs_tpu.pb.rpc import close_all_channels
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.util.fasthttp import FastHTTPClient

    async def body():
        ms = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await ms.start()
        vs = VolumeServer(
            master=ms.address,
            directories=[str(tmp_path)],
            port=free_port_pair(),
            pulse_seconds=0.2,
            max_volume_counts=[10],
        )
        await vs.start()
        try:
            for _ in range(100):
                if ms.topo.data_nodes():
                    break
                await asyncio.sleep(0.1)
            for _ in range(60):
                try:
                    ar = await assign(ms.address)
                    break
                except Exception:
                    await asyncio.sleep(0.25)
            http = FastHTTPClient()
            payload = b"put-body-fast-path" * 40
            # multipart-free PUT body: fast-tier path
            st, body_resp = await http.request(
                "PUT", ar.url, "/" + ar.fid, body=payload,
                content_type="application/x-custom",
            )
            assert st == 201, (st, body_resp)
            st, got = await http.request("GET", ar.url, "/" + ar.fid)
            assert st == 200 and got == payload
            # fsync=true rides the group-commit worker (slow tier)
            ar2 = await assign(ms.address)
            async with aiohttp.ClientSession() as session:
                async with session.put(
                    f"http://{ar2.url}/{ar2.fid}?fsync=true", data=b"gc-body"
                ) as resp:
                    assert resp.status == 201, await resp.text()
            st, got = await http.request("GET", ar2.url, "/" + ar2.fid)
            assert st == 200 and got == b"gc-body"
            gc = vs._group_committers.get(
                int(ar2.fid.split(",")[0])
            )
            assert gc is not None and gc.stats["requests"] >= 1
            await http.close()
        finally:
            await vs.stop()
            await ms.stop()
            await close_all_channels()

    asyncio.run(body())
