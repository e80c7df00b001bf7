"""Operator shell suite end-to-end: volume.balance, volume.fsck, fs.*,
bucket.* (ref: weed/shell/command_volume_balance.go:61,
command_volume_fsck.go:25, command_fs_*.go, command_bucket_*.go)."""

import asyncio
import random

import aiohttp

from test_cluster import Cluster, free_port_pair

from seaweedfs_tpu.client import assign
from seaweedfs_tpu.client.operation import upload_data
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.shell import CommandEnv, run_command


def test_volume_balance(tmp_path):
    async def body():
        random.seed(53)
        cluster = Cluster(tmp_path, n_volume_servers=1)
        await cluster.start()
        try:
            async with aiohttp.ClientSession() as session:
                # create volumes + data on the single server
                async with session.get(
                    f"http://{cluster.master.address}/vol/grow?count=6"
                ) as resp:
                    assert resp.status == 200, await resp.text()
                ar = await assign(cluster.master.address)
                await upload_data(session, ar.url, ar.fid, b"balance-me")

                # a second, empty server joins
                vport = free_port_pair()
                d = tmp_path / "vol-late"
                d.mkdir()
                vs = VolumeServer(
                    master=cluster.master.address,
                    directories=[str(d)],
                    port=vport,
                    pulse_seconds=0.2,
                    max_volume_counts=[20],
                )
                await vs.start()
                cluster.volume_servers.append(vs)
                for _ in range(100):
                    if len(cluster.master.topo.data_nodes()) == 2:
                        break
                    await asyncio.sleep(0.1)

                env = CommandEnv(cluster.master.address)
                # the balance plans from what the heartbeats have listed,
                # and a grown volume is listed a pulse after it is grown
                for _ in range(100):
                    nodes = await env.collect_data_nodes()
                    if max(len(dn.get("volumes", [])) for dn in nodes) >= 6:
                        break
                    await asyncio.sleep(0.1)
                # plan only (no -force): nothing moves
                await run_command(env, "lock")
                plan = await run_command(env, "volume.balance")
                assert "would move" in plan and "move volume" in plan

                out = await run_command(env, "volume.balance -force")
                assert "moved: " in out

                # counts are now even within 1
                await asyncio.sleep(1.0)  # let heartbeats refresh the topo
                nodes = await env.collect_data_nodes()
                counts = sorted(len(dn.get("volumes", [])) for dn in nodes)
                assert counts[-1] - counts[0] <= 1, counts

                # the uploaded blob is still readable wherever it moved
                vid = int(ar.fid.split(",")[0])
                resp = await env.master_stub.call(
                    "LookupVolume", {"volume_ids": [str(vid)]}
                )
                locs = resp["volume_id_locations"][0]["locations"]
                async with session.get(
                    f"http://{locs[0]['url']}/{ar.fid}"
                ) as r2:
                    assert r2.status == 200
                    assert await r2.read() == b"balance-me"
                await run_command(env, "unlock")
        finally:
            await cluster.stop()

    asyncio.run(body())


def test_fsck_fs_and_buckets(tmp_path):
    async def body():
        random.seed(59)
        cluster = Cluster(tmp_path, n_volume_servers=2)
        await cluster.start()
        from seaweedfs_tpu.server.filer import FilerServer

        fs = FilerServer(
            master=cluster.master.address,
            port=free_port_pair(),
            chunk_size=32 * 1024,
        )
        await fs.start()
        try:
            await fs.master_client.wait_connected()
            env = CommandEnv(cluster.master.address, filer=fs.address)
            async with aiohttp.ClientSession() as session:
                base = f"http://{fs.address}"
                # files through the filer (referenced chunks)
                doc = random.randbytes(80 * 1024)  # 3 chunks
                async with session.put(f"{base}/docs/a.bin", data=doc) as r:
                    assert r.status == 201
                async with session.put(
                    f"{base}/docs/sub/b.txt", data=b"hello shell"
                ) as r:
                    assert r.status == 201

                # fs.ls / fs.du / fs.cat
                out = await run_command(env, "fs.ls /docs")
                assert "a.bin" in out and "sub/" in out
                out = await run_command(env, "fs.ls -l /docs")
                assert str(len(doc)) in out
                out = await run_command(env, "fs.du /docs")
                assert f"{len(doc) + len(b'hello shell')} bytes" in out
                assert "2 files" in out and "1 dirs" in out
                out = await run_command(env, "fs.cat /docs/sub/b.txt")
                assert out == "hello shell"

                # fs.mkdir / fs.mv / fs.rm
                out = await run_command(env, "fs.mkdir /made/deep")
                assert "created" in out
                assert fs.filer.find_entry("/made/deep").is_directory
                out = await run_command(env, "fs.mv /docs/a.bin /made/a2.bin")
                assert "moved" in out
                assert fs.filer.find_entry("/docs/a.bin") is None
                assert fs.filer.find_entry("/made/a2.bin") is not None
                out = await run_command(env, "fs.cat /made/a2.bin")
                assert len(out) > 0
                # a directory destination receives the source inside it
                out = await run_command(env, "fs.mv /made/a2.bin /made/deep")
                assert "moved" in out
                assert fs.filer.find_entry("/made/deep/a2.bin") is not None

                # refusals: mkdir over a file, mv into own subtree, rm miss
                out = await run_command(env, "fs.mkdir /made/deep/a2.bin")
                assert "already exists" in out
                assert not fs.filer.find_entry("/made/deep/a2.bin").is_directory
                out = await run_command(env, "fs.mv /made /made/deep/sub")
                assert "into itself" in out
                assert fs.filer.find_entry("/made/deep/a2.bin") is not None
                out = await run_command(env, "fs.rm /nope/missing.bin")
                assert "no entry found" in out

                out = await run_command(env, "fs.rm -r /made")
                assert "removed" in out
                assert fs.filer.find_entry("/made") is None
                # put a.bin back for the fsck phase below
                async with session.put(f"{base}/docs/a.bin", data=doc) as r:
                    assert r.status == 201

                # bucket.*
                out = await run_command(env, "bucket.create -name mybkt")
                assert "created" in out
                out = await run_command(env, "bucket.list")
                assert "mybkt" in out
                assert fs.filer.find_entry("/buckets/mybkt") is not None
                out = await run_command(env, "bucket.delete -name mybkt")
                assert "deleted" in out
                assert fs.filer.find_entry("/buckets/mybkt") is None

                # an orphan: uploaded directly, unknown to the filer
                ar = await assign(cluster.master.address)
                await upload_data(session, ar.url, ar.fid, b"orphan-data")

                await run_command(env, "lock")
                # volume inventories reach the master via heartbeat deltas;
                # poll until the orphan shows up
                out = ""
                for _ in range(50):
                    out = await run_command(env, "volume.fsck")
                    if "1 orphans" in out:
                        break
                    await asyncio.sleep(0.2)
                assert "1 orphans" in out, out

                out = await run_command(
                    env, "volume.fsck -reallyDeleteFromVolume"
                )
                assert "purged 1 orphans" in out, out
                async with session.get(f"http://{ar.url}/{ar.fid}") as r:
                    assert r.status == 404

                out = await run_command(env, "volume.fsck")
                assert "0 orphans" in out, out
                await run_command(env, "unlock")
        finally:
            await fs.stop()
            await cluster.stop()

    asyncio.run(body())


def test_shell_long_tail_commands(tmp_path):
    """fs.tree / fs.cd / fs.pwd / fs.meta.save|load|cat, volume.copy and
    volume.configure.replication against live servers (ref
    command_fs_tree.go, command_fs_meta_save.go, command_volume_copy.go,
    command_volume_configure_replication.go)."""

    async def body():
        random.seed(61)
        cluster = Cluster(tmp_path, n_volume_servers=2)
        await cluster.start()
        from seaweedfs_tpu.server.filer import FilerServer

        fs = FilerServer(
            master=cluster.master.address,
            port=free_port_pair(),
            chunk_size=32 * 1024,
        )
        await fs.start()
        try:
            await fs.master_client.wait_connected()
            env = CommandEnv(cluster.master.address, filer=fs.address)
            async with aiohttp.ClientSession() as session:
                base = f"http://{fs.address}"
                for path, payload in [
                    ("/proj/readme.md", b"hello"),
                    ("/proj/src/main.py", b"print(1)"),
                    ("/proj/src/util.py", b"pass"),
                ]:
                    async with session.put(f"{base}{path}", data=payload) as r:
                        assert r.status == 201

                # fs.tree
                out = await run_command(env, "fs.tree /proj")
                assert "src" in out and "main.py" in out, out
                assert "2 directories" not in out.split("\n")[0]
                assert "directories" in out and "files" in out

                # fs.cd / fs.pwd (relative + absolute + missing)
                assert await run_command(env, "fs.pwd") == "/"
                assert await run_command(env, "fs.cd /proj") == "/proj"
                assert await run_command(env, "fs.pwd") == "/proj"
                assert await run_command(env, "fs.cd src") == "/proj/src"
                # relative paths resolve against the working directory
                out = await run_command(env, "fs.ls .")
                assert "main.py" in out and "util.py" in out, out
                assert await run_command(env, "fs.cd /proj") == "/proj"
                out = await run_command(env, "fs.ls src")
                assert "main.py" in out, out
                # '..' navigation normalizes
                assert await run_command(env, "fs.cd src") == "/proj/src"
                assert await run_command(env, "fs.cd ..") == "/proj"
                out = await run_command(env, "fs.ls ../proj/src")
                assert "main.py" in out, out
                out = await run_command(env, "fs.cd /nope")
                assert "no such directory" in out

                # fs.meta.cat
                out = await run_command(env, "fs.meta.cat /proj/readme.md")
                assert '"full_path"' in out and "readme.md" in out

                # fs.meta.save -> wipe -> fs.meta.load -> listing restored
                meta_file = str(tmp_path / "snap.meta")
                out = await run_command(
                    env, f"fs.meta.save -o {meta_file} /proj"
                )
                assert "saved" in out and "meta entries" in out, out
                out = await run_command(env, "fs.rm -r /proj")
                assert "removed" in out, out
                out = await run_command(env, "fs.ls /proj")
                assert "empty" in out or "error" in out
                out = await run_command(env, f"fs.meta.load {meta_file}")
                assert "restored" in out, out
                out = await run_command(env, "fs.tree /proj")
                assert "main.py" in out and "util.py" in out, out

                # ---- volume.copy + volume.configure.replication ----
                ar = await assign(cluster.master.address)
                await upload_data(
                    session, ar.url, ar.fid, b"copy-me", filename="c.bin"
                )
                vid = int(ar.fid.split(",")[0])
                source = ar.url
                target = next(
                    vs.address
                    for vs in cluster.volume_servers
                    if vs.address != source
                )
                await run_command(env, "lock")
                out = await run_command(
                    env, f"volume.copy {source} {target} {vid}"
                )
                assert "copied" in out, out
                # the copy serves reads directly
                from seaweedfs_tpu.client.operation import read_url

                got = await read_url(session, f"http://{target}/{ar.fid}")
                assert got == b"copy-me"
                # copying onto a holder refuses
                out = await run_command(
                    env, f"volume.copy {target} {target} {vid}"
                )
                assert "same" in out

                # configure must see BOTH holders at the master first
                for _ in range(100):
                    holders = {
                        dn["url"]
                        for dn in await env.collect_data_nodes()
                        if any(
                            int(v["id"]) == vid
                            for v in dn.get("volumes", [])
                        )
                    }
                    if {source, target} <= holders:
                        break
                    await asyncio.sleep(0.1)
                assert {source, target} <= holders, holders

                out = await run_command(
                    env,
                    f"volume.configure.replication -volumeId {vid} "
                    "-replication 001",
                )
                assert "replication" in out, out
                for vs in cluster.volume_servers:
                    v = vs.store.find_volume(vid)
                    if v is not None:
                        assert (
                            v.super_block.replica_placement.to_byte() == 1
                        ), vs.address
                # the change reaches the master via heartbeat deltas: once
                # there, a re-run finds nothing left to configure
                for _ in range(100):
                    out = await run_command(
                        env,
                        f"volume.configure.replication -volumeId {vid} "
                        "-replication 001",
                    )
                    if out == "no volume needs change":
                        break
                    await asyncio.sleep(0.1)
                assert out == "no volume needs change", out
                out = await run_command(
                    env,
                    f"volume.configure.replication -volumeId {vid} "
                    "-replication abc",
                )
                assert "replication format" in out
                await run_command(env, "unlock")
        finally:
            await fs.stop()
            await cluster.stop()

    asyncio.run(body())
