"""Admin lease semantics under contention (ref: wdclient/exclusive_locks/
exclusive_locker.go:14-18 — 4s renewal against a 10s lease) and
heartbeat-break failure detection with client-visible vid deletion
(ref: master_grpc_server.go:24-52)."""

import asyncio
import random

import aiohttp
import pytest

from test_cluster import Cluster, free_port_pair

from seaweedfs_tpu.client import MasterClient, assign
from seaweedfs_tpu.client.operation import upload_data
from seaweedfs_tpu.pb import grpc_address
from seaweedfs_tpu.pb.rpc import Stub
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.shell import CommandEnv


def test_admin_lock_contention_with_renewal(tmp_path):
    async def body():
        mport = free_port_pair()
        # short lease so expiry is testable; A renews well inside it
        ms = MasterServer(port=mport, admin_lease_seconds=1.0)
        await ms.start()
        try:
            env_a = CommandEnv(ms.address, renew_interval=0.3)
            env_b = CommandEnv(ms.address, renew_interval=0.3)
            await env_a.acquire_lock()

            with pytest.raises(RuntimeError, match="already locked"):
                await env_b.acquire_lock()

            # past the ORIGINAL lease duration, A's renewals still hold it
            await asyncio.sleep(1.6)
            with pytest.raises(RuntimeError, match="already locked"):
                await env_b.acquire_lock()

            await env_a.release_lock()
            await env_b.acquire_lock()  # now free
            await env_b.release_lock()
        finally:
            await ms.stop()

    asyncio.run(body())


def test_admin_lock_expires_without_renewal(tmp_path):
    async def body():
        mport = free_port_pair()
        ms = MasterServer(port=mport, admin_lease_seconds=0.5)
        await ms.start()
        try:
            stub = Stub(grpc_address(ms.address), "master")
            r = await stub.call("LeaseAdminToken", {"previous_token": 0})
            assert r.get("token")

            # nobody renews; a second client takes over after expiry
            r2 = await stub.call("LeaseAdminToken", {"previous_token": 0})
            assert r2.get("error") == "already locked"
            await asyncio.sleep(0.7)
            r3 = await stub.call("LeaseAdminToken", {"previous_token": 0})
            assert r3.get("token"), r3
        finally:
            await ms.stop()

    asyncio.run(body())


def test_heartbeat_break_deletes_vids_from_clients(tmp_path):
    """Killing a volume server must unregister it on heartbeat-stream break
    and push the vid deletions to KeepConnected clients."""

    async def body():
        random.seed(71)
        cluster = Cluster(tmp_path, n_volume_servers=2)
        await cluster.start()
        client = MasterClient("test-client", [cluster.master.address])
        await client.start()
        try:
            async with aiohttp.ClientSession() as session:
                from tests.test_cluster import assign_retry

                ar = await assign_retry(cluster.master.address)
                await upload_data(session, ar.url, ar.fid, b"doomed")
            vid = int(ar.fid.split(",")[0])
            await client.wait_connected()
            for _ in range(100):
                if client.vid_map.lookup(vid):
                    break
                await asyncio.sleep(0.1)
            assert ar.url in client.vid_map.lookup(vid)
            # the client has the vid from the push the growth itself sends.
            # What a broken stream takes away is what the node's heartbeat
            # has listed: wait for the pulse that lists it
            node = next(
                n for n in cluster.master.topo.data_nodes() if n.url == ar.url
            )
            for _ in range(100):
                if vid in node.volumes:
                    break
                await asyncio.sleep(0.05)
            assert vid in node.volumes

            # kill the server holding the vid
            victim = cluster.server_for(ar.url)
            await victim.stop()
            cluster.volume_servers.remove(victim)

            # the master's failure detector unregisters it and the client
            # sees the vid location disappear
            for _ in range(200):
                if ar.url not in client.vid_map.lookup(vid):
                    break
                await asyncio.sleep(0.1)
            assert ar.url not in client.vid_map.lookup(vid)

            # the master's topology agrees
            assert all(
                n.url != ar.url for n in cluster.master.topo.data_nodes()
            )
        finally:
            await client.stop()
            await cluster.stop()

    asyncio.run(body())


def test_file_sequencer_survives_restart(tmp_path):
    """FileSequencer leases id windows ahead of use, so a restarted master
    never re-issues a file id (the etcd sequencer's durable role)."""
    from seaweedfs_tpu.sequence import FileSequencer

    path = str(tmp_path / "seq.dat")
    s1 = FileSequencer(path)
    first = s1.next_file_id(5)
    second = s1.next_file_id(3)
    assert second == first + 5

    # a fresh instance (simulating a crash WITHOUT clean shutdown) starts
    # past everything ever handed out
    s2 = FileSequencer(path)
    assert s2.next_file_id(1) > second + 2

    # set_max advances durably too
    s2.set_max(10_000_000)
    s3 = FileSequencer(path)
    assert s3.next_file_id(1) > 10_000_000


def test_drain_deltas_collapses_same_vid_churn(tmp_path):
    """Created+deleted within one pulse must not re-register as a ghost;
    an in-place layout change drains as deleted(old)+new(current)."""
    from seaweedfs_tpu.storage.store import Store

    s = Store("127.0.0.1", 0, "127.0.0.1:0", [str(tmp_path)], [10])
    s.load()

    # create + delete inside one tick -> vid must not appear as new
    v = s.add_volume(3, "", "000", "")
    s.delete_volume(3)
    d = s.drain_deltas()
    assert [int(m["id"]) for m in d["new_volumes"]] == []
    assert [int(m["id"]) for m in d["deleted_volumes"]] == [3]

    # layout change: deleted carries the ORIGINAL layout, new the latest
    v = s.add_volume(4, "", "000", "")
    s.drain_deltas()  # flush the create
    old_msg = s._volume_message(v)
    from seaweedfs_tpu.storage.super_block import (
        ReplicaPlacement,
        SuperBlock,
    )

    sb = v.super_block
    v.super_block = SuperBlock(
        version=sb.version,
        replica_placement=ReplicaPlacement.parse("001"),
        ttl=sb.ttl,
        compaction_revision=sb.compaction_revision,
        extra=sb.extra,
    )
    mid_msg = s._volume_message(v)
    s.note_volume_changed(old_msg, mid_msg)
    # a second change in the same tick: keep FIRST deleted, LAST new
    v.super_block = SuperBlock(
        version=sb.version,
        replica_placement=ReplicaPlacement.parse("010"),
        ttl=sb.ttl,
        compaction_revision=sb.compaction_revision,
        extra=sb.extra,
    )
    s.note_volume_changed(mid_msg, s._volume_message(v))
    d = s.drain_deltas()
    assert [m["replica_placement"] for m in d["deleted_volumes"]] == [0]
    assert [m["replica_placement"] for m in d["new_volumes"]] == [10]
