"""Distributed tracing plane (ISSUE 8 tentpole): context propagation,
flight recorder, tail-based sampling, batch-seam span links, and the
cluster e2e trace covering s3 -> filer -> lease -> upload-gate batch ->
volume append -> replica fan-out (PUT) and fanout -> volume read (GET)."""

import asyncio
import os

import aiohttp
import pytest

from seaweedfs_tpu.util import trace
from seaweedfs_tpu.util import faults

from test_cluster import free_port_pair


@pytest.fixture(autouse=True)
def _reset_recorder():
    trace.RECORDER.configure(enabled=True, sample=0.0)
    yield
    trace.RECORDER.configure(enabled=True, sample=0.01)
    faults.clear_plan()


# ---------------- wire format ----------------


def test_traceparent_roundtrip():
    ctx = trace.SpanCtx(trace._new_trace_id(), trace._new_span_id(), True)
    parsed = trace.parse_traceparent(trace.format_traceparent_bytes(ctx))
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id
    assert parsed.sampled
    ctx.sampled = False
    parsed = trace.parse_traceparent(trace.format_traceparent(ctx))
    assert not parsed.sampled


def test_traceparent_rejects_malformed():
    bad = [
        None,
        b"",
        b"garbage",
        b"00-" + b"z" * 32 + b"-" + b"1" * 16 + b"-01",  # non-hex
        b"00-" + b"0" * 32 + b"-" + b"1" * 16 + b"-01",  # zero trace id
        b"00-" + b"1" * 32 + b"-" + b"0" * 16 + b"-01",  # zero span id
        b"00x" + b"1" * 32 + b"-" + b"1" * 16 + b"-01",  # bad separators
    ]
    for raw in bad:
        assert trace.parse_traceparent(raw) is None, raw


# ---------------- sampling + recording ----------------


def test_unsampled_path_admits_nothing():
    rec = trace.RECORDER
    # sample=0, no parent: the serving-core shape is coin-then-begin;
    # the coin says no, nothing is created, nothing admitted
    for _ in range(100):
        assert not rec.head_sample()
        rec.note_root(0.001)
    assert rec.admitted == 0
    assert rec.spans() == []


def test_sampled_request_records_with_parent_edges():
    sp = trace.begin_request("s3:PUT", None, server="s3")
    with trace.span("filer.write_chunks", chunks=2) as child:
        assert trace.current().span_id == child.ctx.span_id
    sp.finish()
    spans = trace.RECORDER.spans()
    assert [s["name"] for s in spans] == ["filer.write_chunks", "s3:PUT"]
    assert spans[0]["parent"] == spans[1]["span"]
    assert spans[0]["trace"] == spans[1]["trace"]
    assert trace.RECORDER.admitted == 2
    assert trace.current() is None  # context restored


def test_unsampled_join_promoted_by_flag():
    parent = trace.SpanCtx(trace._new_trace_id(), trace._new_span_id(), False)
    sp = trace.begin_request("volume:GET", parent, server="volume")
    trace.flag(trace.FLAG_HEDGE)
    sp.finish()
    spans = trace.RECORDER.spans()
    assert len(spans) == 1
    assert spans[0]["flags"] == ["hedge"]
    assert spans[0]["tags"]["promoted"] == "flagged"
    assert trace.RECORDER.promoted_flagged == 1


def test_slow_root_promotion_past_live_p99():
    rec = trace.RECORDER
    rec.configure(sample=0.0, min_roots=100)
    for _ in range(512):
        rec.note_root(0.001)
    assert not rec.is_slow(0.001)
    assert rec.is_slow(0.1)  # two orders past the observed p99
    rec.promote_slow("volume:GET", 0.1, server="volume")
    spans = rec.spans()
    assert spans and spans[0]["tags"]["promoted"] == "slow"
    assert rec.admitted == rec.promoted_slow == 1


def test_batch_span_links_members():
    a = trace.SpanCtx(trace._new_trace_id(), trace._new_span_id(), True)
    b = trace.SpanCtx(trace._new_trace_id(), trace._new_span_id(), True)
    with trace.batch_span("gate.chunk_put", [a, b], batch=2):
        pass
    spans = trace.RECORDER.spans()
    assert len(spans) == 1
    s = spans[0]
    assert s["trace"] == "%032x" % a.trace_id  # adopts first member
    assert s["parent"] == "%016x" % a.span_id
    linked = {(l["trace"], l["span"]) for l in s["links"]}
    assert ("%032x" % b.trace_id, "%016x" % b.span_id) in linked
    assert s["tags"]["members"] == 2
    # no sampled members -> shared no-op, nothing recorded
    with trace.batch_span("gate.chunk_put", []):
        pass
    assert len(trace.RECORDER.spans()) == 1


def test_ring_is_bounded():
    rec = trace.RECORDER
    rec.configure(capacity=32)
    for i in range(100):
        ctx = trace.SpanCtx(trace._new_trace_id(), trace._new_span_id(), True)
        rec.record({"trace": "%032x" % ctx.trace_id, "span": "x%d" % i})
    assert len(rec.spans()) == 32
    assert rec.admitted == 100
    assert rec.dropped == 68
    rec.configure(capacity=4096)


# ---------------- cluster e2e ----------------


def _sampled_header() -> tuple[str, str]:
    ctx = trace.SpanCtx(trace._new_trace_id(), trace._new_span_id(), True)
    return trace.format_traceparent(ctx), "%032x" % ctx.trace_id


def test_e2e_s3_put_get_single_trace(tmp_path):
    """One traced S3 multi-chunk PUT then a GET through the hedged
    fan-out yields a single merged trace covering s3 -> filer ->
    lease -> upload-gate batch -> volume append -> replica fan-out
    (PUT) and fanout -> volume read (GET), with resolvable parent
    edges and the gate-batch span linked to a member of the trace;
    an injected-fault request is promoted even at sample=0."""
    from seaweedfs_tpu.pb.rpc import close_all_channels
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.s3.server import S3Server

    async def body():
        ms = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await ms.start()
        vss = []
        for i in range(2):
            d = tmp_path / f"vol{i}"
            d.mkdir(exist_ok=True)
            vs = VolumeServer(
                master=ms.address,
                directories=[str(d)],
                port=free_port_pair(),
                pulse_seconds=0.2,
                max_volume_counts=[10],
            )
            await vs.start()
            vss.append(vs)
        # replication 001 -> every chunk fans out to the second replica;
        # chunk_size 1KB -> a 3KB object is a MULTI-chunk upload whose
        # concurrent chunks coalesce in the upload gate
        fs = FilerServer(
            master=ms.address,
            port=free_port_pair(),
            chunk_size=1024,
            replication="001",
        )
        await fs.start()
        s3 = S3Server(fs, port=free_port_pair())
        await s3.start()
        try:
            for _ in range(100):
                if len(ms.topo.data_nodes()) == 2:
                    break
                await asyncio.sleep(0.1)

            payload = os.urandom(3000)
            async with aiohttp.ClientSession() as session:
                async with session.put(
                    f"http://{s3.address}/trace-bucket"
                ) as r:
                    assert r.status == 200
                # warm once untraced so volume growth / lease refill
                # noise stays out of the asserted trace
                async with session.put(
                    f"http://{s3.address}/trace-bucket/warm",
                    data=os.urandom(3000),
                ) as r:
                    assert r.status == 200

                put_header, put_tid = _sampled_header()
                async with session.put(
                    f"http://{s3.address}/trace-bucket/obj",
                    data=payload,
                    headers={"traceparent": put_header},
                ) as r:
                    assert r.status == 200

                get_header, get_tid = _sampled_header()
                async with session.get(
                    f"http://{s3.address}/trace-bucket/obj",
                    headers={"traceparent": get_header},
                ) as r:
                    assert r.status == 200
                    assert await r.read() == payload

                # ---- merged PUT trace (in-process cluster: one ring) ----
                put_spans = [
                    s for s in trace.RECORDER.spans()
                    if s["trace"] == put_tid
                ]
                names = {s["name"] for s in put_spans}
                for expected in (
                    "s3:PUT",            # gateway server span
                    "filer.write_chunks",  # filer chunking
                    "filer.lease",       # fid lease
                    "gate.chunk_put",    # upload-gate batch flush
                    "volume:POST",       # volume append
                    "volume.replicate",  # replica fan-out
                ):
                    assert expected in names, (expected, sorted(names))

                by_span = {s["span"]: s for s in put_spans}
                roots = []
                for s in put_spans:
                    parent = s.get("parent")
                    if parent is None or parent not in by_span:
                        roots.append(s)
                    # parent/child edges: every in-trace parent pointer
                    # resolves to a span of the SAME trace
                    if parent in by_span:
                        assert by_span[parent]["trace"] == put_tid
                # the only unresolvable parent is the client's root span
                # id (the test generated it; no server recorded it)
                assert all(
                    r.get("parent") is not None or r["name"] == "s3:PUT"
                    for r in roots
                )
                s3_put = next(s for s in put_spans if s["name"] == "s3:PUT")
                wc = next(
                    s for s in put_spans if s["name"] == "filer.write_chunks"
                )
                assert wc["parent"] == s3_put["span"]
                assert wc["tags"]["chunks"] >= 3

                # gate-batch span linked to member trace spans
                gate = next(
                    s for s in put_spans if s["name"] == "gate.chunk_put"
                )
                assert gate["links"], "gate flush span carries no links"
                member_ids = {l["span"] for l in gate["links"]}
                assert member_ids & set(by_span), (
                    "gate links do not reference spans of the trace"
                )
                # replica fan-out happened within this trace
                rep = next(
                    s for s in put_spans if s["name"] == "volume.replicate"
                )
                assert rep["tags"]["replicas"] >= 1

                # ---- GET trace: fanout -> volume read ----
                get_spans = [
                    s for s in trace.RECORDER.spans()
                    if s["trace"] == get_tid
                ]
                get_names = {s["name"] for s in get_spans}
                assert "s3:GET" in get_names, sorted(get_names)
                s3_get = next(
                    s for s in get_spans if s["name"] == "s3:GET"
                )
                vol_reads = [
                    s for s in get_spans if s["name"] == "volume:GET"
                ]
                assert vol_reads, sorted(get_names)
                # chunk reads ride the fan-out from inside the gateway
                # handler: each volume read parents to the s3 span
                assert any(
                    s["parent"] == s3_get["span"] for s in vol_reads
                )

                # ---- injected-fault promotion at sample=0 ----
                before = trace.RECORDER.promoted_fault
                plan = faults.FaultPlan(
                    seed=5,
                    rules=[
                        faults.FaultRule(
                            op="http:GET",
                            target=f"*:{vss[0].port}",
                            fault="http_error",
                            nth=1,
                        )
                    ],
                )
                faults.install_plan(plan)
                try:
                    # UNTRACED request (no header, sample=0)
                    async with aiohttp.ClientSession() as s2:
                        async with s2.get(
                            f"http://{vss[0].address}/1,unparseable"
                        ) as r:
                            assert r.status == 503
                finally:
                    faults.clear_plan()
                assert trace.RECORDER.promoted_fault == before + 1
                fault_spans = [
                    s for s in trace.RECORDER.spans()
                    if s.get("tags", {}).get("fault") == "http_error"
                ]
                assert fault_spans, "injected fault was not promoted"
        finally:
            await s3.stop()
            await fs.stop()
            for vs in vss:
                await vs.stop()
            await ms.stop()
            await close_all_channels()

    asyncio.run(body())


def test_grpc_seam_joins_trace(tmp_path):
    """A unary RPC made inside a sampled context records a server-side
    rpc: span joined to the caller's trace (metadata propagation)."""
    from seaweedfs_tpu.pb import grpc_address
    from seaweedfs_tpu.pb.rpc import Stub, close_all_channels
    from seaweedfs_tpu.server.master import MasterServer

    async def body():
        ms = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await ms.start()
        try:
            sp = trace.begin_request("client:op", None, server="test")
            tid = "%032x" % sp.ctx.trace_id
            await Stub(grpc_address(ms.address), "master").call(
                "VolumeList", {}
            )
            sp.finish()
            spans = [
                s for s in trace.RECORDER.spans() if s["trace"] == tid
            ]
            names = {s["name"] for s in spans}
            assert "rpc:VolumeList" in names, sorted(names)
            rpc_span = next(
                s for s in spans if s["name"] == "rpc:VolumeList"
            )
            assert rpc_span["parent"] == "%016x" % sp.ctx.span_id
        finally:
            await ms.stop()
            await close_all_channels()

    asyncio.run(body())


def test_group_commit_flush_links_members(tmp_path):
    """fsync'd writes through the group committer produce one flush span
    linked to the member traces that rode the batch."""
    from seaweedfs_tpu.storage.group_commit import GroupCommitWorker
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    async def body():
        v = Volume(str(tmp_path), "", 77, create=True)
        worker = GroupCommitWorker(v)
        worker.start()
        try:
            sp = trace.begin_request("client:PUT", None, server="test")
            tid = "%032x" % sp.ctx.trace_id
            await asyncio.gather(
                worker.write(Needle(id=1, cookie=1, data=b"a" * 64)),
                worker.write(Needle(id=2, cookie=1, data=b"b" * 64)),
            )
            sp.finish()
            # the committer resolves the writes from its executor thread
            # before the worker coroutine is back to close the flush span
            for _ in range(200):
                flushes = [
                    s for s in trace.RECORDER.spans()
                    if s["name"] == "group_commit.flush" and s["trace"] == tid
                ]
                if flushes:
                    break
                await asyncio.sleep(0.01)
            assert flushes, trace.RECORDER.spans()
            assert flushes[0]["links"]
            assert flushes[0]["tags"]["vid"] == 77
        finally:
            await worker.stop()
            v.close()

    asyncio.run(body())
