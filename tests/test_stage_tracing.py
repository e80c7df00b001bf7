"""Host-stage attribution (ISSUE 26): the one stage helper
(`util/trace.Stage`) and its sites — the RS dispatch, the encode pipeline,
the degraded read, the fast tier — write to `/metrics`, to the flight
recorder and to a profiler trace, and the benchmark's new per-layer metric
files read what they wrote.

Everything here runs on the CPU: the `tpu` codec's jax path stands in for
the device (`device_emulated`), so the counts and the names are checked,
never a time."""

import asyncio
import glob
import json
import os

import aiohttp
import numpy as np
import pytest

from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
from seaweedfs_tpu.storage.erasure_coding import encoder as enc
from seaweedfs_tpu.util import metrics as m
from seaweedfs_tpu.util import trace

from benchmarks.lib import common, metrics as layer_metrics, trace_reduce
from benchmarks.lib.server import parse_prom, sum_metric
from test_cluster import Cluster, assign_retry

RS_SECONDS = "seaweedfs_tpu_rs_dispatch_seconds_total"
RS_BYTES = "seaweedfs_tpu_rs_dispatch_bytes_total"
ENCODE_SECONDS = "seaweedfs_tpu_ec_encode_stage_seconds_total"
READ_SECONDS = "seaweedfs_tpu_ec_degraded_read_stage_seconds_total"


def scrape() -> dict:
    """What /metrics says now, as the benchmark reads it."""
    return parse_prom(m.REGISTRY.render())


def moved(before: dict, after: dict, family: str, **labels) -> float:
    return sum_metric(after, family, **labels) - sum_metric(
        before, family, **labels
    )


def emulated_codec(**kw) -> TpuRSCodec:
    """The `tpu` codec with its jax path in the pipeline's kernel stage
    (no native stand-in), so the `rs.*` stages run without a chip."""
    codec = TpuRSCodec(**kw)
    codec._standin = codec
    return codec


def small_encode(tmp_path, codec, name="v", size=(3 << 20) + 123):
    base = str(tmp_path / name)
    data = np.random.default_rng(26).integers(0, 256, size, dtype=np.uint8)
    with open(base + ".dat", "wb") as f:
        f.write(data.tobytes())
    return base, dict(
        codec=codec, large_block_size=1 << 20, small_block_size=1 << 17,
        chunk=1 << 20,
    )


def host_event_names(trace_dir: str) -> list:
    """The name of every event on the host planes of a profiler trace."""
    assert glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return [
        name
        for plane, lines in trace_reduce.read_planes(trace_dir)
        if plane.startswith("/host:")
        for _line, events in lines
        for name, _start, _dur in events
    ]


# ------------------------------------------------------------- the helper
def _test_stage(name, **kw):
    seconds = m.REGISTRY.counter(
        "seaweedfs_tpu_test_stage_seconds_total", "stage helper test"
    )
    calls = m.REGISTRY.counter(
        "seaweedfs_tpu_test_stage_calls_total", "stage helper test"
    )
    return trace.stage(
        name, seconds.child(stage=name), calls.child(stage=name), **kw
    )


def test_stage_adds_seconds_and_calls_and_survives_an_exception():
    st = _test_stage("t.counts")
    before = scrape()
    with st():
        sum(range(1000))
    with pytest.raises(KeyError):
        with st():
            raise KeyError("inside the block")
    after = scrape()
    assert moved(before, after, "seaweedfs_tpu_test_stage_calls_total",
                 stage="t.counts") == 2
    assert moved(before, after, "seaweedfs_tpu_test_stage_seconds_total",
                 stage="t.counts") > 0


def test_stage_since_adds_a_wait_that_ends_on_another_thread():
    import threading
    import time

    st = _test_stage("t.handoff", annotate=False)
    before = scrape()
    t0 = time.perf_counter()
    worker = threading.Thread(target=lambda: st.since(t0))
    worker.start()
    worker.join(10)
    assert not worker.is_alive()
    after = scrape()
    assert moved(before, after, "seaweedfs_tpu_test_stage_calls_total",
                 stage="t.handoff") == 1
    assert moved(before, after, "seaweedfs_tpu_test_stage_seconds_total",
                 stage="t.handoff") > 0


def test_stage_feeds_a_runs_own_sink_under_its_label():
    run = enc.EncodeRun()
    st = trace.stage("t.sink", label="read")
    with st(run):
        pass
    with st(run):
        pass
    assert set(run.stages()) == {"read_s"} and run.seconds("read") > 0


def test_stage_nests_under_a_sampled_root_and_is_silent_without_one():
    st = _test_stage("t.nested")
    rec = trace.RECORDER
    rec.configure(enabled=True, sample=0.0)
    try:
        with st():  # no context on this thread: nothing recorded
            pass
        assert rec.spans() == []
        root = trace.begin_request("volume:GET", None, server="volume")
        with st():
            pass
        root.finish()
        spans = {s["name"]: s for s in rec.spans()}
        assert set(spans) == {"volume:GET", "t.nested"}
        assert spans["t.nested"]["parent"] == spans["volume:GET"]["span"]
        assert spans["t.nested"]["trace"] == spans["volume:GET"]["trace"]
        # joined but unsampled (a caller's traceparent said 00): silent too
        unsampled = trace.begin_request(
            "volume:GET", trace.SpanCtx(1, 2, False), server="volume"
        )
        with st():
            pass
        unsampled.finish()
        assert len(rec.spans()) == 2
    finally:
        rec.configure()


# ------------------------------------------------------ the encode pipeline
def test_encode_moves_every_pipeline_stage_and_every_rs_encode_stage(tmp_path):
    codec = emulated_codec()
    base, kw = small_encode(tmp_path, codec)
    before = scrape()
    run = enc.write_ec_files(base, **kw)
    after = scrape()
    assert run.route["route"] == "pipeline"
    assert run.route["kernel"] == "device_emulated"
    for stage in ("splice", "read", "slot_wait", "submit", "kernel",
                  "parity_wait", "write", "write_thread", "sync"):
        assert moved(before, after, ENCODE_SECONDS, stage=stage) > 0, stage
    # as many writing threads as the files left to write and the CPUs allow
    n_files = 4 if run.route["spliced"] else 14
    assert run.route["writers"] == enc._stream_writers(
        n_files, run.route["pipeline_depth"]
    )
    for stage in ("pack", "put", "dispatch", "fetch"):
        assert moved(before, after, RS_SECONDS, op="encode", stage=stage) > 0, stage
    # the view back to bytes is no stage since ISSUE 37: in no /metrics line
    assert not [k for k in after if "unpack" in k]
    assert moved(before, after, RS_SECONDS, op="decode") == 0
    # 3 MiB + 123 B in 128 KiB blocks of 10: 3 rows, one dispatch each
    dispatches = moved(before, after, "seaweedfs_tpu_rs_dispatches_total",
                       op="encode", backend="device_emulated")
    assert dispatches == 3
    assert moved(before, after, RS_BYTES, op="encode", kind="real") == 3 * 14 * (1 << 17)
    assert moved(before, after, RS_BYTES, op="encode", kind="padded") == 0
    # the run's own budget keeps its keys
    stages = run.stages()
    for key in ("read_s", "stage_s", "kernel_s", "write_s", "sync_s",
                "total_s", "pipeline_depth", "coverage_of_wall", "ecx_s"):
        assert key in stages, key
    assert stages["stage_s"] == stages["slot_wait_s"] + stages["submit_s"]
    # the counters got what the run's own budget got
    assert abs(moved(before, after, ENCODE_SECONDS, stage="kernel")
               - stages["kernel_s"]) < 1e-6


@pytest.mark.parametrize("writers", [1, 3])
def test_write_thread_over_write_is_the_threads_writing_at_once(
    tmp_path, monkeypatch, writers
):
    """`write` is the ordering thread's wall round a chunk's shard writes,
    `write_thread` what every writing thread spent inside its own: with
    one writer the second lies inside the first, with several it adds up
    to at least as much (1 MiB writes, so the hand-out is small beside
    them)."""
    monkeypatch.setattr(
        "seaweedfs_tpu.util.available_cpus", lambda: 1 + 2 + writers
    )
    base, kw = small_encode(tmp_path, emulated_codec(), size=(40 << 20) + 123)
    before = scrape()
    run = enc.write_ec_files(base, splice_data=False, **kw)
    after = scrape()
    assert run.route["writers"] == writers and not run.route["spliced"]
    wall = moved(before, after, ENCODE_SECONDS, stage="write")
    in_threads = moved(before, after, ENCODE_SECONDS, stage="write_thread")
    assert abs(in_threads - run.seconds("write_thread")) < 1e-6
    # 4 rows of 1 MiB blocks and a 128 KiB row for the tail: 5 chunks, and
    # in each every thread writes its data files, then its parity files
    calls = "seaweedfs_tpu_ec_encode_stage_calls_total"
    assert moved(before, after, calls, stage="write_thread") == 5 * 2 * writers
    assert moved(before, after, calls, stage="write") == 5 * 2
    if writers == 1:
        assert 0 < in_threads <= wall
    else:
        assert in_threads >= wall * 0.9, (in_threads, wall)


def test_a_host_codec_takes_the_same_route_and_keeps_its_keys(tmp_path):
    """The numpy codec's run is the pipeline's: the route dictionary's six
    keys, `write_s` under its own name, and no module-level copy of either
    to read it from (ISSUE 30)."""
    from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec

    base, kw = small_encode(tmp_path, CpuRSCodec(), size=300_000)
    before = scrape()
    run = enc.write_ec_files(base, splice_data=False, **kw)
    after = scrape()
    assert run.route == {
        "route": "pipeline", "spliced": False, "input": "mmap",
        "kernel": "host", "pipeline_depth": 2,
        "writers": enc._stream_writers(14, 2),
    }
    assert {"read_s", "stage_s", "kernel_s", "write_s", "write_thread_s",
            "sync_s", "total_s", "ecx_s"} <= set(run.stages())
    assert "shard_write_s" not in run.stages()
    assert not hasattr(enc, "LAST_STAGES") and not hasattr(enc, "LAST_ROUTE")
    # a host codec's dispatches are no rs.* stage and no device's bytes
    assert moved(before, after, RS_SECONDS, op="encode") == 0
    assert moved(before, after, ENCODE_SECONDS, stage="kernel") > 0


def test_two_encodes_in_flight_keep_their_own_route(tmp_path):
    """What `volume_ec` labels `ec_encoded_bytes_total` from: the run's own
    route, not the module's last one."""
    import threading

    from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec

    a, kw_a = small_encode(tmp_path, emulated_codec(), "a")
    b, kw_b = small_encode(tmp_path, CpuRSCodec(), "b", size=300_000)
    runs = {}

    def go(name, base, kw):
        runs[name] = enc.write_ec_files(base, **kw)

    threads = [threading.Thread(target=go, args=("a", a, kw_a)),
               threading.Thread(target=go, args=("b", b, kw_b))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert runs["a"].route["kernel"] == "device_emulated"
    assert runs["b"].route["kernel"] == "host"
    assert runs["a"] is not runs["b"]


# ------------------------------------------------------------ RS dispatch
@pytest.mark.parametrize(
    "width,padded_width",
    [(128 << 10, 256 << 10), (256 << 10, 256 << 10), (4096, 256 << 10)],
)
def test_decode_bytes_real_and_padded_to_the_kernels_granule(width, padded_width):
    """A 128 KiB x 10 decode (a degraded read's span) is padded to the
    Pallas kernel's 256 KiB granule: as many bytes of padding as of work."""
    codec = TpuRSCodec(force_pallas=True, interpret=True)
    rng = np.random.default_rng(width)
    data = rng.integers(0, 256, (10, width), dtype=np.uint8)
    full = np.concatenate([data, codec.encode(data)])
    shards = [full[i] if i not in (3, 11, 12, 13) else None for i in range(14)]
    before = scrape()
    rows = codec.reconstruct_rows(shards, [3])
    after = scrape()
    assert np.array_equal(rows[0], data[3])
    labels = dict(op="decode", backend="device_emulated")
    assert moved(before, after, RS_BYTES, kind="real", **labels) == 11 * width
    assert moved(before, after, RS_BYTES, kind="padded", **labels) == 11 * (
        padded_width - width
    )
    assert moved(before, after, "seaweedfs_tpu_rs_dispatches_total", **labels) == 1
    for stage in ("stack", "pack", "put", "dispatch", "fetch"):
        assert moved(before, after, RS_SECONDS, op="decode", stage=stage) > 0, stage


def test_the_host_standin_counts_under_its_own_backend():
    codec = TpuRSCodec()
    if codec.pipeline_dispatch_kind != "host_standin":
        pytest.skip("no native codec here: the jax path is the stand-in")
    data = np.random.default_rng(3).integers(0, 256, (10, 8192), dtype=np.uint8)
    before = scrape()
    parity = codec.pipeline_encode(data)
    after = scrape()
    assert np.array_equal(parity, emulated_codec().encode(data))
    labels = dict(op="encode", backend="host_standin")
    assert moved(before, after, "seaweedfs_tpu_rs_dispatches_total", **labels) == 1
    assert moved(before, after, RS_BYTES, kind="real", **labels) == 14 * 8192
    assert moved(before, after, RS_BYTES, kind="padded", **labels) == 0


# ------------------------------------------ the profiler's trace, on the CPU
def test_stages_are_events_of_a_profiler_trace_and_nothing_encloses_them(tmp_path):
    """`TraceAnnotation`s are TraceMe events, not Python-tracer events: they
    are in the trace with `python_tracer_level = 0`, as the benchmark's
    launcher takes it."""
    import jax

    codec = emulated_codec()
    base, kw = small_encode(tmp_path, codec)
    enc.write_ec_files(base, **kw)  # compiles outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        enc.write_ec_files(base, **kw)
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    host_lines = [
        events for plane, lines in trace_reduce.read_planes(trace_dir)
        if plane.startswith("/host:") for _line, events in lines
    ]
    names = {name for events in host_lines for name, _s, _d in events}
    for want in ("rs.fetch", "rs.pack", "rs.put", "rs.dispatch",
                 "ec.encode.write", "ec.encode.read", "ec.encode.sync"):
        assert want in names, (want, sorted(names))
    assert "rs.unpack" not in names  # out with ISSUE 37: nothing read it
    # waits are counters only, and nothing is drawn around the lot
    assert not {"ec.encode.slot_wait", "ec.encode.parity_wait",
                "ec.encode.kernel", "ec.encode.sync_drain"} & names
    for events in host_lines:  # one line is one thread
        for name, start, dur in events:
            if name not in ("rs.fetch", "ec.encode.write"):
                continue
            around = [
                n for n, s, d in events
                if s <= start and s + d >= start + dur and (n, s, d) != (name, start, dur)
            ]
            assert around == [], (name, around)


def test_an_idle_gap_is_named_by_the_programs_stage_not_the_runtimes_event():
    """`trace_reduce` names a gap by the host event that overlaps it most:
    `rs.fetch` is a hair longer than the runtime's `np.asarray(jax.Array)`
    inside it, and so takes its place."""
    kernel = "%_gf_matmul_pallas.1 = u32[4,2048,128]{2,1,0} custom-call("
    planes = [
        ("/device:TPU:0", [("XLA Ops", [(kernel, 0, 50_000), (kernel, 20_050_000, 50_000)])]),
        ("/host:CPU", [
            ("python", [
                ("rs.fetch", 40_000, 20_001_000),
                ("np.asarray(jax.Array)", 40_500, 20_000_000),
                ("rs.unpack", 20_041_500, 5_000),
            ]),
            ("python", [("ec.encode.write", 10_000, 3_000_000)]),
        ]),
    ]
    reduced = trace_reduce.reduce_planes(planes)
    assert reduced["gaps"] == [["rs.fetch", 0.02]]
    # without the program's stage the runtime's event names the gap, as before
    planes[1][1][0] = ("python", planes[1][1][0][1][1:])
    assert trace_reduce.reduce_planes(planes)["gaps"][0][0] == "np.asarray(jax.Array)"


# -------------------- a degraded GET through the fast tier, and the metrics
NEW_METRICS = {
    "warm-rs10.4.ec-encode": [
        "ec_pipeline.read_s_per_gb", "ec_pipeline.slot_wait_s_per_gb",
        "ec_pipeline.write_s_per_gb", "rs_dispatch.encode_pack_s_per_gb",
        "rs_dispatch.encode_submit_s_per_gb", "rs_dispatch.encode_fetch_s_per_gb",
    ],
    "warm-rs10.4.degraded-get-c16": [
        "http.request_wait_ms", "http.request_service_ms", "http.loop_lag_ms",
        "ec_read.survivor_read_ms", "ec_read.executor_wait_ms", "ec_read.decode_ms",
        "ec_read.decode_padding_share", "ec_read.device_decode_share",
    ],
}
# ISSUE 27's, appended to BENCHMARK.json after all of the above
WRITER_METRICS = {
    "warm-rs10.4.ec-encode": ["ec_pipeline.write_parallelism"],
}
# ISSUE 28's: the batch cell reads the encode cell's metrics too (its name is
# appended to their lists), and two of its own come after ISSUE 27's
BATCH_CELL = "warm-rs10.4-maint.ec-encode-full4"
BATCH_METRICS = ["ec_batch.volumes_per_dispatch", "ec_batch.generate_share"]
# ISSUE 29's, last in BENCHMARK.json: how often a degraded read found nobody to ask
NO_HOLDER_METRICS = {
    "warm-rs10.4.degraded-get-c16": ["ec_read.no_holder_skip_share"],
}
# ISSUE 31's, last in BENCHMARK.json: how many GETs the fast tier still replays
PROXIED_METRICS = {
    "warm-rs10.4.degraded-get-c16": ["http.proxied_share"],
}
# ISSUE 33's: the spread cell reads the degraded cell's metrics too (its name
# is appended to their lists), and four of its own come last in BENCHMARK.json,
# then ISSUE 34's one: the streams those survivors rode
SPREAD_CELL = "warm-rs10.4-spread4.server-lost-get-c16"
SPREAD_METRICS = [
    "ec_read.remote_read_ms", "ec_read.remote_survivors_per_reconstruct",
    "ec_read.remote_kb_per_get", "peers.cpu_cores",
    "ec_read.remote_streams_per_reconstruct",
]
# ISSUE 35's: the chunk cell reads the degraded cell's metrics too (its name
# is appended to their lists, after the spread cell's), and seven of its own
# come last in BENCHMARK.json (tests/test_ec_chunk_read.py reads them)
CHUNK_CELL = "warm-rs10.4-filer4m.degraded-chunk-get-c16"
CHUNK_METRICS = [
    "http.body_mb_per_s", "ec_read.intervals_per_get",
    "ec_read.degraded_get_share", "ec_read.local_interval_ms",
    "ec_read.assemble_ms", "ec_read.survivor_mb_per_reconstruct",
    "rs_decode_roofline",
]
# ISSUE 36's one, last: read in the three GET cells (tests/test_ec_chunk_read.py
# and tests/test_ec_spread_read.py evaluate it)
WORKER_READ_METRICS = ["ec_read.worker_read_share"]
# ISSUE 37's ten, last: the loop's own clock, the residues of a GET as stages,
# the stall recorder, the collector (tests/test_loop_clock.py evaluates them)
LOOP_METRICS = [
    "http.loop_cpu_share", "http.loop_held_share", "http.loop_turn_ms",
    "http.write_ms", "ec_read.loop_resume_ms", "ec_read.locate_ms",
    "ec_read.worker_cpu_share", "http.loop_stall_ms_per_s",
    "http.stall_kernel_share", "http.gc_pause_ms_per_s",
]
# ISSUE 38's one, last: read in the three GET cells (tests/test_ecx_mapping.py
# evaluates it)
MAPPING_METRICS = ["ec_read.mapped_locate_share"]
ALL_NEW_METRICS = [
    (cell, name)
    for cells in (NEW_METRICS, WRITER_METRICS, NO_HOLDER_METRICS, PROXIED_METRICS)
    for cell, names in cells.items()
    for name in names
]


async def _encode_lose_a_shard_and_get(tmp_path) -> tuple:
    """One volume server with the `tpu` codec (on the CPU, said outright by
    conftest's JAX_PLATFORMS=cpu): upload, ec.encode, drop shard 0, GET what
    lay on it. Returns /metrics before and after, and the bodies."""
    from seaweedfs_tpu.client.operation import upload_data
    from seaweedfs_tpu.pb import grpc_address
    from seaweedfs_tpu.pb.rpc import Stub
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.storage.file_id import format_needle_id_cookie
    from test_cluster import free_port_pair

    cluster = Cluster(tmp_path, n_volume_servers=0)
    cluster.master = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
    await cluster.master.start()
    (tmp_path / "vol").mkdir()
    vs = VolumeServer(
        master=cluster.master.address, directories=[str(tmp_path / "vol")],
        port=free_port_pair(), pulse_seconds=0.2, max_volume_counts=[20],
        codec_backend="tpu",
    )
    vs.codec._standin = vs.codec  # the jax path in the pipeline, as on a chip
    await vs.start()
    cluster.volume_servers.append(vs)
    try:
        for _ in range(100):
            if cluster.master.topo.data_nodes():
                break
            await asyncio.sleep(0.1)
        async with aiohttp.ClientSession() as session:
            # as the harness waits for the server: the one replayed request
            # that puts request_proxied_total on /metrics before the window
            async with session.get(f"http://{vs.address}/status") as resp:
                assert resp.status == 200
            before = scrape()
            first = await assign_retry(cluster.master.address)
            vid = int(first.fid.split(",")[0])
            rng = np.random.default_rng(7)
            wrote = {}
            for i in range(1, 5):
                fid = f"{vid},{format_needle_id_cookie(i, 0xEC2600 + i)}"
                wrote[fid] = rng.integers(0, 256, 3000 + i, dtype=np.uint8).tobytes()
                await upload_data(session, first.url, fid, wrote[fid])
            stub = Stub(grpc_address(vs.address), "volume")
            for rpc, req in (
                ("VolumeMarkReadonly", {"volume_id": vid}),
                ("VolumeEcShardsGenerate", {"volume_id": vid}),
                ("VolumeEcShardsMount", {"volume_id": vid, "shard_ids": list(range(14))}),
                ("VolumeUnmount", {"volume_id": vid}),
                ("VolumeEcShardsUnmount", {"volume_id": vid, "shard_ids": [0]}),
                ("VolumeEcShardsDelete", {"volume_id": vid, "shard_ids": [0]}),
            ):
                reply = await stub.call(rpc, req, timeout=120)
                assert not reply.get("error"), (rpc, reply)
            got = {}
            for fid in wrote:  # the first is cold, the rest hit its cached span
                async with session.get(f"http://{vs.address}/{fid}") as resp:
                    assert resp.status == 200, (resp.status, fid)
                    got[fid] = await resp.read()
            await asyncio.sleep(0.05)  # a few ticks of the loop-lag probe
        return before, scrape(), wrote, got
    finally:
        await cluster.stop()


@pytest.fixture(scope="module")
def degraded_get(tmp_path_factory):
    return asyncio.run(_encode_lose_a_shard_and_get(tmp_path_factory.mktemp("get")))


def test_degraded_get_moves_the_read_stages_and_the_fast_tiers_clocks(degraded_get):
    before, after, wrote, got = degraded_get
    assert got == wrote
    assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total", kind="cold") >= 1
    for stage in ("remote_attempts", "survivor_read", "executor_wait",
                  "decode", "cache_put"):
        assert moved(before, after, READ_SECONDS, stage=stage) > 0, stage
    volume_get = dict(server="volume", operation="GET")
    assert moved(before, after, "seaweedfs_tpu_request_seconds_count", **volume_get) >= len(wrote)
    assert moved(before, after, "seaweedfs_tpu_request_seconds_sum", **volume_get) > 0
    assert moved(before, after, "seaweedfs_tpu_request_wait_seconds_total", **volume_get) > 0
    assert moved(before, after, "seaweedfs_tpu_event_loop_lag_ticks_total", server="volume") >= 3
    # an EC read is the fast tier's own since ISSUE 31: ServingCore observes
    # its request_seconds, the read is a stage of the fast tier's reads, and
    # nothing is replayed against the aiohttp tier
    assert moved(before, after, "seaweedfs_tpu_request_seconds_count", **volume_get) == len(wrote)
    assert moved(before, after, "seaweedfs_tpu_read_stage_seconds_count", stage="ec_read") == len(wrote)
    assert moved(before, after, "seaweedfs_tpu_request_proxied_total", server="volume") == 0
    assert moved(before, after, "seaweedfs_tpu_request_proxy_seconds_total", server="volume") == 0
    # where the decode ran is on /metrics now: not on a device, here
    assert moved(before, after, "seaweedfs_tpu_rs_dispatches_total",
                 op="decode", backend="device_emulated") >= 1
    assert moved(before, after, "seaweedfs_tpu_rs_dispatches_total",
                 op="decode", backend="device") == 0
    # and the encode's bytes are labelled from its own run's route
    assert moved(before, after, "seaweedfs_tpu_ec_encoded_bytes_total",
                 backend="device_emulated") > 0
    assert set(m._STARTUP_AGES) >= {"store_load", "device", "index_build", "listening"}


def test_the_probe_stops_with_the_tier_it_measures(degraded_get):
    import time

    ticks = sum_metric(scrape(), "seaweedfs_tpu_event_loop_lag_ticks_total", server="volume")
    time.sleep(0.05)
    assert sum_metric(scrape(), "seaweedfs_tpu_event_loop_lag_ticks_total",
                      server="volume") == ticks


@pytest.mark.parametrize("cell,name", ALL_NEW_METRICS)
def test_each_new_metric_file_reads_the_recorded_counters(degraded_get, cell, name):
    """Every new per-layer metric, evaluated as a run evaluates it, against
    the /metrics pair the CPU run above recorded."""
    before, after, _wrote, _got = degraded_get
    spec = common.load("layer_metrics", name + ".json")
    entry = next(e for e in common.benchmark_json()["per_layer"] if e["name"] == name)
    also = [BATCH_CELL] if cell == "warm-rs10.4.ec-encode" else [SPREAD_CELL, CHUNK_CELL]
    assert entry["workloads"] == [cell] + also
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    seen = layer_metrics.Observed(before, after, {}, {}, {}, {}, None, None, {})
    value = seen.value(spec)
    assert value is not None, "a metric absent from the line is a defect"
    if name == "ec_read.device_decode_share":
        assert value == 0.0  # a CPU decoded: the share a chip run must read as 100
    elif name == "ec_read.decode_padding_share":
        assert value == 0.0  # the jax path pads to 4 bytes, the Pallas kernel to 256 KiB
    elif name == "ec_pipeline.write_parallelism":
        # what the writing threads spent inside their own writes, over the
        # ordering thread's `write` wall. That wall stops while the ordering
        # thread waits for parity and its helpers write on, so at this size
        # (one chunk, 14 writes of 1 MiB) the ratio is not held under the
        # number of writers (3.0-4.4 read here with 3). What holds at any
        # size: the file divides those two counters, and no thread wrote
        # outside the RPC that started it
        in_threads = moved(before, after, ENCODE_SECONDS, stage="write_thread")
        wall = moved(before, after, ENCODE_SECONDS, stage="write")
        rpc = moved(before, after, "seaweedfs_tpu_ec_generate_seconds_total", rpc="single")
        assert value == pytest.approx(in_threads / wall)
        assert 0 < in_threads <= enc._stream_writers(14, 2) * rpc
    elif name == "ec_read.no_holder_skip_share":
        # the lost shard has no holder. A GET that comes before the master's
        # heartbeat knows the EC volume gets an error for its lookup, so the
        # table is not fresh and it counts `failed`: the first of the four may
        assert value in (75.0, 100.0)
    elif name == "http.proxied_share":
        assert value == 0.0  # every GET here is an EC read: none was replayed
    else:
        assert value > 0, value
    # with nothing recorded the metric is absent, never 0
    assert layer_metrics.Observed({}, {}, {}, {}, {}, {}, None, None, {}).value(spec) is None


def test_benchmark_json_gained_entries_at_the_end_and_lost_none():
    names = [e["name"] for e in common.benchmark_json()["per_layer"]]
    new = [name for _cell, name in ALL_NEW_METRICS]
    new[-2:-2] = BATCH_METRICS  # before ISSUE 29's one and ISSUE 31's
    new += SPREAD_METRICS + CHUNK_METRICS + WORKER_READ_METRICS + LOOP_METRICS
    new += MAPPING_METRICS
    assert names[-len(new):] == new and len(names) == 13 + len(new)
    per_layer = {e["name"]: e for e in common.benchmark_json()["per_layer"]}
    for name in SPREAD_METRICS:  # the healthy cell has no remote survivor to read
        assert per_layer[name]["workloads"] == [SPREAD_CELL]
        spec = common.load("layer_metrics", name + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert per_layer[name][key] == spec[key], (name, key)
    for name in CHUNK_METRICS:  # read what the chunk deployment adds to the program
        assert per_layer[name]["workloads"] == [CHUNK_CELL]
        spec = common.load("layer_metrics", name + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert per_layer[name][key] == spec[key], (name, key)
    assert len(json.dumps(common.benchmark_json())) < 64 << 10
