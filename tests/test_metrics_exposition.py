"""Metrics-plane satellites of ISSUE 8: strict exposition-format
validation of live /metrics renders, label-value escaping, idempotent
registry registration, the hygiene lint, and the on-demand pprof
round-trip over HTTP."""

import asyncio
import os

import aiohttp
import pytest

from seaweedfs_tpu.util import metrics as m
from seaweedfs_tpu.util import trace

from prom_text import ExpositionError, parse_exposition
from test_cluster import free_port_pair


# ---------------- satellite: label escaping ----------------


def test_label_value_escaping_renders_valid_exposition():
    c = m.REGISTRY.counter(
        "seaweedfs_tpu_test_escaping_total", "escaping test metric"
    )
    evil = 'a"b\\c\nd'
    c.inc(op=evil)
    text = m.REGISTRY.render()
    fams = parse_exposition(text)
    fam = fams["seaweedfs_tpu_test_escaping_total"]
    values = [labels["op"] for _n, labels, _v, _e in fam["samples"]]
    # the escaped wire form round-trips to the original value
    assert evil in values


def test_help_text_escaping():
    g = m.REGISTRY.gauge(
        "seaweedfs_tpu_test_help_escape", "line one\nline two \\ slash"
    )
    g.set(1.0)
    parse_exposition(m.REGISTRY.render())  # a raw newline would split lines


# ---------------- satellite: idempotent registry ----------------


def test_registry_registration_idempotent_and_collision_checked():
    a = m.REGISTRY.counter("seaweedfs_tpu_test_idem_total", "first")
    b = m.REGISTRY.counter("seaweedfs_tpu_test_idem_total", "second")
    assert a is b  # same kind: existing collector returned
    # the duplicate registration must not render the family twice
    text = m.REGISTRY.render()
    assert text.count("# TYPE seaweedfs_tpu_test_idem_total counter") == 1
    with pytest.raises(ValueError):
        m.REGISTRY.gauge("seaweedfs_tpu_test_idem_total")
    with pytest.raises(ValueError):
        m.REGISTRY.histogram("seaweedfs_tpu_test_idem_total")


def test_registry_histogram_bucket_mismatch_raises():
    """The idempotent return must not silently change bucket layout."""
    m.REGISTRY.histogram(
        "seaweedfs_tpu_test_buckets_seconds", "bucket test", buckets=[1, 2]
    )
    with pytest.raises(ValueError):
        m.REGISTRY.histogram(
            "seaweedfs_tpu_test_buckets_seconds", "bucket test",
            buckets=[1, 2, 4],
        )
    # same buckets (or unspecified) stays idempotent
    m.REGISTRY.histogram(
        "seaweedfs_tpu_test_buckets_seconds", "bucket test", buckets=[1, 2]
    )
    m.REGISTRY.histogram("seaweedfs_tpu_test_buckets_seconds")


def test_registry_self_check_renders_parseable():
    """Registry self-check: whatever is registered right now renders to
    text the strict parser accepts, with one family per name."""
    a = m.REGISTRY.histogram(
        "seaweedfs_tpu_test_selfcheck_seconds", "self check"
    )
    a.observe(0.002, stage="x")
    a.observe(5000.0, stage="x")  # above the last bucket -> +Inf only
    fams = parse_exposition(m.REGISTRY.render())
    names = [c.name for c in m.REGISTRY.collectors()]
    assert len(names) == len(set(names))
    assert "seaweedfs_tpu_test_selfcheck_seconds" in fams


# ---------------- satellite: hygiene lint ----------------


def _label_keys(metric) -> list:
    if metric.kind == "histogram":
        keys = metric._counts.keys()
    else:
        keys = metric._values.keys()
    return [tuple(k for k, _v in key) for key in keys]


def test_metrics_hygiene_lint():
    """Every registered metric is seaweedfs_tpu_-prefixed with non-empty
    help, and each family's children agree on their label-key set —
    cardinality/typo drift caught at test time."""
    problems = []
    for metric in m.REGISTRY.collectors():
        if not metric.name.startswith("seaweedfs_tpu_"):
            problems.append(f"{metric.name}: missing seaweedfs_tpu_ prefix")
        if not metric.help.strip():
            problems.append(f"{metric.name}: empty help text")
        keysets = set(_label_keys(metric))
        if len(keysets) > 1:
            problems.append(
                f"{metric.name}: inconsistent label keys {sorted(keysets)}"
            )
    assert not problems, "\n".join(problems)

    # the lint's coverage is only as good as registration at import time:
    # pin the lifecycle-plane families (ISSUE 10) so a refactor that
    # moves them out of util/metrics.py (and out of this lint's reach)
    # fails here instead of silently shrinking coverage
    names = {metric.name for metric in m.REGISTRY.collectors()}
    for family in (
        "seaweedfs_tpu_volume_heat",
        "seaweedfs_tpu_lifecycle_queue_depth",
        "seaweedfs_tpu_lifecycle_conversions_total",
    ):
        assert family in names, f"lifecycle family {family} not registered"
    # tenant QoS plane (ISSUE 12): pin the per-tenant families
    for family in (
        "seaweedfs_tpu_tenant_queue_depth",
        "seaweedfs_tpu_tenant_admitted_total",
        "seaweedfs_tpu_tenant_admitted_seconds",
        "seaweedfs_tpu_overload_shed_total",
    ):
        assert family in names, f"tenant family {family} not registered"
    # needle-index-at-scale plane (ISSUE 13): pin the lsm map families
    # (resident bound, run/compaction health, snapshot age, tail cost)
    for family in (
        "seaweedfs_tpu_needle_map_resident_bytes",
        "seaweedfs_tpu_needle_map_run_count",
        "seaweedfs_tpu_needle_map_snapshot_age_seconds",
        "seaweedfs_tpu_needle_map_tail_replay_entries_total",
    ):
        assert family in names, f"needle_map family {family} not registered"
    # cold-tier plane (ISSUE 14): pin the offload/recall/read-through
    # families (bytes by direction, per-holder recall walls, cache
    # economics) so they can never silently fall out of the exposition
    for family in (
        "seaweedfs_tpu_tier_offload_bytes_total",
        "seaweedfs_tpu_tier_recall_seconds",
        "seaweedfs_tpu_tier_remote_cache_hits_total",
        "seaweedfs_tpu_tier_remote_cache_misses_total",
    ):
        assert family in names, f"cold-tier family {family} not registered"
    # metadata scale-out plane (ISSUE 15): pin the sharded-store and
    # durable-feed families plus the orphan-sweep counter
    for family in (
        "seaweedfs_tpu_meta_shard_ops_total",
        "seaweedfs_tpu_meta_shard_count",
        "seaweedfs_tpu_meta_shard_rebalances_total",
        "seaweedfs_tpu_meta_shard_moved_entries_total",
        "seaweedfs_tpu_meta_feed_events_total",
        "seaweedfs_tpu_meta_feed_segment_count",
        "seaweedfs_tpu_meta_feed_cache_evictions_total",
        "seaweedfs_tpu_tier_orphans_swept_total",
    ):
        assert family in names, f"meta-plane family {family} not registered"
    # metadata device-kernel plane (ISSUE 18): pin the ragged arena
    # families (residency, dispatch/fallback economics, double-buffer
    # uploads, and the identity-check verdict counter)
    for family in (
        "seaweedfs_tpu_needle_map_device_resident_bytes",
        "seaweedfs_tpu_needle_map_device_segments",
        "seaweedfs_tpu_needle_map_device_dispatches_total",
        "seaweedfs_tpu_needle_map_device_probes_total",
        "seaweedfs_tpu_needle_map_device_fallbacks_total",
        "seaweedfs_tpu_needle_map_device_uploads_total",
        "seaweedfs_tpu_needle_map_device_identity_mismatch_total",
    ):
        assert family in names, f"device-kernel family {family} not registered"
    # host-stage attribution (ISSUE 26): the families the stage helper
    # (util/trace.Stage) writes and the benchmark's per-layer metrics read
    for family in (
        "seaweedfs_tpu_rs_dispatch_seconds_total",
        "seaweedfs_tpu_rs_dispatches_total",
        "seaweedfs_tpu_rs_dispatch_bytes_total",
        "seaweedfs_tpu_ec_encode_stage_seconds_total",
        "seaweedfs_tpu_ec_encode_stage_calls_total",
        "seaweedfs_tpu_ec_degraded_read_stage_seconds_total",
        "seaweedfs_tpu_request_wait_seconds_total",
        "seaweedfs_tpu_event_loop_lag_seconds_total",
        "seaweedfs_tpu_event_loop_lag_ticks_total",
        "seaweedfs_tpu_startup_seconds",
    ):
        assert family in names, f"stage family {family} not registered"
    # how often a degraded read had a holder to ask (ISSUE 29)
    assert "seaweedfs_tpu_ec_remote_attempts_total" in names
    # defined, never set or read: gone with ISSUE 26
    for family in (
        "seaweedfs_tpu_volumes",
        "seaweedfs_tpu_needle_map_device_evictions_total",
    ):
        assert family not in names, f"dead family {family} is back"


def test_tenant_label_cardinality_enforced_at_registry_seam():
    """Two seam guarantees, both order-independent:

    1. every live family minting a `tenant` label is registered in
       TENANT_LABELED_FAMILIES — the purge list the top-K policy
       retires through; a family outside it would accumulate unbounded
       tenant series on a million-principal box (and the retirement
       purge must actually remove series from every listed kind);
    2. the label mint itself (util/tenancy.TenantLabelPolicy) emits at
       most cap + 2 distinct values (top-K + other + default) no
       matter how many principals flood it."""
    from seaweedfs_tpu.util import tenancy

    listed = {f.name for f in m.TENANT_LABELED_FAMILIES}

    def label_pairs(key):
        # histogram exemplar keys are ((label pairs...), bucket_idx);
        # everything else is a plain tuple of (k, v) pairs — tolerate
        # both (and empty label sets) without assuming the shape
        if (
            len(key) == 2
            and isinstance(key[1], int)
            and isinstance(key[0], tuple)
        ):
            key = key[0]
        return [
            p for p in key if isinstance(p, tuple) and len(p) == 2
        ]

    problems = []
    for metric in m.REGISTRY.collectors():
        minted = False
        for d in metric._series_dicts():
            for key in d:
                if any(k == "tenant" for k, _v in label_pairs(key)):
                    minted = True
        if minted and metric.name not in listed:
            problems.append(
                f"{metric.name}: mints tenant labels but is not in "
                "TENANT_LABELED_FAMILIES (retirement purge would miss "
                "it — unbounded cardinality)"
            )
    assert not problems, "\n".join(problems)

    # hermetic flood through a fresh policy: the mint is the cap
    retired = []
    pol = tenancy.TenantLabelPolicy(cap=5, on_retire=retired.append)
    out = {pol.label(tenancy.DEFAULT_TENANT)}
    for i in range(500):
        name = f"lint-tenant-{i}"
        pol.note(name)
        out.add(pol.label(name))
    assert len(out) <= 5 + 2, sorted(out)

    # the purge hook removes series from EVERY registered family kind
    # (counter, gauge, histogram)
    m.TENANT_ADMITTED.inc(server="lint", tenant="lint-doomed")
    m.TENANT_QUEUE_DEPTH.set(
        1.0, server="lint", gate="g", tenant="lint-doomed"
    )
    m.TENANT_ADMITTED_SECONDS.observe(
        0.01, server="lint", tenant="lint-doomed"
    )
    tenancy._purge_retired("lint-doomed")
    for fam in m.TENANT_LABELED_FAMILIES:
        assert 'tenant="lint-doomed"' not in "\n".join(fam.render()), (
            fam.name
        )


# ---------------- acceptance: live-cluster exposition ----------------


def test_cluster_full_exposition_and_exemplars(tmp_path):
    """Write/read/scrub workload on a live 3-node cluster + filer + S3
    gateway, then the FULL /metrics render of all four server types must
    pass the strict parser, and histogram exemplars must reference
    trace_ids present in /debug/traces."""
    from seaweedfs_tpu.pb import grpc_address
    from seaweedfs_tpu.pb.rpc import Stub, close_all_channels
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.s3.server import S3Server

    async def body():
        trace.RECORDER.configure(enabled=True, sample=1.0)
        ms = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await ms.start()
        vss = []
        for i in range(3):
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
        fs = FilerServer(
            master=ms.address, port=free_port_pair(), chunk_size=1024
        )
        await fs.start()
        s3 = S3Server(fs, port=free_port_pair())
        await s3.start()
        try:
            for _ in range(100):
                if len(ms.topo.data_nodes()) == 3:
                    break
                await asyncio.sleep(0.1)

            async with aiohttp.ClientSession() as session:
                # --- workload: S3 writes/reads + a forced scrub pass ---
                async with session.put(
                    f"http://{s3.address}/expo-bucket"
                ) as r:
                    assert r.status == 200
                for i in range(6):
                    async with session.put(
                        f"http://{s3.address}/expo-bucket/obj{i}",
                        data=os.urandom(2500),
                    ) as r:
                        assert r.status == 200
                for i in range(6):
                    async with session.get(
                        f"http://{s3.address}/expo-bucket/obj{i}"
                    ) as r:
                        assert r.status == 200
                        await r.read()
                # forced scrub on every volume server (anti-entropy leg)
                for vs in vss:
                    r = await Stub(
                        grpc_address(vs.address), "volume"
                    ).call("VolumeScrub", {})
                    assert "error" not in r or not r["error"]

                # --- strict exposition from all four server types ---
                servers = {
                    "master": ms.address,
                    "volume": vss[0].address,
                    "filer": fs.address,
                    "s3": s3.address,
                }
                exemplar_ids = set()
                for kind, addr in servers.items():
                    # classic text/plain render: must parse AND must be
                    # exemplar-free (a stock Prometheus scraper rejects
                    # the whole exposition otherwise)
                    async with session.get(
                        f"http://{addr}/metrics"
                    ) as r:
                        assert r.status == 200, kind
                        plain = await r.text()
                    try:
                        pfams = parse_exposition(plain)
                    except ExpositionError as e:
                        raise AssertionError(f"{kind} /metrics: {e}")
                    assert any(
                        f.startswith("seaweedfs_tpu_") for f in pfams
                    ), kind
                    for fam in pfams.values():
                        for _n, _l, _v, ex in fam["samples"]:
                            assert ex is None, (kind, _n)
                    # negotiated OpenMetrics render: exemplars + # EOF
                    async with session.get(
                        f"http://{addr}/metrics",
                        headers={
                            "Accept": "application/openmetrics-text"
                        },
                    ) as r:
                        assert r.status == 200, kind
                        assert "openmetrics" in r.headers["Content-Type"]
                        text = await r.text()
                    assert text.endswith("# EOF\n"), kind
                    try:
                        fams = parse_exposition(text)
                    except ExpositionError as e:
                        raise AssertionError(f"{kind} /metrics(om): {e}")
                    for fam in fams.values():
                        for _n, _l, _v, ex in fam["samples"]:
                            if ex is not None:
                                tid = ex["labels"].get("trace_id")
                                assert tid and len(tid) == 32, ex
                                exemplar_ids.add(tid)
                # the sampled workload must have produced exemplars
                assert exemplar_ids

                # --- exemplars reference traces in /debug/traces ---
                async with session.get(
                    f"http://{vss[0].address}/debug/traces"
                ) as r:
                    assert r.status == 200
                    body_text = await r.text()
                import json as _json

                ring_ids = {
                    _json.loads(line)["trace"]
                    for line in body_text.splitlines()
                    if line
                }
                assert exemplar_ids & ring_ids, (
                    f"no exemplar trace_id found in the flight recorder "
                    f"({len(exemplar_ids)} exemplars, {len(ring_ids)} "
                    f"ring traces)"
                )
                # status endpoint sanity
                async with session.get(
                    f"http://{s3.address}/debug/traces?status=1"
                ) as r:
                    st = await r.json()
                    assert st["enabled"] and st["admitted"] > 0
        finally:
            await s3.stop()
            await fs.stop()
            for vs in vss:
                await vs.stop()
            await ms.stop()
            await close_all_channels()
            trace.RECORDER.configure(sample=0.01)

    asyncio.run(body())


# ---------------- satellite: on-demand pprof over HTTP ----------------


def test_pprof_start_stop_dump_roundtrip(tmp_path, monkeypatch):
    """The /debug/pprof handlers promised by util/profiling.py's
    docstring, wired onto ServingCore's shared middleware: start ->
    workload -> stop -> dump returns a cumulative-time report; the
    fixed-window and heap handlers answer too. The surface is opt-in
    (SEAWEEDFS_TPU_PPROF=1 / -pprof)."""
    from seaweedfs_tpu.pb.rpc import close_all_channels
    from seaweedfs_tpu.server.master import MasterServer

    monkeypatch.setenv("SEAWEEDFS_TPU_PPROF", "1")

    async def body():
        ms = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await ms.start()
        try:
            async with aiohttp.ClientSession() as session:
                base = f"http://{ms.address}/debug/pprof"
                async with session.get(f"{base}/start") as r:
                    assert r.status == 200
                # a second start must 409 (cProfile is process-global)
                async with session.get(f"{base}/start") as r:
                    assert r.status == 409
                for _ in range(5):
                    async with session.get(
                        f"http://{ms.address}/dir/status"
                    ) as r:
                        assert r.status == 200
                async with session.get(f"{base}/stop") as r:
                    assert r.status == 200
                async with session.get(f"{base}/dump") as r:
                    assert r.status == 200
                    report = await r.text()
                    assert "cumulative" in report
                async with session.get(f"{base}/profile?seconds=0.05") as r:
                    assert r.status in (200, 409)
                async with session.get(f"{base}/heap") as r:
                    assert r.status == 200
        finally:
            await ms.stop()
            await close_all_channels()

    asyncio.run(body())


def test_pprof_opt_in_default_off(tmp_path, monkeypatch):
    """The profiling surface is OFF by default (403) — a process-global
    slowdown reachable from the public port must be opted into
    (SEAWEEDFS_TPU_PPROF=1 or the volume -pprof flag), matching the
    pre-ServingCore volume posture."""
    from seaweedfs_tpu.pb.rpc import close_all_channels
    from seaweedfs_tpu.server.master import MasterServer

    monkeypatch.delenv("SEAWEEDFS_TPU_PPROF", raising=False)

    async def body():
        ms = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await ms.start()
        try:
            async with aiohttp.ClientSession() as session:
                async with session.get(
                    f"http://{ms.address}/debug/pprof/start"
                ) as r:
                    assert r.status == 403
                # /metrics and /debug/traces stay up
                async with session.get(
                    f"http://{ms.address}/metrics"
                ) as r:
                    assert r.status == 200
        finally:
            await ms.stop()
            await close_all_channels()

    asyncio.run(body())
