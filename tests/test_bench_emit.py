"""The bench emission contract (VERDICT r4 item 1): the FINAL stdout line
must stay under the driver's 2,000-char tail capture no matter how many
metrics the bench grows, with the full record going to BENCH_DETAIL.json.
Round 4's official artifact was `parsed: null` because the one-line JSON
outgrew the window."""

import importlib.util
import json
import os
import signal
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LEG_DEADLINE_S = 120


@pytest.fixture(autouse=True)
def _leg_deadline():
    """A bench leg that never returns costs its own test, not the xdist
    worker and every test queued behind it: `serving.open_loop` has been
    seen waiting for ever in `asyncio.run` (seed tree included), which
    held the driver's whole run to its time limit."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def on_alarm(signum, frame):
        raise TimeoutError(f"bench leg still running after {LEG_DEADLINE_S} s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(LEG_DEADLINE_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench = _load_bench()


def _fat_headline(n_extra=14):
    """A headline the size r5's bench realistically produces: every metric
    carrying a fat detail dict, latency blocks, and long notes."""
    extra = []
    for i in range(n_extra):
        extra.append(
            {
                "metric": f"metric.number_{i}.with_long_name",
                "value": 123.456789,
                "unit": "GB/s",
                "vs_baseline": 17.42,
                "detail": {
                    "latency_ms": {"p50": 1.2, "p95": 3.4, "p99": 9.9},
                    "n_volumes": 64,
                    "host_cpus": 1,
                    "long_note_payload": "x" * 400,
                },
                "note": "a long explanatory note " * 10,
            }
        )
    extra.append({"metric": "broken.leg", "error": "E" * 500})
    extra.append({"metric": "skipped.leg", "skipped": "bench budget spent"})
    return {
        "metric": "ec.encode_throughput",
        "value": 65.241,
        "unit": "GB/s",
        "vs_baseline": 17.4,
        "device_status": "tpu",
        "extra": extra,
    }


def _run_emit(tmp_path, monkeypatch, headline):
    detail = tmp_path / "BENCH_DETAIL.json"
    # _emit_final writes next to bench.py; point it at tmp via __file__
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    # emission is once-per-process (watchdog vs normal completion); tests
    # emit repeatedly, so reset the latch
    bench._EMITTED = False
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        bench._emit_final(headline)
    lines = [l for l in buf.getvalue().splitlines() if l.strip()]
    return lines, detail


def test_final_line_fits_capture_window(tmp_path, monkeypatch):
    lines, detail = _run_emit(tmp_path, monkeypatch, _fat_headline())
    assert len(lines) == 1
    line = lines[-1]
    assert len(line.encode()) < 1900, len(line.encode())
    parsed = json.loads(line)
    assert parsed["metric"] == "ec.encode_throughput"
    assert parsed["device_status"] == "tpu"
    assert parsed["detail_file"] == "BENCH_DETAIL.json"
    # compact entries keep the comparison numbers, drop the prose
    by_name = {e.get("metric"): e for e in parsed["extra"]}
    m0 = by_name["metric.number_0.with_long_name"]
    assert m0["vs_baseline"] == 17.42
    assert "detail" not in m0 and "note" not in m0
    # errors survive, truncated
    assert len(by_name["broken.leg"]["error"]) <= 60


def test_detail_file_carries_everything(tmp_path, monkeypatch):
    head = _fat_headline()
    lines, detail = _run_emit(tmp_path, monkeypatch, head)
    full = json.loads(detail.read_text())
    assert full == head  # nothing lost


def test_pathological_width_still_fits(tmp_path, monkeypatch):
    """Even an absurd metric count degrades to a parseable <1.9KB line."""
    lines, _ = _run_emit(tmp_path, monkeypatch, _fat_headline(n_extra=60))
    line = lines[-1]
    assert len(line.encode()) < 1900
    parsed = json.loads(line)
    assert parsed.get("extra_truncated") is True
    assert parsed["value"] == 65.241  # headline always survives


def test_dict_valued_metric_compacts_to_numbers(tmp_path, monkeypatch):
    head = {
        "metric": "ec.encode_throughput",
        "value": 65.0,
        "unit": "GB/s",
        "vs_baseline": 17.0,
        "device_status": "cpu_standin",
        "extra": [
            {
                "metric": "ec.encode_throughput.geometries",
                "value": {"6.3": 95.23456, "12.4": 79.0, "note": "prose"},
                "unit": "GB/s",
            }
        ],
    }
    lines, _ = _run_emit(tmp_path, monkeypatch, head)
    parsed = json.loads(lines[-1])
    geo = parsed["extra"][0]["value"]
    assert geo == {"6.3": 95.235, "12.4": 79.0}  # numbers kept, prose gone


def test_emit_final_is_once_per_process(tmp_path, monkeypatch, capsys):
    """The watchdog and normal completion can both try to emit; exactly
    one final line may reach stdout."""
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    bench._EMITTED = False
    head = {"metric": "m", "value": 1, "unit": "x", "vs_baseline": 1,
            "extra": []}
    bench._emit_final(head)
    bench._emit_final({**head, "value": 2})
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    assert json.loads(lines[0])["value"] == 1


def test_multi_device_leg_self_invalidates_on_standin():
    """VERDICT §4: every device leg must carry a machine-readable
    valid flag that is False when a CPU stand-in produced the number."""
    import jax

    md = bench.measure_multi_device(
        n_volumes=2, shard_bytes=2048, k_lo=2, k_hi=4
    )
    assert "valid" in md
    assert md["valid"] == (jax.devices()[0].platform == "tpu")
    assert md["wide_gbps"] > 0  # still measured, just labeled


def test_lookup_gate_decomposition_self_invalidates_on_standin():
    import jax

    dec = bench.measure_lookup_gate_decomposition(
        n_entries=5000, batch_sizes=(64, 256)
    )
    on_tpu = jax.devices()[0].platform == "tpu"
    assert dec["valid"] == on_tpu
    if not on_tpu:
        # projections from stand-in kernel time must say so in the note
        assert "stand-in" in dec["note"]
    assert set(dec["projected_local_qps"]) == {"256"}
    assert dec["batches"][64]["t_e2e_ms"] > 0


def test_write_budget_unit_costs_standalone():
    """The budget's standalone mode (no serving sample) keeps emitting
    non-zero unit costs — the no-live-p50 degradation path."""
    wb = bench.measure_write_budget(serving=None)
    assert wb["component_sum_us"] > 0
    for key, val in wb["unit_costs_us"].items():
        assert val > 0, key
    assert "coverage_of_p50" not in wb


def test_vacuum_throughput_leg_shape():
    """ISSUE 5 guard: the vacuum.throughput leg must emit a non-zero stage
    breakdown, the executed route label, and the naive-baseline ratio —
    and the two shadow sets must be content-identical."""
    vt = bench.measure_vacuum_throughput(
        n_needles=1200, needle_bytes=1024, reps=1
    )
    assert vt["best_gbps"] > 0
    assert vt["naive_gbps"] > 0
    assert vt["vs_naive"] > 0  # the naive-baseline ratio is emitted
    assert vt["route"]["route"] in ("pread", "mmap")
    assert vt["route"]["records"] > 0
    stages = vt["stages"]
    assert stages["total_s"] > 0
    assert stages.get("write_s", 0) > 0
    assert vt["identical"] is True
    assert vt["live_bytes"] > 0


def test_serving_open_loop_leg_shape():
    """ISSUE 6 guard: the serving.open_loop leg must emit non-zero
    p50/p99/p999, achieved-vs-offered rate, a cache hit-rate field, and
    the cached-vs-uncached byte-identity verdict."""
    ol = bench.measure_serving_open_loop(
        num_files=400, rate=800, duration=1.5, brownout_leg=True
    )
    summ = ol["open_loop"]
    assert summ["p50_ms"] > 0
    assert summ["p99_ms"] > 0
    assert summ["p999_ms"] > 0
    assert summ["p50_ms"] <= summ["p99_ms"] <= summ["p999_ms"]
    assert ol["achieved_qps"] > 0
    assert summ["offered_qps"] > 0
    assert 0 < summ["achieved_over_offered"] <= 1.5
    assert "hit_rate" in ol["cache"]
    assert ol["cache"]["hit_rate"] > 0  # zipf skew must actually cache
    assert ol["cached_uncached_identical"] is True
    assert ol["open_loop"]["count"] > 0
    assert ol["inline_ping_qps"] > 0
    assert ol["achieved_over_ping"] > 0
    # the brownout sub-leg ran, injected faults, and published its tail
    assert ol["brownout"]["injected"] > 0
    assert ol["brownout"]["p999_ms"] >= ol["brownout"]["p99_ms"] > 0
    # replica fan-out carried the reads (single holder -> no hedges)
    assert ol["read_fanout"]["reads"] > 0


def test_serving_overload_leg_shape():
    """ISSUE 9 guard: the serving.overload leg must emit a goodput vs
    the single-rate ceiling, a bounded server-side admitted p99 ratio,
    real shed decisions counted by (class, reason), a µs-scale shed
    path, and the brownout-recovery sub-leg's per-second goodput
    buckets. Small/short shapes: the guard checks structure and sanity
    bounds, the real acceptance numbers come from the full bench run."""
    # deeper-than-default pool so the server-loop backlog reliably
    # crosses the measured queue budget at this tiny shape; bounded
    # re-runs absorb shared-host noise where the 1x leg's p99 (which
    # SETS the budget) got inflated enough that 3x never backlogs past
    # it — the assertions themselves stay strict
    for _attempt in range(3):
        ov = bench.measure_serving_overload(
            num_files=120,
            base_duration=1.2,
            duration=2.0,
            recovery_duration=3.0,
            workers=96,
        )
        if "error" not in ov and ov["overload"]["shed_responses"] > 0:
            break
    assert "error" not in ov, ov.get("error")
    assert ov["admission_enabled"] is True
    assert ov["corpus_files"] > 0
    assert ov["inline_ping_qps"] > 0
    assert ov["closed_loop_read"]["qps"] > 0
    assert ov["read_budget_ms"] > 0
    ceiling, over = ov["ceiling"], ov["overload"]
    assert ceiling["goodput_qps"] > 0
    assert over["offered_qps"] >= 2.5 * ceiling["offered_qps"]
    # no congestion collapse: goodput at ~3x offered holds near the 1x
    # ceiling (generous floor here: tiny corpus + short windows swing)
    assert ov["goodput_over_ceiling"] >= 0.5
    # the admission plane actually engaged and counted its decisions
    assert over["shed_responses"] > 0
    assert over["shed_by_class_reason"], "sheds not counted by class/reason"
    assert all(
        "class=" in k and "reason=" in k
        for k in over["shed_by_class_reason"]
    )
    # server-side admitted p99 (wait + service) stays within the
    # budget-scaled bound; the refusal itself is microseconds
    assert over["admitted_server_p99_ms"] > 0
    assert ov["admitted_p99_over_ceiling_p99"] <= 8.0
    assert 0 < ov["shed_path_us"] < 50.0
    # client-observed shed RTT is disclosed whenever sheds happened
    assert over["shed_rtt"]["count"] == over["shed_responses"]
    # the limiter published its trajectory and the gate its stats
    assert over["limit_before"] > 0 and over["limit_after"] > 0
    assert over["gate"]["admitted_total"] > 0
    # brownout-recovery sub-leg: injected faults, per-second goodput
    # buckets, and a recovery verdict
    rec = ov["brownout_recovery"]
    assert rec["injected"] > 0
    assert len(rec["goodput_per_second"]) >= 3
    assert rec["recovered_goodput_qps"] > 0
    assert isinstance(rec["recovered"], bool)


def test_qos_fairness_leg_shape():
    """ISSUE 12 guard: the qos.fairness leg must run the solo and
    contended victim legs, quota-shed the aggressor's overage with
    reason=quota per tenant at µs cost, and disclose the server-side
    victim p99 ratio. Small/short shape: structure and loose sanity
    bounds here — the real acceptance (victim p99 <= 2x solo) comes
    from the full bench run."""
    for _attempt in range(3):
        qf = bench.measure_qos_fairness(
            num_files=100,
            object_bytes=64 << 10,
            solo_duration=1.0,
            duration=1.6,
            workers=64,
        )
        if (
            "error" not in qf
            and qf.get("quota_sheds", 0) > 0
            and qf.get("victim_p99_over_solo", 99.0) <= 8.0
        ):
            break
    assert "error" not in qf, qf.get("error")
    assert qf["admission_enabled"] is True
    assert qf["corpus_files"]["victim"] > 0
    assert qf["corpus_files"]["aggr"] > 0
    assert qf["fair_share_qps"] > 0
    assert qf["victim_p99_solo_ms"] > 0
    assert qf["victim_p99_contended_ms"] > 0
    assert qf["victim_p99_over_solo"] > 0
    # loose structural ceiling at this tiny noisy shape — a ~1ms solo
    # p99 makes the ratio a noise amplifier here; the acceptance 2.0 is
    # judged on the full-size leg where both sides are queue-dominated
    assert qf["victim_p99_over_solo"] <= 25.0
    # the aggressor's overage was refused as QUOTA sheds, per tenant
    assert qf["quota_sheds"] > 0
    assert any(
        "reason=quota" in k and "tenant=aggr" in k
        for k in qf["shed_by_class_reason_tenant"]
    ), qf["shed_by_class_reason_tenant"]
    assert 0 < qf["quota_shed_path_us"] < 50.0
    # both tenants actually got service
    assert qf["victim_contended"]["goodput_qps"] > 0
    assert qf["aggressor"]["goodput_qps"] > 0
    assert qf["gate_tenants"]["victim"]["admitted"] > 0
    assert qf["gate_tenants"]["aggr"]["shed"] > 0
    # client-RTT percentiles disclosed alongside the server-side score
    assert qf["victim_rtt_p99_solo_ms"] > 0


def test_multitenant_soak_leg_shape():
    """ISSUE 12 guard: the soak.multi_tenant leg must write keys for
    every tenant through BOTH tiers, verify reads byte-identical to
    each tenant's own corpus with ZERO violations, disclose a fairness
    ratio over the concurrent read window, and keep tenant label
    cardinality bounded. Tiny shape; the >= 1M-key acceptance number
    comes from the full bench run."""
    sk = bench.measure_multitenant_soak(
        total_keys=4000,
        tenants=4,
        s3_fraction=0.05,
        read_window=1.5,
        time_cap_s=150.0,
    )
    assert "error" not in sk, sk.get("error")
    assert sk["keys_written"] >= 4000 * 0.9
    assert sk["raw_keys_written"] > 0
    assert sk["s3_keys_written"] > 0
    assert sk["write_errors"] == 0
    assert sk["identity_violations"] == 0
    assert sk["raw_reads_verified"] > 0
    assert sk["s3_reads_verified"] > 0
    assert sk["read_goodput_qps"] > 0
    # every tenant read in the fairness window, ratio disclosed
    assert sk["fairness_ratio"] is not None
    assert 1.0 <= sk["fairness_ratio"] < 10.0
    assert len(sk["per_tenant_read_qps"]) == 4
    # bounded tenant label cardinality, disclosed from the live registry
    assert sk["tenant_label_cardinality"] <= 16 + 2
    assert sk["time_capped"] is False


def test_production_soak_leg_shape():
    """ISSUE 16 guard: a quick-budget soak.production run must stand up
    a REAL subprocess cluster (distinct PIDs per role), fire >= 2
    seeded process faults including >= 1 SIGKILL with recovery (new
    pid, data intact), finish with ZERO byte-identity violations, ZERO
    tenant-isolation violations, every maintenance queue drained, and
    a fault schedule that regenerates bit-identically from its seed.
    Goodput/p99 are disclosed SLO terms, not asserted at this scale."""
    pk = bench.measure_production_soak(
        total_keys=3000,
        tenants=4,
        volumes=2,
        filers=2,
        soak_window_s=7.0,
        fault_count=2,
        write_workers=4,
        batch=128,
        quiesce_timeout_s=30.0,
        time_cap_s=240.0,
    )
    assert "error" not in pk, pk.get("error")
    # real processes, one per role
    assert pk["distinct_pids"] is True
    assert len(pk["pids"]) >= 2 + 2 + 2  # master+blob, volumes, filers
    assert pk["keys_written"] >= 3000 * 0.9
    assert pk["s3_keys_written"] > 0
    # seeded chaos actually happened, with hard-kill recovery
    assert pk["process_faults_fired"] >= 2
    assert pk["sigkill_recovered"] is True
    assert pk["schedule_reproducible"] is True
    # SLO invariants that hold at ANY scale
    assert pk["identity_violations"] == 0
    assert pk["isolation_violations"] == 0
    assert pk["isolation_probes"] > 0
    assert pk["isolation_denied"] == pk["isolation_probes"]
    assert pk["queues_drained"] is True
    assert pk["post_chaos_reads_verified"] > 0
    assert pk["s3_reads_verified"] > 0
    # disclosed terms present and non-degenerate
    assert pk["goodput_qps"] > 0
    assert pk["fg_p99_ms"] > 0
    assert pk["soak"]["completed"] > 0
    assert pk["slo"]["goodput_floor"] > 0
    assert "pass" in pk["slo"]
    # bloom consultation tail disclosed from the volume processes
    assert pk["bloom"]["runs"] >= 1
    assert "filter_hit_rate" in pk["bloom"]
    assert pk["time_capped"] is False


def test_geo_soak_leg_shape():
    """ISSUE 19 guard: a quick-budget soak.geo run must stand up TWO real
    subprocess clusters (dc-a primary, dc-b second site tailing the
    meta-log), fire a seeded WAN partition INSIDE the second site's
    filer child (ground truth: the child's own faults_injected counter),
    keep every primary write succeeding through the cut, and converge
    after heal with ZERO lost / ZERO duplicated / ZERO byte-mismatched
    mutations and no full resync. Lag p99 must be non-zero (the
    histogram actually recorded applies) and the partition sub-leg must
    be disclosed in the output."""
    gk = bench.measure_geo_soak(
        pre_files=6,
        during_files=8,
        post_files=3,
        partition_start_s=8.0,
        partition_duration_s=6.0,
        time_cap_s=150.0,
    )
    assert "error" not in gk, gk.get("error")
    # two real clusters, one process per role
    assert len(gk["pids"]["A"]) >= 3 and len(gk["pids"]["B"]) >= 3
    assert gk["files_written"] == 6 + 8 + 3
    # primary writes NEVER failed, including through the cut
    assert gk["write_failures"] == 0
    # zero-loss / zero-dup, byte-verified through the peer
    assert gk["missing_on_peer"] == 0
    assert gk["extra_on_peer"] == 0
    assert gk["byte_mismatches"] == 0
    assert gk["resync_required"] is False
    assert gk["drained"] is True
    # the partition sub-leg is disclosed AND actually happened in-child
    assert gk["partition"]["duration_s"] > 0
    assert gk["partition_faults_fired"] > 0
    assert gk["partition_observed"] is True
    # non-zero replication lag p99 from real applies
    assert gk["lag_p99_s"] > 0
    assert gk["applied"] >= gk["files_written"]
    assert "pass" in gk["slo"]
    assert gk["time_capped"] is False


def test_trace_overhead_leg_shape():
    """ISSUE 8 guard: the serving.trace_overhead leg must emit BOTH QPS
    numbers (tracing-off and tracing-on-at-1%) with their ratio, and the
    zero-alloc assertion must hold: across the tracing-on slices, ring
    admissions == sampled roots + tail promotions — admissions scale
    with the sampled count, never one per request."""
    to = bench.measure_trace_overhead(
        num_files=400, duration=2.0, rate=800
    )
    assert "error" not in to, to.get("error")
    assert to["qps_off"] > 0
    assert to["qps_on"] > 0
    # disclosed comparison: in-situ per-request overhead over measured
    # service time; the noisy macro ratio + per-mode CPU ride alongside
    assert 0.9 < to["on_over_off"] <= 1.0
    assert to["on_over_off_macro"] > 0
    assert to["overhead_us_per_request"] >= 0
    assert to["service_us_per_request"] > 0
    assert to["window_count"] >= 2
    assert to["cpu_us_per_request_off"] > 0
    assert to["cpu_us_per_request_on"] > 0
    # the on-windows really ran requests, and sampling stayed a fraction
    assert to["trace_requests"] > 0
    assert to["ring_admissions"] < to["trace_requests"] / 2
    assert to["admissions_equal_sampled"] is True
    assert 0 <= to["sampled_fraction"] < 0.2


def test_s3_gateway_leg_shape():
    """ISSUE 7 guard: the three s3.* legs must emit non-zero p50/p99,
    the PUT stage budget's components must be non-zero and sum to ~the
    measured avg/p50 latency, and the LIST leg must disclose a
    page-bounded scanned-entries-per-request number."""
    r = bench.measure_s3_gateway(
        num_objects=300, obj_bytes=512, list_keys=1500, max_keys=50,
        get_duration=1.2,
    )
    assert "error" not in r, r.get("error")
    # put leg
    assert r["put_qps"] > 0
    assert r["put_latency_ms"]["p50_ms"] > 0
    assert r["put_latency_ms"]["p99_ms"] >= r["put_latency_ms"]["p50_ms"]
    assert r["put_vs_raw"] > 0 and r["raw_put_qps"] > 0
    budget = r["s3_stage_budget"]
    for stage in ("auth", "meta", "lease", "upload", "render"):
        assert budget[f"{stage}_us"] > 0, stage
    # components partition the handler wall; the client p50 adds the
    # request hop on top, so coverage lands near (but under) 1.0
    assert 0.3 <= budget["coverage_of_p50"] <= 1.3, budget
    # get leg (open-loop summary)
    ol = r["get_open_loop"]
    assert r["get_qps"] > 0 and ol["p50_ms"] > 0
    assert ol["p50_ms"] <= ol["p99_ms"] <= ol["p999_ms"]
    assert r["get_vs_raw"] > 0 and r["raw_get_qps"] > 0
    assert r["gateway_direct_identical"] is True
    assert "hit_rate" in r["object_cache"]
    # list leg: latency, QPS, and the scan-work disclosure
    assert r["list_qps"] > 0
    assert r["list_latency_ms"]["p50_ms"] > 0
    assert r["list_latency_ms"]["p99_ms"] > 0
    assert r["list_scanned_per_request"] > 0
    assert r["list_scan_bounded"] is True
    # the bucket is 30x the page here; a full-bucket walker would scan
    # ~1500 entries per request
    assert r["list_scanned_per_request"] < r["list_keys"] / 4
    if r.get("list_full_walks"):
        assert r["list_walk_complete"] is True


def test_lifecycle_convergence_leg_shape():
    """ISSUE 10 guard: the lifecycle.convergence leg must complete
    non-zero auto-EC conversions UNDER the open-loop foreground read
    stream, disclose the foreground p99 with/without ratio, read every
    converted object back byte-identically, and drain the planner queue
    to 0. Small/short shape: structure and sanity bounds here, the real
    acceptance numbers (ratio <= 1.5x) come from the full bench run."""
    lc = bench.measure_lifecycle_convergence(
        n_cold_volumes=2,
        cold_files_per_volume=3,
        cold_file_bytes=32 * 1024,
        fg_files=200,
        window_s=1.2,
    )
    assert "error" not in lc, lc.get("error")
    assert lc["conversions_ec_ok"] > 0  # conversions actually ran
    assert lc["converted_all"] is True
    assert lc["byte_identical"] is True  # EC read-back == bytes written
    assert lc["lifecycle_queue_depth_end"] == 0
    # the contention ratio is disclosed, computed from two non-zero p99s
    assert lc["baseline"]["p99_ms"] > 0
    assert lc["with_conversions"]["p99_ms"] > 0
    assert lc["fg_p99_ratio"] > 0
    # conversion I/O was charged to the shared budget under its plane
    assert lc["maintenance"]["spent_bytes"].get("lifecycle", 0) > 0
    # the foreground stream genuinely ran in both windows
    assert lc["baseline"]["count"] > 0
    assert lc["with_conversions"]["count"] > 0


def test_cold_tier_leg_shape():
    """ISSUE 14 guard: the lifecycle.cold_tier leg must run the whole
    offload → remote-read → recall arc to completion under the open-loop
    foreground stream, disclose a non-zero recall p99 and a cache hit
    rate, read byte-identically at every stage, drain the planner queue,
    and charge the transfer I/O to plane=lifecycle on the shared budget.
    Small/short shape here; the acceptance ratio (fg p99 <= 1.5x) comes
    from the full bench run."""
    ct = bench.measure_cold_tier(
        n_cold_volumes=2,
        cold_files_per_volume=3,
        cold_file_bytes=32 * 1024,
        fg_files=200,
        window_s=1.2,
    )
    assert "error" not in ct, ct.get("error")
    # the arc genuinely completed, byte-identical at every stage
    assert ct["identity"]["ec"] is True
    assert ct["identity"]["offloaded"] is True
    assert ct["identity"]["offloaded_cached"] is True
    assert ct["identity"]["recalled"] is True
    assert ct["byte_identical"] is True
    # recall really happened and its latency is disclosed
    assert ct["recall_walls_s"], "no recall walls recorded"
    assert ct["recall_p99_ms"] > 0
    # the read-through cache served the repeat pass
    assert ct["cache_misses"] > 0
    assert ct["cache_hits"] > 0
    assert 0 < ct["cache_hit_rate"] <= 1
    # foreground stream ran in both windows; the ratio is disclosed
    assert ct["baseline"]["count"] > 0
    assert ct["with_cold_tier"]["count"] > 0
    assert ct["fg_p99_ratio"] > 0
    # planner drained; transfer bytes rode plane=lifecycle
    assert ct["lifecycle_queue_depth_end"] == 0
    assert ct["maintenance"]["spent_bytes"].get("lifecycle", 0) > 0


def test_needle_map_mount_leg_shape():
    """ISSUE 13 guard: the needle_map.mount leg must mount the same log
    both ways, disclose both walls + the speedup, the resident-byte
    story (lsm bounded below dict), the tail-replay count, and a
    byte-identical probe sample. Small shape here; the >=10x / >=2M
    acceptance numbers come from the full bench run."""
    r = bench.measure_needle_map_mount(
        n_keys=120_000, tail_entries=400, sample=800
    )
    assert r["total_entries"] > r["n_keys"]
    assert r["mount_dict_s"] > 0
    assert r["mount_lsm_s"] > 0
    assert r["mount_lsm_cold_s"] > 0
    assert r["loaded_from_snapshot"] is True
    assert r["tail_replayed"] == 400
    assert r["mount_speedup"] > 1.0  # lsm wins even at this tiny shape
    assert r["identical"] is True and r["probe_mismatches"] == 0
    assert r["file_counts_equal"] is True
    assert r["resident_dict_bytes"] > 0
    assert r["resident_lsm_bytes"] > 0
    assert r["resident_bounded_below_dict"] is True
    assert r["resident_ratio"] > 10.0  # the memory story is the point


def test_meta_lookup_qps_leg_shape():
    """ISSUE 15 guard: the meta.lookup_qps leg must drive the same zipf
    path stream against the single store (per-request) and the sharded
    store (gate-sized find_many batches), keep answers entry-identical,
    disclose the batching-only leg and scanned work, and show the
    sharded+gated plane beating the single-store baseline even at this
    small shape (the >=2x acceptance number comes from the full run)."""
    r = bench.measure_meta_lookup_qps(
        n_dirs=32, files_per_dir=24, probes=8_000, reps=2
    )
    assert r["identical"] is True and r["probe_mismatches"] == 0
    assert r["hot_share_top1pct"] > 0.3
    for leg in ("single_seq", "single_batched", "sharded_batched"):
        assert r[leg]["qps"] > 0
        assert r[leg]["p50_us"] <= r[leg]["p99_us"]
        assert r[leg]["store_calls_per_probe"] > 0
    # batching amortizes store calls; sharding keeps them amortized
    assert r["single_batched"]["store_calls_per_probe"] < 0.1
    assert r["qps_ratio_sharded_over_single"] > 1.0
    assert r["qps_ratio_batching_only"] > 1.0


def test_meta_feed_leg_shape():
    """ISSUE 15 guard: the meta.feed leg must replay through segment
    rotation (ring far smaller than the event count), deliver exactly
    the appended sequence to every subscriber, disclose lag p99, and
    resume a killed subscriber from its durable cursor with zero
    missed/duplicated events."""
    r = bench.measure_meta_feed(
        n_subscribers=3, events=1200, segment_events=256,
        ring_capacity=128,
    )
    assert r["exact"] is True
    assert r["segments"] > 1  # rotation really happened
    assert r["append_events_per_s"] > 0
    assert r["lag_p99_ms"] > 0
    assert len(r["lag_p99_ms_per_subscriber"]) == 3
    assert r["resume_exact"] is True
    assert r["resume_missed"] == 0 and r["resume_duplicated"] == 0


def test_needle_map_lookup_leg_shape():
    """ISSUE 13 guard: the needle_map.lookup leg must drive the same
    CO-corrected zipf open-loop stream against both maps, keep answers
    identical entry-wise, achieve its offered rate, and disclose a
    bounded p99 ratio (the read path stays flat)."""
    r = bench.measure_needle_map_lookup(
        n_keys=120_000, probes=30_000, rate=25_000.0
    )
    assert r["identical"] is True and r["probe_mismatches"] == 0
    assert r["hot_share_top1pct"] > 0.5  # the stream really is zipfian
    for leg in ("dict", "lsm"):
        assert r[leg]["p99_us"] > 0
        assert r[leg]["p50_us"] <= r[leg]["p99_us"] <= r[leg]["p999_us"]
        assert r[leg]["achieved_over_offered"] > 0.8
    assert 0 < r["p99_ratio_lsm_over_dict"] <= 12.0
    assert r["lsm_runs"] >= 1
    # ISSUE 15 satellite: per-run bloom filters disclosed on a
    # multi-run map probed with absent keys
    bl = r["bloom"]
    assert bl["runs"] > 1 and bl["runs_with_filter"] == bl["runs"]
    assert bl["filter_hit_rate"] > 0.9
    assert bl["absent_bloom"]["mean_us"] > 0
    assert bl["absent_nobloom"]["mean_us"] > 0
    # ISSUE 17 satellite: the consultation threshold and the per-run
    # consult/hit tail are disclosed (evidence for tuning
    # SEAWEEDFS_TPU_BLOOM_MIN_RUNS)
    assert bl["min_runs"] >= 1
    assert len(bl["per_run"]) == bl["runs"]
    assert all(pr["has_filter"] for pr in bl["per_run"])
    assert sum(pr["probes"] for pr in bl["per_run"]) > 0
    assert any(pr["negatives"] > 0 for pr in bl["per_run"])


def test_meta_fleet_leg_shape():
    """ISSUE 20 guard: the meta.fleet leg must stand up REAL filer
    fleets per process count, emit non-zero lookup/LIST capacity QPS
    for every count with the scaling ratios disclosed, keep every
    probe identity-checked (zero mismatches/errors), PROVE the
    capacity sum additive (forwarded counter 0 everywhere), and count
    the write seam's store rounds gate-on vs gate-off on the same
    burst. Small/short shape: structure + loose bounds here — the
    >=2.5x / >=4x acceptance numbers come from the full bench run."""
    r = bench.measure_meta_fleet(
        n_dirs=12, files_per_dir=8, lookups=500, lists=150,
        fleet_sizes=(1, 2), drivers=2, concurrency=8, put_burst=200,
    )
    assert r["identical"] is True
    assert r["coordination_free"] is True
    assert r["cpu_count"] >= 1
    assert set(r["per_fleet_size"]) == {"1", "2"}
    for n, v in r["per_fleet_size"].items():
        assert v["lookup_capacity_qps"] > 0, n
        assert v["list_capacity_qps"] > 0, n
        assert v["concurrent_lookup"]["qps"] > 0, n
        assert v["concurrent_list"]["qps"] > 0, n
        assert v["forwarded_during_probes"] == 0, n
        assert len(v["per_member_lookup"]) == int(n)
    # scaling ratios disclosed (acceptance thresholds judged full-size)
    assert r["lookup_qps_scaling"] > 0
    assert r["list_qps_scaling"] > 0
    assert r["concurrent_lookup_scaling"] > 0
    # write seam: rounds COUNTED (not projected) on both arms of the
    # same burst; per-entry pays at least one round per object while
    # the gated arm visibly coalesces even at this tiny shape
    assert r["burst_per_entry"]["write_rounds"] >= 200
    assert 0 < r["burst_gated"]["write_rounds"]
    assert r["write_rounds_ratio"] >= 2.0
    gs = r["burst_gated"]["write_gate"]
    assert gs["writes"] >= 200
    assert gs["largest_batch"] > 1
    assert gs["item_retries"] == 0


def test_needle_map_device_lookup_leg_shape():
    """ISSUE 18 guard: the needle_map.device_lookup leg must be a
    MEASURED end-to-end run through the real gate seam — non-zero
    pack/upload/dispatch/readback stage walls that partition the kernel
    wall, entry-wise identity asserted in-leg, the scraped batch-size
    distribution disclosed, and a device_status provenance label."""
    r = bench.measure_needle_map_device_lookup(
        n_volumes=2, entries_per_volume=9000, window_s=0.25,
        concurrency=192,
    )
    # stage walls: each stage really ran and together they partition the
    # kernel wall (python bookkeeping keeps coverage a bit under 1.0)
    st = r["kernel"]["stage_breakdown"]
    for k in ("pack_s", "upload_s", "dispatch_s", "readback_s"):
        assert st[k] > 0, k
    assert 0.7 <= st["coverage_of_wall"] <= 1.3
    assert r["kernel"]["dispatches"] > 0
    assert r["kernel"]["probes_per_s"] > 0
    # identity: every device batch identity-checked plus a dict-oracle
    # pass, zero mismatches anywhere
    ident = r["identity"]
    assert ident["checked_every_dispatch"] is True
    assert ident["device_batches_checked"] > 0
    assert ident["gate_mismatches"] == 0
    assert ident["oracle_checked"] > 0 and ident["oracle_mismatches"] == 0
    assert ident["ok"] is True
    # the scored window really routed through the arena backend
    assert r["device_gate"]["device_batches"] > 0
    assert r["host_gate"]["probes_per_s"] > 0
    assert r["overhead_x_p99"] > 0
    # scraped ragged batch-size distribution disclosed (drives the
    # kernel leg's dispatch shapes)
    assert r["batch_size_dist"] and sum(
        r["batch_size_dist"].values()
    ) > 0
    # provenance: stand-in runs must label the kernel number as such
    assert r["device_status"] in ("tpu", "cpu_standin", "cpu")
    if r["device_status"] != "tpu":
        assert r["kernel"]["standin"] is True
        assert "stand-in" in r["note"]
    assert r["runs_per_volume"] and all(
        c >= 1 for c in r["runs_per_volume"]
    )


def test_device_history_appends_per_emit(tmp_path, monkeypatch):
    """ISSUE 6 satellite: every bench emit appends {run, device_status}
    to DEVICE_HISTORY.jsonl so stand-in runs stop erasing the record of
    when the device was last reachable."""
    head = {
        "metric": "ec.encode_throughput", "value": 1.0, "unit": "GB/s",
        "vs_baseline": 1.0, "device_status": "tpu", "extra": [],
    }
    lines, _ = _run_emit(tmp_path, monkeypatch, dict(head))
    # ISSUE 17 satellite: legs that disclose their own device_status are
    # recorded PER LEG in the history entry (run-level status alone can't
    # say which executor each metric actually landed on)
    lines, _ = _run_emit(
        tmp_path, monkeypatch,
        {
            **head, "device_status": "cpu_standin", "value": 0.5,
            "extra": [
                {"metric": "ec.encode.e2e", "value": 1.2,
                 "device_status": "cpu_standin"},
                {"metric": "ec.encode.sharded", "value": 0.3,
                 "device_status": "cpu_standin"},
                {"metric": "kernel_mxu_bitslice",
                 "skipped": "no MXU on CPU stand-in",
                 "device_status": "cpu_standin"},
                {"metric": "no_status_leg", "value": 1.0},
            ],
        },
    )
    hist_path = tmp_path / "DEVICE_HISTORY.jsonl"
    entries = [
        json.loads(ln) for ln in hist_path.read_text().splitlines() if ln
    ]
    assert [e["run"] for e in entries] == [1, 2]
    assert [e["device_status"] for e in entries] == ["tpu", "cpu_standin"]
    assert "legs" not in entries[0]  # no leg disclosed a status
    assert entries[1]["legs"] == {
        "ec.encode.e2e": "cpu_standin",
        "ec.encode.sharded": "cpu_standin",
        "kernel_mxu_bitslice": "cpu_standin",
    }
    # the final line carries the pointer, not the (unbounded) history
    parsed = json.loads(lines[-1])
    assert parsed["device_history_file"] == "DEVICE_HISTORY.jsonl"
    assert "device_history" not in parsed
    # a torn line (watchdog kill mid-append) must not disable appends
    with open(hist_path, "a") as f:
        f.write('{"run": 3, "device_st')  # no newline, truncated JSON
    lines, _ = _run_emit(tmp_path, monkeypatch, dict(head))
    raw = [ln for ln in hist_path.read_text().splitlines() if ln.strip()]
    last = json.loads(raw[-1])
    assert last["run"] == len(raw)  # numbering survives the torn line
    assert last["device_status"] == "tpu"


def test_watchdog_emits_partial_and_exits(tmp_path):
    """A bench hung past its deadline must still produce a parseable final
    line (the r4 failure mode, one step worse): run a stub main that arms
    the watchdog then sleeps forever, in a subprocess."""
    import subprocess

    code = f"""
import sys, time
sys.path.insert(0, {REPO!r})
import importlib.util
spec = importlib.util.spec_from_file_location("bench", {os.path.join(REPO, "bench.py")!r})
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)
bench.__file__ = {str(tmp_path / "bench.py")!r}
partial = {{"metric": "ec.encode_throughput", "value": 1.5, "unit": "GB/s",
           "vs_baseline": 0.5, "device_status": "tpu", "extra": []}}
bench._arm_watchdog(0.5, partial)
time.sleep(60)  # simulated mid-run hang
"""
    # generous timeout: the child pays bench.py's cold imports, which can
    # take tens of seconds when this burst-throttled host is out of credit
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, timeout=180
    )
    assert r.returncode == 3
    line = r.stdout.decode().strip().splitlines()[-1]
    d = json.loads(line)
    assert d["value"] == 1.5
    assert any(e.get("metric") == "watchdog" for e in d["extra"])


def test_encode_e2e_entry_discloses_stage_budget(tmp_path):
    """ISSUE 17 tier-1 shape guard: the ec.encode.e2e entry must disclose
    non-zero per-stage walls whose blocking sum covers the wall (coverage
    in [0.7, 1.3]) plus a pipeline_depth label — so a future refactor
    can't silently ship an e2e number whose time is unaccounted for.

    Runs a real (small) streamed encode so the stage walls come from the
    shipping pipeline, then feeds the captured stages through
    _e2e_results the way measure_encode_e2e does."""
    import numpy as np

    from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
    from seaweedfs_tpu.storage.erasure_coding import encoder as enc

    rng = np.random.default_rng(17)
    base = str(tmp_path / "v_e2e")
    # non-chunk-aligned extent: final item exercises the staging tail
    data = rng.integers(0, 256, (4 << 20) + 12345, dtype=np.uint8)
    with open(base + ".dat", "wb") as f:
        f.write(data.tobytes())
    run = enc.write_ec_files(
        base, codec=TpuRSCodec(), large_block_size=1 << 20,
        small_block_size=1 << 17, chunk=1 << 20,
    )
    stages = run.stages()
    route = dict(run.route)
    assert route["route"] == "pipeline"

    entry = bench._e2e_results(
        {
            "ref_gbps": 0.34,
            "tpu_gbps": 1.2,
            "tpu_parity": True,
            "tpu_stages": stages,
            "tpu_route": route,
            "tpu_size_bytes": data.size,
            "device_status": "cpu_standin",
        }
    )[0]
    assert entry["metric"] == "ec.encode.e2e"
    bd = entry["stage_breakdown"]
    for wall in ("read_s", "stage_s", "kernel_s", "write_s", "sync_s"):
        assert bd[wall] > 0, (wall, bd)
    # blocking stages partition the wall; kernel_s/write_s are the
    # overlapped walls and deliberately excluded from the sum
    assert 0.7 <= entry["coverage_of_wall"] <= 1.3, bd
    assert entry["pipeline_depth"] >= 1
    assert entry["kernel_dispatch"] in (
        "device", "host_standin", "device_emulated",
    )
    assert entry["device_status"] == "cpu_standin"
