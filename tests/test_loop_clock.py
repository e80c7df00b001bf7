"""The serving loop read from inside (ISSUE 37): the loop's own clock
(`serving_core.LoopClock`, the selector of a loop the CLI makes), the stall
recorder (`LoopWatch`), the collector's pauses (`trace.watch_gc`), and the
ten per-layer metric files that read them.

Everything here runs on the CPU and checks shares with bounds wide enough
for a loaded host: a count of where a second went, never a device number."""

import asyncio
import gc
import signal
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec
from seaweedfs_tpu.server import serving_core as sc
from seaweedfs_tpu.util import trace

from benchmarks.lib import common, metrics as layer_metrics
from test_stage_tracing import LOOP_METRICS, get_cells_of, host_event_names, moved, scrape

LOOP = "seaweedfs_tpu_event_loop_"
DEADLINE_S = 30


@pytest.fixture(autouse=True)
def deadline():
    """A time limit of its own for every case here: a loop that never
    comes back costs one test."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def on_alarm(signum, frame):
        raise TimeoutError(f"still running after {DEADLINE_S} s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# ------------------------------------------------------- the loop's own clock
def loop_reading() -> dict:
    """The clock's families after a publish, on the running loop's thread."""
    sc.loop_watch(asyncio.get_running_loop()).clock.publish()
    page = scrape()
    return {
        "turn": page.get(LOOP + "turn_seconds_total", 0.0),
        "turns": page.get(LOOP + "turns_total", 0.0),
        "cpu": page.get(LOOP + "cpu_seconds_total", 0.0),
        "poll": page.get(LOOP + 'select_seconds_total{mode="poll"}', 0.0),
        "wait": page.get(LOOP + 'select_seconds_total{mode="wait"}', 0.0),
    }


async def spin(seconds: float) -> None:
    """A coroutine that runs pure Python and gives the loop a turn often."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        sum(range(2000))
        await asyncio.sleep(0)


def a_second_of(what: str) -> tuple:
    """(the clock's deltas over about half a second of `what` on a loop the
    factory made, the wall between the two readings)."""

    async def body():
        stop = threading.Event()
        holder = None
        if what == "held":
            def hold_the_lock():
                while not stop.is_set():
                    sum(range(1000))

            holder = threading.Thread(target=hold_the_lock, daemon=True)
            holder.start()
        t_before = time.perf_counter()
        before, t0 = loop_reading(), time.perf_counter()
        try:
            if what == "idle":
                await asyncio.sleep(0.5)
            else:
                await spin(0.5)
        finally:
            stop.set()
        t_after = time.perf_counter()
        after, wall = loop_reading(), time.perf_counter() - t0
        if holder is not None:
            holder.join(10)
            assert not holder.is_alive()
        d = {k: after[k] - before[k] for k in after}
        # a reading publishes the clock somewhere inside its own wall (the
        # render of the whole registry follows the publish): the deltas span
        # no less than the wall between the readings and no more than the
        # wall round both
        d["wall_least"], d["wall_most"] = t_after - t0, wall + t0 - t_before
        return d, wall

    return asyncio.run(body(), loop_factory=sc.new_event_loop)


def test_the_factory_makes_a_selector_loop_with_the_clock_for_selector():
    loop = sc.new_event_loop()
    try:
        assert isinstance(loop, asyncio.SelectorEventLoop)
        assert isinstance(sc.loop_watch(loop).clock, sc.LoopClock)
        # a loop somebody else made has a watch and no clock
        other = asyncio.new_event_loop()
        try:
            assert sc.loop_watch(other).clock is None
        finally:
            other.close()
    finally:
        loop.close()


@pytest.mark.parametrize("what", ["idle", "spin", "held"])
def test_turn_plus_select_is_the_loops_wall(what):
    d, wall = a_second_of(what)
    # to 2 % of the wall, whichever instant of its reading each publish fell on
    assert 0.98 * d["wall_least"] <= d["turn"] + d["poll"] + d["wait"] <= 1.02 * d["wall_most"]
    assert d["turns"] >= 1


def test_an_idle_loop_is_in_select_wait():
    d, wall = a_second_of("idle")
    assert d["wait"] >= 0.90 * wall
    assert d["poll"] + d["turn"] <= 0.10 * wall


def test_a_coroutine_spinning_python_reads_as_cpu():
    d, wall = a_second_of("spin")
    assert d["cpu"] >= 0.70 * wall
    assert d["wait"] <= 0.10 * wall
    assert d["turns"] > 100  # it gave the loop a turn after every 2,000 adds


def test_beside_a_thread_that_holds_the_interpreter_lock_the_loop_is_held_off():
    """`turn + select{poll} - cpu`: the loop had work and did not run. The
    other thread never sleeps, so every `select(0)` of the loop gives the
    lock up and waits a switch interval to have it back."""
    d, wall = a_second_of("held")
    held = d["turn"] + d["poll"] - d["cpu"]
    assert held >= 0.20 * wall, d
    assert d["poll"] >= 0.10 * wall, d  # the taking-back shows in the polls
    assert d["wait"] <= 0.10 * wall


def test_a_metrics_scrape_publishes_the_clock(tmp_path):
    """`/metrics` is rendered on the loop's thread: the render moves the
    selector's sums (and the thread's CPU) to the families first."""
    import aiohttp

    from seaweedfs_tpu.server.master import MasterServer
    from test_cluster import free_port_pair

    async def body():
        ms = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await ms.start()
        try:
            async with aiohttp.ClientSession() as session:
                pages = []
                for _ in range(2):
                    await spin(0.05)
                    async with session.get(f"http://{ms.address}/metrics") as r:
                        assert r.status == 200
                        from benchmarks.lib.server import parse_prom

                        pages.append(parse_prom(await r.text()))
            return pages
        finally:
            await ms.stop()

    first, second = asyncio.run(body(), loop_factory=sc.new_event_loop)
    assert moved(first, second, LOOP + "turn_seconds_total") > 0
    assert moved(first, second, LOOP + "turns_total") > 10
    assert moved(first, second, LOOP + "cpu_seconds_total") > 0.02


# ----------------------------------------------------------- the stall recorder
class FakeLoop:
    """A loop's clock and timer as the probe uses them, moved by hand."""

    def __init__(self):
        self.now = 1000.0
        self.timers = []

    def time(self):
        return self.now

    def call_at(self, when, callback):
        handle = type("Handle", (), {"cancel": lambda self: None})()
        self.timers.append((when, callback))
        return handle

    def run_timers_at(self, now: float) -> None:
        """Every armed timer runs at `now`, as after a stall of the loop."""
        self.now = now
        due, self.timers = self.timers, []
        for _when, callback in due:
            callback()


class FakeKernel:
    """The five sources, each a cumulative reading moved by hand."""

    def __init__(self, lacking=()):
        self.values = {
            "steal": {"steal_s": 5.0},
            "throttled": {"throttled_s": 1.0},
            "runqueue": {"runqueue_s": 2.0},
            "pressure": {"pressure_s": 3.0},
            "rusage": {"process_cpu_s": 50.0, "minflt": 100, "majflt": 0, "nivcsw": 7},
        }
        self.lacking = set(lacking)
        self.reads = 0

    def sources(self) -> dict:
        def source(name):
            def read():
                self.reads += 1
                if name in self.lacking:
                    raise FileNotFoundError(name)
                return dict(self.values[name])

            return read

        return {name: source(name) for name in self.values}


def two_probes_on(loop, watch):
    sc._WATCHES[loop] = watch
    probes = [sc.LoopLagProbe(name, lambda: True) for name in ("t37a", "t37b")]
    for probe in probes:
        probe.start(loop)
        probe.kick()
    return probes


def stall_spans() -> list:
    return [s for s in trace.RECORDER.spans() if s["name"] == "loop.stall"]


@pytest.fixture
def recorder():
    trace.RECORDER.configure(enabled=True, sample=0.0)
    yield trace.RECORDER
    trace.RECORDER.configure()


def test_a_tick_120_ms_late_is_one_stall_with_the_kernels_account(recorder):
    loop, kernel = FakeLoop(), FakeKernel()
    watch = sc.LoopWatch(sources=kernel.sources())
    probes = two_probes_on(loop, watch)
    # an ordinary tick first: the baseline is read, nothing is recorded
    loop.run_timers_at(1000.010)
    assert kernel.reads == 5 and stall_spans() == []
    for probe in probes:
        probe.kick()
    kernel.values["steal"]["steal_s"] += 0.100
    kernel.values["runqueue"]["runqueue_s"] += 0.500  # more than the lateness
    kernel.values["rusage"]["minflt"] += 42
    kernel.values["rusage"]["process_cpu_s"] += 0.3
    before = scrape()
    loop.run_timers_at(1000.020 + 0.120)  # both probes' ticks, 120 ms late
    after = scrape()
    span, = stall_spans()  # one, though two ServingCores' probes share the loop
    assert moved(before, after, LOOP + "stalls_total") == 1
    assert moved(before, after, LOOP + "stall_seconds_total") == pytest.approx(0.120)
    kernel_s = LOOP + "stall_kernel_seconds_total"
    assert moved(before, after, kernel_s, source="steal") == pytest.approx(0.100)
    # capped at the lateness
    assert moved(before, after, kernel_s, source="runqueue") == pytest.approx(0.120)
    assert moved(before, after, kernel_s, source="throttled") == 0
    tags = span["tags"]
    assert span["parent"] is None and tags["promoted"] == "fault" and tags["fault"] == "stall"
    assert span["dur_us"] == pytest.approx(120_000, rel=1e-3)
    assert tags["late_ms"] == pytest.approx(120.0)
    assert tags["steal_ms"] == pytest.approx(100.0)
    assert tags["runqueue_ms"] == pytest.approx(500.0)  # the tag is not capped
    assert tags["throttled_ms"] == 0 and tags["pressure_ms"] == 0
    assert (tags["minflt"], tags["majflt"], tags["nivcsw"]) == (42, 0, 0)
    assert tags["process_cpu_ms"] == pytest.approx(300.0)
    assert tags["baseline_age_ms"] == pytest.approx(130.0)
    assert tags["threads"] == threading.active_count()
    # each probe still counted its own lateness, as before
    for name in ("t37a", "t37b"):
        assert moved(before, after, LOOP + "lag_seconds_total", server=name) == pytest.approx(0.120)
    # the next stall is a new one, measured against the reading this one left
    for probe in probes:
        probe.kick()
    kernel.values["steal"]["steal_s"] += 0.030
    loop.run_timers_at(loop.now + 0.010 + 0.050)
    assert len(stall_spans()) == 2
    assert stall_spans()[-1]["tags"]["steal_ms"] == pytest.approx(30.0)


def test_a_host_with_none_of_the_files_gives_the_span_without_those_tags(recorder):
    loop = FakeLoop()
    kernel = FakeKernel(lacking=("steal", "throttled", "runqueue", "pressure", "rusage"))
    watch = sc.LoopWatch(sources=kernel.sources())
    probe, _ = two_probes_on(loop, watch)
    loop.run_timers_at(1000.010)
    assert kernel.reads == 5
    probe.kick()
    before = scrape()
    loop.run_timers_at(1000.020 + 0.120)
    after = scrape()
    assert kernel.reads == 5  # a source the host lacks is not asked again
    span, = stall_spans()
    assert set(span["tags"]) <= {
        "late_ms", "threads", "cpus", "baseline_age_ms", "gc_ms", "promoted", "fault"}
    assert span["tags"]["late_ms"] == pytest.approx(120.0)
    assert moved(before, after, LOOP + "stalls_total") == 1
    assert moved(before, after, LOOP + "stall_kernel_seconds_total") == 0


def test_a_tick_39_ms_late_is_no_stall(recorder):
    loop, kernel = FakeLoop(), FakeKernel()
    two_probes_on(loop, sc.LoopWatch(sources=kernel.sources()))
    before = scrape()
    loop.run_timers_at(1000.010 + 0.039)
    after = scrape()
    assert stall_spans() == []
    assert moved(before, after, LOOP + "stalls_total") == 0
    assert moved(before, after, LOOP + "stall_seconds_total") == 0
    assert moved(before, after, LOOP + "lag_ticks_total", server="t37a") == 1
    assert moved(before, after, LOOP + "lag_seconds_total", server="t37a") == pytest.approx(0.039)


def test_the_real_sources_read_what_this_host_has():
    """Whatever of the five the host has comes back as numbers, and the
    rest is left out without raising: the same call a second time reads
    only what the first found."""
    watch = sc.LoopWatch()
    seen = watch._account()
    assert {"minflt", "majflt", "nivcsw"} <= set(seen)  # getrusage is everywhere
    assert all(isinstance(v, (int, float)) for v in seen.values())
    assert set(watch._sources) <= set(sc.kernel_sources())
    again = watch._account()
    assert set(again) == set(seen)
    for key in seen:
        assert again[key] >= seen[key], key


def test_a_stall_of_a_real_loop_is_recorded_once(recorder):
    """A callback that holds the loop for 80 ms while a probe is armed."""

    async def body():
        probe = sc.LoopLagProbe("t37real", lambda: True)
        probe.start(asyncio.get_running_loop())
        probe.kick()
        await asyncio.sleep(0.03)  # a few ordinary ticks: the baseline
        probe.kick()
        before = scrape()
        time.sleep(0.08)  # the loop's thread is away
        await asyncio.sleep(0.03)
        probe.stop()
        return before, scrape()

    before, after = asyncio.run(body(), loop_factory=sc.new_event_loop)
    assert moved(before, after, LOOP + "stalls_total") == 1
    assert 0.04 <= moved(before, after, LOOP + "stall_seconds_total") < 1.0
    tags = stall_spans()[-1]["tags"]
    assert tags["late_ms"] >= 40 and "nivcsw" in tags
    # the loop's own account of the interval: it sat in one turn, asleep,
    # and the process burnt next to no CPU meanwhile
    assert tags["loop_turn_ms"] >= 80 > tags["loop_cpu_ms"]
    assert tags["loop_turns"] >= 1 and tags["loop_poll_ms"] < 40
    assert tags["process_cpu_ms"] < tags["loop_turn_ms"]


# ------------------------------------------------------------- the collector
def test_a_generation_2_collection_moves_its_pause_and_count():
    watch = trace.watch_gc()
    assert trace.watch_gc() is watch and gc.callbacks.count(watch) == 1
    total = watch.seconds
    before = scrape()
    gc.collect(2)
    after = scrape()
    assert moved(before, after, "seaweedfs_tpu_gc_collections_total", generation="2") >= 1
    pause = moved(before, after, "seaweedfs_tpu_gc_pause_seconds_total", generation="2")
    assert pause > 0 and watch.seconds - total >= pause


def test_a_collection_is_an_event_of_a_profiler_trace(tmp_path):
    import jax

    trace.watch_gc()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        gc.collect(2)
        trace.mark("loop.stall", late_ms=120.0)
    finally:
        jax.profiler.stop_trace()
    names = host_event_names(trace_dir)
    assert "gc.gen2" in names and "loop.stall" in names, sorted(names)


# -------------------------------------------------------- rs.unpack is gone
def test_rs_unpack_is_in_no_trace_and_in_no_metrics_line(tmp_path):
    import jax

    codec = TpuRSCodec()
    rng = np.random.default_rng(37)
    data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    full = np.concatenate([data, codec.encode(data)])  # compiles outside the trace
    shards = [full[i] if i not in (3, 11) else None for i in range(14)]
    codec.reconstruct_rows(list(shards), [3])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        rows = codec.reconstruct_rows(list(shards), [3])
    finally:
        jax.profiler.stop_trace()
    assert np.array_equal(rows[0], data[3])
    names = set(host_event_names(trace_dir))
    assert {"rs.pack", "rs.put", "rs.dispatch", "rs.fetch"} <= names
    assert "rs.unpack" not in names
    assert not [line for line in scrape() if "unpack" in line]


# ------------------------------------------------------ the ten metric files
def a_recorded_pair() -> tuple:
    """A /metrics pair as a window of a GET cell leaves it, with every
    family the ten files read moved by a known amount."""
    p = "seaweedfs_tpu_"
    before = {
        p + "event_loop_turn_seconds_total": 1.0,
        p + "event_loop_turns_total": 100.0,
        p + 'event_loop_select_seconds_total{mode="poll"}': 0.5,
        p + 'event_loop_select_seconds_total{mode="wait"}': 2.0,
        p + "event_loop_cpu_seconds_total": 0.8,
        p + "event_loop_stalls_total": 0.0,
        p + "event_loop_stall_seconds_total": 0.0,
        p + 'event_loop_stall_kernel_seconds_total{source="steal"}': 0.0,
        p + 'event_loop_stall_kernel_seconds_total{source="runqueue"}': 0.0,
        p + 'gc_pause_seconds_total{generation="0"}': 0.01,
        p + 'gc_pause_seconds_total{generation="2"}': 0.0,
        p + 'response_write_seconds_total{server="volume"}': 0.0,
        p + 'response_writes_total{server="volume"}': 0.0,
        p + 'ec_degraded_read_stage_seconds_total{stage="loop_resume"}': 0.0,
        p + 'ec_reconstructions_total{kind="cold"}': 0.0,
        p + 'ec_read_stage_seconds_total{stage="locate"}': 0.0,
        p + 'ec_needle_reads_total{kind="degraded"}': 0.0,
        p + 'ec_degraded_read_worker_seconds_total{clock="wall"}': 0.0,
        p + 'ec_degraded_read_worker_seconds_total{clock="cpu"}': 0.0,
    }
    moves = {
        p + "event_loop_turn_seconds_total": 6.0,
        p + "event_loop_turns_total": 12_000.0,
        p + 'event_loop_select_seconds_total{mode="poll"}': 1.5,
        p + 'event_loop_select_seconds_total{mode="wait"}': 2.5,
        p + "event_loop_cpu_seconds_total": 5.5,
        p + "event_loop_stalls_total": 2.0,
        p + "event_loop_stall_seconds_total": 0.25,
        p + 'event_loop_stall_kernel_seconds_total{source="steal"}': 0.05,
        p + 'event_loop_stall_kernel_seconds_total{source="runqueue"}': 0.15,
        p + 'gc_pause_seconds_total{generation="0"}': 0.01,
        p + 'gc_pause_seconds_total{generation="2"}': 0.04,
        p + 'response_write_seconds_total{server="volume"}': 0.2,
        p + 'response_writes_total{server="volume"}': 4000.0,
        p + 'ec_degraded_read_stage_seconds_total{stage="loop_resume"}': 30.0,
        p + 'ec_reconstructions_total{kind="cold"}': 2000.0,
        p + 'ec_read_stage_seconds_total{stage="locate"}': 0.4,
        p + 'ec_needle_reads_total{kind="degraded"}': 4000.0,
        p + 'ec_degraded_read_worker_seconds_total{clock="wall"}': 20.0,
        p + 'ec_degraded_read_worker_seconds_total{clock="cpu"}': 5.0,
    }
    return before, {k: before[k] + moves[k] for k in before}


WANT = {
    "http.loop_cpu_share": 55.0,        # 5.5 of 6 + 1.5 + 2.5
    "http.loop_held_share": 20.0,       # 6 + 1.5 - 5.5
    "http.loop_turn_ms": 0.5,
    "http.write_ms": 0.05,
    "ec_read.loop_resume_ms": 15.0,
    "ec_read.locate_ms": 0.1,
    "ec_read.worker_cpu_share": 25.0,
    "http.loop_stall_ms_per_s": 25.0,   # 250 ms late over a 10 s window
    "http.stall_kernel_share": 80.0,    # steal + run queue, of the lateness
    "http.gc_pause_ms_per_s": 5.0,
}


@pytest.mark.parametrize("name", LOOP_METRICS)
def test_each_of_the_ten_metric_files_reads_its_families(name):
    spec = common.load("layer_metrics", name + ".json")
    entry = next(e for e in common.benchmark_json()["per_layer"] if e["name"] == name)
    assert entry["workloads"] == get_cells_of(name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    before, after = a_recorded_pair()
    process = {"window_s": 10.0}
    seen = layer_metrics.Observed(before, after, {}, {}, {}, process, None, None, {})
    assert seen.value(spec) == pytest.approx(WANT[name])
    # the parent's program has none of the families: the metric is left out
    # of the line, and nothing raises
    # (it has the two stage families, whose absent child would read as 0)
    old = {"seaweedfs_tpu_ec_reconstructions_total{kind=\"cold\"}": 1.0,
           "seaweedfs_tpu_ec_needle_reads_total{kind=\"degraded\"}": 1.0,
           "seaweedfs_tpu_ec_degraded_read_stage_seconds_total{stage=\"decode\"}": 1.0,
           "seaweedfs_tpu_ec_read_stage_seconds_total{stage=\"assemble\"}": 1.0}
    assert layer_metrics.Observed(old, old, {}, {}, {}, process, None, None, {}).value(spec) is None


def test_the_three_shares_of_the_loops_wall_add_up_to_100():
    before, after = a_recorded_pair()
    seen = layer_metrics.Observed(before, after, {}, {}, {}, {}, None, None, {})
    cpu, held = (
        seen.value(common.load("layer_metrics", f"http.loop_{which}_share.json"))
        for which in ("cpu", "held")
    )
    idle = 100 * 2.5 / 10.0  # select{wait} over the same wall
    assert cpu + held + idle == pytest.approx(100.0)


def test_a_window_without_a_stall_reads_0_for_both_stall_metrics():
    """A traced run has to report every metric its cell lists: a quiet
    window reads 0.0, it does not drop the share for want of a divisor."""
    before, after = a_recorded_pair()
    quiet = {k: (before[k] if "stall" in k else v) for k, v in after.items()}
    seen = layer_metrics.Observed(before, quiet, {}, {}, {}, {"window_s": 10.0}, None, None, {})
    assert seen.value(common.load("layer_metrics", "http.loop_stall_ms_per_s.json")) == 0.0
    assert seen.value(common.load("layer_metrics", "http.stall_kernel_share.json")) == 0.0
