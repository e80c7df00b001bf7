"""Prometheus-style metrics registry (ref: weed/stats/metrics.go:15-93).

Counters, gauges and histograms with label support, rendered in the
Prometheus text exposition format at /metrics on each server. No external
client library; the push-gateway mode of the reference is replaced by pull.

Histogram bucket samples can carry OpenMetrics-style exemplars (the last
sampled trace_id observed per bucket, see util/trace.py): a `/metrics`
latency spike links straight to the trace that caused it in
`/debug/traces`. Exemplars are only emitted when `render(exemplars=True)`
is asked for — /metrics negotiates via the Accept header, because the
classic text format (text/plain) does not permit them and a stock
Prometheus scraper would reject the whole exposition.
"""

from __future__ import annotations

import os
import threading
import time as _time
from bisect import bisect_left
from collections import defaultdict

_DEFAULT_BUCKETS = [
    0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3, 10,
]

# set by util/trace.py at import: () -> hex trace id of the current
# SAMPLED context, or None. Kept as a module attribute (not an import) so
# the metrics module stays dependency-free at the bottom of the stack.
_exemplar_fn = None


class _Labeled:
    def __init__(self, name: str, help_text: str, kind: str):
        self.name = name
        self.help = help_text
        self.kind = kind
        self._lock = threading.Lock()

    def _series_dicts(self) -> list:
        """Every key->value store holding per-label-set series (the
        subclass's own dicts); remove_label_value edits them in place."""
        return []

    def remove_label_value(self, label: str, value: str) -> int:
        """Drop every series whose `label` equals `value` — the registry
        seam the bounded tenant-label policy uses to retire a displaced
        tenant's series (util/tenancy.TenantLabelPolicy): cumulative
        label cardinality stays capped only if retired values stop
        rendering. Returns the number of series dropped."""
        pair = (label, str(value))
        dropped = 0
        with self._lock:
            for d in self._series_dicts():
                for key in [k for k in d if _key_has(k, pair)]:
                    del d[key]
                    dropped += 1
        return dropped


def _key_has(key, pair) -> bool:
    """Does a label-set key (possibly (key, idx)-wrapped for exemplars)
    contain the (label, value) pair?"""
    if key and isinstance(key[0], tuple) and key[0] and isinstance(
        key[0][0], tuple
    ):
        key = key[0]  # histogram exemplar key: ((labels...), bucket_idx)
    return pair in key


class Counter(_Labeled):
    def __init__(self, name: str, help_text: str = ""):
        super().__init__(name, help_text, "counter")
        self._values: dict[tuple, float] = defaultdict(float)

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] += amount

    def child(self, **labels) -> "_CounterChild":
        """Pre-bound label set with O(1) inc — for per-request hot paths
        where tuple(sorted(labels.items())) per call is measurable."""
        return _CounterChild(self, tuple(sorted(labels.items())))

    def _series_dicts(self) -> list:
        return [self._values]

    def render(self, exemplars: bool = False) -> list[str]:
        out = [
            f"# HELP {self.name} {_escape_help(self.help)}",
            f"# TYPE {self.name} counter",
        ]
        with self._lock:
            for key, v in self._values.items():
                out.append(f"{self.name}{_fmt_labels(key)} {v}")
        return out


class _CounterChild:
    __slots__ = ("_counter", "_key")

    def __init__(self, counter: Counter, key: tuple):
        self._counter = counter
        self._key = key

    def inc(self, amount: float = 1.0) -> None:
        c = self._counter
        with c._lock:
            c._values[self._key] += amount


class Gauge(_Labeled):
    def __init__(self, name: str, help_text: str = ""):
        super().__init__(name, help_text, "gauge")
        self._values: dict[tuple, float] = defaultdict(float)

    def set(self, value: float, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = value

    def add(self, amount: float, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] += amount

    def remove(self, **labels) -> None:
        """Drop ONE series (exact label set). A gauge whose label value
        has been retired by the bounded tenant policy must disappear,
        not be set to 0 — a 0 still renders and re-mints the purged
        series."""
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values.pop(key, None)

    def _series_dicts(self) -> list:
        return [self._values]

    def render(self, exemplars: bool = False) -> list[str]:
        out = [
            f"# HELP {self.name} {_escape_help(self.help)}",
            f"# TYPE {self.name} gauge",
        ]
        with self._lock:
            for key, v in self._values.items():
                out.append(f"{self.name}{_fmt_labels(key)} {v}")
        return out


class Histogram(_Labeled):
    def __init__(self, name: str, help_text: str = "", buckets=None):
        super().__init__(name, help_text, "histogram")
        self.buckets = list(buckets or _DEFAULT_BUCKETS)
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = defaultdict(float)
        self._totals: dict[tuple, int] = defaultdict(int)
        # (key, bucket_idx) -> (trace_hex, observed value, unix ts): the
        # last SAMPLED observation per bucket, written only when the
        # tracing contextvar says the current request is sampled — the
        # unsampled hot path pays one module-attribute load + None check
        self._exemplars: dict[tuple, tuple] = {}

    def observe(self, value: float, **labels) -> None:
        key = tuple(sorted(labels.items()))
        self._observe_key(key, value)

    def _observe_key(self, key: tuple, value: float) -> None:
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            # Prometheus le bounds are INCLUSIVE: a value equal to a
            # boundary belongs in that boundary's bucket (bisect_left).
            # bisect_right pushed every exact boundary hit one bucket up —
            # invisible for continuous latencies, wrong for the integer
            # batch-size buckets where boundary values are the common case
            idx = bisect_left(self.buckets, value)
            if idx < len(counts):
                counts[idx] += 1  # cumulative sums computed at render time
            self._sums[key] += value
            self._totals[key] += 1
        fn = _exemplar_fn
        if fn is not None:
            tid = fn()
            if tid is not None:
                self._exemplars[(key, idx)] = (tid, value, _time.time())

    def child(self, **labels) -> "_HistogramChild":
        """Pre-bound label set with an O(1)-overhead observe — the
        histogram analogue of Counter.child, for per-request hot paths."""
        return _HistogramChild(self, tuple(sorted(labels.items())))

    def _series_dicts(self) -> list:
        return [self._counts, self._sums, self._totals, self._exemplars]

    def _exemplar_suffix(self, key: tuple, idx: int) -> str:
        ex = self._exemplars.get((key, idx))
        if ex is None:
            return ""
        tid, value, ts = ex
        # OpenMetrics exemplar syntax — emitted only for the negotiated
        # application/openmetrics-text exposition (see Registry.render)
        return ' # {trace_id="%s"} %g %.3f' % (tid, value, ts)

    def render(self, exemplars: bool = False) -> list[str]:
        out = [
            f"# HELP {self.name} {_escape_help(self.help)}",
            f"# TYPE {self.name} histogram",
        ]
        ex = self._exemplar_suffix if exemplars else (lambda key, i: "")
        with self._lock:
            for key, counts in self._counts.items():
                cumulative = 0
                for i, (b, c) in enumerate(zip(self.buckets, counts)):
                    cumulative += c
                    out.append(
                        f"{self.name}_bucket{_fmt_labels(key, le=str(b))} "
                        f"{cumulative}{ex(key, i)}"
                    )
                out.append(
                    f'{self.name}_bucket{_fmt_labels(key, le="+Inf")} '
                    f"{self._totals[key]}"
                    f"{ex(key, len(self.buckets))}"
                )
                out.append(f"{self.name}_sum{_fmt_labels(key)} {self._sums[key]}")
                out.append(f"{self.name}_count{_fmt_labels(key)} {self._totals[key]}")
        return out


class _HistogramChild:
    __slots__ = ("_hist", "_key")

    def __init__(self, hist: Histogram, key: tuple):
        self._hist = hist
        self._key = key

    def observe(self, value: float) -> None:
        self._hist._observe_key(self._key, value)


def _escape_label_value(v) -> str:
    """Escape per the exposition-format spec: backslash, double-quote and
    newline inside a label value must be escaped or the whole render is
    unparseable (vacuum route labels and fault `op` labels can carry
    arbitrary strings)."""
    s = str(v)
    if "\\" in s or '"' in s or "\n" in s:
        s = s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    return s


def _escape_help(s: str) -> str:
    """HELP lines escape backslash and newline (spec: help text is the
    rest of the line)."""
    if "\\" in s or "\n" in s:
        s = s.replace("\\", "\\\\").replace("\n", "\\n")
    return s


def _fmt_labels(key: tuple, **extra) -> str:
    items = list(key) + sorted(extra.items())
    if not items:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in items)
    return "{" + inner + "}"


class Registry:
    """Name-keyed metric registry. Registration is idempotent: asking for
    an existing name returns the existing collector when the kind
    matches, and raises when it doesn't — duplicate metric families can
    never render (they are invalid exposition text, and the silent
    variant hid typo'd re-registrations)."""

    def __init__(self):
        self._metrics: list = []
        self._by_name: dict[str, _Labeled] = {}
        self._lock = threading.Lock()

    def _register(self, name: str, kind: str, factory):
        with self._lock:
            m = self._by_name.get(name)
            if m is not None:
                if m.kind != kind:
                    raise ValueError(
                        f"metric {name!r} already registered as {m.kind}, "
                        f"not {kind}"
                    )
                return m
            m = factory()
            self._by_name[name] = m
            self._metrics.append(m)
            return m

    def counter(self, name: str, help_text: str = "") -> Counter:
        return self._register(
            name, "counter", lambda: Counter(name, help_text)
        )

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        return self._register(name, "gauge", lambda: Gauge(name, help_text))

    def histogram(self, name: str, help_text: str = "", buckets=None) -> Histogram:
        m = self._register(
            name, "histogram", lambda: Histogram(name, help_text, buckets)
        )
        if buckets is not None and list(buckets) != m.buckets:
            # idempotent return must not silently change bucket layout:
            # observations from the second site would land in the first
            # site's buckets and render wrong percentiles with no error
            raise ValueError(
                f"metric {name!r} already registered with buckets "
                f"{m.buckets}, not {list(buckets)}"
            )
        return m

    def collectors(self) -> list:
        """Snapshot of registered metrics (hygiene lint / self-checks)."""
        with self._lock:
            return list(self._metrics)

    def render(self, exemplars: bool = False) -> str:
        """Text exposition. `exemplars=True` appends the OpenMetrics
        exemplar suffix to histogram bucket samples — only valid under
        the `application/openmetrics-text` content type (classic
        text-format parsers reject a `#` after the sample value), so
        /metrics serves it via Accept-header negotiation only."""
        lines = []
        with self._lock:
            for m in self._metrics:
                lines.extend(m.render(exemplars=exemplars))
        return "\n".join(lines) + "\n"


# global registry + the server metric families the reference defines
REGISTRY = Registry()

REQUEST_COUNTER = REGISTRY.counter(
    "seaweedfs_tpu_request_total", "number of requests by server/operation"
)
REQUEST_HISTOGRAM = REGISTRY.histogram(
    "seaweedfs_tpu_request_seconds", "request latency by server/operation"
)
# time a request spent between parse completion and the start of service
# on a fast tier (loop backlog + admission queue): beside request_seconds
# (the service wall) it splits a server-side latency into wait and work.
# Counted for full fast-tier responses and for FALLBACKs — on the volume
# server each of those is observed once in request_seconds, by the fast
# tier or by the aiohttp _dispatch it is replayed against
REQUEST_WAIT_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_request_wait_seconds_total",
    "seconds requests waited on a fast tier from parse completion to the "
    "start of service (loop backlog + admission queue), by server/operation; "
    "full responses and FALLBACKs, not DETACHED ones",
)
# a request the fast tier does not fully understand (a range, a query, a
# manifest, /status) is replayed against the internal aiohttp listener over
# a new loopback connection: the wall of that replay, which encloses the
# cold tier's own request_seconds
REQUEST_PROXY_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_request_proxy_seconds_total",
    "seconds fast tiers spent replaying FALLBACK requests against their "
    "cold tier (connect + replay + handler + relay), by server; divide by "
    "request_proxied_total",
)
REQUEST_PROXIED = REGISTRY.counter(
    "seaweedfs_tpu_request_proxied_total",
    "requests a fast tier replayed against its cold tier, by server",
)
# one probe per ServingCore (serving_core.LoopLagProbe): a callback armed
# by a request and re-armed every 10 ms while requests keep arriving adds
# how late it ran — the one reading of time a request spends in the socket
# before the loop parses it
EVENT_LOOP_LAG_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_lag_seconds_total",
    "seconds the serving loop ran its 10 ms probe callback late, by server",
)
EVENT_LOOP_LAG_TICKS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_lag_ticks_total",
    "loop-lag probe callbacks run, by server",
)
# the serving loop's own clock (serving_core.LoopClock, the selector of a
# loop the CLI makes): two clock reads a turn split the loop's wall into
# select (inside the system call: `wait` with nothing ready, idle until an
# event; `poll` with callbacks ready and timeout 0, where the kernel returns
# at once and the wall is the loop taking the interpreter lock back) and
# turn (from one select's return to the next one's call: the callbacks and
# task steps a socket that became readable waits behind). turn + select is
# the loop's wall; cpu of it the thread ran; turn + select{poll} - cpu it
# had work and did not run (the interpreter lock, the kernel's run queue);
# select{wait} it had none. One loop a process: no `server` label. Advanced
# at every tenth tick of a lag probe and when /metrics is rendered
EVENT_LOOP_SELECT_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_select_seconds_total",
    "wall seconds the serving loop spent inside select, by mode (wait = "
    "nothing ready, idle until an event; poll = callbacks ready, timeout 0: "
    "the loop taking the interpreter lock back after the system call)",
)
EVENT_LOOP_TURN_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_turn_seconds_total",
    "wall seconds of the serving loop between one select's return and the "
    "next one's call (the callbacks and task steps of its turns); with "
    "event_loop_select_seconds_total it is the loop's whole wall",
)
EVENT_LOOP_TURNS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_turns_total",
    "turns of the serving loop (select calls)",
)
EVENT_LOOP_CPU_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_cpu_seconds_total",
    "CPU seconds of the serving loop's thread (time.thread_time): over turn "
    "+ select the share of its wall the loop ran",
)


class JobsInFlight:
    """Background jobs running in this process now (`n`, read without a
    lock): a conversion (erasure_coding/encoder.write_ec_files) enters it
    for its whole run, and a serving loop's clock splits its second by it
    (server/serving_core.LoopClock, `event_loop_converting_seconds_total`)."""

    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()

    def __enter__(self):
        with self._lock:
            self.n += 1

    def __exit__(self, *exc):
        with self._lock:
            self.n -= 1


BACKGROUND_JOBS = JobsInFlight()
# the same clock while a background job runs in the process (BACKGROUND_JOBS
# above 0): running = cpu (at most turn + poll), held = turn + poll -
# running, idle = wait; a turn belongs to the state seen at its select call
EVENT_LOOP_CONVERTING_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_converting_seconds_total",
    "wall seconds of the serving loop while a conversion (write_ec_files) "
    "was in flight in the process, by mode (running = on a CPU; held = had "
    "work and did not run; idle = select with nothing ready)",
)
# a lag-probe tick LOOP_STALL_SECONDS late or later is a stall, counted once
# a loop whichever servers share it; beside it the kernel's account of the
# same interval, each delta capped at the lateness (a source the host lacks
# has no sample): steal = /proc/stat's steal column (all CPUs), throttled =
# the cgroup's cpu.stat throttled_usec, runqueue = the loop thread's
# run-queue delay (/proc/thread-self/schedstat)
EVENT_LOOP_STALLS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_stalls_total",
    "lag-probe ticks of the serving loop that ran 40 ms late or later, once "
    "a loop (each is a loop.stall span in /debug/traces)",
)
EVENT_LOOP_STALL_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_stall_seconds_total",
    "seconds those ticks ran late",
)
EVENT_LOOP_STALL_KERNEL_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_event_loop_stall_kernel_seconds_total",
    "the kernel's account of the stalls' intervals, by source (steal = "
    "hypervisor steal of /proc/stat; throttled = the cgroup's CPU quota; "
    "runqueue = the loop thread's run-queue delay), each capped at the "
    "stall's lateness",
)
# the collector's pauses (gc.callbacks, util/trace.watch_gc): every thread
# that needs the interpreter lock waits through one
GC_PAUSE_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_gc_pause_seconds_total",
    "wall seconds inside garbage collections, by generation",
)
GC_COLLECTIONS = REGISTRY.counter(
    "seaweedfs_tpu_gc_collections_total",
    "garbage collections run, by generation",
)
# the fast tier's own work on the loop a request (util/fasthttp.py): slicing
# requests out of what a socket delivered, and handing a full answer to the
# transport; what the transport still buffers right after the write leaves
# over later turns of the loop
REQUEST_PARSE_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_request_parse_seconds_total",
    "seconds fast tiers spent slicing requests out of received bytes "
    "(data_received -> parsed and queued), by server",
)
RESPONSE_WRITE_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_response_write_seconds_total",
    "seconds fast tiers spent in transport.write of full answers, by "
    "server; divide by response_writes_total",
)
RESPONSE_WRITES = REGISTRY.counter(
    "seaweedfs_tpu_response_writes_total",
    "full answers fast tiers wrote, by server",
)
RESPONSE_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_response_bytes_total",
    "bytes of those answers, by server",
)
RESPONSE_BUFFERED_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_response_buffered_bytes_total",
    "bytes the transport still buffered right after each of those writes "
    "(what did not go out at once), by server",
)
# set once at start-up: where the seconds from process start to the first
# served request go (imports/device/store_load/index_build/listening)
STARTUP_SECONDS = REGISTRY.gauge(
    "seaweedfs_tpu_startup_seconds",
    "seconds since process start at which each start-up phase was done, "
    "by phase (imports/device/store_load/index_build/listening)",
)
EC_ENCODE_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_ec_encoded_bytes_total", "bytes erasure-coded, by backend"
)
# what the device planes' process spent compiling (util/device.py
# watch_compiles): a chip run's cost before its first answer
JAX_COMPILES = REGISTRY.counter(
    "seaweedfs_tpu_jax_compiles_total",
    "XLA backend compiles (persistent-cache loads included)",
)
JAX_COMPILE_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_jax_compile_seconds_total",
    "seconds in XLA backend compiles (persistent-cache loads included)",
)
JAX_COMPILE_CACHE = REGISTRY.counter(
    "seaweedfs_tpu_jax_compile_cache_total",
    "persistent compile cache lookups, by result (hit/miss)",
)

# degraded-mode visibility (see docs/robustness.md): every retry loop,
# on-the-fly EC reconstruction and load-time torn-tail repair counts here,
# so a chaos run can assert HOW the system survived, not just that it did
RETRY_COUNTER = REGISTRY.counter(
    "seaweedfs_tpu_retries_total", "retry attempts by operation"
)
EC_RECONSTRUCTIONS = REGISTRY.counter(
    "seaweedfs_tpu_ec_reconstructions_total",
    "EC intervals served by reconstruction from >= data_shards other shards, "
    'by kind (kind="cold" = full survivor fetch + decode, kind="cache_hit" '
    "= served from the degraded-read interval cache)",
)
TORN_TAIL_COUNTER = REGISTRY.counter(
    "seaweedfs_tpu_torn_tail_total",
    "torn-tail recovery on volume load, by item "
    "(volumes/records_recovered/dat_bytes_dropped/idx_entries_dropped)",
)
FAULTS_INJECTED = REGISTRY.counter(
    "seaweedfs_tpu_faults_injected_total",
    "faults fired by the active injection plan, by op/kind",
)

# serving-plane write-path attribution (see docs/perf.md): stages of one
# replicated/fsync'd POST — local_append (append[+fsync] wall),
# replicate_wait (extra wall the ack spent on the fan-out AFTER the local
# write finished; overlap means this shrinks toward 0), group_commit_wait
# (enqueue -> fsync'd-batch-resolution wall on the fsync=true tier)
WRITE_STAGE_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_write_stage_seconds",
    "volume write path stage wall time, by stage",
)
GROUP_COMMIT_BATCH_SIZE = REGISTRY.histogram(
    "seaweedfs_tpu_group_commit_batch_size",
    "requests per group-commit fsync batch",
    buckets=[1, 2, 4, 8, 16, 32, 64, 128],
)
GROUP_COMMIT_FSYNCS = REGISTRY.counter(
    "seaweedfs_tpu_group_commit_fsyncs_total",
    "group-commit batches flushed (one fsync each)",
)

# serving read plane (see docs/perf.md "Serving read plane"): the read
# path gets the same itemized-stage treatment as writes, and the
# hot-needle cache in front of the volume tier is externally auditable —
# hit rate, bytes it absorbed, and the LRU's churn
READ_STAGE_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_read_stage_seconds",
    "volume read path stage wall time, by stage (cache_hit = full request "
    "served from the hot-needle cache; read_render = map probe + pread + "
    "parse + response render on a miss; ec_read = a read of a locally "
    "mounted EC volume answered by the fast tier: locate + intervals, "
    "reconstructed where a shard is lost, + render)",
)
READ_CACHE_HITS = REGISTRY.counter(
    "seaweedfs_tpu_read_cache_hits_total",
    "reads served whole from the hot-needle cache",
)
READ_CACHE_MISSES = REGISTRY.counter(
    "seaweedfs_tpu_read_cache_misses_total",
    "cacheable reads that went to the volume tier (includes entries "
    "invalidated by overwrite/delete/vacuum since they were cached)",
)
READ_CACHE_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_read_cache_bytes_total",
    "response bytes served from the hot-needle cache",
)
READ_CACHE_EVICTIONS = REGISTRY.counter(
    "seaweedfs_tpu_read_cache_evictions_total",
    "hot-needle cache entries evicted (LRU byte bound) or invalidated "
    "(overwrite/delete/vacuum-commit), by reason",
)

# repair-plane attribution (see docs/perf.md "Repair plane"): rebuild gets
# the same itemized-budget treatment the write path got — per-stage walls
# of every rebuild_ec_files run (stages overlap on the pipelined route, so
# their sum can exceed the rebuild wall), degraded-read interval latency
# split cold vs cache-served, and the decode-matrix LRU's hit rate
# host-stage attribution of the codec's device path (util/trace.stage):
# every TpuRSCodec call is pack -> put -> dispatch -> fetch
# (+ stack on a reconstruct), by op (encode/decode/apply). Stages run on
# pool threads, so their sum can exceed the wall.
RS_DISPATCH_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_rs_dispatch_seconds_total",
    "host seconds of a TpuRSCodec call, by op (encode/decode/apply) and "
    "stage (stack/pack/put/dispatch/fetch)",
)
RS_DISPATCHES = REGISTRY.counter(
    "seaweedfs_tpu_rs_dispatches_total",
    "TpuRSCodec calls, by op and backend (device = the Pallas kernel on a "
    "TPU; device_emulated = jax on the CPU; host_standin = the native "
    "codec the streamed pipeline substitutes without a TPU)",
)
RS_DISPATCH_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_rs_dispatch_bytes_total",
    "bytes of TpuRSCodec calls, by op, backend and kind (real = (rows in "
    "+ rows out) x the caller's width; padded = what the pad to the "
    "kernel's granule added)",
)
# which other codec call a call met between its dispatch and the end of its
# fetch: another op's first (encode/decode/apply), else its own op's, else
# none; counted once a call that returned
RS_DISPATCHES_BESIDE = REGISTRY.counter(
    "seaweedfs_tpu_rs_dispatches_beside_total",
    "TpuRSCodec calls by op and by the op of another call in flight "
    "between its dispatch and the end of its fetch (beside = another op "
    "first, else the same op, else none)",
)
# encode-plane attribution (the mirror of ec_rebuild_stage_seconds):
# stage walls of every write_ec_files run; kernel (pool) and write /
# parity_wait (the ordering writer thread) overlap the main thread's
# stages; write_thread is summed over every thread that writes shards
EC_ENCODE_STAGE_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_ec_encode_stage_seconds_total",
    "write_ec_files stage wall seconds, by stage (splice/read/slot_wait/"
    "submit/kernel/parity_wait/write/write_thread/sync; pipelined stages "
    "overlap)",
)
EC_ENCODE_STAGE_CALLS = REGISTRY.counter(
    "seaweedfs_tpu_ec_encode_stage_calls_total",
    "times each write_ec_files stage ran, by stage",
)
# what only a batch has. Pieces: the volume pieces the streamed pipeline's
# dispatches carried, summed; over rs_dispatches_total{op="encode"} it is
# volumes per dispatch. Every dispatch carries one today (a batch converts
# its volumes in turn), so the ratio reads 1.0: nothing was batched.
# Generate seconds: the wall of a generate RPC's handler (encode + .ecx +
# .vif), rpc = "batch" (VolumeEcShardsGenerateBatch) or "single". Fallbacks:
# a batch that failed as one call and whose volumes were then converted one
# by one, reason = "io" (an OSError) or "codec" (anything else)
EC_ENCODE_BATCH_PIECES = REGISTRY.counter(
    "seaweedfs_tpu_ec_encode_batch_pieces_total",
    "volume pieces carried by the streamed encode pipeline's dispatches",
)
EC_GENERATE_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_ec_generate_seconds_total",
    "wall seconds in EC generate RPC handlers, by rpc (batch/single)",
)
EC_ENCODE_BATCH_FALLBACKS = REGISTRY.counter(
    "seaweedfs_tpu_ec_encode_batch_fallback_total",
    "batched encodes that fell back to one volume at a time, by reason",
)
# where a degraded read's wall goes. Of a cold reconstruct: survivor_read
# (the gathers of remote survivor fetches on the loop, plus the worker's
# wall filling the decode's input rows: local survivors read, fetched ones
# copied in), batch_wait (handed to the batcher -> its job handed to the
# executor), executor_wait (-> the worker's first line), decode (the
# worker's wall around reconstruct_rows alone), loop_resume (the worker's
# last line -> the coroutine's first line after the hop: the loop coming
# round to it), cache_put; divide by ec_reconstructions_total{kind="cold"}. Before any reconstruct, hit or
# cold: remote_attempts
EC_DEGRADED_READ_STAGE_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_ec_degraded_read_stage_seconds_total",
    "degraded EC read stage wall seconds, by stage (remote_attempts = the "
    "TTL'd location refresh, and where a holder is listed the holders "
    "tried and the forced refreshes after them, before any reconstruct; of "
    "a cold reconstruct: batch_wait/survivor_read/executor_wait/decode/"
    "loop_resume/cache_put, which add up to "
    "ec_degraded_read_seconds{result=cold}, a stage that reconstructs of one "
    "batch shared charged once to each; "
    "remote_read = each survivor fetched from another server, inside "
    "survivor_read: divide by ec_remote_shard_reads_total)",
)
# once for every EC interval that got past the local shard and the cold
# tier: was there anyone to ask for it, and did they answer
EC_REMOTE_ATTEMPTS = REGISTRY.counter(
    "seaweedfs_tpu_ec_remote_attempts_total",
    "EC interval reads of a shard this server does not hold, by outcome "
    "(no_holder = the fresh location table names nobody and the interval "
    "was reconstructed at once; served = a listed holder answered; failed "
    "= holders listed and none answered after the forced refreshes, or "
    "the table could not be refreshed: on to reconstruct)",
)
# a reconstruct's survivors that are on other servers, on the asking side:
# one count a survivor fetched through the remote path (its wall is
# ec_degraded_read_stage_seconds_total{stage="remote_read"}), and the bytes
# of the spans that came back whole
EC_REMOTE_SHARD_READS = REGISTRY.counter(
    "seaweedfs_tpu_ec_remote_shard_reads_total",
    "survivor spans a reconstruct asked other servers for, by outcome (ok = "
    "the whole span came back; short = a holder answered another length and "
    "the survivor is not used; failed = holders listed and none answered, or "
    "a tombstone; no_holder = the location table names nobody, nothing sent)",
)
EC_REMOTE_SHARD_READ_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_ec_remote_shard_read_bytes_total",
    "bytes of survivor spans read whole from other servers for reconstructs",
)
# the RPCs those survivors cost: the survivors one holder lists ride one
# stream, so over ec_reconstructions_total{kind="cold"} this is the calls a
# reconstruct made where ec_remote_shard_reads_total is the spans it asked
EC_REMOTE_SHARD_READ_STREAMS = REGISTRY.counter(
    "seaweedfs_tpu_ec_remote_shard_read_streams_total",
    "VolumeEcShardRead streams sent for a reconstruct's survivors, by shape "
    "(grouped = one stream for the two or more survivors one holder lists; "
    "single = one survivor's, every retry and further holder one more; "
    "regrouped = a single stream sent because a survivor of a grouped "
    "stream did not arrive whole)",
)
# the serving side of VolumeEcShardRead (its wall and count are
# request_seconds{server="volume",operation="VolumeEcShardRead"}), and the
# pulling side of VolumeEcShardsCopy
EC_SHARD_READ_SERVED_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_ec_shard_read_served_bytes_total",
    "shard bytes streamed to other servers by VolumeEcShardRead",
)
EC_SHARD_COPY_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_ec_shard_copy_bytes_total",
    "bytes of shard and index files pulled by VolumeEcShardsCopy",
)
EC_SHARD_COPY_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_ec_shard_copy_seconds_total",
    "wall seconds in VolumeEcShardsCopy handlers",
)
# a needle read of a mounted EC volume, healthy or degraded, by what it is
# made of: a needle is one or more intervals (five for a 4 MiB chunk needle
# over 1 MiB blocks), each served by exactly one source; what the two stages
# outside a reconstruct take (a healthy interval's pread, the assembly)
EC_READ_INTERVALS = REGISTRY.counter(
    "seaweedfs_tpu_ec_read_intervals_total",
    "EC needle intervals read, once an interval by what served it (source: "
    "local = a shard file here; cold_tier = a shard this server offloaded; "
    "remote = a listed holder over VolumeEcShardRead; reconstructed = a "
    "cold reconstruct from survivors; cache = the degraded-read span cache)",
)
EC_NEEDLE_READS = REGISTRY.counter(
    "seaweedfs_tpu_ec_needle_reads_total",
    "EC needles read whole (every interval in, parsed, CRC checked), by kind "
    "(degraded = at least one interval came from a reconstruct or the "
    "degraded-read cache; healthy = none did)",
)
EC_READ_STAGE_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_ec_read_stage_seconds_total",
    "EC needle read wall seconds outside any reconstruct, by stage "
    "(local_interval = the synchronous pread of an interval on a local "
    "shard: divide by ec_read_intervals_total{source=\"local\"}; assemble = "
    "join + parse + CRC of the needle, locate = the .ecx binary search, in "
    "the index's mapping since PR 38: see ec_index_lookups_total; divide "
    "both by ec_needle_reads_total; fetch = the wall of a needle's "
    "intervals that are not local, asked at once, from the first ask to "
    "the last arrival, and fetch_interval = the walls of those intervals "
    "summed: their ratio is how many were in flight at once)",
)
EC_REMOTE_INTERVAL_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_ec_remote_interval_seconds_total",
    "wall seconds of healthy needle intervals fetched from a listed holder "
    "over VolumeEcShardRead, from the first ask to the bytes (retries and "
    "forced refreshes included): divide by "
    "ec_read_intervals_total{source=\"remote\"}",
)
EC_REMOTE_INTERVAL_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_ec_remote_interval_bytes_total",
    "bytes of healthy needle intervals fetched from a listed holder over "
    "VolumeEcShardRead (ec_read_intervals_total{source=\"remote\"}); a "
    "reconstruct's survivors are ec_remote_shard_read_bytes_total",
)
EC_INDEX_LOOKUPS = REGISTRY.counter(
    "seaweedfs_tpu_ec_index_lookups_total",
    "searches of a mounted EC volume's .ecx for one needle "
    "(EcVolume._locate_entry: reads, a delete's check and its own search, "
    "the file_key check of VolumeEcShardRead), by what was searched (via: mapping = the mapping "
    "of the index made at mount, no system call a probe; pread = a pread a "
    "probe, where the filesystem refused a mapping at mount); an empty "
    "index is searched by neither",
)
EC_RECONSTRUCT_SURVIVOR_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_ec_reconstruct_survivor_bytes_total",
    "bytes of survivor spans read for cold reconstructs, by origin (local = "
    "a shard file here: the spans the decode uses and no spare, unless a "
    "read came short; remote = another server or the cold tier): over "
    "ec_reconstructions_total{kind=\"cold\"} it is what one reconstruct "
    "reads, data_shards spans",
)
EC_RECONSTRUCT_LOCAL_READS = REGISTRY.counter(
    "seaweedfs_tpu_ec_reconstruct_local_reads_total",
    "survivor spans a cold reconstruct read from shard files here, by where "
    "the read ran (worker = the executor thread that then decodes, straight "
    "into the decode's input array; loop = a thread that runs an event "
    "loop, where a read holds every other request up)",
)
EC_RECONSTRUCT_FILLS = REGISTRY.counter(
    "seaweedfs_tpu_ec_reconstruct_fills_total",
    "cold reconstructs' survivor rows filled, once a fill, by how (one_call "
    "= the first data_shards survivors read and copied into their rows in "
    "one native call, the interpreter lock given up once; row_by_row = a "
    "read or a copy a row, where the native library is missing)",
)
EC_RECONSTRUCT_BATCHES = REGISTRY.counter(
    "seaweedfs_tpu_ec_reconstruct_batches_total",
    "jobs of cold reconstructs handed to the executor together, one a job: "
    "one fill of every member's survivor rows, one decode call a decode "
    "matrix; ec_reconstructions_total{kind=cold} over it is the "
    "reconstructs a job",
)
# the worker thread of a cold reconstruct, round the whole of its work
# (fill + decode): wall without cpu is the worker waiting (the interpreter
# lock, the device, the disk); the rs.* and ec.read.pread leaves say where
EC_DEGRADED_READ_WORKER_SECONDS = REGISTRY.counter(
    "seaweedfs_tpu_ec_degraded_read_worker_seconds_total",
    "seconds of the executor thread that fills and decodes a cold "
    "reconstruct, by clock (wall = perf_counter, cpu = time.thread_time)",
)
EC_REBUILD_STAGE_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_ec_rebuild_stage_seconds",
    "rebuild_ec_files per-stage wall seconds, by stage (read/decode/write; "
    "pipelined stages overlap)",
)
EC_DEGRADED_READ_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_ec_degraded_read_seconds",
    "degraded EC interval read latency, by result (cold/cache_hit)",
)
EC_DECODE_MATRIX_CACHE = REGISTRY.counter(
    "seaweedfs_tpu_ec_decode_matrix_cache_total",
    "decode-matrix LRU lookups, by outcome (hit/miss)",
)

# anti-entropy plane (see docs/robustness.md "Anti-entropy plane"): the
# background scrub proves stored bytes still verify, replica digests catch
# diverged/stale copies, and the master's repair scheduler turns both into
# rebuilds/resyncs — each stage observable so a chaos run can assert the
# loop closed (corruption found -> repaired -> re-scrub clean)
SCRUB_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_scrub_bytes_total",
    "bytes read and verified by the scrubber, by kind (dat/idx/ec)",
)
SCRUB_CORRUPTIONS = REGISTRY.counter(
    "seaweedfs_tpu_scrub_corruptions_found_total",
    "latent damage found by scrub, by kind (needle_crc/needle_id/"
    "idx_extent/ec_data/ec_parity/ec_shard_size/ec_unidentified)",
)
SCRUB_PASSES = REGISTRY.counter(
    "seaweedfs_tpu_scrub_passes_total",
    "completed scrub passes, by plane (volume/ec)",
)
ANTIENTROPY_RESYNCS = REGISTRY.counter(
    "seaweedfs_tpu_antientropy_resyncs_total",
    "replica repairs dispatched by digest/scrub anti-entropy, by kind "
    "(tail_sync = catch-up append replay, recopy = full re-pull of a "
    "quarantined replica)",
)
REPAIR_QUEUE_DEPTH = REGISTRY.gauge(
    "seaweedfs_tpu_repair_queue_depth",
    "repair tasks currently queued on the master (fewest-survivors-first)",
)
ANTIENTROPY_DIVERGED = REGISTRY.gauge(
    "seaweedfs_tpu_antientropy_diverged_volumes",
    "volumes whose healthy replicas disagree on content digest with EQUAL "
    "append frontiers — divergence the tail path cannot fix (operator "
    "action: volume.fsck / re-replicate); refreshed every scheduler scan",
)
REPAIR_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_repair_seconds",
    "wall seconds per dispatched repair, by kind (ec_rebuild/replica_"
    "recopy/tail_sync/vacuum) and result (ok/error/skipped)",
)

# geo plane (see docs/robustness.md "Geo plane"): DC/rack-aware placement
# violations found by the master's anti-entropy scan, and the cross-cluster
# async replicator's applied/skipped/retried ledger + lag — the observable
# core of the "bounded-lag, zero-loss/zero-dup after heal" SLO
PLACEMENT_VIOLATIONS = REGISTRY.gauge(
    "seaweedfs_tpu_placement_violations",
    "volumes/EC volumes whose current holders violate placement policy, "
    "by kind (replica_spread = replicas packed below the ReplicaPlacement "
    "rack/DC spread, ec_domain = one failure domain holds more EC shards "
    "than the volume can lose); refreshed every anti-entropy scan",
)
GEO_EVENTS_APPLIED = REGISTRY.counter(
    "seaweedfs_tpu_geo_events_applied_total",
    "meta-log events applied on the peer cluster by the geo replicator, "
    "by type (create/update/delete/rename)",
)
GEO_EVENTS_SKIPPED = REGISTRY.counter(
    "seaweedfs_tpu_geo_events_skipped_total",
    "meta-log events the geo replicator skipped, by reason (dup = "
    "idempotency key already applied — the kill/restart replay shield, "
    "stale = behind the durable cursor, internal = bookkeeping paths)",
)
GEO_EVENTS_RETRIED = REGISTRY.counter(
    "seaweedfs_tpu_geo_events_retried_total",
    "geo replicator apply attempts that failed and were retried (WAN "
    "partition / peer outage shows up here, never as a skipped event)",
)
GEO_REPLICATION_LAG = REGISTRY.histogram(
    "seaweedfs_tpu_geo_replication_lag_seconds",
    "age of each applied event at apply time (primary append -> peer "
    "apply); p99 is the replication-lag SLO the soak scores",
    buckets=[0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120, 300],
)
GEO_FULL_RESYNC_REQUIRED = REGISTRY.counter(
    "seaweedfs_tpu_geo_full_resync_required_total",
    "times the replicator's cursor fell behind the primary meta-log "
    "retention (MetaLogTrimmed): events in the hole can never stream; "
    "the replicator halts LOUDLY and requires an operator full resync — "
    "it never silently skips the gap",
)

# object gateway (see docs/perf.md "Object gateway"): the S3/filer fast
# path gets the same itemized-stage treatment as the volume write path —
# every fast-tier PutObject partitions its handler wall into
# auth/meta/lease/upload/render (GETs into auth/meta/fetch/render), and
# the LIST path discloses how many store entries each request actually
# scanned (the O(max-keys)-not-O(bucket) claim, externally auditable)
S3_STAGE_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_s3_stage_seconds",
    "S3 gateway fast-path stage wall seconds, by verb and stage (PUT: "
    "auth/meta/lease/upload/render partition the handler wall; GET: "
    "auth/meta/fetch/render)",
)
S3_LIST_SCANNED = REGISTRY.counter(
    "seaweedfs_tpu_s3_list_scanned_entries_total",
    "filer-store entries pulled by ListObjects range scans (per-request "
    "work bound: O(max-keys + returned CommonPrefixes))",
)
S3_LIST_REQUESTS = REGISTRY.counter(
    "seaweedfs_tpu_s3_list_requests_total",
    "ListObjects requests served by the range-scan path",
)
CHUNK_BATCH_PUT_SIZE = REGISTRY.histogram(
    "seaweedfs_tpu_chunk_batch_put_size",
    "needles per batched fast-tier chunk PUT (POST /!batch/put — the "
    "filer upload gate's same-tick coalescing width)",
    buckets=[1, 2, 4, 8, 16, 32, 64],
)
FILER_CHUNK_DELETE_BATCHES = REGISTRY.counter(
    "seaweedfs_tpu_filer_chunk_delete_batches_total",
    "batched per-host chunk-delete RPC rounds drained by the filer GC, "
    "by result (ok/retry)",
)

# vacuum plane (see docs/perf.md "Vacuum plane"): compaction gets the same
# itemized treatment as the rebuild plane — per-stage walls of every
# extent-coalesced copy (pipelined read overlaps write, so stage sums can
# exceed total), the master's garbage-driven queue depth, and the shared
# maintenance budget's per-plane spend so the combined background I/O cap
# is externally auditable
VACUUM_STAGE_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_vacuum_stage_seconds",
    "compaction copy per-stage wall seconds, by stage (plan/read/write/"
    "verify/idx/total; pipelined stages overlap)",
)
VACUUM_QUEUE_DEPTH = REGISTRY.gauge(
    "seaweedfs_tpu_vacuum_queue_depth",
    "vacuum tasks currently queued on the master (highest-garbage-first)",
)
MAINTENANCE_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_maintenance_bytes_total",
    "bytes charged to the shared maintenance I/O budget, by plane "
    "(scrub/vacuum/repair)",
)

# overload control plane (see docs/robustness.md "Overload plane"): every
# admission decision, limit move, breaker transition and suppressed retry
# is counted so a brownout/overload run can assert HOW goodput survived —
# lowest-class-first shedding, breakers isolating the sick peer, retries
# capped at a fraction of successes — not just that it did
OVERLOAD_SHED = REGISTRY.counter(
    "seaweedfs_tpu_overload_shed_total",
    "requests shed by the admission gate, by server, priority class "
    "(read/write/meta/maint), tenant (top-K by heat + 'other' — see "
    "docs/robustness.md Tenant QoS) and reason (deadline = waited past "
    "the class's queue budget, queue_full = class's queue share "
    "exhausted, quota = tenant rate/byte token bucket dry)",
)
ADMISSION_QUEUE_DEPTH = REGISTRY.gauge(
    "seaweedfs_tpu_admission_queue_depth",
    "requests queued behind the adaptive concurrency limit, per server",
)
ADMISSION_LIMIT = REGISTRY.gauge(
    "seaweedfs_tpu_admission_limit",
    "live adaptive concurrency limit (AIMD on latency vs baseline), "
    "per server",
)
RETRIES_SUPPRESSED = REGISTRY.counter(
    "seaweedfs_tpu_retries_suppressed_total",
    "retries/hedges withheld by the shared RetryBudget (token bucket "
    "refilled by successes — no retry storms), by op",
)
CIRCUIT_TRANSITIONS = REGISTRY.counter(
    "seaweedfs_tpu_circuit_transitions_total",
    "circuit-breaker state transitions, by peer and target state",
)
CIRCUIT_OPEN = REGISTRY.gauge(
    "seaweedfs_tpu_circuit_open",
    "1 while a peer's circuit breaker is open (calls fail fast)",
)
MAINTENANCE_YIELDS = REGISTRY.counter(
    "seaweedfs_tpu_maintenance_pressure_yields_total",
    "maintenance budget consumes that yielded extra time to foreground "
    "pressure (admission gates shedding/queueing), by plane",
)

# lifecycle plane (see docs/perf.md "Lifecycle plane"): the hot→warm arc
# made observable — per-server aggregate access heat as sampled into
# heartbeats, the master's conversion queue depth, and every conversion
# the planner dispatched counted by direction and outcome, so an
# operator (and the bench's convergence leg) can assert the loop ran,
# drained, and did not flap
VOLUME_HEAT = REGISTRY.gauge(
    "seaweedfs_tpu_volume_heat",
    "per-server aggregate decayed access heat, by kind (read/write = "
    "normal volumes, ec_read = EC volumes); refreshed at the heartbeat "
    "digest tick",
)
LIFECYCLE_QUEUE_DEPTH = REGISTRY.gauge(
    "seaweedfs_tpu_lifecycle_queue_depth",
    "lifecycle conversion tasks currently queued on the master "
    "(coldest-first for auto-EC, hottest-first for re-inflation)",
)
LIFECYCLE_CONVERSIONS = REGISTRY.counter(
    "seaweedfs_tpu_lifecycle_conversions_total",
    "lifecycle conversions dispatched by the master planner, by "
    "direction (ec = hot→warm auto-encode, inflate = warm→hot "
    "re-inflation) and result (ok/error/skipped)",
)

# tenant QoS plane (see docs/robustness.md "Tenant QoS"): per-tenant
# admission visibility with BOUNDED label cardinality — tenant label
# values pass through util/tenancy.tenant_label (top-K by decayed heat +
# 'other'; retired tenants' series are purged via remove_label_value at
# the registry seam), so these families stay <= K+2 tenant values no
# matter how many principals the box serves
TENANT_QUEUE_DEPTH = REGISTRY.gauge(
    "seaweedfs_tpu_tenant_queue_depth",
    "requests queued behind the admission limit per tenant subqueue "
    "(deficit-round-robin within each priority class), by server, gate "
    "and tenant (top-K + other)",
)
TENANT_ADMITTED = REGISTRY.counter(
    "seaweedfs_tpu_tenant_admitted_total",
    "requests admitted by the gate per tenant (top-K + other), by "
    "server and tenant",
)
TENANT_ADMITTED_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_tenant_admitted_seconds",
    "server-side latency (admission wait + service) of admitted "
    "requests per tenant (top-K + other), by server and tenant",
)

# needle-index-at-scale plane (see docs/perf.md "Needle index at
# scale"): the out-of-core LSM needle map's memory story and mount
# behavior made observable — resident memtable bytes (the bound the map
# enforces), run counts (compaction health), how stale the snapshot a
# mount consumed was, and how many tail entries it had to replay past
# the fold frontier (the O(tail) claim, measurable in production)
NEEDLE_MAP_RESIDENT_BYTES = REGISTRY.gauge(
    "seaweedfs_tpu_needle_map_resident_bytes",
    "estimated resident memory held by needle-map memtables on this "
    "server, by map kind (the LSM map's byte bound; runs are mmap'd "
    "page cache and excluded on purpose)",
)
NEEDLE_MAP_RUN_COUNT = REGISTRY.gauge(
    "seaweedfs_tpu_needle_map_run_count",
    "immutable sorted runs currently backing needle maps on this "
    "server, by map kind (tiered merges keep this bounded)",
)
NEEDLE_MAP_SNAPSHOT_AGE = REGISTRY.gauge(
    "seaweedfs_tpu_needle_map_snapshot_age_seconds",
    "age of the persisted needle-map snapshot the most recent mount "
    "loaded, by map kind (how far behind the fold frontier was)",
)
NEEDLE_MAP_TAIL_REPLAY = REGISTRY.counter(
    "seaweedfs_tpu_needle_map_tail_replay_entries_total",
    "index entries replayed past the snapshot fold frontier at mount "
    "(the O(tail) mount cost actually paid)",
)

# metadata device-kernel plane (ISSUE 18, see docs/perf.md "Metadata
# device kernel"): the ragged-batch lookup arena made observable —
# what is pinned HBM-resident, how often whole gate wakeups run as one
# device dispatch vs fall back to host maps, and the identity-check
# verdicts that keep the arena an accelerator rather than an authority
NEEDLE_MAP_DEVICE_RESIDENT = REGISTRY.gauge(
    "seaweedfs_tpu_needle_map_device_resident_bytes",
    "bytes of sealed-run index columns pinned device-resident by the "
    "current DeviceColumnArena generation (LRU-bounded by "
    "SEAWEEDFS_TPU_ARENA_MB)",
)
NEEDLE_MAP_DEVICE_SEGMENTS = REGISTRY.gauge(
    "seaweedfs_tpu_needle_map_device_segments",
    "sealed segments resident in the current DeviceColumnArena "
    "generation (needle-map runs and filer path-spine segments share "
    "one arena)",
)
NEEDLE_MAP_DEVICE_DISPATCHES = REGISTRY.counter(
    "seaweedfs_tpu_needle_map_device_dispatches_total",
    "ragged-batch lookup dispatches answered on the device (one per "
    "gate wakeup routed to the arena, regardless of how many volumes "
    "or spine chains it spanned)",
)
NEEDLE_MAP_DEVICE_PROBES = REGISTRY.counter(
    "seaweedfs_tpu_needle_map_device_probes_total",
    "(key, segment) probe slots answered by ragged device dispatches "
    "(a key probing a 4-run volume counts 4)",
)
NEEDLE_MAP_DEVICE_FALLBACKS = REGISTRY.counter(
    "seaweedfs_tpu_needle_map_device_fallbacks_total",
    "gate flushes served by the host maps instead of the arena, by "
    "reason (cold arena, device absent, arena killed, oversize "
    "offsets)",
)
NEEDLE_MAP_DEVICE_UPLOADS = REGISTRY.counter(
    "seaweedfs_tpu_needle_map_device_uploads_total",
    "double-buffered arena generation uploads completed (each builds "
    "the next resident set while the previous keeps serving)",
)
NEEDLE_MAP_DEVICE_IDENTITY_MISMATCH = REGISTRY.counter(
    "seaweedfs_tpu_needle_map_device_identity_mismatch_total",
    "device answers that disagreed with the host map under the "
    "identity check (the host answer is served; any non-zero value is "
    "a kernel bug)",
)

# cold-tier plane (ISSUE 14, see docs/perf.md "Cold tier"): the
# hot→warm→cold arc's third band made observable — bytes moved between
# local disk and the remote backend by direction, per-holder recall
# walls (the latency a reheating volume pays before it is local again),
# and the remote read-through cache's hit economics (each miss is one
# ranged remote GET)
TIER_OFFLOAD_BYTES = REGISTRY.counter(
    "seaweedfs_tpu_tier_offload_bytes_total",
    "EC shard bytes moved between local disk and the remote cold-tier "
    "backend, by direction (offload = local→remote, recall = "
    "remote→local)",
)
TIER_RECALL_SECONDS = REGISTRY.histogram(
    "seaweedfs_tpu_tier_recall_seconds",
    "wall seconds one holder spent recalling a volume's offloaded "
    "shards back to local disk (download + rename + manifest commit + "
    "remote delete, per VolumeEcShardsRecall)",
)
TIER_REMOTE_CACHE_HITS = REGISTRY.counter(
    "seaweedfs_tpu_tier_remote_cache_hits_total",
    "reads of offloaded EC shards served from the byte-range "
    "read-through cache (no remote round trip)",
)
TIER_REMOTE_CACHE_MISSES = REGISTRY.counter(
    "seaweedfs_tpu_tier_remote_cache_misses_total",
    "reads of offloaded EC shards that paid a ranged remote GET "
    "(readahead-widened span fetched and cached)",
)

# metadata scale-out plane (ISSUE 15, see docs/perf.md "Metadata
# plane"): the prefix-sharded filer store's shape and churn, and the
# durable meta-log change feed's health
META_SHARD_OPS = REGISTRY.counter(
    "seaweedfs_tpu_meta_shard_ops_total",
    "filer-store operations routed through the prefix-sharded store, "
    "by op kind (find/find_many/list/insert/delete/delete_children)",
)
META_SHARD_COUNT = REGISTRY.gauge(
    "seaweedfs_tpu_meta_shard_count",
    "shards in the prefix-sharded filer store's committed shard map",
)
META_SHARD_REBALANCES = REGISTRY.counter(
    "seaweedfs_tpu_meta_shard_rebalances_total",
    "heat-driven shard-map rebalances committed (purge/copy/commit/"
    "cleanup moves of a directory band to the cooler neighbor)",
)
META_SHARD_MOVED = REGISTRY.counter(
    "seaweedfs_tpu_meta_shard_moved_entries_total",
    "filer entries copied between shards by rebalance moves",
)
META_FEED_EVENTS = REGISTRY.counter(
    "seaweedfs_tpu_meta_feed_events_total",
    "namespace change events appended to the durable meta-log "
    "change feed (segmented on-disk log)",
)
META_FEED_SEGMENTS = REGISTRY.gauge(
    "seaweedfs_tpu_meta_feed_segment_count",
    "on-disk segments currently retained by the durable meta log",
)
META_FEED_EVICTIONS = REGISTRY.counter(
    "seaweedfs_tpu_meta_feed_cache_evictions_total",
    "object-cache entries proactively evicted by change-feed events "
    "(overwrite/delete/rename seen before the next read, not by "
    "validate-on-hit)",
)

# metadata serving fleet (ISSUE 20, see docs/perf.md "Metadata fleet"):
# shard-range filer PROCESSES behind one crash-safe fleet map, the
# gate-batched write seam, and meta-log-fed read replicas
FLEET_FORWARDED = REGISTRY.counter(
    "seaweedfs_tpu_fleet_forwarded_total",
    "filer requests forwarded to the owning fleet member because the "
    "fleet map routes the path elsewhere, by op — zero-misroute never "
    "depends on client map freshness, the server-side hop is the "
    "authority",
)
FLEET_INGESTED = REGISTRY.counter(
    "seaweedfs_tpu_fleet_ingested_entries_total",
    "entries applied straight to the local store by FleetIngest "
    "(range-move copy/delta pages and directory-spine broadcasts)",
)
FLEET_MOVES = REGISTRY.counter(
    "seaweedfs_tpu_fleet_range_moves_total",
    "fleet range moves by outcome (committed/failed): a committed move "
    "re-homed a prefix range between two live filer processes under "
    "the fence-and-delta discipline",
)
META_WRITE_GATE_BATCHES = REGISTRY.counter(
    "seaweedfs_tpu_meta_write_gate_batches_total",
    "write-gate flushes: each one is ONE store round (insert_many) "
    "carrying every create/update enqueued in the same event-loop tick",
)
META_WRITE_GATE_WRITES = REGISTRY.counter(
    "seaweedfs_tpu_meta_write_gate_writes_total",
    "individual entry writes that rode a write-gate flush (writes / "
    "batches = the measured coalescing factor)",
)
FOLLOWER_EVENTS = REGISTRY.counter(
    "seaweedfs_tpu_meta_follower_events_total",
    "meta-log events a read replica applied to its local store, by "
    "type (upsert/delete/rename)",
)
FOLLOWER_REDIRECTS = REGISTRY.counter(
    "seaweedfs_tpu_meta_follower_redirects_total",
    "follower reads redirected to the primary because the caller's "
    "read-your-writes watermark (min_ts_ns) was ahead of the tail "
    "cursor",
)
ARENA_PREFETCH = REGISTRY.counter(
    "seaweedfs_tpu_arena_prefetch_total",
    "LSM flush-path arena residency hints, by result (queued = this "
    "hint scheduled the refresh, piggybacked = one was already queued, "
    "resident = already uploaded, no_arena = no device gate ever "
    "created an arena, unavailable = device absent or arena killed, "
    "error = hint path failed — never the flush itself)",
)
GEO_RESYNCS = REGISTRY.counter(
    "seaweedfs_tpu_geo_resyncs_total",
    "operator-driven geo full resyncs by outcome (ok/failed): a "
    "namespace re-seed from the primary after MetaLogTrimmed halted "
    "the tail",
)
GEO_RESYNCED_ENTRIES = REGISTRY.counter(
    "seaweedfs_tpu_geo_resynced_entries_total",
    "entries re-seeded onto the peer by geo full resyncs, by kind "
    "(upserted/pruned)",
)
GEO_TOMBSTONES = REGISTRY.counter(
    "seaweedfs_tpu_geo_tombstones_total",
    "geo tombstones written under /.seaweedfs/geo_tomb for replicated "
    "deletes/renames, by op (delete/rename) — the replay shield for "
    "destructive events whose target entry no longer exists",
)

# cold-tier follow-up (ISSUE 15 satellite): remote objects deleted by
# the master-dispatched orphan sweep — bytes leaked by crashes between
# manifest uncommit and remote delete, reclaimed (never data)
TIER_ORPHANS_SWEPT = REGISTRY.counter(
    "seaweedfs_tpu_tier_orphans_swept_total",
    "remote cold-tier objects deleted by the orphan sweep because no "
    "live .ctm manifest names them (past the grace age)",
)

def _process_start_epoch() -> float:
    """When the kernel started this process (Linux: field 22 of
    /proc/self/stat, in clock ticks since boot), so a start-up phase
    counts the interpreter's own start too; elsewhere, now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rpartition(")")[2].split()[19])
        with open("/proc/stat") as f:
            boot = next(
                int(line.split()[1]) for line in f if line.startswith("btime")
            )
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return _time.time()


_PROCESS_START = _process_start_epoch()
_STARTUP_AGES: dict = {}


def mark_startup(phase: str) -> None:
    """Set `startup_seconds{phase}` to this process's age now: a server
    calls it once as each start-up phase is done."""
    age = _time.time() - _PROCESS_START
    _STARTUP_AGES[phase] = age
    STARTUP_SECONDS.set(age, phase=phase)


def startup_line() -> str:
    """`phase=seconds` of every phase marked so far, in the order they
    were done: the ready log line's tail."""
    return " ".join(
        f"{phase}={age:.3f}"
        for phase, age in sorted(_STARTUP_AGES.items(), key=lambda kv: kv[1])
    )


# the registry seam the bounded-cardinality lint checks: every family
# that carries a `tenant` label MUST be listed here, or a retired
# tenant's series would survive the purge and grow cardinality without
# bound (tests/test_metrics_exposition.py pins this)
TENANT_LABELED_FAMILIES = (
    OVERLOAD_SHED,
    TENANT_QUEUE_DEPTH,
    TENANT_ADMITTED,
    TENANT_ADMITTED_SECONDS,
)
