"""Distributed tracing plane: cross-hop spans, tail-based sampling and an
always-on flight recorder (ISSUE 8 tentpole).

Every serving plane already exposes per-stage histograms, but those are
aggregates: when the open-loop p999 spikes, nothing connects one slow S3
PUT to the specific master lease, gate batch, volume append and replica
fan-out it rode. This module closes that attribution gap:

- **Context**: a W3C-traceparent-style (trace_id, span_id, sampled) triple
  carried through `contextvars`, so one request's identity follows it
  across awaits, `ensure_future` fan-outs and `call_soon` continuations.
  Propagation over HTTP rides a ``traceparent`` header
  (`util/fasthttp.py` client inject, `server/serving_core.py` server
  extract — byte-level parse, no regex) and over the gRPC seam via call
  metadata (`pb/rpc.py`), so master/volume/filer/S3 all join one trace.

- **Flight recorder**: finished spans land in a bounded per-process ring
  (`SEAWEEDFS_TPU_TRACE_RING` spans, default 4096) — always on, never
  growing, exported as JSONL at ``/debug/traces`` on every server and
  merged cluster-wide by the ``trace.dump`` shell command.

- **Tail-based sampling**: a configurable head fraction
  (`SEAWEEDFS_TPU_TRACE_SAMPLE`, default 0.01) is recorded up front, but
  the slow and weird requests are kept BY CONSTRUCTION even at sample=0:
  roots that exceed the live p99 (tracked in an allocation-free log
  histogram over every root request) are retro-promoted, and requests
  that touched an error / retry / hedge / injected fault are flagged on
  their context and promoted at finish. The unsampled fast path allocates
  NOTHING per request — no context object, no span — which the
  `serving.trace_overhead` bench leg asserts via the admission counters
  (ring admissions == spans of sampled+promoted requests, never one per
  request).

- **Span links**: batch seams (lookup gate, chunk-upload gate, group
  commit) amortize many requests into one flush; the flush records ONE
  span that adopts the first sampled member's trace and carries
  ``links`` to every member (trace_id, span_id), so per-request timelines
  show the shared work they rode.

- **Background planes**: scrub/vacuum/repair/anti-entropy open root spans
  tagged ``plane=...`` (`span_root`), and their dispatch RPCs inherit the
  context — serving-vs-maintenance interference is visible in one
  timeline.

- **Stages** (ISSUE 26): `stage(...)` binds one named stage of host work
  per site; ``with STAGE():`` then writes a `jax.profiler.TraceAnnotation`
  (an event on the profiler's own clock, beside the device plane), adds
  the wall to a `/metrics` counter child, and — under a sampled context —
  records a child span here. The RS dispatch, the encode pipeline and the
  degraded read are staged with it; docs/observability.md lists them.

The reference (weed/) has no tracing; the design follows the W3C Trace
Context wire format and Dapper-style in-process recording.
"""

from __future__ import annotations

import contextvars
import json
import os
import random
import sys
import threading
import time
from typing import Optional

from . import metrics as _metrics

# ---------------------------------------------------------------- context --

FLAG_ERROR = 1
FLAG_RETRY = 2
FLAG_HEDGE = 4
FLAG_FAULT = 8
FLAG_SHED = 16

_FLAG_NAMES = (
    (FLAG_ERROR, "error"),
    (FLAG_RETRY, "retry"),
    (FLAG_HEDGE, "hedge"),
    (FLAG_FAULT, "fault"),
    (FLAG_SHED, "shed"),
)


class SpanCtx:
    """One hop's identity: 128-bit trace id, 64-bit span id, sampled flag,
    plus the tail-sampling flags accumulated while the request ran."""

    __slots__ = ("trace_id", "span_id", "sampled", "flags")

    def __init__(self, trace_id: int, span_id: int, sampled: bool):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled
        self.flags = 0


_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "seaweedfs_tpu_trace", default=None
)

_rand = random.Random()


def _new_span_id() -> int:
    return _rand.getrandbits(64) or 1


def _new_trace_id() -> int:
    return _rand.getrandbits(128) or 1


def current() -> Optional[SpanCtx]:
    return _CTX.get()


def current_sampled() -> Optional[SpanCtx]:
    c = _CTX.get()
    return c if c is not None and c.sampled else None


def current_trace_hex() -> Optional[str]:
    """Hex trace id of the current SAMPLED context (metrics exemplars)."""
    c = _CTX.get()
    if c is None or not c.sampled:
        return None
    return "%032x" % c.trace_id


def flag(bit: int) -> None:
    """Mark the current trace as having touched an error/retry/hedge/fault
    — a no-op without a context (the zero-alloc unsampled path stays
    zero-alloc), a promotion trigger for unsampled-but-propagated ones."""
    c = _CTX.get()
    if c is not None:
        c.flags |= bit


# ------------------------------------------------------------- wire format --


def format_traceparent(ctx: SpanCtx) -> str:
    return "00-%032x-%016x-%s" % (
        ctx.trace_id, ctx.span_id, "01" if ctx.sampled else "00"
    )


def format_traceparent_bytes(ctx: SpanCtx) -> bytes:
    return format_traceparent(ctx).encode("ascii")


def parse_traceparent(raw) -> Optional[SpanCtx]:
    """Byte-level fast parse of a ``traceparent`` value ->
    SpanCtx(parent ids) or None on any malformation. Accepts str too
    (gRPC metadata values arrive as str)."""
    if raw is None:
        return None
    if isinstance(raw, str):
        raw = raw.encode("ascii", "replace")
    if len(raw) < 55:
        return None
    # 00-<32 hex>-<16 hex>-<2 hex>
    if raw[2] != 0x2D or raw[35] != 0x2D or raw[52] != 0x2D:
        return None
    try:
        trace_id = int(raw[3:35], 16)
        span_id = int(raw[36:52], 16)
        flags = int(raw[53:55], 16)
    except ValueError:
        return None
    if not trace_id or not span_id:
        return None
    return SpanCtx(trace_id, span_id, bool(flags & 1))


# ------------------------------------------------------------ the recorder --


def _env_float(name: str, default: str) -> float:
    try:
        return float(os.environ.get(name, default) or 0.0)
    except ValueError:
        return float(default)


class Recorder:
    """Per-process flight recorder: bounded span ring + sampling state.

    The ring only ever receives spans of sampled (head or promoted)
    traces; `admitted` counts ring writes and the per-reason counters
    partition where sampling decisions came from, so
    ``admitted == spans created for sampled traces`` is checkable from
    the outside (the no-per-request-allocation assertion)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.configure(
            enabled=(os.environ.get("SEAWEEDFS_TPU_TRACE", "1") or "1") != "0",
            sample=_env_float("SEAWEEDFS_TPU_TRACE_SAMPLE", "0.01"),
            capacity=int(
                _env_float("SEAWEEDFS_TPU_TRACE_RING", "4096") or 4096
            ),
        )

    def configure(
        self,
        enabled: Optional[bool] = None,
        sample: Optional[float] = None,
        capacity: Optional[int] = None,
        min_roots: int = 500,
    ) -> None:
        """(Re)configure and reset counters/ring — tests and the
        trace_overhead bench flip enabled/sample between phases."""
        with self._lock:
            if enabled is not None:
                self.enabled = enabled
            if sample is not None:
                self.sample = max(0.0, min(1.0, sample))
            if capacity is not None:
                self.capacity = max(16, capacity)
            self._ring: list = [None] * self.capacity
            self._n = 0
            self.admitted = 0
            self.dropped = 0
            self.sampled_roots = 0
            self.joined = 0
            self.promoted_slow = 0
            self.promoted_flagged = 0
            self.promoted_fault = 0
            # allocation-free root-latency log histogram (2x-wide
            # ns-bit-length buckets): feeds the live-p99 promotion
            # threshold for roots the head sampler skipped
            self._root_buckets = [0] * 64
            self._root_count = 0
            self._slow_ns = float("inf")
            # same threshold in SECONDS as a plain attribute, so the
            # serving-core hot path can do one float compare instead of
            # an is_slow() method call per request
            self.slow_s = float("inf")
            self.min_roots = min_roots

    reset = configure  # alias: tests call RECORDER.reset()

    # --- sampling ---
    def head_sample(self) -> bool:
        return self.sample > 0.0 and _rand.random() < self.sample

    def note_root(self, dt_seconds: float) -> None:
        """Record one root request's wall into the p99 tracker — int ops
        only, no allocation (runs on EVERY request when tracing is
        enabled, sampled or not)."""
        ns = int(dt_seconds * 1e9)
        b = ns.bit_length()
        if b > 63:
            b = 63
        self._root_buckets[b] += 1
        self._root_count += 1
        if self._root_count & 0xFF == 0:
            self._recompute_slow()

    def _recompute_slow(self) -> None:
        total = self._root_count
        if total < self.min_roots:
            return
        target = total * 0.99
        acc = 0
        for i, c in enumerate(self._root_buckets):
            acc += c
            if acc >= target:
                # promote only past the bucket's UPPER edge (bucket i
                # holds bit_length==i, i.e. [2^(i-1), 2^i)): the gate
                # lands between p99 and 2*p99 of observed roots, so
                # promotions stay a sub-1% tail, never a steady stream
                self._slow_ns = float(1 << i)
                self.slow_s = self._slow_ns / 1e9
                return

    def is_slow(self, dt_seconds: float) -> bool:
        return dt_seconds * 1e9 > self._slow_ns

    # --- recording ---
    def record(self, span: dict) -> None:
        with self._lock:
            i = self._n % self.capacity
            if self._ring[i] is not None:
                self.dropped += 1
            self._ring[i] = span
            self._n += 1
            self.admitted += 1

    def promote_slow(self, name: str, dt: float, **tags) -> None:
        """Retro-record a root span for an untraced request that finished
        past the live p99 — the tail kept by construction."""
        self.promoted_slow += 1
        ctx = SpanCtx(_new_trace_id(), _new_span_id(), True)
        self.record(
            _span_dict(
                ctx, 0, name, time.time() - dt, dt,
                dict(tags, promoted="slow"), None, None,
            )
        )

    def promote_fault(
        self, name: str, kind: str, dur: float = 0.0, **tags
    ) -> None:
        """Retro-record a root span for an untraced request that hit the
        fault-injection seam (promotion even at sample=0). `dur`: the
        seconds up to now the span covers, where the fault has a length
        (a stall of the serving loop)."""
        self.promoted_fault += 1
        ctx = SpanCtx(_new_trace_id(), _new_span_id(), True)
        self.record(
            _span_dict(
                ctx, 0, name, time.time() - dur, dur,
                dict(tags, promoted="fault", fault=kind), None, None,
            )
        )

    # --- export ---
    def spans(self) -> list:
        """Ring contents, oldest first."""
        with self._lock:
            n, cap = self._n, self.capacity
            if n <= cap:
                return [s for s in self._ring[:n] if s is not None]
            i = n % cap
            return [s for s in self._ring[i:] + self._ring[:i] if s is not None]

    def dump_jsonl(self) -> str:
        return "".join(json.dumps(s) + "\n" for s in self.spans())

    def status(self) -> dict:
        return {
            "enabled": self.enabled,
            "sample": self.sample,
            "capacity": self.capacity,
            "spans_in_ring": min(self._n, self.capacity),
            "admitted": self.admitted,
            "dropped": self.dropped,
            "sampled_roots": self.sampled_roots,
            "joined": self.joined,
            "promoted_slow": self.promoted_slow,
            "promoted_flagged": self.promoted_flagged,
            "promoted_fault": self.promoted_fault,
            "roots_seen": self._root_count,
            "slow_threshold_ms": (
                round(self._slow_ns / 1e6, 3)
                if self._slow_ns != float("inf")
                else None
            ),
        }


RECORDER = Recorder()


def _span_dict(
    ctx: SpanCtx,
    parent_id: int,
    name: str,
    start: float,
    dur: float,
    tags: Optional[dict],
    links,
    err: Optional[str],
) -> dict:
    d = {
        "trace": "%032x" % ctx.trace_id,
        "span": "%016x" % ctx.span_id,
        "parent": ("%016x" % parent_id) if parent_id else None,
        "name": name,
        "start": round(start, 6),
        "dur_us": round(dur * 1e6, 1),
    }
    if tags:
        d["tags"] = tags
    if links:
        d["links"] = [
            {"trace": "%032x" % t, "span": "%016x" % s} for t, s in links
        ]
    if err:
        d["err"] = err
    if ctx.flags:
        d["flags"] = [n for b, n in _FLAG_NAMES if ctx.flags & b]
    return d


# ---------------------------------------------------------------- spans --


class ActiveSpan:
    """A request-scoped span: installs its context on construction,
    records (when sampled, or promoted via flags) and restores the outer
    context on finish(). Built by `begin_request`."""

    __slots__ = ("name", "ctx", "parent_id", "tags", "start", "_t0", "_token")

    def __init__(self, name: str, ctx: SpanCtx, parent_id: int, tags: dict):
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.tags = tags
        self.start = time.time()
        self._t0 = time.perf_counter()
        self._token = _CTX.set(ctx)

    def tag(self, key: str, value) -> None:
        self.tags[key] = value

    def drop(self) -> None:
        """Restore the outer context WITHOUT recording — for requests
        that turn out to be proxied (FALLBACK): the µs fast-tier
        hand-off wall is not the request, and a head-sampled root here
        would be an orphan (the replay carries the client's original
        headers, not this span's identity)."""
        try:
            _CTX.reset(self._token)
        except ValueError:
            pass

    def finish(self, err: Optional[BaseException] = None) -> float:
        try:
            _CTX.reset(self._token)
        except ValueError:
            pass  # finished from a different context (detached completion)
        ctx = self.ctx
        dur = time.perf_counter() - self._t0
        if err is not None:
            ctx.flags |= FLAG_ERROR
        rec = RECORDER
        if ctx.sampled:
            rec.record(
                _span_dict(
                    ctx, self.parent_id, self.name, self.start, dur,
                    self.tags, None, str(err) if err else None,
                )
            )
        elif ctx.flags:
            # tail promotion: an unsampled-but-propagated request touched
            # an error/retry/hedge/fault — keep it
            ctx.sampled = True
            rec.promoted_flagged += 1
            rec.record(
                _span_dict(
                    ctx, self.parent_id, self.name, self.start, dur,
                    dict(self.tags, promoted="flagged"), None,
                    str(err) if err else None,
                )
            )
        return dur


def begin_request(
    name: str, parent: Optional[SpanCtx] = None, **tags
) -> Optional[ActiveSpan]:
    """Server-side entry point (HTTP fast tier, gRPC handlers, aiohttp
    middleware). Joins `parent` when given (sampled or not — unsampled
    joins still carry flags for tail promotion); with parent=None the
    CALLER has already won the head-sample coin (`RECORDER.head_sample`)
    and this starts a sampled root. The untraced fast path therefore
    never reaches this function — the coin is two comparisons and no
    allocation at the call site."""
    rec = RECORDER
    if not rec.enabled:
        return None
    if parent is not None:
        ctx = SpanCtx(parent.trace_id, _new_span_id(), parent.sampled)
        rec.joined += 1
        return ActiveSpan(name, ctx, parent.span_id, tags)
    rec.sampled_roots += 1
    ctx = SpanCtx(_new_trace_id(), _new_span_id(), True)
    return ActiveSpan(name, ctx, 0, tags)


class _NullSpan:
    """Shared no-op context manager for the unsampled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, key, value) -> None:
        pass

    def link(self, ctx) -> None:
        pass


_NULL = _NullSpan()
NULL_SPAN = _NULL  # public no-op CM for conditional span sites


class _SpanCM:
    """Child-span context manager (``with trace.span("filer.lease"):``).
    Only built when the current context is sampled; installs a child
    context for the duration so downstream hops parent correctly."""

    __slots__ = ("name", "ctx", "parent_id", "tags", "links", "start",
                 "_t0", "_token")

    def __init__(self, name: str, ctx: SpanCtx, parent_id: int, tags: dict):
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.tags = tags
        self.links: Optional[list] = None

    def tag(self, key: str, value) -> None:
        self.tags[key] = value

    def link(self, ctx: SpanCtx) -> None:
        if self.links is None:
            self.links = []
        self.links.append((ctx.trace_id, ctx.span_id))

    def __enter__(self):
        self.start = time.time()
        self._t0 = time.perf_counter()
        self._token = _CTX.set(self.ctx)
        return self

    def __exit__(self, et, ev, tb):
        try:
            _CTX.reset(self._token)
        except ValueError:
            pass
        RECORDER.record(
            _span_dict(
                self.ctx, self.parent_id, self.name, self.start,
                time.perf_counter() - self._t0, self.tags, self.links,
                str(ev) if ev is not None else None,
            )
        )
        return False


def span(name: str, **tags):
    """In-process child span of the current context. Returns a shared
    no-op when untraced/unsampled — safe on hot paths."""
    c = _CTX.get()
    if c is None or not c.sampled or not RECORDER.enabled:
        return _NULL
    child = SpanCtx(c.trace_id, _new_span_id(), True)
    return _SpanCM(name, child, c.span_id, tags)


def span_root(name: str, **tags):
    """Always-recorded root span for background planes (scrub, vacuum,
    repair, anti-entropy): tag ``plane=...`` so maintenance work shows up
    in the same timeline as the serving traces it interferes with.
    Dispatch RPCs made inside inherit the context."""
    if not RECORDER.enabled:
        return _NULL
    ctx = SpanCtx(_new_trace_id(), _new_span_id(), True)
    return _SpanCM(name, ctx, 0, tags)


def batch_span(name: str, members: list, **tags):
    """Flush span for a batch seam (lookup gate / chunk-upload gate /
    group commit): adopts the FIRST sampled member's trace (so merging by
    trace_id finds it) and links every member context, making the
    amortized work visible from each rider's timeline. `members` is the
    list of sampled member SpanCtx objects captured at enqueue; no-op
    when none were sampled."""
    if not members or not RECORDER.enabled:
        return _NULL
    first = members[0]
    ctx = SpanCtx(first.trace_id, _new_span_id(), True)
    cm = _SpanCM(name, ctx, first.span_id, dict(tags, members=len(members)))
    for m in members:
        cm.link(m)
    return cm


def note_fault(name: str, kind: str, **tags) -> None:
    """Fault-seam hook: flag the current trace, or — when the request is
    untraced (sample=0, no upstream header) — retro-promote a root span
    so injected faults are ALWAYS kept (the e2e acceptance invariant)."""
    rec = RECORDER
    if not rec.enabled:
        return
    c = _CTX.get()
    if c is not None:
        c.flags |= FLAG_FAULT
        return
    rec.promote_fault(name, kind, **tags)


def note_shed(name: str, **tags) -> None:
    """Admission-gate hook: a shed request flags its trace (joined from
    the caller's traceparent) or retro-promotes a root — load-shedding
    decisions are kept by the tail sampler even at sample=0, exactly
    like injected faults. No-op (one attr load) while the recorder is
    off, so the µs shed path stays µs."""
    rec = RECORDER
    if not rec.enabled:
        return
    c = _CTX.get()
    if c is not None:
        c.flags |= FLAG_SHED
        return
    rec.promote_fault(name, "shed", **tags)


# ---------------------------------------------------------------- stages --

_perf = time.perf_counter
_TRACE_ME = None


def _trace_me():
    """`jax.profiler.TraceAnnotation`, once this process has imported jax
    by itself; None for a process that never does (master, filer), whose
    stages are counters alone."""
    global _TRACE_ME
    if _TRACE_ME is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _TRACE_ME = TraceAnnotation
    return _TRACE_ME


class Stage:
    """One named stage of host work, bound once per site (at import or
    construction, like `ServingCore.count`'s children) and entered as
    ``with STAGE():``. One pair of clock reads then feeds three sinks:

    - a `jax.profiler.TraceAnnotation(name)` around the block, so the
      stage is an event on the `/host:CPU` plane of whatever profiler
      trace is being taken, on the clock the device plane is on
      (`annotate=False` for a WAIT: an event around a wait, or one held
      across an `await`, would cover other work and name nothing);
    - `seconds` (a `/metrics` counter child) += the wall, `calls` += 1
      where the site has no count of its own (a leaf inside a counted
      stage passes neither and is an event alone);
    - a child span in the flight recorder when this thread's context is
      sampled, so a slow request in `/debug/traces` shows its stages.

    `sink`, when a run passes one, also gets ``sink.add(label, seconds)``
    (the per-run budget of `write_ec_files`). With no profiler running and
    the request unsampled a stage costs two clock reads, one inactive
    TraceMe and one counter add."""

    __slots__ = ("name", "label", "_seconds", "_calls", "_annotate")

    def __init__(self, name: str, seconds=None, calls=None,
                 annotate: bool = True, label: Optional[str] = None):
        self.name = name
        self.label = label or name
        self._seconds = seconds
        self._calls = calls
        self._annotate = annotate

    def __call__(self, sink=None) -> "_StageCM":
        return _StageCM(self, sink)

    def since(self, t0: float, sink=None) -> float:
        """Add the seconds since `t0` (a `perf_counter` reading) and
        return now: for a wait that starts on one thread and ends on
        another, which no `with` block can hold. Counter only."""
        now = _perf()
        self._add(now - t0, sink)
        return now

    def _add(self, dt: float, sink) -> None:
        if self._seconds is not None:
            self._seconds.inc(dt)
        if self._calls is not None:
            self._calls.inc()
        if sink is not None:
            sink.add(self.label, dt)


class _StageCM:
    __slots__ = ("_stage", "_sink", "_t0", "_event", "_span")

    def __init__(self, st: Stage, sink):
        self._stage = st
        self._sink = sink

    def __enter__(self):
        st = self._stage
        self._span = None
        c = _CTX.get()
        if c is not None and c.sampled:
            sp = span(st.name)
            if sp is not _NULL:
                self._span = sp
                sp.__enter__()
        self._event = None
        if st._annotate:
            trace_me = _TRACE_ME or _trace_me()
            if trace_me is not None:
                self._event = trace_me(st.name)
                self._event.__enter__()
        self._t0 = _perf()
        return self

    def tag(self, key: str, value) -> None:
        """A tag on the stage's child span, where the request is sampled."""
        if self._span is not None:
            self._span.tag(key, value)

    def __exit__(self, et, ev, tb):
        dt = _perf() - self._t0
        if self._event is not None:
            self._event.__exit__(et, ev, tb)
        if self._span is not None:
            self._span.__exit__(et, ev, tb)
        self._stage._add(dt, self._sink)
        return False


stage = Stage  # ``trace.stage(name, seconds_child, ...)`` at a site


def mark(name: str, **tags) -> None:
    """A point on the profiler's clock: an event entered and left at once,
    for something that is known only when it is over (a stall of the loop).
    Nothing in a process that never imported jax."""
    trace_me = _TRACE_ME or _trace_me()
    if trace_me is not None:
        with trace_me(name, **tags):
            pass


# ------------------------------------------------------------ collections --


class GcWatch:
    """The one `gc.callbacks` hook of a process: a collection's wall into
    `gc_pause_seconds_total{generation}`, its count beside it, and a
    `gc.gen<N>` event from start to stop (same thread, synchronous), so a
    collection under a device-idle gap names it. `seconds` is the sum over
    all generations as a plain attribute: the stall recorder reads a
    stall's share of it without a scrape."""

    def __init__(self):
        self.seconds = 0.0
        self._t0 = 0.0
        self._event = None
        self._children = [
            (
                _metrics.GC_PAUSE_SECONDS.child(generation=str(g)),
                _metrics.GC_COLLECTIONS.child(generation=str(g)),
            )
            for g in range(3)
        ]
        for seconds, count in self._children:
            # on /metrics before the first collection
            seconds.inc(0.0)
            count.inc(0.0)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            trace_me = _TRACE_ME or _trace_me()
            if trace_me is not None:
                self._event = trace_me("gc.gen%d" % info["generation"])
                self._event.__enter__()
            self._t0 = _perf()
            return
        dt = _perf() - self._t0
        if self._event is not None:
            self._event.__exit__(None, None, None)
            self._event = None
        self.seconds += dt
        seconds, count = self._children[info["generation"]]
        seconds.inc(dt)
        count.inc()


GC_WATCH: Optional[GcWatch] = None


def watch_gc() -> GcWatch:
    """Install the process's GcWatch, once; every server's start asks."""
    global GC_WATCH
    if GC_WATCH is None:
        import gc

        GC_WATCH = GcWatch()
        gc.callbacks.append(GC_WATCH)
    return GC_WATCH


# exemplar hook: histograms ask for the live sampled trace id at observe
# time (metrics.py must not import trace — this wiring keeps the
# dependency one-way)
_metrics._exemplar_fn = current_trace_hex
