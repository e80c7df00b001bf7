"""Shared HTTP serving core: one byte-level fast tier + one aiohttp cold tier.

Factored out of the volume server's start() (ISSUE 7 tentpole) so every
HTTP-facing server — volume, master, filer, S3 gateway — runs the same
two-tier shape instead of re-wiring it by hand:

- the PUBLIC port is owned by a `util/fasthttp.FastHTTPServer` whose
  handler is the server's fast tier (zero-copy body handoff, pre-rendered
  heads, slim request queue — the data plane);
- the full aiohttp application listens on an INTERNAL loopback port and
  receives every request the fast tier does not fully understand
  (FALLBACK replay keeps the two tiers semantically identical);
- the server-side HTTP fault seam (`util/faults.py`) fires here, so the
  existing fault plans — latency, brownout, reset, http_error, crash —
  apply to gateway/filer/master requests exactly like they already did to
  the client seam. The seam op is ``http:<METHOD>`` with the LISTENING
  address as target, i.e. a plan rule like
  ``FaultRule(op="http:GET", target="*:8333", fault="latency", ...)``
  brownouts the S3 gateway's served reads. NOTE the deliberate
  consequence for IN-CLUSTER hops: a request one of our own clients
  sends to one of our own servers consults the plan twice (client seam
  at `FastHTTPClient.request`, server seam here), so a rule targeting a
  serving address injects on both sides and burns two `nth` matches per
  such request — the peer degrades AND the network to it degrades,
  which is what a real brownout looks like. Pin `target` to an address
  only one seam sees (or use distinct rules) when single-fire matters;
- per-method request counters (`seaweedfs_tpu_request_total{server=...}`)
  with pre-bound children, shared by the sync-return path and DETACHED
  completions;
- the distributed-tracing plane (ISSUE 8, `util/trace.py`): the fast
  tier extracts ``traceparent`` (byte-level parse) or head-samples a new
  root, times EVERY root into the live-p99 tracker, and tail-promotes
  untraced requests that finish past it or hit the fault seam — the slow
  and weird requests are kept even at sample=0, while the untraced fast
  path allocates nothing per request;
- a uniform observability surface on the cold tier of every server type:
  ``/metrics`` (Prometheus exposition + exemplars), ``/debug/traces``
  (flight-recorder JSONL, ``?status=1`` for counters) and the on-demand
  ``/debug/pprof/{start,stop,dump,profile,heap,device}`` handlers. These paths
  are reserved: the fast tiers FALLBACK them, and the middleware answers
  before any route (including the S3 bucket router) sees them.
"""

from __future__ import annotations

import asyncio
import functools
import os
import resource
import selectors
import threading
import time
import weakref
from typing import Optional

from aiohttp import web

from ..util import faults, overload, tenancy, trace
from ..util.fasthttp import (
    DETACHED,
    FALLBACK,
    FastHTTPServer,
    TierStages,
    render_response,
)
from ..util.metrics import (
    EVENT_LOOP_CPU_SECONDS,
    EVENT_LOOP_LAG_SECONDS,
    EVENT_LOOP_LAG_TICKS,
    EVENT_LOOP_SELECT_SECONDS,
    EVENT_LOOP_STALL_KERNEL_SECONDS,
    EVENT_LOOP_STALL_SECONDS,
    EVENT_LOOP_STALLS,
    EVENT_LOOP_TURN_SECONDS,
    EVENT_LOOP_TURNS,
    REQUEST_COUNTER,
    REQUEST_HISTOGRAM,
    REQUEST_WAIT_SECONDS,
)

# bound once: _dispatch pays these per request at serving QPS rates
_perf = time.perf_counter
_coin = trace._rand.random
_classify = overload.classify_method
_set_tenant = tenancy.set_current
_reset_tenant = tenancy.reset_current

LOOP_LAG_PROBE_SECONDS = 0.010
# a tick this late (four ticks) is a stall of the loop: counted, and kept as
# a `loop.stall` span with the kernel's account of the interval
LOOP_STALL_SECONDS = 0.040
# the kernel's account is read anew every tenth tick, so a stall's deltas
# cover at most 100 ms before it
_BASELINE_SECONDS = 10 * LOOP_LAG_PROBE_SECONDS


class LoopClock(selectors.DefaultSelector):
    """The selector of a serving loop, reading the clock on both sides of
    the system call: where the loop's second goes. `select` is the wall
    inside it (`poll` where the loop passed timeout 0 because callbacks
    were ready: the kernel returns at once, so that wall is the loop
    taking the interpreter lock BACK after giving it up at the call;
    `wait` otherwise: idle until an event), a turn the wall from one
    select's return to the next one's call. The sums are plain attributes;
    `publish` moves them to /metrics with the thread's CPU time, so turn +
    select is the loop's wall at every reading. Made on the thread that
    runs the loop, as `asyncio.run(..., loop_factory=new_event_loop)`
    makes it."""

    def __init__(self):
        super().__init__()
        self.turns = 0
        self.turn_s = self.poll_s = self.wait_s = 0.0
        self._returned = _perf()
        self._cpu = time.thread_time()

    def select(self, timeout=None):
        t0 = _perf()
        self.turn_s += t0 - self._returned
        self.turns += 1
        events = _select(self, timeout)  # no super() lookup a turn
        t1 = self._returned = _perf()
        if timeout == 0:
            self.poll_s += t1 - t0
        else:
            self.wait_s += t1 - t0
        return events

    def publish(self) -> dict:
        """On the loop's thread (a probe's tick, the /metrics render).
        Returns what it moved, in ms: the loop's own account of the time
        since the last publish, which a stall's span carries."""
        now = _perf()
        cpu = time.thread_time()
        turn_s = self.turn_s + now - self._returned  # this turn so far
        moved = {
            "loop_cpu_ms": round((cpu - self._cpu) * 1e3, 3),
            "loop_turn_ms": round(turn_s * 1e3, 3),
            "loop_poll_ms": round(self.poll_s * 1e3, 3),
            "loop_wait_ms": round(self.wait_s * 1e3, 3),
            "loop_turns": self.turns,
        }
        _LOOP_TURN.inc(turn_s)
        _LOOP_TURNS.inc(self.turns)
        _LOOP_POLL.inc(self.poll_s)
        _LOOP_WAIT.inc(self.wait_s)
        _LOOP_CPU.inc(cpu - self._cpu)
        self.turns = 0
        self.turn_s = self.poll_s = self.wait_s = 0.0
        self._returned, self._cpu = now, cpu
        return moved


_select = selectors.DefaultSelector.select
_LOOP_TURN = EVENT_LOOP_TURN_SECONDS.child()
_LOOP_TURNS = EVENT_LOOP_TURNS.child()
_LOOP_POLL = EVENT_LOOP_SELECT_SECONDS.child(mode="poll")
_LOOP_WAIT = EVENT_LOOP_SELECT_SECONDS.child(mode="wait")
_LOOP_CPU = EVENT_LOOP_CPU_SECONDS.child()
_STALLS = EVENT_LOOP_STALLS.child()
_STALL_SECONDS = EVENT_LOOP_STALL_SECONDS.child()


# ---- the kernel's account of an interval: each source returns cumulative
# readings by tag (`*_s` seconds, the rest counts) and raises OSError on a
# host that lacks it
class _KeptFile:
    """A /proc or cgroup file read anew by ONE `pread` of a descriptor kept
    open: a read on the loop's thread gives the interpreter lock up once,
    where open + fstat + read + read + close would five times (on the
    chip's host, beside a thread that holds the lock, 5 ms against 16)."""

    def __init__(self, path: str):
        self.path = path
        self._fd: Optional[int] = None

    def read(self) -> bytes:
        if self._fd is None:
            self._fd = os.open(self.path, os.O_RDONLY)
            weakref.finalize(self, os.close, self._fd)
        return os.pread(self._fd, 8192, 0)


@functools.cache
def _cgroup_cpu_stat() -> str:
    """The process's cgroup's cpu.stat (v2: `0::<path>`)."""
    with open("/proc/self/cgroup") as f:
        for line in f:
            if line.startswith("0::"):
                return "/sys/fs/cgroup" + line[3:].strip().rstrip("/") + "/cpu.stat"
    raise FileNotFoundError("no cgroup v2 entry")


_CLK_TCK = os.sysconf("SC_CLK_TCK")


def kernel_sources() -> dict:
    """The five sources of a LoopWatch, by name. Made on the loop's thread
    (`/proc/thread-self` is the opener's)."""
    stat = _KeptFile("/proc/stat")
    schedstat = _KeptFile("/proc/thread-self/schedstat")
    pressure = _KeptFile("/proc/pressure/cpu")
    cpu_stat: list = []  # the cgroup's file, looked up at the first read

    def steal() -> dict:
        # cpu user nice system idle iowait irq softirq STEAL ..., all CPUs
        return {"steal_s": int(stat.read().split(None, 9)[8]) / _CLK_TCK}

    def throttled() -> dict:
        if not cpu_stat:
            cpu_stat.append(_KeptFile(_cgroup_cpu_stat()))
        for line in cpu_stat[0].read().splitlines():
            if line.startswith(b"throttled_usec"):
                return {"throttled_s": int(line.split()[1]) / 1e6}
        raise FileNotFoundError("cpu.stat has no throttled_usec")

    def runqueue() -> dict:
        # of the opening thread: on-CPU ns, run-queue delay ns, timeslices
        return {"runqueue_s": int(schedstat.read().split()[1]) / 1e9}

    def pressure_some() -> dict:
        some = pressure.read().split(b"\n", 1)[0]
        return {"pressure_s": int(some.rsplit(b"total=", 1)[1]) / 1e6}

    def rusage() -> dict:
        # the process's own account: CPU of all its threads, faults, switches
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {"process_cpu_s": time.process_time(), "minflt": ru.ru_minflt,
                "majflt": ru.ru_majflt, "nivcsw": ru.ru_nivcsw}

    return {"steal": steal, "throttled": throttled, "runqueue": runqueue,
            "pressure": pressure_some, "rusage": rusage}


# the sources with a child of event_loop_stall_kernel_seconds_total
_KERNEL_SECONDS = ("steal", "throttled", "runqueue")


class LoopWatch:
    """One a LOOP, shared by the lag probes of every ServingCore on it:
    the loop's clock (None for a loop the CLI did not make) and the stall
    recorder. A tick LOOP_STALL_SECONDS late is a stall, counted once
    whichever probes saw it, with what the kernel counted meanwhile and
    what the loop's clock did, as deltas against a baseline refreshed at
    most every 100 ms while ticks run (never on an idle server): a ROOT
    span `loop.stall` in the flight recorder (kept at any sampling rate,
    as a shed is), and a `loop.stall` mark on the profiler's clock where
    the stall ENDED."""

    def __init__(self, clock: Optional[LoopClock] = None, sources=None):
        self.clock = clock
        self._sources = sources  # None: kernel_sources(), at the first tick
        self._kernel: dict = {}  # source -> its kernel_seconds child
        self._base: Optional[dict] = None
        self._base_at = float("-inf")
        self._stalled_until = float("-inf")
        _STALLS.inc(0.0)  # on /metrics before the first stall
        _STALL_SECONDS.inc(0.0)

    def _account(self) -> dict:
        if self._sources is None:
            self._sources = kernel_sources()
        seen = {}
        for name, read in list(self._sources.items()):
            try:
                seen.update(read())
            except (OSError, ValueError, IndexError):
                del self._sources[name]  # the host lacks it: left out
                continue
            if name in _KERNEL_SECONDS and name not in self._kernel:
                child = EVENT_LOOP_STALL_KERNEL_SECONDS.child(source=name)
                child.inc(0.0)  # on /metrics before the first stall
                self._kernel[name] = child
        if trace.GC_WATCH is not None:
            seen["gc_s"] = trace.GC_WATCH.seconds
        return seen

    def tick(self, due: float, now: float, late: float) -> None:
        """A probe's tick, due at `due`, ran at `now` (the loop's clock)."""
        if late >= LOOP_STALL_SECONDS:
            if due > self._stalled_until:  # else another probe's: the same
                self._stall(now, late)
            self._stalled_until = now
        elif now - self._base_at >= _BASELINE_SECONDS:
            self._base, self._base_at = self._account(), now
            if self.clock is not None:
                self.clock.publish()

    def _stall(self, now: float, late: float) -> None:
        _STALLS.inc()
        _STALL_SECONDS.inc(late)
        tags = {"late_ms": round(late * 1e3, 3),
                "threads": threading.active_count(),
                "cpus": os.cpu_count()}  # steal and pressure sum all of them
        base, age = self._base, now - self._base_at
        seen = self._account()
        self._base, self._base_at = seen, now
        if self.clock is not None:
            # what the loop itself did meanwhile: on a CPU all the while (a
            # long turn of its own work), in a turn without one (held off
            # at a system call inside a callback), or in a poll (held off
            # at select)
            tags.update(self.clock.publish())
        if base is not None:
            tags["baseline_age_ms"] = round(age * 1e3, 3)
            for key, value in seen.items():
                if key not in base:
                    continue
                delta = value - base[key]
                if key.endswith("_s"):
                    tags[key[:-2] + "_ms"] = round(delta * 1e3, 3)
                    child = self._kernel.get(key[:-2])
                    if child is not None:
                        child.inc(min(max(delta, 0.0), late))
                else:
                    tags[key] = delta
        if trace.RECORDER.enabled:
            trace.RECORDER.promote_fault("loop.stall", "stall", late, **tags)
        trace.mark("loop.stall", late_ms=tags["late_ms"])


_WATCHES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def new_event_loop() -> asyncio.AbstractEventLoop:
    """`loop_factory` of every server the CLI runs: a selector loop whose
    selector is a LoopClock."""
    clock = LoopClock()
    loop = asyncio.SelectorEventLoop(clock)
    _WATCHES[loop] = LoopWatch(clock)
    return loop


def loop_watch(loop) -> LoopWatch:
    watch = _WATCHES.get(loop)
    if watch is None:
        watch = _WATCHES[loop] = LoopWatch()
    return watch


class LoopLagProbe:
    """A callback on the serving loop, re-armed every 10 ms while requests
    keep arriving, that adds how late it ran to
    `event_loop_lag_seconds_total{server}` and counts its ticks: the one
    reading of the time a request spends in the socket before the loop
    parses it, which `req.t_arrive` cannot see. A request arms it
    (`kick`); a tick that saw no request since the last one does not
    re-arm, so an idle server has no timer (on the chip's host two cores
    ticking an idle loop cost a conversion 3 % more CPU: PERF.md, PR 26).
    It ends with the tier it measures (`alive`), whoever stops that. Each
    tick is also handed to the loop's LoopWatch."""

    def __init__(self, server: str, alive):
        self._seconds = EVENT_LOOP_LAG_SECONDS.child(server=server)
        self._ticks = EVENT_LOOP_LAG_TICKS.child(server=server)
        self._alive = alive
        self._loop = None
        self._watch: Optional[LoopWatch] = None
        self._handle = None
        self._due = 0.0
        self._kicked = False

    def start(self, loop) -> None:
        self._loop = loop
        self._watch = loop_watch(loop)

    def kick(self) -> None:
        self._kicked = True
        if self._handle is None and self._loop is not None:
            self._arm()

    def _arm(self) -> None:
        self._due = self._loop.time() + LOOP_LAG_PROBE_SECONDS
        self._handle = self._loop.call_at(self._due, self._tick)

    def _tick(self) -> None:
        self._handle = None
        if self._loop is None or not self._alive():
            return
        now = self._loop.time()
        late = max(0.0, now - self._due)
        self._seconds.inc(late)
        self._ticks.inc()
        self._watch.tick(self._due, now, late)
        if self._kicked:
            self._kicked = False
            self._arm()

    def stop(self) -> None:
        self._loop = None
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None


def _make_debug_middleware(name: str, address: str, pprof=None, ext=None):
    """Cold-tier middleware serving the shared observability surface and
    re-joining traces on fallback-replayed requests.

    A closure over plain values ON PURPOSE: a bound ServingCore method
    here would close the cycle app -> middleware -> core -> runner ->
    app, which survives to interpreter finalization and then raises out
    of aiohttp __del__ hooks ("Error in sys.excepthook" at process exit
    under pytest)."""

    @web.middleware
    async def middleware(request, handler):
        path = request.path
        if path == "/metrics" or path.startswith("/debug/"):
            return await _serve_debug(
                name, address, request, path, pprof, ext
            )
        tp = request.headers.get("traceparent")
        if tp is None:
            return await handler(request)
        pctx = trace.parse_traceparent(tp)
        if pctx is None:
            # malformed header: same as no header — begin_request with
            # parent=None would mean "caller won the head-sample coin"
            # and force-record garbage-sending clients at sample=0
            return await handler(request)
        sp = trace.begin_request(
            f"{name}:{request.method}",
            pctx,
            server=name,
            addr=address,
            tier="cold",
        )
        if sp is None:
            return await handler(request)
        sp.tags["path"] = path
        try:
            resp = await handler(request)
        except Exception as e:
            sp.finish(err=e)
            raise
        sp.finish()
        return resp

    return middleware


async def _serve_debug(name: str, address: str, request, path: str,
                       pprof=None, ext=None):
    # server-specific debug extensions (e.g. the volume server's
    # /debug/needle_map bloom-sidecar disclosure). Checked FIRST so an
    # extension can also specialize a shared path; handlers must close
    # over leaf state (a store, not the server) — see the middleware
    # factory's cycle warning.
    if ext:
        handler_fn = ext.get(path)
        if handler_fn is not None:
            return await handler_fn(request)
    if path == "/metrics":
        from ..util.metrics import REGISTRY

        watch = _WATCHES.get(asyncio.get_running_loop())
        if watch is not None and watch.clock is not None:
            watch.clock.publish()  # this handler runs on the loop's thread

        # content negotiation: exemplars are only legal in the
        # OpenMetrics exposition — classic text/plain parsers reject a
        # '#' after the sample value, so a stock Prometheus scrape must
        # get the exemplar-free classic render by default
        if "openmetrics" in request.headers.get("Accept", ""):
            return web.Response(
                text=REGISTRY.render(exemplars=True) + "# EOF\n",
                content_type="application/openmetrics-text",
            )
        return web.Response(text=REGISTRY.render(), content_type="text/plain")
    if path == "/debug/traces":
        rec = trace.RECORDER
        if request.query.get("status"):
            return web.json_response(
                {"server": name, "addr": address, **rec.status()}
            )
        return web.Response(
            text=rec.dump_jsonl(), content_type="application/x-ndjson"
        )
    if path == "/debug/overload":
        # the overload plane's live state, per process: every admission
        # gate this process runs (in-process clusters share one list —
        # the `server` key on each gate disambiguates), the per-peer
        # circuit breakers, and the shared retry budget. The shell's
        # `overload.status` merges these cluster-wide. Served from the
        # cold tier so it stays reachable WHILE the fast tier sheds.
        from ..util.backoff import shared_retry_budget

        budget = shared_retry_budget()
        return web.json_response(
            {
                "server": name,
                "addr": address,
                # process identity for the shell's cluster-wide merge:
                # gates are per-PROCESS, so (host, pid, gate-server) is
                # the dedup key — counter values are not an identity
                "pid": os.getpid(),
                "admission_enabled": overload.admission_enabled(),
                "gates": overload.gate_stats(),
                "breakers": overload.BREAKERS.stats(),
                "retry_budget": (
                    budget.snapshot() if budget is not None else None
                ),
            }
        )
    if path.startswith("/debug/pprof/"):
        # profiling is a process-global slowdown and the fast tiers
        # FALLBACK these paths from the PUBLIC port, so the surface is
        # OPT-IN (matching the old volume -pprof posture): serve only
        # when the server forced it on (-pprof) or the operator set
        # SEAWEEDFS_TPU_PPROF=1
        env_on = (
            os.environ.get("SEAWEEDFS_TPU_PPROF", "0") or "0"
        ) not in ("0", "")
        if not (pprof is True or (pprof is None and env_on)):
            return web.json_response(
                {"error": "pprof disabled (set SEAWEEDFS_TPU_PPROF=1 "
                          "or start with -pprof)"},
                status=403,
            )
        from ..util import profiling

        handler_fn = {
            "/debug/pprof/profile": profiling.handle_pprof_profile,
            "/debug/pprof/heap": profiling.handle_pprof_heap,
            "/debug/pprof/start": profiling.handle_pprof_start,
            "/debug/pprof/stop": profiling.handle_pprof_stop,
            "/debug/pprof/dump": profiling.handle_pprof_dump,
            "/debug/pprof/device": profiling.handle_pprof_device,
        }.get(path)
        if handler_fn is None:
            return web.json_response(
                {"error": "unknown profile endpoint"}, status=404
            )
        return await handler_fn(request)
    return web.json_response({"error": "not found"}, status=404)


class ServingCore:
    """Two-tier HTTP serving shared by volume/master/filer/S3 servers.

    `handler` is the fast tier: ``async (FastRequest) -> bytes | FALLBACK
    | DETACHED``. The aiohttp application passed to :meth:`start` is the
    cold tier every FALLBACK replays against."""

    def __init__(self, name: str, handler, host: str, port: int,
                 pprof=None, tenant_fn=None, debug_handlers=None):
        self.name = name
        # extra /debug/* paths this server exposes: {path: async handler}.
        # Handlers must close over leaf state only (a Store, a registry)
        # — never the server object — so the middleware closure does not
        # resurrect the app->core->runner->app cycle documented on
        # _make_debug_middleware.
        self.debug_handlers = debug_handlers or None
        self.handler = handler
        self.host = host
        self.port = port
        # tenant QoS (ISSUE 12): derive the request's tenant principal
        # BEFORE admission so the gate's weighted-fair dequeue and
        # per-tenant quotas see it. The default derivation is the
        # explicit X-Seaweed-Tenant header, else the `collection` query
        # parameter; servers with richer identity install their own
        # (S3: V4 access key -> IAM identity; volume: read-path vid ->
        # collection). None from the fn means the shared default pool.
        self.tenant_fn = tenant_fn or tenancy.tenant_from_request
        # None = env opt-in (SEAWEEDFS_TPU_PPROF=1), False = refuse the
        # /debug/pprof surface, True = force it on (volume -pprof flag)
        self.pprof = pprof
        self.address = f"{host}:{port}"
        self.fast_server: Optional[FastHTTPServer] = None
        self._http_runner: Optional[web.AppRunner] = None
        self.internal_port: Optional[int] = None
        # per-method pre-bound children: (request_total, request_seconds,
        # request_wait_seconds_total)
        self._req_children: dict = {}
        self._lag_probe = LoopLagProbe(name, self._serving)
        # overload control (ISSUE 9): priority admission + adaptive
        # concurrency limit in front of EVERY fast tier — None when
        # SEAWEEDFS_TPU_ADMIT=0. The shed answer is pre-rendered once:
        # refusing work must cost microseconds, or shedding at 3x
        # offered load is itself the collapse.
        self.gate = overload.new_server_gate(name)
        retry_after = 1
        if self.gate is not None:
            retry_after = max(1, int(round(self.gate.retry_after_s)))
        self._shed_resp = render_response(
            503,
            b'{"error":"overloaded, request shed"}',
            extra=b"Retry-After: %d\r\n" % retry_after,
        )

    async def start(self, app: web.Application) -> None:
        app.middlewares.append(
            _make_debug_middleware(
                self.name, self.address, self.pprof, self.debug_handlers
            )
        )
        self._http_runner = web.AppRunner(app, access_log=None)
        await self._http_runner.setup()
        site = web.TCPSite(self._http_runner, "127.0.0.1", 0)
        await site.start()
        self.internal_port = site._server.sockets[0].getsockname()[1]
        self.fast_server = FastHTTPServer(
            self._dispatch, backend=("127.0.0.1", self.internal_port),
            stages=TierStages(self.name),
        )
        trace.watch_gc()
        self._lag_probe.start(asyncio.get_running_loop())
        await self.fast_server.start(self.host, self.port)

    def _serving(self) -> bool:
        # the volume and master servers stop the fast tier directly, not
        # through stop(): the probe ends with it either way
        return self.fast_server is not None and self.fast_server.serving

    async def stop(self) -> None:
        self._lag_probe.stop()
        overload.drop_gate(self.gate)
        if self.fast_server is not None:
            await self.fast_server.stop()
        if self._http_runner is not None:
            await self._http_runner.cleanup()
        # aiohttp caches per-(handler, middlewares) chains in a
        # module-level lru_cache (web_app._cached_build_middleware); with
        # any middleware installed that cache pins our bound route
        # handlers — and through them the whole server object graph,
        # gRPC server included — until interpreter finalization, where
        # cygrpc's teardown then raises ("Error in sys.excepthook").
        # Dropping the cache on stop releases the graph; live apps just
        # rebuild their entries on the next request.
        try:
            from aiohttp.web_app import _cached_build_middleware

            _cached_build_middleware.cache_clear()
        except (ImportError, AttributeError):
            pass  # private API: absent on other aiohttp versions

    def _children(self, method: str) -> tuple:
        bound = self._req_children.get(method)
        if bound is None:
            labels = {"server": self.name, "operation": method}
            bound = self._req_children[method] = (
                REQUEST_COUNTER.child(**labels),
                REQUEST_HISTOGRAM.child(**labels),
                REQUEST_WAIT_SECONDS.child(**labels),
            )
        return bound

    def count(self, method: str) -> None:
        """Count one served request; pre-bound children keep this O(1) on
        the hot path (DETACHED completions call this from their flush
        callback, so a proxied continuation is never double-counted)."""
        self._children(method)[0].inc()

    async def _dispatch(self, req):
        """Fast-tier entry: trace join/head-sample, server-side fault
        seam, handler, tail promotion. The untraced path (no traceparent
        header, head sampler says no) builds no span name, no tags dict,
        no context object — tail sampling still keeps the slow requests:
        every root's wall feeds an allocation-free log histogram, and a
        root past the live p99 is retro-promoted into the recorder. This
        runs once per request at serving QPS rates: the sampling coin is
        inlined and the clock/coin callables are module-bound, because
        each avoided method call is measurable in the trace_overhead
        leg's off-vs-on-at-1% comparison."""
        if req.path == "/metrics" or req.path.startswith("/debug/"):
            # reserved observability surface: ONE structural check in
            # front of every fast tier (instead of a per-server
            # convention) — the cold-tier middleware serves these. Also
            # exempt from admission: the overloaded state must stay
            # observable WHILE it sheds.
            return FALLBACK
        self._lag_probe.kick()
        gate = self.gate
        # tenant principal (ISSUE 12): derived BEFORE admission so the
        # gate's per-tenant subqueues and quotas order THIS request, and
        # set as the current-context tenant so in-cluster hops (filer ->
        # volume chunk I/O) carry the same principal downstream. None =
        # the shared default pool — exactly the pre-tenant behavior.
        tenant = self.tenant_fn(req)
        if gate is not None:
            # priority admission BEFORE any per-request machinery: the
            # wait charged against the class budget is everything since
            # parse completion (event-loop backlog included — under
            # single-loop saturation that backlog IS the queue), so a
            # request that would blow its caller's deadline anyway is
            # refused in microseconds with the pre-rendered 503.
            waited = _perf() - req.t_arrive
            adm = gate.try_admit(
                _classify(req.method), waited, tenant, len(req.body)
            )
            if adm is not True:
                if adm is not False:
                    adm = await gate.wait_queued(
                        _classify(req.method), adm, waited
                    )
                if adm is False:
                    if trace.RECORDER.enabled:
                        trace.note_shed(
                            f"{self.name}:{req.method}",
                            server=self.name, path=req.path,
                            tenant=tenant or "default",
                        )
                    return self._shed_resp
        rec = trace.RECORDER
        sp = None
        enabled = rec.enabled
        t0 = _perf()
        if enabled:
            tp = req.headers.get(b"traceparent")
            pctx = (
                trace.parse_traceparent(tp) if tp is not None else None
            )
            if pctx is not None or (
                rec.sample > 0.0 and _coin() < rec.sample
            ):
                sp = trace.begin_request(
                    f"{self.name}:{req.method}", pctx,
                    server=self.name, addr=self.address, path=req.path,
                )
                if sp is not None and tenant is not None:
                    sp.tags["tenant"] = tenant
        tok = None if tenant is None else _set_tenant(tenant)
        try:
            plan = faults._PLAN
            if plan is not None:
                try:
                    out = await self._apply_fault(plan, req)
                except BaseException:
                    if gate is not None:
                        gate.release(tenant=tenant)
                    raise
                if out is not None:
                    if gate is not None:
                        gate.release(tenant=tenant)
                    if sp is not None:
                        sp.finish()
                    return out
            try:
                out = await self.handler(req)
            except BaseException as e:
                # BaseException: a CancelledError (peer dropped
                # mid-handler) must release the admission slot too, or
                # capacity leaks
                if gate is not None:
                    gate.release(tenant=tenant)
                if sp is not None:
                    sp.finish(err=e)
                raise
        finally:
            if tok is not None:
                _reset_tenant(tok)
        # full fast-tier responses only, for the AIMD limiter and for
        # request_seconds alike: FALLBACK walls are µs of proxy hand-off
        # (the cold tier observes the replay) and DETACHED walls end at
        # handler return — either would drag the latency signal (and
        # thus the limit) toward fiction
        full = out is not FALLBACK and out is not DETACHED
        if out is not DETACHED:
            _count, seconds, wait = self._children(req.method)
            # the wait before service (loop backlog after parse +
            # admission queue) is its own counter, beside request_seconds
            # — which a FALLBACK gets from the cold tier it is replayed
            # against (server/volume.py _dispatch), and a full response
            # here: the service wall, on both tiers
            wait.inc(t0 - req.t_arrive)
            if full:
                now = _perf()
                seconds.observe(now - t0)
        if gate is not None:
            if not full:
                gate.release(tenant=tenant)
            else:
                # service wall feeds the AIMD limit; wait+service feeds
                # the admitted-latency histograms (per-server AND
                # per-tenant), response bytes the tenant's byte quota
                gate.release(
                    now - t0, now - req.t_arrive, tenant,
                    len(out) if type(out) is bytes else 0,
                )
        if enabled:
            if not full:
                # FALLBACK walls are µs of proxy hand-off (the real work
                # happens on the cold-tier replay) and DETACHED walls end
                # at handler return, not response write — feeding either
                # into the root-latency tracker would collapse the live
                # p99 threshold and turn promote_slow into a per-request
                # firehose. A FALLBACK'd span is DROPPED outright: the
                # cold-tier middleware traces the replay (joining via
                # the client's own traceparent), and a head-sampled
                # fast-tier root for a proxied request would be a
                # meaningless µs orphan in the ring.
                if sp is not None:
                    if out is FALLBACK:
                        sp.drop()
                    else:
                        sp.finish()
            else:
                dt = _perf() - t0
                if sp is None:
                    rec.note_root(dt)
                    if dt > rec.slow_s:
                        rec.promote_slow(
                            f"{self.name}:{req.method}", dt,
                            server=self.name, addr=self.address,
                            path=req.path,
                        )
                else:
                    if sp.parent_id == 0:
                        rec.note_root(dt)
                    sp.finish()
        if full:
            _count.inc()
        return out

    async def _apply_fault(self, plan, req):
        """Server-side HTTP seam: consult the plan at request arrival.
        Returns response bytes / DETACHED to short-circuit, or None to
        proceed to the handler (latency rules have already slept). Every
        fired fault promotes the request into the flight recorder
        (trace.note_fault) — injected faults are kept even at sample=0."""
        try:
            ev = await faults.async_fault(
                plan, f"http:{req.method}", self.address
            )
        except faults.SimulatedCrash:
            # the 'process' is dead: connections just drop, mid-request
            if req.transport is not None:
                req.transport.close()
            return DETACHED  # connection_lost tears the request loop down
        except ConnectionError:
            # injected reset OR partition (ConnectionResetError is a
            # ConnectionError): the peer sees a dropped connection,
            # exactly like the client-seam variant
            trace.note_fault(
                f"{self.name}:{req.method}", "reset",
                server=self.name, path=req.path,
            )
            if req.transport is not None:
                req.transport.close()
            return DETACHED
        except TimeoutError:
            trace.note_fault(
                f"{self.name}:{req.method}", "hang",
                server=self.name, path=req.path,
            )
            # injected hang already slept through the window; surface the
            # way a gateway's upstream timeout would
            return render_response(
                500, b'{"error":"injected hang"}', keep_alive=False
            )
        if ev is not None and ev.kind == "http_error":
            trace.note_fault(
                f"{self.name}:{req.method}", "http_error",
                server=self.name, path=req.path,
            )
            # shed-shaped statuses carry Retry-After like the admission
            # gate's real 503s, so clients exercise the same honor path
            extra = (
                b"Retry-After: 1\r\n"
                if ev.rule.status in (503, 429)
                else b""
            )
            return render_response(
                ev.rule.status, b'{"error":"injected fault"}', extra=extra
            )
        return None
