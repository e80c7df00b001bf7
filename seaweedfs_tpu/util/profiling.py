"""CPU/memory profiling hooks (ref: weed/command/volume.go:55-81 -cpuprofile/
-memprofile/-pprof, weed/command/benchmark.go:119-126, util/grace/pprof.go).

Python equivalents of the Go pprof flags: cProfile stats files for the CPU
profile, tracemalloc snapshots for the memory profile, and on-demand HTTP
handlers (/debug/pprof/...) for a live server; /debug/pprof/device takes a
`jax.profiler` trace of the process that holds the chip, with the program's
stages (util/trace.stage) on the clock the device plane is on.
"""

from __future__ import annotations

import cProfile
import io
import pstats
import sys
import tempfile
from typing import Optional


class Profiler:
    """Process-wide profile collection started by CLI flags and dumped on
    shutdown (the Go flags' start-at-boot, write-at-exit semantics)."""

    def __init__(self, cpu_path: str = "", mem_path: str = ""):
        self.cpu_path = cpu_path
        self.mem_path = mem_path
        self._cpu: Optional[cProfile.Profile] = None

    def start(self) -> "Profiler":
        if self.cpu_path:
            self._cpu = cProfile.Profile()
            self._cpu.enable()
        if self.mem_path:
            import tracemalloc

            tracemalloc.start(10)
        return self

    def __enter__(self) -> "Profiler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop_and_dump()

    def stop_and_dump(self) -> None:
        if self._cpu is not None:
            self._cpu.disable()
            self._cpu.dump_stats(self.cpu_path)  # load with pstats.Stats
            self._cpu = None
        if self.mem_path:
            import tracemalloc

            snapshot = tracemalloc.take_snapshot()
            with open(self.mem_path, "w") as f:
                for stat in snapshot.statistics("lineno")[:200]:
                    f.write(f"{stat}\n")
            tracemalloc.stop()


def profile_sorted_text(profile: cProfile.Profile, limit: int = 50) -> str:
    """Human-readable cumulative-time report for HTTP handlers."""
    buf = io.StringIO()
    stats = pstats.Stats(profile, stream=buf)
    stats.sort_stats(pstats.SortKey.CUMULATIVE)
    stats.print_stats(limit)
    return buf.getvalue()


_profile_lock = None  # created lazily on the serving event loop


async def handle_pprof_profile(request):
    """GET /debug/pprof/profile?seconds=N — profile the event loop's
    process for N seconds and return the report (ref util/grace/pprof.go).

    cProfile is process-global, so requests serialize on a lock and the
    profiler always disables (even on client disconnect); a boot-level
    -cpuprofile already holds the C profiler, which surfaces as a 409.
    """
    import asyncio

    from aiohttp import web

    global _profile_lock
    if _profile_lock is None:
        _profile_lock = asyncio.Lock()

    try:
        seconds = min(float(request.query.get("seconds", 5)), 120.0)
    except ValueError:
        return web.Response(status=400, text="bad seconds parameter\n")
    async with _profile_lock:
        prof = cProfile.Profile()
        try:
            prof.enable()
        except ValueError as e:  # another profiler (e.g. -cpuprofile) active
            return web.Response(status=409, text=f"{e}\n")
        try:
            await asyncio.sleep(seconds)
        finally:
            prof.disable()
    return web.Response(text=profile_sorted_text(prof), content_type="text/plain")


async def handle_pprof_heap(request):
    """GET /debug/pprof/heap — tracemalloc top allocations (starts
    tracemalloc on first use)."""
    import tracemalloc

    from aiohttp import web

    if not tracemalloc.is_tracing():
        tracemalloc.start(10)
        return web.Response(
            text="tracemalloc started; call again for a snapshot\n",
            content_type="text/plain",
        )
    snapshot = tracemalloc.take_snapshot()
    lines = [str(s) for s in snapshot.statistics("lineno")[:100]]
    return web.Response(text="\n".join(lines) + "\n", content_type="text/plain")


# --- on-demand start/stop/dump profiling of a LIVE server (ISSUE 8
# satellite: the docstring's promised /debug/pprof handlers, wired onto
# ServingCore's shared cold-tier middleware for every server type).
# Unlike /debug/pprof/profile (fixed window), start/stop bracket an
# operator-chosen workload; dump renders the captured stats — while the
# profiler is still running it snapshots (disable -> render -> enable).

_live_profiler: Optional[cProfile.Profile] = None
_live_running = False


async def handle_pprof_start(request):
    """GET /debug/pprof/start — begin collecting; 409 when a collection
    is already active (cProfile is process-global)."""
    from aiohttp import web

    global _live_profiler, _live_running
    if _live_running:
        return web.Response(status=409, text="profile already running\n")
    prof = cProfile.Profile()
    try:
        prof.enable()
    except ValueError as e:  # another profiler (-cpuprofile) holds the hook
        return web.Response(status=409, text=f"{e}\n")
    _live_profiler, _live_running = prof, True
    return web.Response(text="profiling started\n", content_type="text/plain")


async def handle_pprof_stop(request):
    """GET /debug/pprof/stop — stop collecting; the stats stay in memory
    for /debug/pprof/dump."""
    from aiohttp import web

    global _live_running
    if not _live_running or _live_profiler is None:
        return web.Response(status=409, text="no profile running\n")
    _live_profiler.disable()
    _live_running = False
    return web.Response(text="profiling stopped\n", content_type="text/plain")


async def handle_pprof_dump(request):
    """GET /debug/pprof/dump[?limit=N] — cumulative-time report of the
    last start/stop collection (snapshots a still-running one)."""
    from aiohttp import web

    if _live_profiler is None:
        return web.Response(status=404, text="no profile collected\n")
    try:
        limit = min(int(request.query.get("limit", 50)), 500)
    except ValueError:
        return web.Response(status=400, text="bad limit parameter\n")
    if _live_running:
        _live_profiler.disable()
        try:
            text = profile_sorted_text(_live_profiler, limit)
        finally:
            _live_profiler.enable()
    else:
        text = profile_sorted_text(_live_profiler, limit)
    return web.Response(text=text, content_type="text/plain")


_device_trace_running = False


async def handle_pprof_device(request):
    """GET /debug/pprof/device?seconds=S: a `jax.profiler` trace of this
    process for S seconds (the device's plane and, on the `/host:CPU`
    plane, every thread's `rs.*` / `ec.*` / `http.*` / `gc.*` stages),
    written to a new directory under the temporary directory; the answer
    names it. start_trace and stop_trace (which writes the `.xplane.pb`)
    run off the loop. 404 in a process that never imported jax (a master,
    a filer: no device to trace), 409 while a trace is being taken (the
    profiler is process-global)."""
    import asyncio

    from aiohttp import web

    global _device_trace_running
    if "jax" not in sys.modules:
        return web.json_response(
            {"error": "this process never imported jax: no device plane"},
            status=404,
        )
    try:
        seconds = min(float(request.query.get("seconds", 5)), 120.0)
    except ValueError:
        return web.Response(status=400, text="bad seconds parameter\n")
    if _device_trace_running:
        return web.Response(status=409, text="device trace already running\n")
    import jax

    _device_trace_running = True
    loop = asyncio.get_running_loop()
    trace_dir = tempfile.mkdtemp(prefix="seaweedfs_tpu_device_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer slows the loop
    try:
        await loop.run_in_executor(
            None,
            lambda: jax.profiler.start_trace(
                trace_dir, profiler_options=options
            ),
        )
        try:
            await asyncio.sleep(seconds)
        finally:
            await loop.run_in_executor(None, jax.profiler.stop_trace)
    finally:
        _device_trace_running = False
    return web.json_response({"dir": trace_dir, "seconds": seconds})
