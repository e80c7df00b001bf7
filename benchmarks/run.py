#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run builds the cell's store offline from --seed, starts the one server child
that owns the chip, warms the cell's own shapes, measures for --seconds, stops
the child, compares what the window produced with the benchmark's own
reference, and prints one JSON object as the last line of standard output.
Everything before the window opens is `setup_s`. Earlier lines are
observations (`{"note": ...}`); the numbers compared stand beside their limits
as the last lines of standard error and under `compared` in the last line.

The cell, its configuration, its traffic and its per-layer metrics are data:
BENCHMARK.json names them, and benchmarks/configs/<config>.json,
benchmarks/workloads/<cell>.json and benchmarks/layer_metrics/<metric>.json
hold them. A store kind is the module benchmarks/lib/stores/<kind>.py and a
traffic kind the module benchmarks/lib/traffic/<kind>.py, found by name.

`--rehearse` walks the same path on the CPU at the sizes the files give under
`rehearse`, to find wrong paths without the chip; it never says `correct: true`.
This process never imports JAX: the child holds the chip.
"""

from __future__ import annotations

import argparse
import importlib
import json
import multiprocessing
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks.lib import common, metrics  # noqa: E402
from benchmarks.lib.server import Server, sum_metric  # noqa: E402

COMPILES = "seaweedfs_tpu_jax_compiles_total"
COMPILE_SECONDS = "seaweedfs_tpu_jax_compile_seconds_total"
COMPILE_CACHE = "seaweedfs_tpu_jax_compile_cache_total"


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise common.Failed(f"BENCHMARK.json has no {what} named {name!r}")


def compile_cache_dir() -> tuple:
    """Where the child will keep its compiled programs, and whether that is
    empty now: the program's own rule (util/device.py), read, not set."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        common.CHECKOUT, ".jax_cache"
    )
    return path, not (os.path.isdir(path) and os.listdir(path))


SCRATCH_PREFIX = "seaweedfs_bench_"


def make_scratch(placement: dict) -> tuple:
    """The run's two directories, each named after the run's process id.

    `scratch`, under the temporary directory the environment names ($TMPDIR):
    the store's template, logs, the trace. `memory`, under the root the
    configuration's `placement.server_directory` names (`/dev/shm`): the
    server's own directory, with the shard files it writes. There is one placement and no second choice: where
    the root lacks `placement.needs_free_bytes`, the run ends with no result.
    A directory whose process is gone (a run that was killed) is removed
    first, so that what one run leaves never takes the room of the next."""
    tmp = tempfile.gettempdir()
    root = placement["server_directory"]
    for where in {tmp, root}:
        for name in os.listdir(where):
            pid = name[len(SCRATCH_PREFIX):].split("_")[0]
            if name.startswith(SCRATCH_PREFIX) and pid.isdigit() and not os.path.exists(f"/proc/{pid}"):
                shutil.rmtree(os.path.join(where, name), ignore_errors=True)
    free = shutil.disk_usage(root).free
    if free < placement["needs_free_bytes"]:
        raise common.Failed(f"{root} has {free} bytes free; the configuration needs "
                            f"{placement['needs_free_bytes']} there and names no other place")
    scratch = tempfile.mkdtemp(prefix=f"{SCRATCH_PREFIX}{os.getpid()}_", dir=tmp)
    memory = tempfile.mkdtemp(prefix=f"{SCRATCH_PREFIX}{os.getpid()}_", dir=root)
    return scratch, memory


def file_system(path: str) -> str:
    """`<type> on <mount point>` of the mount that holds `path`."""
    best = ("", "unknown")
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mount, kind = line.split()[:3]
            if (path == mount or path.startswith(mount.rstrip("/") + "/")) and len(mount) >= len(best[0]):
                best = (mount, kind)
    return f"{best[1]} on {best[0]}"


def worker_count() -> int:
    """Processes that build a store and compare what the window wrote."""
    return max(2, min(8, (os.cpu_count() or 2) - 1))


class Tracer:
    """Asks the launcher for one profiler trace inside the window. The traffic
    says when: a loop of requests names seconds of the window (`during`), a run
    of jobs begins before one job and ends after its reply (`begin`, `end`)."""

    def __init__(self, server: Server, trace_dir: str):
        self.server, self.dir = server, trace_dir
        self.ended = False
        self.error = None
        self._thread = None

    def begin(self) -> None:
        self.server.ask("trace_start", dir=self.dir)

    def end(self) -> None:
        self.server.ask("trace_stop", limit_s=300)
        self.ended = True

    def during(self, start_s: float, seconds: float) -> None:
        """From now: wait, trace for some seconds, stop; in a thread of its own."""

        def go():
            try:
                time.sleep(start_s)
                self.begin()
                time.sleep(seconds)
                self.end()
            except common.Failed as e:
                self.error = str(e)

        self._thread = threading.Thread(target=go, daemon=True)
        self._thread.start()

    def finish(self) -> None:
        if self._thread is not None:
            self._thread.join(600)
        if self.error or not self.ended:
            raise common.Failed(f"the trace was not taken: {self.error}")


def reduce_trace(trace_dir: str, out: str) -> dict:
    env = common.child_env()
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join(common.LIB, "trace_reduce.py"), trace_dir, out],
        cwd=common.CHECKOUT, env=env, capture_output=True, text=True, timeout=300,
    )
    if done.returncode != 0:
        raise common.Failed(f"the trace reduction failed: {done.stderr[-2000:]}")
    with open(out) as f:
        return json.load(f)


def run(args) -> tuple:
    t_setup = time.perf_counter()
    bench = common.benchmark_json()
    cell = find(bench["workloads"], args.workload, "workload")
    config = common.load("configs", cell["config"] + ".json")
    spec = common.load("workloads", cell["name"] + ".json")
    if spec["config"] != cell["config"]:
        raise common.Failed(f"{cell['name']}: BENCHMARK.json and the cell's file name different configurations")
    params, recipe = dict(spec["traffic"]), dict(config["store"])
    if args.rehearse:
        params.update(spec.get("rehearse", {}))
        recipe.update(config.get("rehearse", {}))
    layer_specs = {
        m["name"]: common.load("layer_metrics", m["name"] + ".json")
        for m in common.cell_metrics(bench, cell["name"], "per_layer")
    }
    peaks_table = common.load("peaks.json")

    from seaweedfs_tpu import native

    cache_dir, cold = compile_cache_dir()
    common.say("run", workload=cell["name"], seed=args.seed, seconds=args.seconds,
               trace=args.trace, rehearse=args.rehearse, host_codec_tier=native.tier(),
               compile_cache_dir=cache_dir, compile_cache_cold=cold)

    scratch, memory = make_scratch(config["placement"])
    common.say("scratch", dir=scratch, file_system=file_system(scratch),
               server_directory=memory, its_file_system=file_system(memory))
    workers = worker_count()
    server = traffic = None
    pool = multiprocessing.get_context("spawn").Pool(workers)
    try:
        server = Server(scratch, os.path.join(memory, "data"), config["server_flags"],
                        args.rehearse, args.fault)
        t0 = time.perf_counter()
        store_mod = importlib.import_module(f"benchmarks.lib.stores.{recipe['kind']}")
        dirs = types.SimpleNamespace(scratch=scratch, data=server.data_dir)
        store = store_mod.build(recipe, dirs, args.seed, pool.map, workers)
        common.say("store", seconds=round(time.perf_counter() - t0, 3),
                   **{k: v for k, v in store.items() if isinstance(v, (int, str))})
        ctx = types.SimpleNamespace(
            params=params, config=config, store=store, seed=args.seed, scratch=scratch,
            rehearse=args.rehearse, workers=workers, pool_map=pool.map,
        )
        traffic_mod = importlib.import_module(f"benchmarks.lib.traffic.{params['kind']}")
        traffic = traffic_mod.Traffic(ctx)
        traffic.stage(server)
        t0 = time.perf_counter()
        server.start()
        device = server.wait_ready()
        common.say("start", seconds=round(time.perf_counter() - t0, 3), **device)
        if not args.rehearse and (device["platform"] != "tpu" or device["count"] != cell["chips"]):
            raise NoChip(f"the cell asks for {cell['chips']} TPU chip(s); the server runs on {device}")
        peaks = peaks_table.get("devices", {}).get(device["kind"])
        if peaks is None and not args.rehearse:
            raise common.Failed(f"benchmarks/peaks.json has no device kind {device['kind']!r}")
        t0 = time.perf_counter()
        traffic.warm(server)
        common.say("warm", seconds=round(time.perf_counter() - t0, 3))

        pages = sorted({
            t["page"] for s in layer_specs.values() for side in ("num", "den")
            for t in s["value"].get(side) or [] if t["from"] == "debug_json"
        })
        tracer = Tracer(server, os.path.join(scratch, "trace")) if args.trace else None
        prom0 = server.metrics()
        pages0 = {p: server.debug_json(p) for p in pages}
        setup_s = time.perf_counter() - t_setup

        # ---- the window
        result = traffic.run(server, args.seconds, tracer)
        if tracer:
            tracer.finish()
        device_memory = server.ask("memory")
        prom1 = server.metrics()
        pages1 = {p: server.debug_json(p) for p in pages}
        traffic.after_window(server)
        common.say(
            "window", seconds=round(result["window_s"], 4),
            compiles_inside=int(sum_metric(prom1, COMPILES) - sum_metric(prom0, COMPILES)),
            compile_seconds_inside=round(
                sum_metric(prom1, COMPILE_SECONDS) - sum_metric(prom0, COMPILE_SECONDS), 4),
            compiles_so_far=int(sum_metric(prom1, COMPILES)),
            compile_seconds_so_far=round(sum_metric(prom1, COMPILE_SECONDS), 3),
            cache_hits=int(sum_metric(prom1, COMPILE_CACHE, result="hit")),
            cache_misses=int(sum_metric(prom1, COMPILE_CACHE, result="miss")),
            **{k: v for k, v in result.items() if k != "end_to_end"},
        )
        server.stop()

        # ---- once the window has closed and the program's state is freed
        reduced = None
        if tracer:
            t0 = time.perf_counter()
            reduced = reduce_trace(tracer.dir, os.path.join(scratch, "trace.json"))
            common.say("trace", reduce_seconds=round(time.perf_counter() - t0, 3),
                       busy_s=reduced["busy_s"], trace_s=reduced["trace_s"],
                       span_s=reduced["span_s"], planes=reduced["device_planes"],
                       distinct_ops=len(reduced["ops"]))
        observed = metrics.Observed(
            prom0, prom1, pages0, pages1, client=result,
            process={"server_cpu_s": result.get("server_cpu_s"), "window_s": result["window_s"]},
            trace=reduced, peaks=peaks, config=config,
        )
        t0 = time.perf_counter()
        compared = traffic.check(server, result, observed)
        if not args.rehearse:  # the CPU stands in for no device
            compared += metrics.device_proof(params.get("device_proof", {}), observed)
        common.say("check", seconds=round(time.perf_counter() - t0, 3))
    finally:
        if traffic is not None:
            try:
                traffic.after_window(server)
            except Exception:
                traceback.print_exc()
        if server is not None:
            server.stop()
        pool.terminate()
        pool.join()
        shutil.rmtree(memory, ignore_errors=True)
        shutil.rmtree(scratch, ignore_errors=True)

    if args.trace:
        values = {name: observed.value(s) for name, s in layer_specs.items()}
        units = {m["name"]: m["unit"] for m in bench["per_layer"]}
        values = {k: v for k, v in values.items() if v is not None}
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
        wanted = [m["name"] for m in common.cell_metrics(bench, cell["name"], "end_to_end")]
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        missing = [n for n in wanted if values.get(n) is None]
        if missing:
            raise common.Failed(f"the window gave no reading for {missing}")
        values = {n: values[n] for n in wanted}
    by_name = {n: {"value": v, "limit": lim} for n, v, lim in compared}
    line = {
        # a rehearsal ran on the CPU at a rehearsal's size: whatever it
        # compared, it is no result
        "correct": verdict(by_name) and not args.rehearse,
        "attempted": result["attempted"], "failed": result["failed"],
        "metrics": {n: {"value": v, "unit": units[n]} for n, v in values.items()},
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": device_memory["memory_peak_bytes"]},
    }
    if reduced is not None:
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["trace_s"]
        top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1][1])[:10]
        line["breakdown"] = {
            "device_ops": [[name, seconds] for name, (_n, seconds) in top],
            "idle_gaps": reduced["gaps"][:10],
        }
    line["compared"] = by_name  # last in the line
    return line, compared


def verdict(compared: dict) -> bool:
    """Every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in compared.values())


class NoChip(common.Failed):
    """No accelerator, or fewer chips than the cell asks for: no result at all."""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU at the files' rehearsal sizes; never correct")
    ap.add_argument("--fault", help="plant a fault under the timed path (tests, controls)")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(common.benchmark_json()["run_seconds"])
    # a run that is told to end still stops its children and empties its scratch
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(1))
    try:
        line, compared = run(args)
    except BaseException as e:
        traceback.print_exc()
        common.eprint(f"no result: {type(e).__name__}: {e}"[:4000])
        return 1
    if "jax" in sys.modules:
        common.eprint("no result: the benchmark's own process imported jax")
        return 1
    for name, value, limit in compared:
        common.eprint(f"compared {name} {value} limit {limit}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
