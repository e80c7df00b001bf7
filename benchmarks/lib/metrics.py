"""Per-layer metrics as data: each file under benchmarks/layer_metrics/ says
where its number comes from, and this module reads it off what a run observed.

A metric's value is `scale * sum(num terms) / sum(den terms)`; with no `den` it
is `scale * sum(num terms)`. A term names one source kind:

    {"from": "prom", "family": "...", "labels": {...}}      delta over the window
    {"from": "debug_json", "page": "/debug/...", "path": "a.b"}   delta over the window
    {"from": "client", "key": "..."}        a count or time of the load generator
    {"from": "process", "key": "..."}       CPU seconds of a child over the window
    {"from": "trace", "key": "busy_s" | "trace_s"}     on the profiler's clock
    {"from": "trace", "reduce": "kernel_seconds" | "kernel_calls", "patterns": [...]}
    {"from": "trace", "reduce": "least_seconds_hbm", "patterns": [...],
     "rows_in": "config:geometry.data_shards", "rows_out": 4}    roofline numerator

A term that finds nothing to read (a family that never moved and is absent, a
kernel that never ran, no trace) makes the metric absent from the line: it is
never reported as 0 for want of a reading. A denominator of 0 does the same.
"""

from __future__ import annotations

from . import common, trace_reduce
from .server import sum_metric


def dig(obj, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj


class Observed:
    """What one run saw: counters before and after the window, the load
    generator's own numbers, CPU seconds, and the reduced trace if there is one."""

    def __init__(self, prom0, prom1, pages0, pages1, client, process, trace, peaks, config):
        self.prom0, self.prom1 = prom0, prom1
        self.pages0, self.pages1 = pages0, pages1
        self.client, self.process, self.trace = client, process, trace
        self.peaks, self.config = peaks, config

    def prom_delta(self, family: str, **labels):
        keys = [k for k in self.prom1 if k == family or k.startswith(family + "{")]
        if not keys:
            return None
        return sum_metric(self.prom1, family, **labels) - sum_metric(self.prom0, family, **labels)

    def term(self, t: dict):
        kind = t["from"]
        if kind == "prom":
            return self.prom_delta(t["family"], **t.get("labels", {}))
        if kind == "debug_json":
            a = dig(self.pages0.get(t["page"], {}), t["path"])
            b = dig(self.pages1.get(t["page"], {}), t["path"])
            return None if a is None or b is None else b - a
        if kind == "client":
            return self.client.get(t["key"])
        if kind == "process":
            return self.process.get(t["key"])
        if kind == "trace":
            if self.trace is None:
                return None
            if "key" in t:
                return self.trace.get(t["key"])
            if t["reduce"] == "kernel_seconds":
                return trace_reduce.kernel_seconds(self.trace, t["patterns"])
            if t["reduce"] == "kernel_calls":
                return trace_reduce.kernel_calls(self.trace, t["patterns"])
            if t["reduce"] == "least_seconds_hbm":
                moved = trace_reduce.rs_bytes_moved(
                    self.trace, t["patterns"],
                    self.lookup(t["rows_in"]), self.lookup(t["rows_out"]),
                )
                if moved is None:
                    return None
                return trace_reduce.least_seconds_hbm(moved, self.peaks["hbm_bytes_per_s"])
        raise common.Failed(f"a metric file names an unknown source: {t}")

    def lookup(self, value):
        """A number, or `config:a.b` for a number of the configuration's file."""
        if isinstance(value, str) and value.startswith("config:"):
            return dig(self.config, value[len("config:"):])
        return value

    def value(self, spec: dict):
        total = {}
        for side in ("num", "den"):
            terms = spec["value"].get(side)
            if terms is None:
                total[side] = 1.0
                continue
            got = [self.term(t) for t in terms]
            if any(g is None for g in got):
                return None
            total[side] = float(sum(g * t.get("times", 1) for g, t in zip(got, terms)))
        if total["den"] == 0:
            return None
        return spec["value"].get("scale", 1.0) * total["num"] / total["den"]


def device_proof(want: dict, seen: Observed) -> list:
    """Numbers for `compared` that say the cell's work ran on the chip, from
    the device's own side, as the cell's file asks under `device_proof`:
    `kernels_in_trace`: {name: patterns}: a traced run saw each kernel run. A
    run without a trace has no such reading and compares nothing here: the
    device's memory statistics count no allocations (`num_allocs` stands still
    on the TPU), and the program counts where an encode ran but not a decode."""
    if seen.trace is None:
        return []
    return [
        (f"{name}_absent_from_trace",
         int(not trace_reduce.kernel_calls(seen.trace, patterns)), 0)
        for name, patterns in want.get("kernels_in_trace", {}).items()
    ]
