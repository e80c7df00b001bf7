"""From a profiler trace (`*.xplane.pb`) to the numbers the metric files ask for.

    python benchmarks/lib/trace_reduce.py <trace dir> <out.json>

Run as a process of its own, pinned to the CPU, once the server is gone: it
needs `jax.profiler.ProfileData` and nothing of a backend. What it gives:

- `busy_s`: per device plane, the union of the intervals in which an operation
  ran (the plane's `XLA Ops` line), averaged over the device planes;
- `ops`: {operation name: [count, seconds]} on the device planes, added up;
- `span_s`: from the first to the last device operation;
- `trace_s`: from the first to the last event of any plane, host or device:
  the length of the traced window on the profiler's own clock, which busy
  time is set against (a host clock around the request to trace would count
  the profiler's own start and stop);
- `gaps`: the longest stretches with no operation on the first device plane,
  each with the host event (`/host:CPU` plane) that overlaps it most.

The reductions the metric files name (`kernel_seconds`, `kernel_calls`) are
functions here; `least_seconds_hbm` is the roofline's arithmetic.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def union_seconds(intervals: list) -> float:
    """Seconds covered by the union of (start_ns, end_ns) intervals."""
    busy, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            busy += end - start
            reach = end
        elif end > reach:
            busy += end - reach
            reach = end
    return busy / 1e9


def gaps_of(intervals: list, top: int) -> list:
    """The longest (start_ns, end_ns) stretches between the intervals."""
    gaps, reach = [], None
    for start, end in sorted(intervals):
        if reach is not None and start > reach:
            gaps.append((reach, start))
        reach = end if reach is None else max(reach, end)
    return sorted(gaps, key=lambda g: g[0] - g[1])[:top]


def reduce_planes(planes: list, top: int = 10) -> dict:
    """planes: [(plane name, [(line name, [(event name, start_ns, dur_ns)])])]."""
    device = [p for p in planes if DEVICE_PLANE.match(p[0])]
    ops: dict = {}
    busy, first_intervals, lo, hi = [], None, None, None
    for _name, lines in device:
        chosen = [ln for ln in lines if ln[0] == OPS_LINE] or lines
        intervals = []
        for _line, events in chosen:
            for name, start, dur in events:
                if dur <= 0:
                    continue
                intervals.append((start, start + dur))
                count_s = ops.setdefault(name, [0, 0.0])
                count_s[0] += 1
                count_s[1] += dur / 1e9
        busy.append(union_seconds(intervals))
        if first_intervals is None:
            first_intervals = intervals
        for start, end in intervals:
            lo = start if lo is None else min(lo, start)
            hi = end if hi is None else max(hi, end)
    host = [
        (name, start, start + dur)
        for plane, lines in planes if plane.startswith("/host:")
        for _line, events in lines for name, start, dur in events if dur > 0
    ]
    ends = [
        (start, start + dur)
        for _plane, lines in planes for _line, events in lines
        for _name, start, dur in events if dur >= 0
    ]
    trace_s = (max(e for _s, e in ends) - min(s for s, _e in ends)) / 1e9 if ends else 0.0
    gaps = []
    for g0, g1 in gaps_of(first_intervals or [], top):
        best, best_overlap = "no host event", 0
        for name, start, end in host:
            overlap = min(end, g1) - max(start, g0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        gaps.append([best, (g1 - g0) / 1e9])
    return {
        "device_planes": [p[0] for p in device],
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "span_s": (hi - lo) / 1e9 if lo is not None else 0.0,
        "trace_s": trace_s,
        "ops": ops,
        "gaps": gaps,
    }


def matching(reduced: dict, patterns: list) -> list:
    """[(name, count, seconds)] of the operations whose name matches any pattern."""
    return [(name, n, s) for name, (n, s) in reduced["ops"].items()
            if any(re.search(p, name) for p in patterns)]


def kernel_seconds(reduced: dict, patterns: list) -> float | None:
    """Device seconds of the matching operations; None where none ran."""
    found = matching(reduced, patterns)
    return sum(s for _name, _n, s in found) if found else None


def kernel_calls(reduced: dict, patterns: list) -> int | None:
    found = matching(reduced, patterns)
    return sum(n for _name, n, _s in found) if found else None


def least_seconds_hbm(bytes_moved: float, hbm_bytes_per_s: float) -> float:
    """The least time the chip could take for a call bound by memory: the bytes
    the algorithm must read and write, over the peak bytes per second."""
    return bytes_moved / hbm_bytes_per_s


RESULT_SHAPE = re.compile(r"= \(?[a-z]+?(\d+)\[([\d,]*)\]")


def result_bytes(op_name: str) -> int | None:
    """Bytes of the first result of an operation, from the HLO text the trace
    names it by: `%x = u32[4,2048,128]{...} custom-call(...)` is 4 MiB."""
    found = RESULT_SHAPE.search(op_name)
    if not found:
        return None
    n = int(found.group(1)) // 8
    for dim in filter(None, found.group(2).split(",")):
        n *= int(dim)
    return n


def rs_bytes_moved(reduced: dict, patterns: list, rows_in: int, rows_out: int) -> int | None:
    """The bytes the matching RS calls must move, whatever the formulation: a
    call that writes `rows_out` rows of N bytes reads `rows_in` rows of N, so
    (k + m) * N for an encode. N is read off the call's result shape, so rows
    added to its inputs are not counted as work; columns added to a row would
    be, so this is for calls whose rows are whole blocks of the layout (an
    encode of 1 MiB blocks), not for a decode padded to the kernel's granule."""
    found = matching(reduced, patterns)
    total = 0
    for name, count, _s in found:
        out = result_bytes(name)
        if out is None:
            return None
        total += count * (rows_in + rows_out) * (out // rows_out)
    return total if found else None


def read_planes(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    return [
        (plane.name, [
            (line.name, [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events])
            for line in plane.lines
        ])
        for plane in data.planes
    ]


def main() -> None:
    trace_dir, out = sys.argv[1], sys.argv[2]
    reduced = reduce_planes(read_planes(trace_dir))
    with open(out, "w") as f:
        json.dump(reduced, f)


if __name__ == "__main__":
    main()
