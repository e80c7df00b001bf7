"""The launcher of the one child that owns the chip.

It runs the module `seaweedfs_tpu` as `__main__` in this process, with
`sys.argv` set to the `server ...` arguments, so `__main__.py`'s
`setup_compile_cache()` and `command.cli.main` run exactly as under
`python -m seaweedfs_tpu server`. Beside that one thread reads commands from
the launcher's standard input, one JSON object to a line, and answers each in
`<control dir>/<id>.json`:

    {"id": 1, "op": "trace_start", "dir": "..."}   jax.profiler.start_trace
    {"id": 2, "op": "trace_stop"}                  jax.profiler.stop_trace
    {"id": 3, "op": "memory"}                      peak bytes on the fullest chip

Only the process that holds the chip can trace it or read its memory, and the
program has no endpoint for either; this adds none. When standard input closes
(the benchmark is gone) the launcher ends the server.

`--fault <name>` plants a fault under the timed path, for the tests under
benchmarks/tests/ and the controls: a benchmark run never passes it.
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import signal
import sys
import threading


def _memory() -> dict:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"memory_peak_bytes": max(peaks), "per_device": peaks}


def _trace_start(cmd: dict) -> dict:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer slows the server's loop
    jax.profiler.start_trace(cmd["dir"], profiler_options=options)
    return {}


def _trace_stop(_cmd: dict) -> dict:
    import jax

    jax.profiler.stop_trace()
    return {}


OPS = {"trace_start": _trace_start, "trace_stop": _trace_stop,
       "memory": lambda _cmd: _memory()}


def control(control_dir: str) -> None:
    for line in sys.stdin:
        cmd = json.loads(line)
        try:
            reply = OPS[cmd["op"]](cmd)
        except Exception as e:  # the benchmark decides what a failed command means
            reply = {"error": f"{type(e).__name__}: {e}"}
        path = os.path.join(control_dir, f"{cmd['id']}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(reply, f)
        os.replace(path + ".tmp", path)
    os.kill(os.getpid(), signal.SIGTERM)


# ------------------------------------------------------------------- faults
def fault_ec_parity_byte() -> None:
    """One parity byte of every encoded chunk altered where it is produced."""
    import numpy as np

    from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec

    inner = TpuRSCodec.pipeline_encode

    def altered(self, data):
        out = np.array(inner(self, data))
        out[0, 0] ^= 1
        return out

    TpuRSCodec.pipeline_encode = altered


def fault_ec_decode_byte() -> None:
    """One byte in 64 of every reconstructed row altered where it is produced."""
    import numpy as np

    from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec

    inner = TpuRSCodec.reconstruct_rows

    def altered(self, shards, wanted, *args, **kw):
        rows = [np.array(r) for r in inner(self, shards, wanted, *args, **kw)]
        for r in rows:
            r[::64] ^= 1
        return rows

    TpuRSCodec.reconstruct_rows = altered


FAULTS = {"ec_parity_byte": fault_ec_parity_byte, "ec_decode_byte": fault_ec_decode_byte}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control-dir", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("server_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if args.fault:
        FAULTS[args.fault]()
    threading.Thread(
        target=control, args=(args.control_dir,), daemon=True, name="bench-control"
    ).start()
    sys.argv = ["seaweedfs_tpu", *[a for a in args.server_args if a != "--"]]
    runpy.run_module("seaweedfs_tpu", run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
