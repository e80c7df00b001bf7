"""The program's shell as a child kept open: one line in, one reply line out.
Trimmed from chip_smoke.py's use of `python -m seaweedfs_tpu shell`."""

from __future__ import annotations

import os
import select
import subprocess
import sys
import time

from . import common


class Repl:
    """The shell child, pinned to the CPU so it can never reach for the chip."""

    def __init__(self, master: str, log_path: str):
        env = common.child_env()
        env["JAX_PLATFORMS"] = "cpu"
        self._err = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master", master],
            cwd=common.CHECKOUT, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self._err,
        )
        self._buf = b""

    def ask(self, command: str, until: str, limit_s: float) -> str:
        """Send one line; return the first line of output that holds `until`
        or says `error`/`failed`/`not found`."""
        self.proc.stdin.write(command.encode() + b"\n")
        self.proc.stdin.flush()
        deadline = time.perf_counter() + limit_s
        fd = self.proc.stdout.fileno()
        while True:
            while b"\n" in self._buf:
                line, _, self._buf = self._buf.partition(b"\n")
                text = line.decode(errors="replace").replace("> ", "").strip()
                if until in text or any(w in text for w in ("error", "failed", "not found")):
                    return text
            left = deadline - time.perf_counter()
            if left <= 0 or self.proc.poll() is not None:
                raise common.Failed(f"shell: no answer to {command!r} ({self._buf[-300:]!r})")
            if select.select([fd], [], [], min(left, 1.0))[0]:
                chunk = os.read(fd, 65536)
                if not chunk:
                    raise common.Failed(f"shell closed its output after {command!r}")
                self._buf += chunk

    def close(self) -> None:
        try:
            if self.proc.poll() is None:
                self.proc.stdin.close()
                self.proc.wait(20)
        except (OSError, subprocess.TimeoutExpired):
            self.proc.kill()
            self.proc.wait()
        finally:
            self._err.close()
