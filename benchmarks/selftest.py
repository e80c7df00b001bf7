#!/usr/bin/env python3
"""Runs the benchmark's own tests (benchmarks/tests/) here on the CPU, one
after another, without pytest. Not part of tier-1."""

from __future__ import annotations

import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmarks.tests import test_benchmark

    failed = 0
    for name in sorted(vars(test_benchmark)):
        if not name.startswith("test_"):
            continue
        t0 = time.perf_counter()
        try:
            getattr(test_benchmark, name)()
            print(f"ok    {name} ({time.perf_counter() - t0:.1f} s)", flush=True)
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"FAIL  {name}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
