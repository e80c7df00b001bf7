"""Paths, the index that BENCHMARK.json is, and the lines a run prints."""

from __future__ import annotations

import json
import os
import sys

LIB = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(LIB)
CHECKOUT = os.path.dirname(BENCH)


class Failed(Exception):
    """The run cannot give a result; it ends with `correct: false`."""


def say(what: str, **kv) -> None:
    """One observation, on an earlier line of standard output."""
    print(json.dumps({"note": what, **kv}), flush=True)


def load(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def benchmark_json() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, group: str) -> list:
    """The metrics of `end_to_end` or `per_layer` that this cell reports: those
    that list it under `workloads`, and those that list nothing."""
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def child_env() -> dict:
    """The environment of every child: the checkout on the path, nothing buffered."""
    env = dict(os.environ)
    env["PYTHONPATH"] = CHECKOUT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)
