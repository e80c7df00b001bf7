"""The benchmark's own tests of `warm-rs10.4-spread4`: run here on the CPU, none
of them part of tier-1.

    python -m pytest benchmarks/tests/test_spread.py -q

The traffic kind `http_closed_loop_spread` is documented in its module's
docstring (benchmarks/lib/traffic/http_closed_loop_spread.py): peers beside the
one server child, `ec.encode`, the spread held to the plain rule, GETs while
every server is up, a peer killed, the master's answer and the chip server's
table waited for, then `http_closed_loop`'s window. Here:

- the plain reference (`benchmarks/reference/ec_spread.py`) against cases
  worked out by hand: the balanced counts, the three numbers a spread is judged
  by, where a needle's bytes lie, the least survivors a reconstruct fetches from
  other servers;
- the cell's file says what the issue's table says, and reports what
  `warm-rs10.4.degraded-get-c16` reports and the four new metrics;
- a `--rehearse` of the cell on the CPU walks every step: three peers start and
  none is left, everything compared is within its limit, and it never says
  `correct: true`;
- the controls' faults: a rehearsal whose peers alter a byte of the spans they
  serve, and one that loses two peers, are not correct.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import controls_spread, run as bench_run  # noqa: E402
from benchmarks.lib import common  # noqa: E402
from benchmarks.reference import ec_spread  # noqa: E402

CELL = controls_spread.CELL
OLD = "warm-rs10.4.degraded-get-c16"
MB = 1 << 20


# ------------------------------------------------------------ the reference
def test_balanced_counts_by_hand():
    assert ec_spread.balanced_counts(14, 4) == [4, 4, 3, 3]
    assert ec_spread.balanced_counts(14, 1) == [14]
    assert ec_spread.balanced_counts(14, 14) == [1] * 14
    assert ec_spread.balanced_counts(14, 16) == [1] * 14 + [0, 0]
    assert ec_spread.balanced_counts(9, 3) == [3, 3, 3]


def test_a_spread_is_judged_by_three_numbers():
    nodes = ["a", "b", "c", "d"]
    dealt = {s: [nodes[s % 4]] for s in range(14)}  # a: 0 4 8 12, b: 1 5 9 13, c: 2 6 10, d: 3 7 11
    clean = {"shards_unplaced": 0, "shards_doubled": 0, "spread_uneven": 0}
    assert ec_spread.judge_spread(dealt, nodes, 14) == clean
    # all 14 on the source, as before any spread: three nodes hold none
    assert ec_spread.judge_spread({s: ["a"] for s in range(14)}, nodes, 14) == {
        **clean, "spread_uneven": 13}
    # 5, 3, 3, 3: one more than the rule allows
    moved = {**dealt, 13: ["a"]}
    assert ec_spread.judge_spread(moved, nodes, 14) == {**clean, "spread_uneven": 1}
    # a shard nobody holds, and one whose holder the master does not list as a node
    assert ec_spread.judge_spread({s: at for s, at in dealt.items() if s != 7}, nodes, 14)["shards_unplaced"] == 1
    assert ec_spread.judge_spread({**dealt, 7: ["gone"]}, nodes, 14)["shards_unplaced"] == 1
    # a shard mounted on two servers
    twice = ec_spread.judge_spread({**dealt, 2: ["c", "d"]}, nodes, 14)
    assert twice["shards_doubled"] == 1 and twice["shards_unplaced"] == 0
    # a node that holds nothing counts: 14 over three of four listed nodes
    three = {s: [nodes[s % 3]] for s in range(14)}  # 5, 5, 4, 0
    assert ec_spread.judge_spread(three, nodes, 14)["spread_uneven"] == 4


def test_where_a_needles_bytes_lie_by_hand():
    dat = 1133808816  # the 1 GiB template: 109 rows of small blocks, no large row
    assert ec_spread.locate(8, 100, dat) == [(0, 8, 100)]
    assert ec_spread.locate(3 * MB + 5, 10, dat) == [(3, 5, 10)]
    # row 2 starts at 20 MiB of the .dat and at 2 MiB of every shard file
    assert ec_spread.locate(20 * MB + 7 * MB + 1, 2, dat) == [(7, 2 * MB + 1, 2)]
    # a record that crosses from shard 3's block into shard 4's
    assert ec_spread.locate(4 * MB - 6, 10, dat) == [(3, MB - 6, 6), (4, 0, 4)]
    # and from the last block of a row into the first of the next
    assert ec_spread.locate(10 * MB - 1, 2, dat) == [(9, MB - 1, 1), (0, MB, 1)]
    # with a large row (12 GiB: one row of 1 GiB blocks, then small rows)
    big, gib = 12 << 30, 1 << 30
    assert ec_spread.locate(gib + 5, 1, big) == [(1, 5, 1)]
    assert ec_spread.locate(10 * gib + 3 * MB + 9, 4, big) == [(3, gib + 9, 4)]
    # the load generator's own rule for `pick.on_shard` agrees on a small-block volume
    for x in (8, 5 * MB + 17, 37 * MB, 1133808000):
        assert ec_spread.locate(x, 1, dat)[0][0] == (x // MB) % 10


def test_the_least_survivors_a_reconstruct_fetches_from_others():
    least = ec_spread.least_remote_survivors
    # the chip's server holds 0 4 8 12, the lost peer held 3 7 11: ten needed, four at home
    assert least(3, {0, 4, 8, 12}, {3, 7, 11}) == 6
    # it holds three (2 6 10) and the lost peer four: seven, which is all that is left elsewhere
    assert least(1, {2, 6, 10}, {1, 5, 9, 13}) == 7
    # degraded-get-c16: all the rest at home, nothing to fetch
    assert least(3, set(range(14)) - {3, 11}, {3, 11}) == 0
    # two peers lost, seven shards: three survive elsewhere, six would be needed
    assert least(3, {0, 4, 8, 12}, {3, 7, 11, 1, 5, 9, 13}) is None
    # a lost shard that the server itself held is no survivor
    assert least(3, {0, 3, 4, 8, 12}, {3, 7, 11}) == 6


# ------------------------------------------------------------ the cell's file
def test_the_cells_file_says_what_the_issue_says():
    spec = common.load("workloads", CELL + ".json")
    traffic, old = spec["traffic"], common.load("workloads", OLD + ".json")["traffic"]
    assert traffic["kind"] == "http_closed_loop_spread" and spec["config"] == "warm-rs10.4-spread4"
    for key in ("connections", "client_processes", "warm_gets_per_connection", "link_volumes",
                "must_move", "trace", "device_proof"):
        assert traffic[key] == old[key], key  # the two cells differ in where the survivors are
    assert traffic["pick"]["on_shard"] == old["pick"]["on_shard"] == traffic["lose"]["holder_of_shard"] == 3
    assert traffic["prepare"] == old["prepare"][:1]  # the same shell step: lock, ec.encode -volumeId 1, unlock
    assert traffic["healthy_gets"] == 200 and traffic["lose"]["peers"] == 1
    assert "peer_fault" not in traffic  # the control's, never a cell's
    config = common.load("configs", "warm-rs10.4-spread4.json")
    base = common.load("configs", "warm-rs10.4.json")
    for key in ("server_flags", "geometry", "store", "placement"):
        assert config[key] == base[key], key
    assert config["peers"]["count"] == 3 and config["peers"]["environment"] == {"JAX_PLATFORMS": "cpu"}
    assert config["peers"]["flags"] == ["-max", "8", "-storageBackend", "cpu", "-index", "lsm"]
    assert config["architecture"] is None
    bench = common.benchmark_json()
    entry = next(c for c in bench["configs"] if c["name"] == "warm-rs10.4-spread4")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == ["chips", "nodes", "volume_bytes"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200

    def reported(name):
        return {m["name"] for group in ("end_to_end", "per_layer")
                for m in common.cell_metrics(bench, name, group)}

    new = {"ec_read.remote_read_ms", "ec_read.remote_survivors_per_reconstruct",
           "ec_read.remote_kb_per_get", "peers.cpu_cores"}
    assert reported(CELL) == reported(OLD) | new and not new & reported(OLD)
    for name in new:  # a data file each, existing term kinds only
        value = common.load("layer_metrics", name + ".json")["value"]
        assert {t["from"] for side in ("num", "den") for t in value[side]} <= {"prom", "client"}


# ------------------------------------------------------------- the rehearsal
def peers_running() -> list:
    """Every process started as a volume server against another's master."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().split(b"\0")
        except OSError:
            continue
        if b"-mserver" in argv and b"volume" in argv:
            found.append((int(pid), b" ".join(argv).decode(errors="replace")))
    return found


def test_a_rehearsal_of_the_cell_walks_every_step_and_leaves_no_peer():
    args = argparse.Namespace(workload=CELL, seed=3_000_000_031, seconds=1.5, trace=1,
                              rehearse=True, fault=None)
    line, _compared = bench_run.run(args)
    assert bench_run.verdict(line["compared"]) is True, line["compared"]
    assert line["correct"] is False  # a rehearsal is no result
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"shards_unplaced", "shards_doubled", "spread_uneven", "source_dat_left",
            "healthy_bodies_wrong", "healthy_remote_reads_never_moved", "lost_shards_listed",
            "holders_not_live", "lost_beyond_parity", "probe_bodies_wrong",
            "peers_died_in_the_window", "remote_survivor_reads_short",
            "bodies_wrong", "gets_unanswered", "reconstructions_never_moved"} <= set(line["compared"])
    m = {name: v["value"] for name, v in line["metrics"].items()}
    # what the decode needs of others and no more: the plain reference's least
    assert m["ec_read.remote_survivors_per_reconstruct"] == ec_spread.least_remote_survivors(
        3, {0, 4, 8, 12}, {3, 7, 11}) == 6
    assert m["ec_read.remote_read_ms"] > 0 and m["ec_read.remote_kb_per_get"] > 0
    assert m["peers.cpu_cores"] >= 0 and m["ec_read.no_holder_skip_share"] > 0
    assert peers_running() == []


# --------------------------------------------------------------- the controls
def test_control_peers_that_alter_a_byte_of_their_spans_are_not_correct():
    reading = controls_spread.control_run(3_000_000_032, 1.5, rehearse=True)
    assert reading["healthy_bodies_wrong"] > 0 and reading["bodies_wrong"] > 0
    assert reading["failed"] > 0 and reading["not_correct"] is True
    assert peers_running() == []


def test_control_two_peers_lost_is_not_correct():
    reading = controls_spread.control_run(3_000_000_033, 1.5, rehearse=True, control="two_lost")
    assert reading["lost_beyond_parity"] >= 2 and reading["bodies_wrong"] > 0
    assert reading["failed"] == reading["attempted"] > 0  # no GET of a lost shard got its body
    assert reading["not_correct"] is True
    assert peers_running() == []
