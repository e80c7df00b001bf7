"""The benchmark's own tests of `warm-rs10.4-maint`: run here on the CPU, none of
them part of tier-1.

    python -m pytest benchmarks/tests/test_maint.py -q

- the plain selection against a table worked out by hand;
- a `--rehearse` of the new cell walks every step, and everything it compares
  but the device's share of the bytes is within its limit;
- the controls: a rehearsal whose decoy volume was converted, and one whose
  command asked for ten quiet years, are not correct.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import controls_maint, run as bench_run  # noqa: E402
from benchmarks.lib import common  # noqa: E402
from benchmarks.lib.traffic import shell_collection_jobs  # noqa: E402
from benchmarks.reference import ec_selection  # noqa: E402

CELL = controls_maint.CELL
MB = 1024 * 1024


def test_selection_reference_against_a_table_worked_out_by_hand():
    now = 1_800_000_000
    limit = 1024  # MB: 95 % of it is 1,020,054,732.8 bytes
    volumes = [
        (1, "c1", 1_131_743_184, 1_700_000_000),  # the full template, stamped 2023
        (2, "c1", 1_131_743_184, now - 3601),     # quiet by one second more than the hour
        (3, "c1", 1_131_743_184, now - 3600),     # modified + 3600 == now: not before now
        (4, "c1", 1_131_743_184, now - 10),       # still taking writes
        (5, "c1", 25_181_104, 1_700_000_000),     # the decoy: 2.3 % full
        (6, "c1", 1_020_054_732, 1_700_000_000),  # under the boundary by 0.8 of a byte
        (7, "c1", 1_020_054_733, 1_700_000_000),  # over it by 0.2
        (8, "c2", 1_131_743_184, 1_700_000_000),  # another collection
        (9, "", 1_131_743_184, 1_700_000_000),    # the empty collection
    ]
    assert ec_selection.select(volumes, "c1", limit, 95, 3600, now) == [1, 2, 7]
    assert ec_selection.select(volumes, "c2", limit, 95, 3600, now) == [8]
    assert ec_selection.select(volumes, "", limit, 95, 3600, now) == [9]
    assert ec_selection.select(volumes, "c1", limit, 95, 0, now) == [1, 2, 3, 4, 7]
    assert ec_selection.select(volumes, "c1", limit, 0, 3600, now) == [1, 2, 5, 6, 7]  # ignoring fullness
    assert ec_selection.select(volumes, "c1", 30000, 95, 3600, now) == []  # upstream's limit: none is full
    assert ec_selection.select([], "c1", limit, 95, 3600, now) == []
    assert ec_selection.duration_seconds("1h") == 3600 and ec_selection.duration_seconds("0s") == 0
    assert ec_selection.duration_seconds("1h30m") == 5400 and ec_selection.duration_seconds("45s") == 45


def test_the_cells_file_says_what_its_command_says():
    spec = common.load("workloads", CELL + ".json")["traffic"]
    assert spec["command"] == "ec.encode -collection {collection} -fullPercent=95 -quietFor=1h"
    assert f"-fullPercent={spec['full_percent']}" in spec["command"]
    assert f"-quietFor={spec['quiet_for']}" in spec["command"]
    config = common.load("configs", "warm-rs10.4-maint.json")
    volumes = (spec["collections"] + spec["warm_collections"]) * (spec["full_volumes"] + spec["small_volumes"])
    assert volumes == 35 <= int(config["server_flags"][config["server_flags"].index("-max") + 1])
    assert shell_collection_jobs.server_limit_mb(config["server_flags"]) == 1024
    # the full template is over 95 % of the limit, the small one far under it
    assert config["store"]["full"]["fill_to_bytes"] > 0.95 * 1024 * MB > config["store"]["small"]["fill_to_bytes"]
    bench = common.benchmark_json()
    reported = {m["name"] for group in ("end_to_end", "per_layer") for m in common.cell_metrics(bench, CELL, group)}
    assert {"ec_encode_rate", "ec_encode_host_cpu", "setup_s", "ec_batch.volumes_per_dispatch",
            "ec_batch.generate_share", "rs_encode_roofline", "device_idle_share.ec"} <= reported
    assert "ec_pipeline.seconds_per_conversion" not in reported


def test_hold_selection_counts_what_is_missed_and_what_is_extra():
    hold = shell_collection_jobs.hold_selection
    replied = {1: "encoded, spread {}", 2: "encoded, spread {}"}
    assert hold([1, 2], replied, "encoded") == {"missed": 0, "extra": 0}
    assert hold([1, 2, 3], replied, "encoded") == {"missed": 1, "extra": 0}
    assert hold([1], replied, "encoded") == {"missed": 0, "extra": 1}
    assert hold([1, 2], {1: "encoded", 2: "generate failed: no"}, "encoded") == {"missed": 1, "extra": 0}


def test_a_rehearsal_of_the_new_cell_walks_every_step():
    args = argparse.Namespace(workload=CELL, seed=3_000_000_021, seconds=1.5, trace=1,
                              rehearse=True, fault=None)
    line, _compared = bench_run.run(args)
    off_device = {"bytes_encoded_off_device", "bytes_uncounted_on_device"}  # the CPU's stand-in
    assert all(c["value"] <= c["limit"] for n, c in line["compared"].items() if n not in off_device), line["compared"]
    assert line["correct"] is False  # a rehearsal is no result
    assert line["attempted"] >= 2 and line["failed"] == 0  # a traced run reaches the job it records
    assert line["metrics"]["ec_batch.volumes_per_dispatch"]["value"] >= 1.0
    assert 0 < line["metrics"]["ec_batch.generate_share"]["value"] <= 100
    for name in ("ec_pipeline.read_s_per_gb", "ec_pipeline.write_s_per_gb", "ec_pipeline.write_parallelism"):
        assert line["metrics"][name]["value"] > 0, name


def test_control_a_rehearsal_whose_decoy_was_converted_is_not_correct():
    reading = controls_maint.control_run(3_000_000_022, 1.5, rehearse=True)
    assert reading["selection_extra"] >= 1 and reading["failed"] >= 1
    assert reading["not_correct"] is True


def test_control_a_rehearsal_whose_second_job_asked_for_ten_quiet_years_is_not_correct():
    reading = controls_maint.control_run(3_000_000_023, 1.5, rehearse=True, control="quiet")
    assert reading["selection_missed"] == 4 and reading["failed"] == 1 < reading["attempted"]
    assert reading["not_correct"] is True
