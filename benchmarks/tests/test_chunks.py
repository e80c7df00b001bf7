"""The benchmark's own tests of `warm-rs10.4-filer4m`: run here on the CPU, none
of them part of tier-1.

    python -m pytest benchmarks/tests/test_chunks.py -q

- the store (`benchmarks/lib/stores/sealed_chunks.py`): its plan is files cut
  into chunks of exactly `chunk_bytes` with each file's tail shorter, and the
  files it builds hold what the plan says, record for record;
- the plain reference (`benchmarks/reference/ec_locate.py`) against needles
  worked out by hand: five intervals a 4 MiB chunk, six at a block's edge,
  large rows, the width a decode is padded to;
- the chunk client (`benchmarks/lib/http_chunk_client_proc.py`) against a canned
  server: bodies of megabytes received whole, compared, counted in bytes and in
  the reference's intervals; a wrong byte is a wrong body;
- the cell's file says what the issue says, and the cell reports what
  `warm-rs10.4.degraded-get-c16` reports and its own seven;
- a `--rehearse` of the cell walks every step, everything compared is within
  its limit, and it never says `correct: true`;
- the controls' faults, rehearsed, are not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import threading
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import controls_chunks, run as bench_run  # noqa: E402
from benchmarks.lib import common  # noqa: E402
from benchmarks.lib.stores import sealed_chunks  # noqa: E402
from benchmarks.reference import ec_locate, rs_codec  # noqa: E402

CELL = controls_chunks.CELL
OLD = "warm-rs10.4.degraded-get-c16"
MB = 1 << 20
GB = 1 << 30
SMALL_RECIPE = {"kind": "sealed_chunks", "chunk_bytes": 4 * MB, "file_bytes_min": MB,
                "file_bytes_max": 12 * MB, "fill_to_bytes": 32 * MB}


# ------------------------------------------------------------------ the store
def test_the_plan_cuts_files_into_chunks_of_the_limit_with_shorter_tails():
    recipe = common.load("configs", "warm-rs10.4-filer4m.json")["store"]
    p = sealed_chunks.plan(recipe, 3_000_000_051)
    chunk = recipe["chunk_bytes"]
    assert chunk == 4 * MB and recipe["fill_to_bytes"] == GB
    last = np.r_[p["file"][1:] != p["file"][:-1], True]  # a file's last chunk
    assert (p["size"][~last] == chunk).all()  # every chunk but a file's last is exactly the limit
    assert ((p["size"][last] >= 1) & (p["size"][last] <= chunk)).all()
    per_file = np.bincount(p["file"], weights=p["size"])
    assert per_file.min() >= recipe["file_bytes_min"] and per_file.max() <= recipe["file_bytes_max"]
    assert 200 <= len(p["size"]) <= 400 and 0.65 <= (p["size"] == chunk).mean() <= 0.85
    # appended until the volume has reached its limit: the last file carries it over
    assert GB <= p["dat_bytes"] <= GB + recipe["file_bytes_max"] + 64 * len(p["size"])
    assert p["dat_bytes"] - int(p["record"][p["file"] == p["files"] - 1].sum()) < GB
    # records one after another from the super block on
    assert p["offset"][0] == 8 and (np.diff(p["offset"]) == p["record"][:-1]).all()
    assert (p["record"] == [ec_locate.record_bytes(int(s)) for s in p["size"]]).all()


def test_the_built_files_hold_what_the_plan_says(tmp_path):
    import multiprocessing

    seed = 3_000_000_052
    dirs = types.SimpleNamespace(scratch=str(tmp_path), data=str(tmp_path))
    with multiprocessing.get_context("spawn").Pool(2) as pool:
        store = sealed_chunks.build(SMALL_RECIPE, dirs, seed, pool.map, 2)
    p = sealed_chunks.plan(SMALL_RECIPE, seed)
    reader = sealed_chunks.Reader(store, seed, {"volume": 1, "lost_shards": [3, 11]})
    assert store["needles"] == reader.count == len(p["size"]) and store["dat_bytes"] == p["dat_bytes"]
    assert os.path.getsize(store["dat"]) == p["dat_bytes"]
    from seaweedfs_tpu.storage.needle import Needle

    idx = np.fromfile(store["idx"], dtype=sealed_chunks.IDX_ENTRY)
    assert (idx["key"] == np.arange(1, reader.count + 1)).all()
    assert (idx["off"].astype(np.int64) * 8 == p["offset"]).all()
    with open(store["dat"], "rb") as f:
        for i in range(reader.count):
            f.seek(int(p["offset"][i]))
            n = Needle()
            n.read_bytes(f.read(int(p["record"][i])), int(p["offset"][i]), int(idx["size"][i]), 3)
            assert n.id == i + 1 and n.cookie == int(p["cookie"][i])
            assert bytes(n.data) == reader.body(i)  # the CRC was checked by read_bytes
            assert reader.target(i) == f"1,{i + 1:x}{n.cookie:08x}"
    # the draw is among all needles, whatever shard their record starts on
    import random

    rng = random.Random(7)
    assert {reader.draw_index(rng) for _ in range(2000)} == set(range(reader.count))
    tallies = reader.tallies()
    assert (tallies[:, 0] == [len(reader.intervals(i)) for i in range(reader.count)]).all()
    assert set(tallies[:, 2]) <= {0, 1} and (tallies[:, 1] >= tallies[:, 2]).all()


# ------------------------------------------------------------ the reference
def test_where_a_chunk_needles_bytes_lie_by_hand():
    dat = 1_100_000_000  # rows of 1 MiB blocks only
    rec = ec_locate.record_bytes(4 * MB)
    assert rec == 4 * MB + 40 and ec_locate.record_bytes(1000) == 1040
    # the first chunk of a volume: super block, then five blocks touched
    assert ec_locate.locate(8, rec, dat) == [
        (0, 8, MB - 8), (1, 0, MB), (2, 0, MB), (3, 0, MB), (4, 0, 48)]
    # from the last shards of a row into the next row's first
    assert ec_locate.locate(8 * MB + 10, rec, dat) == [
        (8, 10, MB - 10), (9, 0, MB), (0, MB, MB), (1, MB, MB), (2, MB, 50)]
    # a record that starts within its own 40 bytes of a block's end is six intervals
    assert [n for _s, _o, n in ec_locate.locate(3 * MB - 16, rec, dat)] == [16, MB, MB, MB, MB, 24]
    # counts against the lost shards 3 and 11: intervals, lost ones, needles that meet one
    assert ec_locate.tally(ec_locate.locate(8, rec, dat), {3, 11}) == (5, 1, 1)
    assert ec_locate.tally(ec_locate.locate(4 * MB + 8, rec, dat), {3, 11}) == (5, 0, 0)
    # a large-block volume (12 GiB): one row of 1 GiB blocks, a chunk on one shard, two at an edge
    big = 12 * GB
    assert ec_locate.large_rows(big, 10) == 1 and ec_locate.large_rows(10 * GB, 10) == 0
    assert ec_locate.locate(3 * GB + 5, rec, big) == [(3, 5, rec)]
    assert ec_locate.locate(4 * GB - MB, rec, big) == [(3, GB - MB, MB), (4, 0, rec - MB)]
    assert ec_locate.locate(10 * GB + 3 * MB + 9, 4, big) == [(3, GB + 9, 4)]
    # it agrees with the spread deployment's reference where both speak
    from benchmarks.reference import ec_spread

    for x in (8, 5 * MB + 17, 37 * MB, 1_099_000_000):
        assert ec_locate.locate(x, 3 * MB, dat) == ec_spread.locate(x, 3 * MB, dat)


def test_the_warm_up_asks_once_for_every_needle_on_a_lost_shard():
    """Whatever shapes the program decodes a span in, a window's GET rebuilds
    none that the warm-up has not: the benchmark states no span and no granule."""
    from benchmarks.lib.traffic import http_closed_loop_chunks

    seed = 3_000_000_058
    p = sealed_chunks.plan(SMALL_RECIPE, seed)
    pick = {"volume": 1, "lost_shards": [3, 11], "data_shards": 10}
    store = {"kind": "sealed_chunks", "recipe": SMALL_RECIPE, "dat_bytes": p["dat_bytes"]}
    ctx = argparse.Namespace(store=store, seed=seed, params={"pick": pick})
    traffic = http_closed_loop_chunks.Traffic.__new__(http_closed_loop_chunks.Traffic)
    traffic.ctx = ctx
    first = traffic.first_needles(4)
    reader = sealed_chunks.Reader(store, seed, pick)
    met = [i for i in range(reader.count) if any(s == 3 for s, _o, _n in reader.intervals(i))]
    assert len(first) == 4 and sorted(i for share in first for i in share) == met
    assert 0 < len(met) < reader.count and max(map(len, first)) - min(map(len, first)) <= 1
    assert first == traffic.first_needles(4)  # from the seed
    assert "decode_widths" not in common.load("workloads", CELL + ".json")["traffic"]


def test_a_lost_interval_rebuilt_by_the_plain_codec():
    rng = np.random.default_rng(35)
    codec = rs_codec.Codec(10, 4)
    data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    full = np.concatenate([data, codec.encode(data)])

    def read(shard, offset, length):
        assert shard not in (3, 11)
        return full[shard, offset : offset + length].tobytes()

    survivors = [s for s in range(14) if s not in (3, 11)]
    assert ec_locate.rebuild(codec, read, 3, 100, 1000, survivors) == full[3, 100:1100].tobytes()


# ------------------------------------------------------------ the chunk client
class CannedServer(threading.Thread):
    """Answers `GET /<volume>,<key hex><cookie>` with the store's body for that
    key, one byte altered for the keys in `wrong`; a 503 first for `shed`."""

    def __init__(self, reader, wrong=(), shed=()):
        super().__init__(daemon=True)
        self.reader, self.wrong, self.shed = reader, set(wrong), set(shed)
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.hostport = "127.0.0.1:%d" % self.sock.getsockname()[1]

    def run(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self.serve, args=(conn,), daemon=True).start()

    def serve(self, conn):
        buf = b""
        with conn:
            while True:
                while b"\r\n\r\n" not in buf:
                    chunk = conn.recv(65536)
                    if not chunk:
                        return
                    buf += chunk
                head, _, buf = buf.partition(b"\r\n\r\n")
                fid = head.split()[1].decode().lstrip("/")
                i = int(fid.split(",")[1][:-8], 16) - 1
                if i in self.shed:
                    self.shed.discard(i)
                    conn.sendall(b"HTTP/1.1 503 Service Unavailable\r\nRetry-After: 0.05\r\n"
                                 b"Content-Length: 4\r\n\r\nbusy")
                    continue
                body = bytearray(self.reader.body(i))
                if i in self.wrong:
                    body[len(body) // 2] ^= 1
                conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Type: application/octet-stream\r\n"
                             b"Content-Length: %d\r\n\r\n" % len(body))
                conn.sendall(body)


def run_client(tmp_path, reader, store, seed, server, first, seconds=1.0):
    import time

    job = {"hostport": server.hostport, "connections": len(first), "first_index": 0, "seed": seed,
           "pick": {"volume": 1, "lost_shards": [3, 11]}, "store": store, "warm_gets": 2,
           "latency_file": str(tmp_path / "latency.f64"), "first": first,
           "extras_file": str(tmp_path / "extras.json")}
    proc = subprocess.Popen(
        [sys.executable, os.path.join(common.LIB, "http_chunk_client_proc.py")],
        cwd=common.CHECKOUT, env=common.child_env(), stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True)
    proc.stdin.write(json.dumps(job) + "\n")
    proc.stdin.flush()
    ready = json.loads(proc.stdout.readline())
    proc.stdin.write(f"go {time.perf_counter() + seconds!r}\n")
    proc.stdin.flush()
    done = json.loads(proc.stdout.readline())
    proc.stdin.close()
    proc.wait(30)
    with open(job["extras_file"]) as f:
        return ready, done, json.load(f)


def test_the_chunk_client_receives_compares_and_counts(tmp_path):
    seed = 3_000_000_053
    p = sealed_chunks.plan(SMALL_RECIPE, seed)
    store = {"kind": "sealed_chunks", "recipe": SMALL_RECIPE, "dat_bytes": p["dat_bytes"]}
    reader = sealed_chunks.Reader(store, seed, {"volume": 1, "lost_shards": [3, 11]})
    tallies = reader.tallies()
    server = CannedServer(reader, shed={0})
    server.start()
    ready, done, extras = run_client(tmp_path, reader, store, seed, server, first=[[0, 1], [2]])
    server.sock.close()
    # a connection's given needles, then its two drawn ones
    assert ready["ready"] and ready["warm_bad"] == 0 and ready["warm_good"] == 7
    assert done["wrong"] == done["unanswered"] == 0 and done["good"] > 20 and done["error"] is None
    assert done["good"] == done["sent"]  # the one shed GET was in the warm-up, and asked again
    # the window's counts are the plan's: bytes, and the reference's intervals
    mean = done["bytes_good"] / done["good"]
    assert p["size"].min() <= mean <= p["size"].max()
    assert extras == {k: done[k] for k in ("bytes_good", "intervals", "lost_intervals", "lost_needles")}
    assert tallies[:, 0].min() * done["good"] <= extras["intervals"] <= tallies[:, 0].max() * done["good"]
    assert extras["lost_needles"] <= extras["lost_intervals"] <= extras["intervals"]
    assert os.path.getsize(tmp_path / "latency.f64") == 8 * done["good"]


def test_one_wrong_byte_in_a_body_of_megabytes_is_a_wrong_body(tmp_path):
    seed = 3_000_000_054
    p = sealed_chunks.plan(SMALL_RECIPE, seed)
    store = {"kind": "sealed_chunks", "recipe": SMALL_RECIPE, "dat_bytes": p["dat_bytes"]}
    reader = sealed_chunks.Reader(store, seed, {"volume": 1, "lost_shards": [3, 11]})
    server = CannedServer(reader, wrong=range(reader.count))
    server.start()
    _ready, done, extras = run_client(tmp_path, reader, store, seed, server, first=[[0]], seconds=0.3)
    server.sock.close()
    assert done["good"] == 0 and done["wrong"] > 0 and extras["bytes_good"] == extras["intervals"] == 0


# ------------------------------------------------------------ the cell's file
def test_the_cells_file_says_what_the_issue_says():
    spec = common.load("workloads", CELL + ".json")
    traffic, old = spec["traffic"], common.load("workloads", OLD + ".json")["traffic"]
    assert traffic["kind"] == "http_closed_loop_chunks" and spec["config"] == "warm-rs10.4-filer4m"
    for key in ("connections", "client_processes", "link_volumes", "prepare", "must_move",
                "trace", "device_proof"):
        assert traffic[key] == old[key], key  # degraded-get-c16's, as data
    assert traffic["warm_gets_per_connection"] == 12
    assert traffic["pick"] == {"volume": 1, "lost_shards": [3, 11], "data_shards": 10}
    assert "on_shard" not in traffic["pick"]  # none picked by shard
    assert "server_fault" not in traffic  # the control's, never a cell's
    config = common.load("configs", "warm-rs10.4-filer4m.json")
    base = common.load("configs", "warm-rs10.4.json")
    for key in ("server_flags", "geometry", "placement"):
        assert config[key] == base[key], key
    assert config["store"]["kind"] == "sealed_chunks" and config["filer"]["max_mb"] == 4
    assert config["filer"]["max_mb_in_releases_of_early_2020"] == 32
    assert config["store"]["chunk_bytes"] == config["filer"]["max_mb"] * MB
    assert {"max_mb", "file_sizes", "lost_shards"} <= set(config["assumed"]) and len(config["guarantees"]) == 5
    bench = common.benchmark_json()
    entry = bench["configs"][-1]
    assert entry["name"] == "warm-rs10.4-filer4m" and entry["file"] == "benchmarks/configs/warm-rs10.4-filer4m.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == ["nodes", "volume_bytes"]
    cell = bench["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "degraded-chunk-get-c16"

    def reported(name):
        return {m["name"] for group in ("end_to_end", "per_layer")
                for m in common.cell_metrics(bench, name, group)}

    new = {"http.body_mb_per_s", "ec_read.intervals_per_get", "ec_read.degraded_get_share",
           "ec_read.local_interval_ms", "ec_read.assemble_ms", "ec_read.survivor_mb_per_reconstruct",
           "rs_decode_roofline"}
    assert reported(CELL) == reported(OLD) | new and not new & reported(OLD)
    assert [m["name"] for m in bench["per_layer"][-7:]] == [
        "http.body_mb_per_s", "ec_read.intervals_per_get", "ec_read.degraded_get_share",
        "ec_read.local_interval_ms", "ec_read.assemble_ms", "ec_read.survivor_mb_per_reconstruct",
        "rs_decode_roofline"]
    for name in new:  # a data file each, existing term kinds only
        value = common.load("layer_metrics", name + ".json")["value"]
        assert {t["from"] for side in ("num", "den") for t in value[side]} <= {"prom", "client", "trace"}
    roofline = common.load("layer_metrics", "rs_decode_roofline.json")["value"]
    encode = common.load("layer_metrics", "rs_encode_roofline.json")["value"]
    assert roofline["den"] == encode["den"] and roofline["num"][0]["rows_out"] == 1
    assert roofline["num"][0]["reduce"] == encode["num"][0]["reduce"] == "least_seconds_hbm"


# ------------------------------------------------------------- the rehearsal
def test_a_rehearsal_of_the_cell_walks_every_step():
    args = argparse.Namespace(workload=CELL, seed=3_000_000_055, seconds=1.5, trace=1,
                              rehearse=True, fault=None)
    line, _compared = bench_run.run(args)
    assert bench_run.verdict(line["compared"]) is True, line["compared"]
    assert line["correct"] is False  # a rehearsal is no result
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"bodies_wrong", "gets_unanswered", "reconstructions_never_moved", "intervals_miscounted",
            "degraded_intervals_miscounted", "degraded_needles_miscounted",
            "compiles_inside_window"} <= set(line["compared"])
    assert "device_decode_share_short" not in line["compared"]  # the CPU stands in for no device
    m = {name: v["value"] for name, v in line["metrics"].items()}
    assert 1 <= m["ec_read.intervals_per_get"] <= 6 and 0 < m["ec_read.degraded_get_share"] < 100
    assert m["http.body_mb_per_s"] > 0 and m["ec_read.local_interval_ms"] > 0 and m["ec_read.assemble_ms"] > 0
    assert "rs_decode_roofline" not in m  # no device trace on the CPU: absent, never 0
    assert m["http.proxied_share"] < 1.0


# --------------------------------------------------------------- the controls
def test_control_a_byte_altered_in_a_rebuilt_span_is_not_correct():
    reading = controls_chunks.control_run(3_000_000_056, 1.5, rehearse=True)
    assert reading["bodies_wrong"] > 0 and 0 < reading["failed"] < reading["attempted"]
    assert reading["not_correct"] is True


def test_control_a_healthy_interval_read_a_block_off_is_not_correct():
    reading = controls_chunks.control_run(3_000_000_057, 1.5, rehearse=True, control="block_off")
    assert reading["bodies_wrong"] > 0 and reading["failed"] > 0
    assert reading["not_correct"] is True
