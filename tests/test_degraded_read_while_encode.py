"""Degraded reads while the maintenance pass converts, in one server: the
cell warm-rs10.4-converting.degraded-get-c16-while-encode at a rehearsal's
size, on the CPU, with the device codec's jnp path doing both halves.

One volume server, on a loop made as the CLI makes it (so its clock is
there), serves GETs of the needles on shard 3 of EC volume 1 with shards 3
and 11 deleted, while `ec.encode -volumeId N` converts other volumes of the
same template in the same process. Every body is compared with the seeded
store, every converted shard file byte for byte with the benchmark's own
codec, and the two new families are held to what they must add up to:
`rs_dispatches_beside_total` and `event_loop_converting_seconds_total`."""

import asyncio
import json
import os
import random
import subprocess
import sys
import threading
import time
import types
import urllib.request

import pytest

from seaweedfs_tpu.pb import grpc_address
from seaweedfs_tpu.pb.rpc import Stub, close_all_channels
from seaweedfs_tpu.server import serving_core as sc
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.shell import CommandEnv, run_command

from benchmarks.lib import common, metrics as layer_metrics
from benchmarks.lib.http_client_proc import Connection
from benchmarks.lib.server import parse_prom
from benchmarks.lib.stores import sealed_template
from benchmarks.lib.traffic import http_closed_loop_while_encode as while_encode, shell_jobs
from benchmarks.reference import rs_codec
from test_cluster import free_port_pair
from test_stage_tracing import emulated_codec, moved

CELL = "warm-rs10.4-converting.degraded-get-c16-while-encode"
SEED = 2147542042
K, M = 10, 4
DEGRADED, CONVERTED = 1, [2, 3, 4, 5, 6, 7]
BESIDE = "seaweedfs_tpu_rs_dispatches_beside_total"
CONVERTING = "seaweedfs_tpu_event_loop_converting_seconds_total"
LOOP = "seaweedfs_tpu_event_loop_"
NEW_METRICS = ["rs_dispatch.decode_beside_encode_share", "http.loop_held_share_converting"]
BUILD = """
import json, sys, types
from benchmarks.lib.stores import sealed_template
recipe, scratch, seed = json.loads(sys.argv[1]), sys.argv[2], int(sys.argv[3])
store = sealed_template.build(recipe, types.SimpleNamespace(scratch=scratch), seed,
                              lambda f, jobs: list(map(f, jobs)), 1)
print(json.dumps(store))
"""


def build_store(scratch: str) -> dict:
    """The cell's store at its rehearsal size, built as a run builds it: in
    a process that never imports JAX."""
    config = common.load("configs", "warm-rs10.4-converting.json")
    recipe = {**config["store"], **config["rehearse"]}
    done = subprocess.run(
        [sys.executable, "-c", BUILD, json.dumps(recipe), scratch, str(SEED)],
        cwd=common.CHECKOUT, env=common.child_env(), capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


class Loop:
    """An event loop on a thread of its own, made as the CLI makes it
    (serving_core.new_event_loop, on the thread that runs it)."""

    def __init__(self):
        made = threading.Event()

        def run():
            self.loop = sc.new_event_loop()
            asyncio.set_event_loop(self.loop)
            made.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()
        assert made.wait(30)

    def run(self, coro, timeout=300):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def stop(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)


class Served:
    """Master + one volume server on a clocked loop, the shell and the
    test's gRPC calls on the same loop (the program's gRPC channels are
    cached for the loop that opened them); the GETs come from threads."""

    def __init__(self, tmp_path):
        self.store = build_store(str(tmp_path / "scratch"))
        self.dir = tmp_path / "vol"
        self.dir.mkdir()
        for vid in (DEGRADED, *CONVERTED):
            sealed_template.link_volume(self.store, str(self.dir), vid)
        self.server = Loop()
        try:
            self.server.run(self._start())
            self.server.run(self._prepare())
        except BaseException:
            self.close()
            raise

    async def _start(self):
        self.master = MasterServer(port=free_port_pair(), pulse_seconds=0.2)
        await self.master.start()
        self.vs = VolumeServer(
            master=self.master.address, directories=[str(self.dir)],
            port=free_port_pair(), pulse_seconds=0.2, max_volume_counts=[20],
        )
        self.vs._codec = emulated_codec()
        await self.vs.start()
        for _ in range(100):
            if self.master.topo.data_nodes():
                break
            await asyncio.sleep(0.1)

    async def _prepare(self):
        self.env = CommandEnv(self.master.address)
        for _ in range(100):
            nodes = await self.env.collect_data_nodes()
            if sum(len(dn.get("volumes", [])) for dn in nodes) >= 1 + len(CONVERTED):
                break
            await asyncio.sleep(0.1)
        assert (await run_command(self.env, "lock")) == "locked"
        out = await run_command(self.env, f"ec.encode -volumeId {DEGRADED}")
        assert "encoded" in out, out
        stub = Stub(grpc_address(self.vs.address), "volume")
        for rpc in ("VolumeEcShardsUnmount", "VolumeEcShardsDelete"):
            reply = await stub.call(rpc, {"volume_id": DEGRADED, "collection": "",
                                          "shard_ids": [3, 11]}, timeout=60)
            assert not reply.get("error"), (rpc, reply)

    async def convert(self, vid: int) -> str:
        return await run_command(self.env, f"ec.encode -volumeId {vid}")

    def metrics(self) -> dict:
        """/metrics over HTTP: its handler publishes the loop's clock."""
        with urllib.request.urlopen(f"http://{self.vs.address}/metrics", timeout=60) as r:
            return parse_prom(r.read().decode())

    def close(self):
        async def stop_server():
            if getattr(self, "vs", None) is not None:
                await self.vs.stop()
            if getattr(self, "master", None) is not None:
                await self.master.stop()

        try:
            self.server.run(stop_server(), timeout=60)
            self.server.run(close_all_channels(), timeout=30)
        finally:
            self.server.stop()


def get_all(served: Served, reader, order: list, stop: threading.Event, done: list) -> None:
    """One keep-alive connection GETting the needles in `order`, round and
    round, until told to stop; each answer kept as (status, body right)."""
    conn = Connection(served.vs.address)
    i = 0
    try:
        while not stop.is_set() or i < len(order):
            n = order[i % len(order)]
            target = f"{reader.volume},{n + 1:x}{int(reader.p['cookie'][n]):08x}"
            status, _head, body = conn.get(target)
            done.append((status, body == sealed_template.body_of(reader.p, reader.pool, n)))
            i += 1
    finally:
        if conn.sock is not None:
            conn.sock.close()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """GETs on four connections while the conversions run one after another
    (at least two, until a decode met an encode and the loop was held off
    while one ran: the two new metrics then have something to read): the
    answers, the volumes converted, and /metrics before and after."""
    served = Served(tmp_path_factory.mktemp("converting"))
    try:
        reader = sealed_template.Reader(served.store, SEED, {"volume": DEGRADED, "on_shard": 3})
        warm = Connection(served.vs.address)
        for n in reader.candidates[:8]:  # the decode's shapes compile here
            assert warm.get(f"1,{n + 1:x}{int(reader.p['cookie'][n]):08x}")[0] == 200
        warm.sock.close()
        before, t0 = served.metrics(), time.perf_counter()
        stop, answers, threads = threading.Event(), [[] for _ in range(4)], []
        for j, done in enumerate(answers):
            order = list(reader.candidates)
            random.Random(j).shuffle(order)
            t = threading.Thread(target=get_all, args=(served, reader, order, stop, done), daemon=True)
            t.start()
            threads.append(t)
        converted, replies = [], []
        try:
            for vid in CONVERTED:
                replies.append(served.server.run(served.convert(vid)))
                converted.append(vid)
                now = served.metrics()
                if (len(converted) >= 2
                        and moved(before, now, BESIDE, op="decode", beside="encode")
                        and moved(before, now, CONVERTING, mode="held")):
                    break
        finally:
            stop.set()
            for t in threads:
                t.join(120)
        after, wall = served.metrics(), time.perf_counter() - t0
        yield {"served": served, "reader": reader, "answers": [a for d in answers for a in d],
               "converted": converted, "replies": replies, "before": before, "after": after,
               "wall": wall}
    finally:
        served.close()


def test_every_get_beside_the_conversions_is_answered_with_the_seeded_body(recorded):
    answers = recorded["answers"]
    assert len(answers) >= 4 * len(recorded["reader"].candidates)
    assert all(status == 200 and right for status, right in answers)


def test_every_converted_volume_is_the_reference_codecs_byte_for_byte(recorded):
    served = recorded["served"]
    assert all("encoded" in r for r in recorded["replies"]), recorded["replies"]
    bases = [os.path.join(str(served.dir), str(vid)) for vid in recorded["converted"]]
    differing, compared, unrecovered = shell_jobs.compare_files(
        served.store, bases, K, M, SEED, map, 1)
    shard = rs_codec.shard_size(served.store["dat_bytes"], K)
    assert (differing, unrecovered) == (0, 0)
    assert compared == len(bases) * (K + M) * shard


def test_decodes_met_encodes_and_every_call_is_counted_once_beside_something(recorded):
    before, after = recorded["before"], recorded["after"]
    assert moved(before, after, BESIDE, op="decode", beside="encode") > 0
    assert moved(before, after, BESIDE, op="encode", beside="decode") > 0
    for op in ("encode", "decode"):
        calls = moved(before, after, "seaweedfs_tpu_rs_dispatches_total", op=op)
        assert calls > 0
        assert moved(before, after, BESIDE, op=op) == calls


def test_the_converting_seconds_moved_and_fit_inside_the_loops_own(recorded):
    before, after = recorded["before"], recorded["after"]
    modes = {mode: moved(before, after, CONVERTING, mode=mode) for mode in ("running", "held", "idle")}
    converting = sum(modes.values())
    loop_wall = moved(before, after, LOOP + "turn_seconds_total") + moved(
        before, after, LOOP + "select_seconds_total")
    assert converting > 0 and all(v >= 0 for v in modes.values()), modes
    assert converting <= loop_wall <= recorded["wall"] + 0.05
    assert modes["running"] <= moved(before, after, LOOP + "cpu_seconds_total") + 1e-6
    # nothing converts now: a later reading adds nothing to the family
    later = recorded["served"].metrics()
    assert moved(after, later, CONVERTING) == 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_file_reads_the_recorded_window(recorded, name):
    """Evaluated as a run evaluates it; on a program without the family
    (the parent) it is left out of the line, never 0."""
    spec = common.load("layer_metrics", name + ".json")
    entry = next(e for e in common.benchmark_json()["per_layer"] if e["name"] == name)
    assert entry["workloads"] == [CELL]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    before, after = recorded["before"], recorded["after"]
    value = layer_metrics.Observed(before, after, {}, {}, {}, {}, None, None, {}).value(spec)
    assert 0 < value <= 100
    gone = (BESIDE, CONVERTING)
    parents = [{k: v for k, v in page.items() if not k.startswith(gone)} for page in (before, after)]
    assert layer_metrics.Observed(*parents, {}, {}, {}, {}, None, None, {}).value(spec) is None


def test_the_cells_files_hold_the_solo_cells_load_and_store():
    """degraded-get-c16's load and warm-rs10.4's store and server, letter for
    letter, beside a conversion due every period; the GET metrics only."""
    bench = common.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("warm-rs10.4-converting", 1)
    mine, solo = (common.load("workloads", name + ".json")["traffic"]
                  for name in (CELL, "warm-rs10.4.degraded-get-c16"))
    for key in ("connections", "client_processes", "warm_gets_per_connection",
                "link_volumes", "pick", "prepare", "trace"):
        assert mine[key] == solo[key], key
    config, plain = (common.load("configs", name + ".json")
                     for name in ("warm-rs10.4-converting", "warm-rs10.4"))
    for key in ("server_flags", "geometry", "store", "rehearse", "placement"):
        assert config[key] == plain[key], key
    assert mine["background"]["command"] == "ec.encode -volumeId {vid}"
    reports = [m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])]
    assert reports == ["get_rate", "get_p95_ms", "setup_s"]


@pytest.mark.parametrize("period,seconds,due", [(2.0, 10.0, 5), (1.5, 10.0, 7), (1.0, 10.0, 10), (2.0, 3.0, 2)])
def test_the_conversions_due_in_a_window(period, seconds, due):
    params = dict(common.load("workloads", CELL + ".json")["traffic"])
    params["background"] = {**params["background"], "period_s": period}
    ctx = types.SimpleNamespace(params=params, config=common.load("configs", "warm-rs10.4-converting.json"))
    assert while_encode.Traffic(ctx).due_count(seconds) == due


def test_the_sampled_rows_are_a_seeded_sixteenth_of_each_volume():
    rows = while_encode.sample_rows(108, 16, SEED, 3)
    assert rows == while_encode.sample_rows(108, 16, SEED, 3)
    assert len(rows) == len(set(rows)) == 6 and rows == sorted(rows) and rows[-1] < 108
    assert rows != while_encode.sample_rows(108, 16, SEED, 4)
    assert while_encode.sample_rows(3, 16, SEED, 3) in ([0], [1], [2])
