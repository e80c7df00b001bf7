"""The cold reconstructs that wait at the same time, decoded together
(`volume_ec.ReconstructBatcher`): one job a hand-off to the executor, one
native fill of every member's survivor rows, one decode call a decode
matrix over the members' spans side by side, and the answers handed back
in one callback.

Exactness: plans of mixed spans and two survivor sets, decoded in one job,
against the same plans decoded one at a time and against the shard files,
byte for byte, under the host codec and the Pallas kernel interpreted; a
member whose row comes short is finished alone. Scheduling, on an event
loop with a codec that names its columns: a lone plan starts at once, the
plans that come while a job runs go in the next, a plan at the call's
width cap never waits, every call is one of the four widths, a decode
matrix's first job compiles them, one count a job, a job that raises fails
every member, and a member's stages add up to its wall."""

import asyncio
import threading
import time

import numpy as np
import pytest

from benchmarks.lib import common, metrics as layer_metrics

from seaweedfs_tpu.ops.gf256 import _gf_matmul_pallas
from seaweedfs_tpu.ops.rs_kernel import TpuRSCodec, rows_of_one_array
from seaweedfs_tpu.server.volume_ec import (
    EC_DECODE_CALL_BYTES,
    ReconstructBatcher,
    ReconstructPlan,
)
from seaweedfs_tpu.storage.erasure_coding import to_ext, write_ec_files
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolume, EcVolumeShard
from seaweedfs_tpu.tpu.coder import get_codec

from test_degraded_read_cache import _Host
from test_stage_tracing import moved, scrape

K, M, DEAD = 10, 4, 3
KIB = 1 << 10
GRANULE = 256 * KIB  # the Pallas kernel's block: four of them are the cap
BATCHES = "seaweedfs_tpu_ec_reconstruct_batches_total"
STAGES = "seaweedfs_tpu_ec_degraded_read_stage_seconds_total"
COLD_SECONDS = "seaweedfs_tpu_ec_degraded_read_seconds_sum"
FILLS = "seaweedfs_tpu_ec_reconstruct_fills_total"
GET_CELLS = [
    "warm-rs10.4.degraded-get-c16",
    "warm-rs10.4-spread4.server-lost-get-c16",
    "warm-rs10.4-filer4m.degraded-chunk-get-c16",
    "warm-rs10.4-filer4m-spread4.server-lost-chunk-get-c16",
    "warm-rs10.4-converting.degraded-get-c16-while-encode",
]


def _run(coro, limit_s: float = 120):
    return asyncio.run(asyncio.wait_for(coro, limit_s))


# ------------------------------------------------------------- exactness
@pytest.fixture(scope="module")
def shard_files(tmp_path_factory):
    """{shard id: an open EcVolumeShard} of every shard but DEAD, and
    {shard id: its bytes} of all fourteen: a 10 MiB .dat, so that every
    shard holds 1 MiB of random bytes."""
    tmp = tmp_path_factory.mktemp("batch")
    base = str(tmp / "1")
    rng = np.random.default_rng(44)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size=10 << 20, dtype=np.uint8).tobytes())
    write_ec_files(base)
    shards = {i: EcVolumeShard(str(tmp), "", 1, i) for i in range(K + M) if i != DEAD}
    data = {}
    for i in range(K + M):
        with open(base + to_ext(i), "rb") as f:
            data[i] = f.read()
    assert all(len(b) == 1 << 20 for b in data.values())
    yield shards, data
    for shard in shards.values():
        shard.close()


# (start, size, survivor set): 16 KiB, 128 KiB, 256 KiB, one across a
# 128 KiB boundary and one at an odd offset; set 0 plans the ten lowest
# survivors, set 1 the ten lowest without shard 0
SPANS = [
    (5 * 16 * KIB, 16 * KIB, 0),
    (128 * KIB, 128 * KIB, 1),
    (256 * KIB, 256 * KIB, 0),
    (3 * 128 * KIB - 40_000, 128 * KIB, 1),
    (17, 16 * KIB, 0),
]
SHORT = 2  # the plan whose row of shard 0 comes a byte short, where asked
FETCHED = {1: (5,), 0: (12,)}  # survivors whose span came from elsewhere


def _plans(codec, shard_files) -> list:
    shards, data = shard_files
    plans = []
    for start, size, which in SPANS:
        ids = [i for i in range(K + M) if i != DEAD and not (which and i == 0)]
        sources = [
            data[i][start : start + size] if i in FETCHED[which] else shards[i]
            for i in ids
        ]
        plans.append(ReconstructPlan(codec, DEAD, start, size, ids, sources))
    return plans


CODECS = {
    "host": lambda: get_codec("cpu"),
    "pallas": lambda: TpuRSCodec(force_pallas=True, interpret=True),
}


@pytest.mark.parametrize("short", [False, True], ids=["whole", "short_row"])
@pytest.mark.parametrize("codec", sorted(CODECS))
def test_a_batch_decodes_each_member_as_it_decodes_alone(shard_files, monkeypatch, codec, short):
    """One lone plan, then five submitted while it runs: the five go in ONE
    job, one fill and one decode call a survivor set, and each answer is
    the one the plan gets decoded alone, and the shard's own bytes. Where
    a member's row of shard 0 comes short, that member is finished alone
    from a spare; the others' bytes are the same."""
    shards, data = shard_files
    codec = CODECS[codec]()
    fills, decodes = [], []
    inner_fill, codec_type = EcVolumeShard.fill_rows, type(codec)

    def fill_rows(rows, sources, offset):
        offsets = offset if np.ndim(offset) else [offset] * len(rows)
        fills.append(offset)
        if short:
            rows = [row[:-1] if src is shards[0] and at == SPANS[SHORT][0] else row
                    for row, src, at in zip(rows, sources, offsets)]
        return inner_fill(rows, sources, offset)

    def reconstruct_rows(self, given, wanted, _inner=codec_type.reconstruct_rows):
        decodes.append(tuple(i for i, row in enumerate(given) if row is not None))
        return _inner(self, given, wanted)

    monkeypatch.setattr(EcVolumeShard, "fill_rows", staticmethod(fill_rows))
    monkeypatch.setattr(codec_type, "reconstruct_rows", reconstruct_rows)

    async def one_at_a_time():
        batcher, out = ReconstructBatcher(), []
        for plan in _plans(codec, shard_files):
            span, _t = await batcher.submit(plan)
            out.append(bytes(span))
        return out

    async def together():
        batcher = ReconstructBatcher()
        lead, *plans = [_plans(codec, shard_files)[0]] + _plans(codec, shard_files)
        futures = [batcher.submit(lead)] + [batcher.submit(p) for p in plans]
        return [bytes(span) for span, _t in await asyncio.gather(*futures)][1:]

    alone = _run(one_at_a_time())
    del fills[:], decodes[:]
    before = scrape()
    got = _run(together())
    after = scrape()
    want = [data[DEAD][start : start + size] for start, size, _w in SPANS]
    assert got == alone == want
    # the lone plan's job, then the five's: a fill each, the second with an
    # offset a row
    assert moved(before, after, BATCHES) == 2
    assert len(fills) == 2 and np.ndim(fills[0]) == 0 and np.ndim(fills[1]) == 1
    assert moved(before, after, FILLS, how="one_call") == 6
    sets = [tuple(i for i in range(K + M) if i != DEAD)[:K],
            tuple(i for i in range(1, K + M) if i != DEAD)[:K]]
    # one decode call a survivor set, and the short member's own
    tail = [sets[0], sets[1]] + ([tuple(s for s in sets[0] if s != 0) + (11,)] if short else [])
    assert decodes == [sets[0]] + tail


# ------------------------------------------------------------ scheduling
class _NamingCodec:
    """A codec whose decode answers a member's columns of the first
    survivor's row (each member's fetched spans are its own byte), and
    records the width each call's rows come in, as the device would upload
    them. `gate`, while it is clear, holds every call; `fail` raises."""

    data_shards, parity_shards, total_shards = K, M, K + M

    def __init__(self, granule: int = GRANULE):
        self.granule = granule
        self.widths, self.compiled = [], []
        self.gate = threading.Event()
        self.gate.set()
        self.fail = False

    def row_granule(self) -> int:
        return self.granule

    def compile_decode(self, survivors, wanted, max_width):
        self.compiled.append((tuple(survivors), tuple(wanted), max_width))

    def reconstruct_rows(self, shards, wanted):
        given = [s for s in shards if s is not None][:K]
        self.widths.append(rows_of_one_array(given).shape[1])
        assert self.gate.wait(30)
        if self.fail:
            raise RuntimeError("injected")
        return [np.array(given[0])]


def _named(codec, size: int, tag: int, missing: int = DEAD) -> ReconstructPlan:
    ids = [i for i in range(K + M) if i != missing]
    return ReconstructPlan(codec, missing, 0, size, ids, [bytes([tag]) * size] * len(ids))


def test_a_lone_plan_starts_at_once():
    codec = _NamingCodec()

    async def go():
        before = scrape()
        future = ReconstructBatcher().submit(_named(codec, 16 * KIB, 7))
        started = moved(before, scrape(), BATCHES)
        span, _t = await future
        return started, bytes(span), before, scrape()

    started, span, before, after = _run(go())
    assert started == 1 and span == bytes([7]) * (16 * KIB)
    assert moved(before, after, STAGES, stage="batch_wait") < 0.01
    assert codec.widths == [GRANULE]


def test_plans_that_come_while_a_job_runs_go_together_in_the_next():
    codec = _NamingCodec()
    codec.gate.clear()
    sizes = [128 * KIB, 16 * KIB, 256 * KIB, 128 * KIB - 3]

    async def go():
        batcher = ReconstructBatcher()
        before = scrape()
        futures = [batcher.submit(_named(codec, 128 * KIB, 1))]
        futures += [batcher.submit(_named(codec, n, 2 + j)) for j, n in enumerate(sizes)]
        waiting = moved(before, scrape(), BATCHES)
        codec.gate.set()
        spans = [bytes(s) for s, _t in await asyncio.gather(*futures)]
        return waiting, spans, before, scrape()

    waiting, spans, before, after = _run(go())
    assert waiting == 1 and moved(before, after, BATCHES) == 2
    assert spans == [bytes([1]) * (128 * KIB)] + [bytes([2 + j]) * n for j, n in enumerate(sizes)]
    # the four side by side: 528 KiB less 3 bytes, in three blocks
    assert codec.widths == [GRANULE, 3 * GRANULE]


def test_a_plan_that_fills_a_call_never_waits():
    codec = _NamingCodec()
    codec.gate.clear()

    async def go():
        batcher = ReconstructBatcher()
        before = scrape()
        futures = [batcher.submit(_named(codec, 16 * KIB, 1))]
        futures.append(batcher.submit(_named(codec, 16 * KIB, 2)))
        futures.append(batcher.submit(_named(codec, EC_DECODE_CALL_BYTES - 100, 3)))
        futures.append(batcher.submit(_named(codec, EC_DECODE_CALL_BYTES + 4096, 4)))
        started = moved(before, scrape(), BATCHES)
        codec.gate.set()
        await asyncio.gather(*futures)
        return started, before, scrape()

    started, before, after = _run(go())
    # the first, and the two as wide as a call at once; the second waited
    assert started == 3 and moved(before, after, BATCHES) == 4
    assert sorted(codec.widths) == [GRANULE, GRANULE, 4 * GRANULE, 5 * GRANULE]


def test_every_call_is_one_of_the_four_widths_and_a_job_counts_once():
    codec = _NamingCodec()
    codec.gate.clear()
    rng = np.random.default_rng(3)
    sizes = [int(n) for n in rng.integers(1, 300 * KIB, 24)]

    async def go():
        batcher = ReconstructBatcher()
        before = scrape()
        futures = [batcher.submit(_named(codec, 16 * KIB, 0))]
        futures += [batcher.submit(_named(codec, n, 1 + j)) for j, n in enumerate(sizes)]
        codec.gate.set()
        spans = [bytes(s) for s, _t in await asyncio.gather(*futures)]
        return spans, before, scrape()

    spans, before, after = _run(go())
    assert spans[1:] == [bytes([1 + j]) * n for j, n in enumerate(sizes)]
    assert set(codec.widths) <= {GRANULE, 2 * GRANULE, 3 * GRANULE, 4 * GRANULE}
    # more than the cap was waiting: it went in more than one job, each
    # counted once and each a call
    assert moved(before, after, BATCHES) == len(codec.widths) > 2
    assert moved(before, after, "seaweedfs_tpu_ec_reconstruct_fills_total") == 25


def test_a_decode_matrixs_first_job_compiles_its_four_widths():
    """The batcher asks the codec once a decode matrix, for calls up to the
    cap; the Pallas codec then compiles four widths, and a call of any
    width up to the cap compiles nothing more."""
    codec = _NamingCodec()

    async def go():
        batcher = ReconstructBatcher()
        for missing in (DEAD, DEAD, 5, DEAD):
            await batcher.submit(_named(codec, 16 * KIB, 1, missing))

    _run(go())
    lowest = [i for i in range(K + M) if i != DEAD][:K]
    assert codec.compiled == [
        (tuple(lowest), (DEAD,), EC_DECODE_CALL_BYTES),
        (tuple(i for i in range(K + 1) if i != 5), (5,), EC_DECODE_CALL_BYTES),
    ]
    pallas = TpuRSCodec(force_pallas=True, interpret=True)
    survivors = [0, 1, 2, 3, 4, 5, 6, 8, 9, 12]  # a matrix no other test decodes with
    seen = _gf_matmul_pallas._cache_size()
    assert pallas.compile_decode(survivors, [7], EC_DECODE_CALL_BYTES) == 4
    assert _gf_matmul_pallas._cache_size() == seen + 4
    rows = np.random.default_rng(1).integers(0, 256, (K, 600 * KIB), dtype=np.uint8)
    given = [None] * (K + M)
    for j, i in enumerate(survivors):
        given[i] = rows[j]
    got = pallas.reconstruct_rows(given, [7])[0]
    assert _gf_matmul_pallas._cache_size() == seen + 4
    assert bytes(got) == bytes(CpuRSCodec().reconstruct_rows(given, [7])[0])
    # the jnp path takes any width: nothing to compile ahead
    assert TpuRSCodec().compile_decode(survivors, [7], EC_DECODE_CALL_BYTES) == 0


def test_a_job_that_raises_fails_every_member_and_the_next_job_runs():
    codec = _NamingCodec()
    codec.gate.clear()

    async def go():
        batcher = ReconstructBatcher()
        first = batcher.submit(_named(codec, 16 * KIB, 1))
        rest = [batcher.submit(_named(codec, 16 * KIB, 2 + j)) for j in range(3)]
        codec.fail = True
        codec.gate.set()
        outcomes = await asyncio.gather(first, *rest, return_exceptions=True)
        codec.fail = False
        after = await batcher.submit(_named(codec, 16 * KIB, 9))
        return outcomes, bytes(after[0])

    outcomes, after = _run(go())
    assert all(isinstance(o, RuntimeError) and str(o) == "injected" for o in outcomes)
    assert after == bytes([9]) * (16 * KIB)


class _SlowCodec(CpuRSCodec):
    """The numpy codec, each decode 30 ms longer: the reconstructs asked
    for meanwhile wait for the next job."""

    def reconstruct_rows(self, shards, wanted, out=None):
        time.sleep(0.03)
        return super().reconstruct_rows(shards, wanted, out)


def test_a_members_stages_add_up_to_its_wall(tmp_path):
    """Six cold reconstructs at once: the first alone, the five others in
    one job. The stages of each, a shared wall charged once to each member,
    add up to the sum of their reconstruct walls; the best of three rounds
    is held to the clocks' limits."""
    base = str(tmp_path / "1")
    with open(base + ".dat", "wb") as f:
        f.write(np.random.default_rng(6).integers(0, 256, 10 << 20, dtype=np.uint8).tobytes())
    write_ec_files(base)
    with open(base + ".ecx", "wb"):
        pass
    ev = EcVolume(str(tmp_path), "", 1)
    for i in range(K + M):
        if i != DEAD:
            ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, i))
    host = _Host()
    host.codec = _SlowCodec()
    with open(base + to_ext(DEAD), "rb") as f:
        want = f.read()
    names = ("batch_wait", "survivor_read", "executor_wait", "decode", "loop_resume", "cache_put")

    async def six(round_: int):
        host._ec_degraded_cache().invalidate(1)
        at = [(round_ * 6 + j) * 131 * KIB % ((1 << 20) - 4 * KIB) for j in range(6)]
        return at, await asyncio.gather(
            *(host._recover_one_interval(ev, DEAD, a, 4 * KIB, 0) for a in at))

    try:
        for round_ in range(3):
            before = scrape()
            at, got = _run(six(round_))
            after = scrape()
            assert got == [want[a : a + 4 * KIB] for a in at]
            assert moved(before, after, "seaweedfs_tpu_ec_reconstructions_total", kind="cold") == 6
            assert moved(before, after, BATCHES) == 2
            stages = {s: moved(before, after, STAGES, stage=s) for s in names}
            whole = moved(before, after, COLD_SECONDS, result="cold")
            # the five waited out the first job: their batch_wait is most of it
            if (stages["batch_wait"] >= 5 * 0.02 and stages["decode"] >= 6 * 0.03
                    and abs(whole - sum(stages.values())) <= max(0.10 * whole, 0.003)):
                break
        else:
            pytest.fail(f"three rounds, the last: {whole} against {stages}")
    finally:
        ev.close()
    # the cells' metric files, as a run evaluates them, and on a program
    # without the batches family
    seen = layer_metrics.Observed(before, after, {}, {}, {}, {}, None, None, {})
    parents = [{k: v for k, v in page.items() if not k.startswith(BATCHES)}
               for page in (before, after)]
    parent = layer_metrics.Observed(*parents, {}, {}, {}, {}, None, None, {})
    want = {"ec_read.reconstructs_per_batch": 3.0,
            "ec_read.batch_wait_ms": 1000 * stages["batch_wait"] / 6}
    for name, value in want.items():
        spec = common.load("layer_metrics", name + ".json")
        entry = next(e for e in common.benchmark_json()["per_layer"] if e["name"] == name)
        assert entry["workloads"] == GET_CELLS
        for field in ("unit", "better", "source", "layer", "moves"):
            assert entry[field] == spec[field], field
        assert seen.value(spec) == pytest.approx(value)
        assert parent.value(spec) is None
