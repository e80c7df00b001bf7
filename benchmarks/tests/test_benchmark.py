"""The benchmark's own tests: run here on the CPU, none of them part of tier-1.

    python benchmarks/selftest.py            (or: python -m pytest benchmarks/tests -q)

- the reference codec against parities worked out by hand;
- the trace reduction and the roofline's arithmetic on the recorded trace under
  benchmarks/testdata/ and on intervals small enough to add up by hand;
- the controls: the reference put in the program's place with one guarantee of
  the configuration broken comes out as not correct;
- the faults: the rest of a run (everything but the look for a chip) with the
  timed path broken underneath, an answer altered where it is produced, comes
  out as not correct, and the same run without the fault as correct;
- the device's own side of `correct`, and where a run keeps its files.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import controls, run as bench_run  # noqa: E402
from benchmarks.lib import common, metrics, trace_reduce  # noqa: E402
from benchmarks.lib.traffic import shell_jobs  # noqa: E402
from benchmarks.reference import rs_codec  # noqa: E402


# ------------------------------------------------------------ the reference
def test_reference_codec_against_hand_computed_parity():
    codec = rs_codec.Codec(10, 4)
    # klauspost's RS(10,4) parity rows (reedsolomon.New(10, 4)), first and last
    assert codec.parity_matrix[0].tolist() == [129, 150, 175, 184, 210, 196, 254, 232, 3, 2]
    assert codec.parity_matrix[3].tolist() == [214, 191, 10, 98, 111, 6, 183, 223, 4, 5]
    data = np.zeros((10, 3), dtype=np.uint8)
    data[9] = [1, 2, 0x80]
    # column 9 of the parity rows is (2, 3, 4, 5); in GF(2^8) mod 0x11D:
    # 2*0x80 = 0x100 ^ 0x11D = 0x1D, 3*0x80 = 0x1D ^ 0x80 = 0x9D,
    # 4*0x80 = 2*0x1D = 0x3A, 5*0x80 = 0x3A ^ 0x80 = 0xBA
    assert codec.encode(data).tolist() == [
        [2, 4, 0x1D], [3, 6, 0x9D], [4, 8, 0x3A], [5, 10, 0xBA],
    ]
    data[0] = [1, 0, 0]  # adds column 0, (129, 150, 191, 214), to byte 0
    assert codec.encode(data)[:, 0].tolist() == [129 ^ 2, 150 ^ 3, 191 ^ 4, 214 ^ 5]


def test_any_ten_of_fourteen_give_the_data_back():
    codec = rs_codec.Codec(10, 4)
    data = np.random.default_rng(1).integers(0, 256, (10, 257), dtype=np.uint8)
    full = np.concatenate([data, codec.encode(data)])
    for lost in ([0, 1, 2, 3], [3, 11], [10, 11, 12, 13], [9, 10, 4, 13]):
        alive = {i: full[i] for i in range(14) if i not in lost}
        assert np.array_equal(codec.recover(alive, list(range(10))), data)


def test_layout_of_a_one_gib_volume_is_small_blocks_only():
    assert rs_codec.row_counts(1133808816, 10) == (0, 109)
    assert rs_codec.shard_size(1133808816, 10) == 109 << 20
    assert rs_codec.row_counts(12 << 30, 10) == (1, 2 * 1024 // 10 + 1)


# ------------------------------------------------------ the trace reduction
def test_busy_union_idle_share_and_kernel_time_by_hand():
    gf = "%gf.1 = u8[4,1000]{1,0} custom-call(u8[16,1000]{1,0} %padded)"
    planes = [
        ("/device:TPU:0", [
            ("XLA Ops", [(gf, 0, 100), ("copy.2", 50, 100), (gf, 400, 100)]),
            ("XLA Modules", [("jit_f", 0, 1000)]),
        ]),
        ("/host:CPU", [("python", [("wait_for_chunk", 140, 250), ("tiny", 0, 10)])]),
    ]
    r = trace_reduce.reduce_planes(planes)
    assert r["busy_s"] == 250e-9  # [0,150) and [400,500): the overlap counts once
    assert r["ops"] == {gf: [2, 200e-9], "copy.2": [1, 100e-9]}
    assert trace_reduce.result_bytes(gf) == 4000
    assert trace_reduce.result_bytes("%x = u32[4,2048,128]{2,1,0:T(8,128)} custom-call(") == 4 << 20
    assert r["gaps"] == [["wait_for_chunk", 250e-9]]
    assert trace_reduce.kernel_seconds(r, [r"^%gf\."]) == 200e-9
    assert trace_reduce.kernel_calls(r, [r"^%gf\."]) == 2
    assert trace_reduce.kernel_seconds(r, ["never"]) is None  # nothing to read: no 0
    # the traced window on the profiler's own clock: first to last event of any plane
    assert r["trace_s"] == 1000e-9 and r["span_s"] == 500e-9
    seen = metrics.Observed({}, {}, {}, {}, {}, {}, r, {"hbm_bytes_per_s": 14e9}, {"k": 10})
    idle = common.load("layer_metrics", "device_idle_share.ec.json")
    assert abs(seen.value(idle) - 75.0) < 1e-9
    per_call = common.load("layer_metrics", "rs_decode_kernel_us.json")
    per_call["value"]["num"][0]["patterns"] = per_call["value"]["den"][0]["patterns"] = [r"^%gf\."]
    assert abs(seen.value(per_call) - 0.1) < 1e-9  # 200 ns over 2 calls, in us
    # two calls of (10 + 4) rows of 1000 bytes at 14 GB/s: 2 us at the least, 200 ns
    # taken; the 16 rows the padded input has are not what the algorithm must read
    roof = {"value": {"scale": 100, "num": [
        {"from": "trace", "reduce": "least_seconds_hbm", "patterns": [r"^%gf\."],
         "rows_in": "config:k", "rows_out": 4}],
        "den": [{"from": "trace", "reduce": "kernel_seconds", "patterns": [r"^%gf\."]}]}}
    assert abs(seen.value(roof) - 1000.0) < 1e-6
    assert metrics.Observed({}, {}, {}, {}, {}, {}, None, None, {}).value(roof) is None


def test_recorded_trace_reduces_to_the_recorded_numbers():
    with open(os.path.join(common.BENCH, "testdata", "trace_planes.json")) as f:
        recorded = json.load(f)
    r = trace_reduce.reduce_planes(recorded["planes"])
    want = recorded["expect"]
    assert r["device_planes"] == want["device_planes"]
    assert abs(r["busy_s"] - want["busy_s"]) < 1e-12
    peaks = common.load("peaks.json")["devices"][want["device_kind"]]
    spec = common.load("layer_metrics", "rs_encode_roofline.json")
    kernel = spec["value"]["den"][0]["patterns"]
    assert trace_reduce.kernel_calls(r, kernel) == want["kernel_calls"]
    assert abs(trace_reduce.kernel_seconds(r, kernel) - want["kernel_seconds"]) < 1e-12
    least = want["kernel_calls"] * 14 * (1 << 20) / peaks["hbm_bytes_per_s"]
    config = common.load("configs", "warm-rs10.4.json")
    seen = metrics.Observed({}, {}, {}, {}, {}, {}, r, peaks, config)
    assert abs(seen.value(spec) - 100 * least / want["kernel_seconds"]) < 1e-9
    assert 0 < seen.value(spec) <= 100


# ------------------------------------------------------------- the controls
def _template(tmp: str, seed: int = 5) -> dict:
    from benchmarks.lib.stores import sealed_template

    recipe = dict(common.load("configs", "warm-rs10.4.json")["store"])
    recipe.update(needles=300, fill_to_bytes=12 << 20)
    dirs = argparse.Namespace(scratch=tmp, data=tmp)
    return sealed_template.build(recipe, dirs, seed, lambda f, jobs: list(map(f, jobs)), 2)


def _write_shards(store: dict, base: str, parity_matrix: np.ndarray) -> None:
    controls.write_shards(store, base, parity_matrix, lambda f, jobs: list(map(f, jobs)), 1)


def test_control_a_codec_that_breaks_the_guarantee_is_not_correct():
    """The control of warm-rs10.4: the reference in the program's place with the
    guarantee broken — parity shard 13 a copy of shard 12, so that RS(10,4) is
    RS(10,3) and some 10 of the 14 shards no longer give the data back. Through
    the comparison a run makes: the blocks' digests, the bytes, the recovery."""
    serial = lambda f, jobs: list(map(f, jobs))  # noqa: E731
    with tempfile.TemporaryDirectory() as tmp:
        store = _template(tmp)
        good, bad = os.path.join(tmp, "1"), os.path.join(tmp, "2")
        matrix = rs_codec.Codec(10, 4).parity_matrix
        _write_shards(store, good, matrix)
        broken = matrix.copy()
        broken[3] = broken[2]
        _write_shards(store, bad, broken)
        want = shell_jobs.reference_digests(store, 10, 4, serial, 3)
        rows = len(rs_codec.row_spans(store["dat_bytes"], 10))
        assert len(want) == rows * 14 * shell_jobs.DIGEST_BYTES
        lost = [0, 1, 2, 12]  # recovery has to lean on shard 13
        got = shell_jobs.digests_of_files(good, store["dat_bytes"], 10, 4, serial, 2)
        assert shell_jobs.digests_differing(got, want) == 0
        d, c, u = shell_jobs.compare_files(store, [good], 10, 4, 5, serial, 2, lost=lost)
        assert (d, u) == (0, 0) and c == 14 * rs_codec.shard_size(store["dat_bytes"], 10)
        got = shell_jobs.digests_of_files(bad, store["dat_bytes"], 10, 4, serial, 2)
        assert shell_jobs.digests_differing(got, want) == rows  # shard 13's block in every row
        d, _c, u = shell_jobs.compare_files(store, [bad], 10, 4, 5, serial, 2, lost=lost)
        assert d > 0 and u > 0
        assert shell_jobs.digests_differing(got[:-16], want) == rows * 14  # a file cut short


def test_device_proof_reads_the_devices_own_side():
    want = {"kernels_in_trace": {"rs_decode_kernel": ["^(?=.*gf_matmul)(?=.*custom-call)"]}}

    def seen(trace):
        return metrics.Observed({}, {}, {}, {}, {}, {}, trace, None, {})

    ran = {"ops": {"%_gf_matmul_pallas.1 = u32[1,512,128] custom-call(": [3, 4e-5]}}
    assert metrics.device_proof(want, seen(ran)) == [("rs_decode_kernel_absent_from_trace", 0, 0)]
    on_the_host = {"ops": {"%copy.1 = u32[1,512,128] copy(": [3, 1e-5]}}
    assert metrics.device_proof(want, seen(on_the_host)) == [("rs_decode_kernel_absent_from_trace", 1, 0)]
    assert metrics.device_proof(want, seen(None)) == []  # no trace, no reading


def test_a_run_has_one_placement_and_stale_directories_go():
    with tempfile.TemporaryDirectory() as tmp, tempfile.TemporaryDirectory() as shm:
        before = tempfile.tempdir
        tempfile.tempdir = tmp
        try:
            stale = os.path.join(shm, f"{bench_run.SCRATCH_PREFIX}4194999_left")  # no such process
            mine = os.path.join(shm, f"{bench_run.SCRATCH_PREFIX}{os.getpid()}_other")
            os.makedirs(os.path.join(stale, "data"))
            os.makedirs(mine)
            scratch, memory = bench_run.make_scratch({"server_directory": shm, "needs_free_bytes": 1})
            assert os.path.dirname(scratch) == tmp and os.path.dirname(memory) == shm
            assert str(os.getpid()) in os.path.basename(memory)
            assert not os.path.exists(stale) and os.path.isdir(mine)
            try:  # no room where the configuration says: no result, and no second place
                bench_run.make_scratch({"server_directory": shm, "needs_free_bytes": 1 << 62})
            except common.Failed:
                pass
            else:
                raise AssertionError("a run with no room must not look for another place")
        finally:
            tempfile.tempdir = before


# --------------------------------------------------------------- the faults
def _rehearsal(workload: str, fault, seed: int) -> dict:
    args = argparse.Namespace(workload=workload, seed=seed, seconds=1.5, trace=0,
                              rehearse=True, fault=fault)
    line, _compared = bench_run.run(args)
    return line


def test_fault_a_parity_byte_altered_where_it_is_produced():
    off_device = {"bytes_encoded_off_device", "bytes_uncounted_on_device"}  # the CPU's stand-in
    clean = _rehearsal("warm-rs10.4.ec-encode", None, 11)["compared"]
    assert all(c["value"] <= c["limit"] for n, c in clean.items() if n not in off_device)
    line = _rehearsal("warm-rs10.4.ec-encode", "ec_parity_byte", 11)
    assert line["compared"]["shard_blocks_differing"]["value"] > 0
    assert line["compared"]["shard_bytes_differing"]["value"] > 0  # the last conversion's files
    assert bench_run.verdict(line["compared"]) is False and line["correct"] is False


def test_fault_a_reconstructed_byte_altered_where_it_is_produced():
    clean = _rehearsal("warm-rs10.4.degraded-get-c16", None, 13)
    assert bench_run.verdict(clean["compared"]) is True
    assert clean["compared"]["reconstructions_never_moved"]["value"] == 0
    # the program checks a needle's CRC after decoding, so the altered bytes come
    # back as 500s, not as wrong bodies: either way no right body, and not correct
    line = _rehearsal("warm-rs10.4.degraded-get-c16", "ec_decode_byte", 13)
    assert line["compared"]["bodies_wrong"]["value"] > 0 and line["failed"] > 0
    assert bench_run.verdict(line["compared"]) is False and line["correct"] is False
