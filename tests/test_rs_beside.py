"""Which codec calls met which (`rs_dispatches_beside_total{op, beside}`):
`gf_matmul_bytes` keeps the calls between their dispatch and the end of
their fetch, and each call is counted once, when it returns, by the op of
another call it met there — another op first, else its own, else none.

A fake kernel holds each call inside the window until the test lets it go,
so the overlaps are made, not hoped for."""

import os
import sys
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.util import metrics as m
from test_stage_tracing import moved, scrape

BESIDE = "seaweedfs_tpu_rs_dispatches_beside_total"
MATRIX = np.arange(1, 41, dtype=np.uint8).reshape(4, 10)
WAIT_S = 20


class HeldKernel:
    """Stands in for the kernel of the off-chip path: a call named by its
    thread waits for its own release, and raises where told to."""

    def __init__(self):
        self.entered: dict = {}
        self.release: dict = {}
        self.fail: set = set()

    def hold(self, name: str) -> None:
        self.entered[name] = threading.Event()
        self.release[name] = threading.Event()

    def __call__(self, key, words):
        name = threading.current_thread().name
        if name in self.entered:
            self.entered[name].set()
            assert self.release[name].wait(WAIT_S)
        if name in self.fail:
            raise RuntimeError("the device went away")
        return np.zeros((len(key), words.shape[1]), dtype=np.uint32)


def in_flight(op=None) -> int:
    """Codec calls between their dispatch and the end of their fetch now."""
    return sum(1 for o, _met in list(gf256._IN_FLIGHT.values()) if op in (None, o))


@pytest.fixture
def kernel(monkeypatch):
    fake = HeldKernel()
    monkeypatch.setattr(gf256, "_gf_matmul_jnp_packed", fake)
    yield fake
    assert in_flight() == 0


def call(op: str) -> None:
    gf256.gf_matmul_bytes(MATRIX, np.ones((10, 64), dtype=np.uint8),
                          force_pallas=False, op=op)


def start(kernel: HeldKernel, name: str, op: str, held: bool = True) -> threading.Thread:
    """A thread named `name` that makes one `op` call; held inside it until
    released, and known to be inside it when this returns."""
    errors = []

    def go():
        try:
            call(op)
        except Exception as e:  # noqa: BLE001 — the test reads it
            errors.append(e)

    if held:
        kernel.hold(name)
    t = threading.Thread(target=go, name=name, daemon=True)
    t.errors = errors
    t.start()
    if held:
        assert kernel.entered[name].wait(WAIT_S)
    return t


def finish(kernel: HeldKernel, *threads) -> None:
    for t in threads:
        if t.name in kernel.release:
            kernel.release[t.name].set()
        t.join(WAIT_S)
        assert not t.is_alive()


def beside(before: dict, after: dict) -> dict:
    """{(op, beside): calls counted} between two scrapes, what moved only."""
    got = {}
    for op in gf256.RS_OPS:
        for label in (*gf256.RS_OPS, "none"):
            n = moved(before, after, BESIDE, op=op, beside=label)
            if n:
                got[(op, label)] = n
    return got


def test_a_call_alone_is_beside_none(kernel):
    before = scrape()
    call("decode")
    assert beside(before, scrape()) == {("decode", "none"): 1}


def test_an_encode_and_a_decode_in_flight_at_once_are_beside_each_other(kernel):
    before = scrape()
    enc = start(kernel, "enc", "encode")
    assert in_flight("encode") == 1 and in_flight("decode") == 0
    dec = start(kernel, "dec", "decode")
    assert in_flight("decode") == 1 and in_flight() == 2
    finish(kernel, dec)
    assert in_flight() == 1
    finish(kernel, enc)
    assert not enc.errors and not dec.errors
    assert beside(before, scrape()) == {("encode", "decode"): 1, ("decode", "encode"): 1}


def test_a_call_that_begins_and_ends_inside_another_marks_both(kernel):
    """The later call finds the earlier one and marks it too, though the
    earlier one looked before the later one came."""
    before = scrape()
    enc = start(kernel, "enc", "encode")
    dec = start(kernel, "dec", "decode", held=False)
    dec.join(WAIT_S)
    assert beside(before, scrape()) == {("decode", "encode"): 1}
    finish(kernel, enc)
    assert beside(before, scrape()) == {("decode", "encode"): 1, ("encode", "decode"): 1}


def test_two_calls_of_one_op_are_beside_that_op(kernel):
    before = scrape()
    a = start(kernel, "dec-a", "decode")
    b = start(kernel, "dec-b", "decode")
    finish(kernel, a, b)
    assert beside(before, scrape()) == {("decode", "decode"): 2}


def test_another_op_is_named_before_the_calls_own(kernel):
    """A decode beside a decode and an encode is beside `encode`; the encode
    met two decodes and is beside `decode`, once."""
    before = scrape()
    a = start(kernel, "dec-a", "decode")
    enc = start(kernel, "enc", "encode")
    b = start(kernel, "dec-b", "decode")
    finish(kernel, a, b, enc)
    assert beside(before, scrape()) == {("decode", "encode"): 2, ("encode", "decode"): 1}


def test_a_call_that_raises_leaves_no_call_in_flight_and_is_not_counted(kernel):
    before = scrape()
    kernel.fail.add("dec")
    enc = start(kernel, "enc", "encode")
    dec = start(kernel, "dec", "decode")
    finish(kernel, dec)
    assert isinstance(dec.errors[0], RuntimeError)
    assert in_flight("decode") == 0 and in_flight("encode") == 1
    finish(kernel, enc)
    assert in_flight() == 0
    # the encode met the decode that failed; the failed call is in no count
    assert beside(before, scrape()) == {("encode", "decode"): 1}
    calls = moved(before, scrape(), "seaweedfs_tpu_rs_dispatches_total", op="decode")
    assert calls == 0
    after_fail = scrape()
    call("decode")
    assert beside(after_fail, scrape()) == {("decode", "none"): 1}


def test_every_call_counted_by_op_is_counted_once_beside_something(kernel):
    """Over any mix, the family's sum for an op is that op's calls."""
    before = scrape()
    threads = [start(kernel, f"t{i}", op) for i, op in enumerate(
        ["encode", "decode", "decode", "encode", "apply"])]
    finish(kernel, *threads)
    call("decode")
    after = scrape()
    for op in gf256.RS_OPS:
        assert moved(before, after, BESIDE, op=op) == moved(
            before, after, "seaweedfs_tpu_rs_dispatches_total", op=op)


def test_the_family_is_on_metrics_with_its_help_and_type(kernel):
    call("encode")
    page = m.REGISTRY.render()
    assert f"# TYPE {BESIDE} counter" in page
    assert f"# HELP {BESIDE} " in page
    assert f'{BESIDE}{{beside="none",op="encode"}}' in page


def test_many_threads_at_a_short_switch_interval_lose_no_call(monkeypatch):
    """More threads than cores, switching every microsecond: no call is
    left in flight and none is counted twice or lost, with no lock."""
    monkeypatch.setattr(gf256, "_gf_matmul_jnp_packed", HeldKernel())
    ops = ["encode", "decode", "apply"]
    errors, before = [], scrape()

    def go(i):
        try:
            for j in range(40):
                call(ops[(i + j) % 3])
        except Exception as e:  # noqa: BLE001 — the test reads it
            errors.append(e)

    threads = [threading.Thread(target=go, args=(i,), daemon=True)
               for i in range(2 * (os.cpu_count() or 4))]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    assert in_flight() == 0
    after = scrape()
    for k, op in enumerate(ops):
        made = sum(1 for i in range(len(threads)) for j in range(40) if (i + j) % 3 == k)
        assert moved(before, after, "seaweedfs_tpu_rs_dispatches_total", op=op) == made
        assert moved(before, after, BESIDE, op=op) == made
