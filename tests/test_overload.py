"""Overload control plane (ISSUE 9): priority admission, adaptive
concurrency limits, circuit breakers, retry budgets, pressure coupling.

Three layers, all tier-1 fast:

- pure units over `util/overload.py` / `util/backoff.RetryBudget` with
  fake clocks (AIMD moves, shed order, budget refill, breaker states);
- seam tests over the real FastHTTP client/server pair (deadline
  enforcement, Retry-After surfacing, breaker fast-fail, admission gate
  shedding on a live fast tier);
- a cluster chaos test: a browned-out (503-shedding) replica trips its
  breaker while cluster-wide reads keep succeeding byte-identical via
  the remaining replica — the acceptance scenario.
"""

import asyncio
import random
import time

import pytest

from seaweedfs_tpu.util import faults, overload
from seaweedfs_tpu.util.backoff import (
    BackoffPolicy,
    RetryBudget,
    configure_retry_budget,
    retry_async,
    shared_retry_budget,
)
from seaweedfs_tpu.util.overload import (
    CLASS_MAINT,
    CLASS_META,
    CLASS_READ,
    CLASS_WRITE,
    AdaptiveLimiter,
    AdmissionGate,
    CircuitBreaker,
    CircuitOpenError,
    classify_method,
    latency_percentile,
)


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ------------------------------------------------------ adaptive limiter --


def test_classify_method_priority_order():
    assert classify_method("GET") == CLASS_READ
    assert classify_method("HEAD") == CLASS_READ
    assert classify_method("POST") == CLASS_WRITE
    assert classify_method("PUT") == CLASS_WRITE
    assert classify_method("DELETE") == CLASS_WRITE
    assert classify_method("OPTIONS") == CLASS_META
    # shedding is lowest-class-first: maint below meta below writes
    assert CLASS_READ < CLASS_WRITE < CLASS_META < CLASS_MAINT


def test_adaptive_limiter_aimd_moves():
    lim = AdaptiveLimiter(initial=64, window=8, tolerance=2.0)
    # window 1 establishes the baseline (~1ms)
    for _ in range(8):
        lim.on_sample(0.001, inflight=1)
    assert lim.baseline_s == pytest.approx(0.001)
    before = lim.limit
    # healthy latency but the limit was never the binding constraint:
    # no additive increase
    for _ in range(8):
        lim.on_sample(0.001, inflight=3)
    assert lim.limit == before and lim.increases == 0
    # healthy AND saturated: +1
    for _ in range(8):
        lim.on_sample(0.001, inflight=lim.limit)
    assert lim.limit == before + 1 and lim.increases == 1
    # congested window (avg >> baseline * tolerance): multiplicative cut
    for _ in range(8):
        lim.on_sample(0.010, inflight=lim.limit)
    assert lim.limit < before + 1 and lim.decreases == 1


def test_adaptive_limiter_bimodal_mix_does_not_pin_at_min():
    """A µs fast mode beside a ms slow mode: the baseline tracks the
    floor of windowed MEANS, so a steady 50/50 mix is 'healthy' (every
    window averages the same) instead of every window comparing against
    the µs mode and decreasing to min_limit."""
    lim = AdaptiveLimiter(initial=64, window=16, tolerance=2.0)
    for _ in range(20):  # many windows of the same bimodal mix
        for i in range(16):
            lim.on_sample(0.00001 if i % 2 else 0.002, inflight=1)
    assert lim.limit == 64 and lim.decreases == 0


def test_adaptive_limiter_baseline_recovers_after_regime_change():
    lim = AdaptiveLimiter(initial=64, window=8)
    for _ in range(8):
        lim.on_sample(0.001, inflight=1)
    # regime shifts to a heavier payload mix: decreases at first, then
    # the 10%/window upward drift absorbs the new floor and stops them
    for _ in range(80):
        for _ in range(8):
            lim.on_sample(0.004, inflight=1)
    decreases_then = lim.decreases
    for _ in range(10):
        for _ in range(8):
            lim.on_sample(0.004, inflight=1)
    assert lim.decreases == decreases_then  # no longer cutting
    assert lim.baseline_s == pytest.approx(0.004, rel=0.05)


# ------------------------------------------------------- admission gate --


def _gate(clock=None, **kw) -> AdmissionGate:
    kw.setdefault("limiter", AdaptiveLimiter(initial=2, min_limit=2))
    kw.setdefault("read_budget_s", 0.05)
    return AdmissionGate("t", clock=clock or FakeClock(), **kw)


def test_gate_deadline_shed_is_lowest_class_first():
    g = _gate()
    # per-class budgets scale DOWN with class: a wait that sheds maint
    # still admits reads
    w = 0.02  # between maint budget (0.2*50ms=10ms) and read (50ms)
    assert g.try_admit(CLASS_MAINT, w) is False
    assert g.try_admit(CLASS_READ, w) is True
    g.release()
    assert g.shed_total == 1
    assert g.stats()["shed_total"] == 1


def test_gate_queue_full_sheds_by_class_share():
    g = _gate(max_queue=8)

    async def main():
        assert g.try_admit(CLASS_READ) is True
        assert g.try_admit(CLASS_READ) is True  # limit 2 reached
        # one read queued (share 1.0 allows the full queue) ...
        f0 = g.try_admit(CLASS_READ)
        assert asyncio.isfuture(f0)
        # ... and maint's 0.1 share (0.8 slots) is now exhausted: the
        # next maint request sheds at arrival while reads still queue
        assert g.try_admit(CLASS_MAINT) is False
        futs = [g.try_admit(CLASS_READ) for _ in range(7)]
        assert all(asyncio.isfuture(f) for f in futs)
        assert g.try_admit(CLASS_READ) is False  # 9th: queue full
        return futs

    asyncio.run(main())
    assert g.queued == 8
    assert g.shed_total == 2
    # shed children are keyed (class, reason, tenant-label) since the
    # tenant QoS plane (ISSUE 12); unattributed sheds land on "default"
    assert (CLASS_MAINT, "queue_full", "default") in g._shed_children
    assert (CLASS_READ, "queue_full", "default") in g._shed_children


def test_gate_cancelled_waiter_leaks_no_accounting():
    """A queued request whose task dies (client disconnect mid-overload —
    the exact regime the gate exists for) must not leak queue-depth or
    inflight accounting: a leaked `queued` count would shed lower classes
    forever at zero load and report phantom pressure to maintenance."""
    g = _gate()

    async def main():
        assert g.try_admit(CLASS_READ) is True
        assert g.try_admit(CLASS_READ) is True  # limit 2 reached
        # case 1: cancelled while still queued — the husk stops counting
        fut = g.try_admit(CLASS_READ)
        assert asyncio.isfuture(fut)
        t = asyncio.ensure_future(g.wait_queued(CLASS_READ, fut))
        await asyncio.sleep(0)  # t parked inside wait_for
        t.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t
        assert g.queued == 0
        assert g.inflight == 2

        # case 2: the race — _wake grants the slot, THEN the waiter's
        # task cancellation lands before it resumed
        fut2 = g.try_admit(CLASS_READ)
        assert asyncio.isfuture(fut2)
        t2 = asyncio.ensure_future(g.wait_queued(CLASS_READ, fut2))
        await asyncio.sleep(0)
        g.release()  # grants fut2 via _wake
        assert fut2.done() and fut2.result() is True
        t2.cancel()
        try:
            if await t2:
                # 3.10 wait_for: a completed grant wins over the cancel —
                # the caller was admitted and releases normally
                g.release()
        except asyncio.CancelledError:
            pass  # 3.12+ semantics: wait_queued handed the slot back
        assert g.queued == 0
        assert g.inflight == 1
        g.release()
        assert g.inflight == 0
        # the gate still admits normally after both cancellations
        assert g.try_admit(CLASS_READ) is True
        g.release()

    asyncio.run(main())


def test_gate_wake_order_is_highest_class_first():
    async def main():
        g = _gate()
        assert g.try_admit(CLASS_READ) is True
        assert g.try_admit(CLASS_READ) is True
        f_maint = g.try_admit(CLASS_MAINT, 0.0)
        f_read = g.try_admit(CLASS_READ, 0.0)
        assert asyncio.isfuture(f_maint) and asyncio.isfuture(f_read)
        g.release()
        # the freed slot goes to the READ waiter even though the maint
        # one queued first
        assert f_read.done() and f_read.result() is True
        assert not f_maint.done()
        g.release()
        assert f_maint.done()

    asyncio.run(main())


def test_gate_queued_wait_past_budget_sheds():
    async def main():
        g = _gate(read_budget_s=0.02)
        assert g.try_admit(CLASS_READ) is True
        assert g.try_admit(CLASS_READ) is True
        fut = g.try_admit(CLASS_READ)
        assert asyncio.isfuture(fut)
        admitted = await g.wait_queued(CLASS_READ, fut, 0.0)
        assert admitted is False  # nobody released within the budget
        assert g.queued == 0  # live count dropped NOW
        key = (CLASS_READ, "deadline", "default")
        assert key in g._shed_children

    asyncio.run(main())


def test_gate_grant_in_the_turn_of_the_timeout_is_an_admission(monkeypatch):
    """The slot is granted in the same loop turn as the waiter's budget
    runs out, so `wait_for` raises TimeoutError over a granted future.
    The grant counted the request inflight and out of the queue: it is
    admitted, and its release frees the slot. Shedding it instead left
    the slot held for good and `queued` below zero, until every slot was
    held and the gate shed everything by deadline."""
    g = _gate(read_budget_s=0.02)
    real_wait_for = asyncio.wait_for

    async def grant_then_time_out(fut, timeout):
        g.release()  # a slot frees; _wake grants `fut`
        assert fut.done() and fut.result() is True
        raise asyncio.TimeoutError

    async def main():
        assert g.try_admit(CLASS_READ) is True
        assert g.try_admit(CLASS_READ) is True  # limit 2 reached
        fut = g.try_admit(CLASS_READ)
        assert asyncio.isfuture(fut)
        monkeypatch.setattr(overload.asyncio, "wait_for", grant_then_time_out)
        try:
            assert await g.wait_queued(CLASS_READ, fut, 0.0) is True
        finally:
            monkeypatch.setattr(overload.asyncio, "wait_for", real_wait_for)
        assert (g.inflight, g.queued) == (2, 0)
        assert (CLASS_READ, "deadline", "default") not in g._shed_children
        assert not g._fut_tenant
        g.release()
        g.release()
        assert (g.inflight, g.queued) == (0, 0)
        assert g.try_admit(CLASS_READ) is True
        g.release()

    asyncio.run(main())


def test_gate_pressure_signal_decays():
    clk = FakeClock()
    g = _gate(clock=clk)
    assert g.pressure() == 0.0
    g._shed(CLASS_READ, "deadline")
    assert g.pressure() == 1.0  # shed within the last second
    clk.advance(2.0)
    assert g.pressure() == 0.0
    # queue fullness is the fallback signal
    g.queued = g.max_queue // 2
    assert g.pressure() == pytest.approx(0.5)


def test_global_pressure_over_registered_gates(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_ADMIT", "1")
    g = overload.new_server_gate("t-global")
    try:
        assert g is not None
        base = overload.global_pressure()
        g._shed(CLASS_READ, "deadline")
        assert overload.global_pressure() == 1.0
    finally:
        overload.drop_gate(g)
    # dropped gates stop contributing
    assert overload.global_pressure() <= max(base, 1.0)


def test_admitted_latency_histogram_percentiles():
    g = _gate()
    for _ in range(99):
        assert g.try_admit(CLASS_READ, 0.0) in (True,) or True
        g.release(total_s=0.001)
    g.try_admit(CLASS_READ, 0.0)
    g.release(total_s=1.0)  # one outlier
    p50 = latency_percentile(g.admitted_counts, 50)
    p99 = latency_percentile(g.admitted_counts, 99)
    assert p50 == pytest.approx(0.001, rel=0.25)  # <= ~19% bucket error
    assert p99 < 0.002
    assert latency_percentile(g.admitted_counts, 99.9) > 0.5
    assert g.stats()["admitted_p50_ms"] > 0


# ------------------------------------------------------- circuit breaker --


def test_breaker_opens_on_consecutive_failures_and_half_open_probes():
    clk = FakeClock()
    br = CircuitBreaker("p:1", fail_threshold=3, open_s=0.5, clock=clk)
    for _ in range(2):
        br.record_failure()
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "open" and br.opens == 1
    assert not br.allow() and br.blocked()
    clk.advance(0.6)  # open window over: one half-open probe
    assert br.allow()
    assert br.state == "half_open"
    assert not br.allow()  # second caller: probe already out
    br.record_success()
    assert br.state == "closed" and br.allow()


def test_breaker_failed_probe_reopens():
    clk = FakeClock()
    br = CircuitBreaker("p:2", fail_threshold=2, open_s=0.5, clock=clk)
    br.record_failure()
    br.record_failure()
    clk.advance(0.6)
    assert br.allow()  # half-open probe
    br.record_failure()
    assert br.state == "open"  # straight back to open
    assert not br.allow()


def test_breaker_trips_on_shed_rate_and_honors_retry_after():
    clk = FakeClock()
    br = CircuitBreaker("p:3", shed_window=10, shed_trip=0.5, clock=clk)
    # sheds below half the window never trip
    for _ in range(6):
        br.record_success()
    for _ in range(3):
        br.record_shed()
    assert br.state == "closed"
    br.record_shed()
    br.record_shed(retry_after_s=3.0)
    assert br.state == "open"  # 5 sheds in the 10-deep ring >= 50%
    clk.advance(1.0)
    assert not br.allow()  # the peer asked for 3s: still open
    clk.advance(2.5)
    assert br.allow()  # half-open after the peer's own hint
    assert br.shedding() is False or True  # shedding() is time-based


def test_breaker_cancelled_probe_returns_slot():
    """A half-open probe abandoned without an outcome (hedged read
    losing its race) must return the slot via record_cancelled, or the
    breaker refuses the peer until the probe lease expires."""
    clk = FakeClock()
    br = CircuitBreaker("p:5", fail_threshold=2, open_s=0.5, clock=clk)
    br.record_failure()
    br.record_failure()
    clk.advance(0.6)
    assert br.allow()  # half-open probe out
    assert not br.allow() and br.blocked()
    br.record_cancelled()  # caller cancelled: no verdict, slot back
    assert br.state == "half_open" and not br.blocked()
    assert br.allow()  # next caller probes immediately
    br.record_success()
    assert br.state == "closed"
    # cancellation outside half-open is a no-op on the state machine
    br.record_cancelled()
    assert br.state == "closed" and br.allow()


def test_breaker_probe_lease_reclaims_leaked_slot():
    """Backstop for callers that never report at all: the probe slot
    leases for probe_timeout_s, after which allow() hands it out again
    instead of refusing the peer until process restart."""
    clk = FakeClock()
    br = CircuitBreaker(
        "p:6", fail_threshold=2, open_s=0.5, probe_timeout_s=5.0,
        clock=clk,
    )
    br.record_failure()
    br.record_failure()
    clk.advance(0.6)
    assert br.allow()  # probe out, never reported
    assert not br.allow() and br.blocked()
    clk.advance(5.1)  # lease expired
    assert not br.blocked()
    assert br.allow()  # reclaimed: a fresh probe goes out
    assert not br.allow()  # and holds its own lease
    br.record_success()
    assert br.state == "closed"


def test_breaker_shedding_window():
    clk = FakeClock()
    br = CircuitBreaker("p:4", clock=clk)
    assert not br.shedding()
    br.record_shed()
    assert br.shedding()
    clk.advance(1.5)
    assert not br.shedding()


def test_peer_breaker_registry_shared_and_env_gated(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "0")
    assert overload.peer_breaker("x:1") is None
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    br = overload.peer_breaker("x:1")
    assert br is overload.peer_breaker("x:1")  # one breaker per peer
    assert overload.BREAKERS.peek("x:1") is br
    assert overload.BREAKERS.peek("never-seen:2") is None


# ---------------------------------------------------------- retry budget --


def test_retry_budget_drains_and_refills():
    b = RetryBudget(ratio=0.1, max_tokens=10.0)
    assert b.allow("t")  # full bucket
    for _ in range(6):
        b.on_failure()
    assert not b.allow("t")  # below half
    from seaweedfs_tpu.util.metrics import RETRIES_SUPPRESSED

    key = (("op", "t"),)
    assert RETRIES_SUPPRESSED._values.get(key, 0) >= 1
    # 10 successes deposit ratio each: back above half
    for _ in range(11):
        b.on_success()
    assert b.allow("t")
    assert b.snapshot()["max_tokens"] == 10.0


def test_shared_budget_env(monkeypatch):
    configure_retry_budget(None)
    monkeypatch.setenv("SEAWEEDFS_TPU_RETRY_BUDGET_TOKENS", "0")
    assert shared_retry_budget() is None  # 0 disables
    monkeypatch.setenv("SEAWEEDFS_TPU_RETRY_BUDGET_TOKENS", "7")
    monkeypatch.setenv("SEAWEEDFS_TPU_RETRY_BUDGET_RATIO", "0.5")
    configure_retry_budget(None)
    b = shared_retry_budget()
    assert b is not None and b.max_tokens == 7.0 and b.ratio == 0.5
    assert shared_retry_budget() is b  # memoized
    configure_retry_budget(None)


def test_retry_async_suppressed_by_drained_budget():
    b = RetryBudget(max_tokens=4.0)
    for _ in range(3):
        b.on_failure()  # below half before we start
    calls = [0]

    async def fn():
        calls[0] += 1
        raise IOError("boom")

    async def main():
        with pytest.raises(IOError):
            await retry_async(
                fn,
                policy=BackoffPolicy(base=0.001, cap=0.01, attempts=5),
                budget=b,
                rng=random.Random(1),
                op="t-suppress",
            )

    asyncio.run(main())
    assert calls[0] == 1  # first attempt only: retries suppressed


def test_retry_async_delay_floor_honors_retry_after(monkeypatch):
    sleeps: list = []

    async def fake_sleep(d):
        sleeps.append(d)

    monkeypatch.setattr(asyncio, "sleep", fake_sleep)
    calls = [0]

    async def fn():
        calls[0] += 1
        if calls[0] < 3:
            raise IOError("shed")
        return "ok"

    async def main():
        return await retry_async(
            fn,
            policy=BackoffPolicy(base=0.0001, cap=0.001, attempts=5),
            budget=None,
            rng=random.Random(2),
            delay_floor=lambda: 0.25,
        )

    assert asyncio.run(main()) == "ok"
    assert len(sleeps) == 2 and all(d >= 0.25 for d in sleeps)


def test_retry_async_shared_budget_deposits_exactly_once():
    """The transports (FastHTTPClient.request / Stub.call) deposit every
    completed response into the shared budget — retry_async must NOT
    deposit shared-budget successes too, or the effective retry cap is
    ~2x the configured ratio. An explicitly passed budget (not fed by
    any transport) still deposits here."""

    async def ok():
        return "ok"

    async def main():
        shared = RetryBudget(ratio=0.1, max_tokens=10.0)
        shared.tokens = 6.0
        configure_retry_budget(shared)
        try:
            assert await retry_async(ok, op="t-dep") == "ok"
            assert shared.tokens == 6.0  # no deposit: transports own it
        finally:
            configure_retry_budget(None)
        own = RetryBudget(ratio=0.1, max_tokens=10.0)
        own.tokens = 6.0
        assert await retry_async(ok, budget=own, op="t-dep") == "ok"
        assert own.tokens == pytest.approx(6.1)  # explicit budget deposits

    asyncio.run(main())


# ------------------------------------------ fasthttp client seam duties --


def _fast_server(handler):
    from seaweedfs_tpu.util.fasthttp import FastHTTPServer

    return FastHTTPServer(handler)


def test_client_deadline_fires_and_breaker_counts_it(monkeypatch):
    """A hung peer costs the caller its deadline, not 30s — and the
    timeout is a breaker-visible failure."""
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    from seaweedfs_tpu.util.fasthttp import FastHTTPClient, render_response

    async def handler(req):
        await asyncio.sleep(30)
        return render_response(200, b"late")

    async def main():
        srv = _fast_server(handler)
        await srv.start("127.0.0.1", 0)
        port = srv._server.sockets[0].getsockname()[1]
        http = FastHTTPClient()
        try:
            t0 = time.perf_counter()
            with pytest.raises(OSError):  # TimeoutError is an OSError
                await http.request(
                    "GET", f"127.0.0.1:{port}", "/x", timeout=0.15
                )
            assert time.perf_counter() - t0 < 5.0
            br = overload.BREAKERS.peek(f"127.0.0.1:{port}")
            assert br is not None and br._consec_fail >= 1
        finally:
            await http.close()
            await srv.stop()

    asyncio.run(main())


def test_connect_timeout_is_breaker_failure_and_builtin_timeout(
    monkeypatch,
):
    """wait_for's connect deadline raises asyncio.TimeoutError — on 3.10
    neither an OSError nor the builtin TimeoutError, so it would slip
    past both the breaker's `except OSError` and callers catching
    TimeoutError. The client must record the failure (a SYN-dropping
    peer has to trip eventually) and surface builtin TimeoutError."""
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    from seaweedfs_tpu.util import fasthttp

    async def main():
        http = fasthttp.FastHTTPClient()

        async def never_connects(hostport, timeout=None):
            raise asyncio.TimeoutError()

        http._get = never_connects
        with pytest.raises(TimeoutError):
            await http.request("GET", "sinkhole:79", "/x", timeout=0.01)
        br = overload.BREAKERS.peek("sinkhole:79")
        assert br is not None and br._consec_fail == 1

    asyncio.run(main())


def test_stale_retry_uses_remaining_deadline(monkeypatch):
    """The one clean retry after a stale pooled connection runs against
    the REMAINING deadline, not a fresh copy of the original — one
    logical request never spends ~2x its stated budget."""
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "0")
    from seaweedfs_tpu.util import fasthttp

    class _FakeTransport:
        def __init__(self):
            self._closing = False

        def write(self, data):
            pass

        def close(self):
            self._closing = True

        def is_closing(self):
            return self._closing

    class _FakeConn:
        def __init__(self, loop, fail):
            self._loop = loop
            self.closed = False
            self.transport = _FakeTransport()
            self._fail = fail

        def begin(self):
            fut = self._loop.create_future()
            if self._fail:
                fut.set_exception(ConnectionResetError("stale"))
            else:
                fut.set_result((200, b"ok", False, None))
            return fut

    seen: list = []

    async def main():
        http = fasthttp.FastHTTPClient()
        loop = asyncio.get_running_loop()
        conns = [_FakeConn(loop, True), _FakeConn(loop, False)]

        async def fake_get(hostport, timeout=None):
            seen.append(timeout)
            await asyncio.sleep(0.05)  # measurable spend before failing
            return conns.pop(0)

        http._get = fake_get
        assert await http.request(
            "GET", "x:1", "/k", timeout=2.0
        ) == (200, b"ok")

    asyncio.run(main())
    assert len(seen) == 2
    assert seen[0] is not None and 2.0 - 0.01 <= seen[0] <= 2.0
    assert seen[1] is not None and seen[1] <= 2.0 - 0.04


def test_response_deadline_armed_with_remaining_budget(monkeypatch):
    """One logical request spends ONE deadline across its phases: after
    time spent connecting, the response timer is armed with the
    remaining budget, not a fresh copy of the original timeout."""
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "0")
    from seaweedfs_tpu.util.fasthttp import FastHTTPClient, render_response

    async def handler(req):
        await asyncio.sleep(30)
        return render_response(200, b"late")

    async def main():
        srv = _fast_server(handler)
        await srv.start("127.0.0.1", 0)
        port = srv._server.sockets[0].getsockname()[1]
        http = FastHTTPClient()
        try:
            real_get = http._get

            async def slow_connect(hostport, timeout=None):
                await asyncio.sleep(0.15)  # eats over half the budget
                return await real_get(hostport, timeout)

            http._get = slow_connect
            t0 = time.perf_counter()
            with pytest.raises(OSError):  # deadline, not 2x deadline
                await http.request(
                    "GET", f"127.0.0.1:{port}", "/x", timeout=0.25
                )
            assert time.perf_counter() - t0 < 0.4  # not 0.15 + 0.25
        finally:
            await http.close()
            await srv.stop()

    asyncio.run(main())


def test_stale_retry_returns_half_open_probe_before_recursing(monkeypatch):
    """The one clean retry after a stale pooled connection re-enters
    request() and thus allow(): if the first attempt held the half-open
    probe slot, it must be handed back first — otherwise the retry
    fast-fails with CircuitOpenError against a now-healthy peer and the
    slot leaks for the rest of its lease."""
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    from seaweedfs_tpu.util import fasthttp

    class _FakeTransport:
        def __init__(self):
            self._closing = False

        def write(self, data):
            pass

        def close(self):
            self._closing = True

        def is_closing(self):
            return self._closing

    class _FakeConn:
        def __init__(self, loop, fail):
            self._loop = loop
            self.closed = False
            self.transport = _FakeTransport()
            self._fail = fail

        def begin(self):
            fut = self._loop.create_future()
            if self._fail:
                fut.set_exception(ConnectionResetError("stale"))
            else:
                fut.set_result((200, b"ok", False, None))
            return fut

    async def main():
        peer = "probe-retry:1"
        br = overload.peer_breaker(peer)
        for _ in range(br.fail_threshold):
            br.record_failure()
        assert br.state == "open"
        await asyncio.sleep(br.open_s + 0.05)
        http = fasthttp.FastHTTPClient()
        loop = asyncio.get_running_loop()
        conns = [_FakeConn(loop, True), _FakeConn(loop, False)]

        async def fake_get(hostport, timeout=None):
            return conns.pop(0)

        http._get = fake_get
        # this request IS the half-open probe; its stale-conn retry must
        # succeed (and close the breaker), not raise CircuitOpenError
        assert await http.request("GET", peer, "/k") == (200, b"ok")
        assert br.state == "closed"

    asyncio.run(main())


def test_gate_identity_unique_per_process():
    """Server names repeat in in-process clusters (three volume servers
    are all 'volume'): every gate carries a per-process unique id in its
    stats — the shell merge and metric series key on it so distinct
    same-named gates can no longer collapse into one."""
    a = overload.AdmissionGate("volume")
    b = overload.AdmissionGate("volume")
    assert a.stats()["server"] == b.stats()["server"] == "volume"
    assert a.stats()["gate"] != b.stats()["gate"]


def test_cancelled_inflight_request_returns_half_open_probe(monkeypatch):
    """A hedged read losing its race is cancelled mid-flight; if it held
    the half-open probe slot the slot must come back immediately, or
    every future call to the peer raises CircuitOpenError until the
    probe lease expires."""
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    from seaweedfs_tpu.util.fasthttp import FastHTTPClient, render_response

    async def handler(req):
        await asyncio.sleep(30)
        return render_response(200, b"late")

    async def main():
        srv = _fast_server(handler)
        await srv.start("127.0.0.1", 0)
        port = srv._server.sockets[0].getsockname()[1]
        hostport = f"127.0.0.1:{port}"
        http = FastHTTPClient()
        try:
            br = overload.peer_breaker(hostport)
            for _ in range(br.fail_threshold):
                with pytest.raises(OSError):
                    await http.request("GET", hostport, "/x", timeout=0.02)
            assert br.state == "open"
            await asyncio.sleep(br.open_s + 0.05)
            task = asyncio.ensure_future(
                http.request("GET", hostport, "/x", timeout=30)
            )
            await asyncio.sleep(0.1)  # in flight: holds the probe slot
            assert br.state == "half_open" and br.blocked()
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
            assert not br.blocked()  # slot returned: peer probe-able now
        finally:
            await http.close()
            await srv.stop()

    asyncio.run(main())


def test_client_surfaces_retry_after_and_breaker_opens_then_fast_fails(
    monkeypatch,
):
    """The satellite fix end-to-end: a 503 with Retry-After is surfaced
    via retry_after_remaining, a shed-heavy window opens the breaker for
    the peer's own hint, and an open breaker fails calls in µs."""
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    from seaweedfs_tpu.util.fasthttp import FastHTTPClient, render_response

    shed = render_response(
        503, b'{"error":"overloaded"}', extra=b"Retry-After: 2\r\n"
    )

    async def handler(req):
        return shed

    async def main():
        srv = _fast_server(handler)
        await srv.start("127.0.0.1", 0)
        port = srv._server.sockets[0].getsockname()[1]
        hostport = f"127.0.0.1:{port}"
        http = FastHTTPClient()
        try:
            st, _ = await http.request("GET", hostport, "/x")
            assert st == 503
            assert 1.5 < http.retry_after_remaining(hostport) <= 2.0
            # keep hammering: the shed-rate trip opens the breaker
            opened = False
            for _ in range(25):
                try:
                    st, _ = await http.request("GET", hostport, "/x")
                    assert st == 503
                except CircuitOpenError:
                    opened = True
                    break
            assert opened, "shed-heavy window never tripped the breaker"
            # open breaker fails fast, without a wire round trip
            t0 = time.perf_counter()
            with pytest.raises(CircuitOpenError):
                await http.request("GET", hostport, "/x")
            assert time.perf_counter() - t0 < 0.05
        finally:
            await http.close()
            await srv.stop()

    asyncio.run(main())


def test_serving_core_sheds_with_retry_after_and_counts(monkeypatch):
    """A live ServingCore fast tier past its queue deadline answers the
    pre-rendered 503 + Retry-After in the same connection, and counts
    the decision."""
    monkeypatch.setenv("SEAWEEDFS_TPU_ADMIT", "1")
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "0")
    from aiohttp import web

    from seaweedfs_tpu.server.serving_core import ServingCore
    from seaweedfs_tpu.util.fasthttp import FastHTTPClient, render_response

    ok = render_response(200, b"served")

    async def handler(req):
        return ok

    async def main():
        core = ServingCore("t-shed", handler, "127.0.0.1", 0)
        # port 0: bind and read back
        app = web.Application()
        await core.start(app)
        port = core.fast_server._server.sockets[0].getsockname()[1]
        hostport = f"127.0.0.1:{port}"
        http = FastHTTPClient()
        try:
            st, body = await http.request("GET", hostport, "/x")
            assert (st, body) == (200, b"served")
            # shrink every class budget to ~zero: the next dispatch has
            # ALWAYS waited past it (loop hop >= ns) -> instant shed
            core.gate.set_read_budget(1e-9)
            st, body = await http.request("GET", hostport, "/x")
            assert st == 503 and b"shed" in body
            assert http.retry_after_remaining(hostport) > 0
            assert core.gate.shed_total >= 1
            key = (CLASS_READ, "deadline", "default")
            assert key in core.gate._shed_children
            # /metrics stays reachable WHILE shedding (falls back to the
            # cold tier, exempt from admission)
            st, body = await http.request("GET", hostport, "/metrics")
            assert st == 200 and b"overload_shed_total" in body
            st, body = await http.request("GET", hostport, "/debug/overload")
            assert st == 200 and b"admission_enabled" in body
        finally:
            await http.close()
            await core.stop()

    asyncio.run(main())


# ------------------------------------------------- maintenance coupling --


def test_maintenance_yields_under_pressure():
    from seaweedfs_tpu.storage.maintenance import (
        MaintenanceBudget,
        yield_for_pressure,
    )

    slept: list = []
    # no pressure: zero cost
    assert (
        yield_for_pressure("t", 0.01, sleep=slept.append, pressure=lambda: 0.0)
        == 0.0
    )
    assert slept == []
    # full pressure: the per-consume cap, an effective pause
    y = yield_for_pressure("t", 0.01, sleep=slept.append, pressure=lambda: 1.0)
    assert y == pytest.approx(0.5) and slept == [y]
    # half pressure: extra == base (rate halves), not the cap
    y2 = yield_for_pressure(
        "t", 0.01, sleep=slept.append, pressure=lambda: 0.5
    )
    assert y2 == pytest.approx(0.01)
    from seaweedfs_tpu.util.metrics import MAINTENANCE_YIELDS

    assert MAINTENANCE_YIELDS._values.get((("plane", "t"),), 0) >= 2

    # budget-level integration: consume() charges the yield and reports
    # it per plane
    waits: list = []
    clk = FakeClock()
    budget = MaintenanceBudget(
        rate_mbps=1000.0, clock=clk, sleep=lambda d: waits.append(d)
    )
    g = overload.AdmissionGate("t-maint", clock=clk)
    overload._GATES.append(g)
    try:
        g._shed(CLASS_READ, "deadline")  # pressure -> 1.0
        budget.consume(1 << 20, plane="scrub")
    finally:
        overload.drop_gate(g)
    st = budget.snapshot()
    assert st["pressure_yield_seconds"]["scrub"] > 0
    assert any(w > 0 for w in waits)


def test_explicit_plane_bucket_is_pressure_shaped():
    from seaweedfs_tpu.storage import maintenance

    class Bucket:
        rate = 1e6

        def __init__(self):
            self.consumed = []

        def consume(self, n):
            self.consumed.append(n)
            return 0.0

    explicit = Bucket()
    shaped = maintenance.plane_bucket("vacuum", explicit)
    clk = FakeClock()
    g = overload.AdmissionGate("t-exp", clock=clk)
    overload._GATES.append(g)
    try:
        g._shed(CLASS_READ, "deadline")
        slept = shaped.consume(1 << 20)
    finally:
        overload.drop_gate(g)
    assert explicit.consumed == [1 << 20]  # the plane's own knob applied
    assert slept > 0  # plus the foreground-pressure yield


# ---------------------------------------------------- hedge/fanout pause --


def test_reader_pauses_hedging_into_shedding_pool(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    from seaweedfs_tpu.client.read_fanout import ReplicaReader

    reader = ReplicaReader(http=None, vid_map=None)
    overload.BREAKERS.get("peer:1").record_shed()
    assert reader._may_hedge("peer:1") is False
    assert reader.hedges_suppressed == 1
    assert reader._may_hedge("healthy:2") is True
    # a drained shared budget also pauses hedging
    b = RetryBudget(max_tokens=4.0)
    for _ in range(3):
        b.on_failure()
    configure_retry_budget(b)
    try:
        assert reader._may_hedge("healthy:2") is False
    finally:
        configure_retry_budget(None)
    assert reader.stats()["hedges_suppressed"] == 2


def test_reader_skips_breaker_blocked_replicas(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    from seaweedfs_tpu.client.read_fanout import ReplicaReader

    reader = ReplicaReader(http=None, vid_map=None)
    br = overload.BREAKERS.get("sick:1")
    for _ in range(10):
        br.record_failure()
    assert br.blocked()
    assert reader._alive(["sick:1", "ok:2"]) == ["ok:2"]
    # every holder blocked: fall back to the original order (the read
    # must still be tried; half-open probes are how the pool heals)
    br2 = overload.BREAKERS.get("ok:2")
    for _ in range(10):
        br2.record_failure()
    assert reader._alive(["sick:1", "ok:2"]) == ["sick:1", "ok:2"]


# ------------------------------------------------------- shell command --


def test_overload_status_shell_command(tmp_path, monkeypatch):
    """`overload.status` merges /debug/overload cluster-wide: per-gate
    adaptive limit + admitted/shed counters, tripped breakers, and the
    shared retry-budget fill."""
    monkeypatch.setenv("SEAWEEDFS_TPU_ADMIT", "1")
    from test_cluster import Cluster

    from seaweedfs_tpu.shell.command_env import CommandEnv
    from seaweedfs_tpu.shell.commands import run_command
    from seaweedfs_tpu.util.fasthttp import FastHTTPClient

    async def body():
        cluster = Cluster(tmp_path, n_volume_servers=1)
        await cluster.start()
        http = FastHTTPClient()
        try:
            # some traffic so the gates have admitted counts
            for _ in range(5):
                st, _ = await http.request(
                    "GET", cluster.master.address, "/dir/status"
                )
            env = CommandEnv(cluster.master.address)
            out = await run_command(env, "overload.status")
            assert "limit=" in out and "admitted=" in out, out
            assert "shed=" in out
            assert "retry budget:" in out
            # every server type in this process reports its own gate
            assert "master" in out and "volume" in out
        finally:
            await http.close()
            await cluster.stop()

    asyncio.run(body())


# ----------------------------------------------------- cluster chaos e2e --


def test_browned_out_replica_trips_breaker_reads_survive(
    tmp_path, monkeypatch
):
    """Acceptance chaos test: one replica of a 2-replica volume starts
    shedding (injected 503s with Retry-After at its address), its
    breaker trips, and cluster-wide reads keep succeeding byte-identical
    through the healthy replica — degraded isolation, not collapse."""
    monkeypatch.setenv("SEAWEEDFS_TPU_BREAKER", "1")
    from test_cluster import Cluster, assign_retry

    from seaweedfs_tpu.client import MasterClient
    from seaweedfs_tpu.client.operation import upload_data
    from seaweedfs_tpu.client.read_fanout import ReplicaReader
    from seaweedfs_tpu.util.fasthttp import FastHTTPClient

    async def body():
        import aiohttp

        cluster = Cluster(tmp_path, n_volume_servers=2)
        await cluster.start()
        http = FastHTTPClient()
        mc = MasterClient("t-chaos", [cluster.master.address])
        await mc.start()
        try:
            payloads = {}
            async with aiohttp.ClientSession() as session:
                for i in range(6):
                    ar = await assign_retry(
                        cluster.master.address, replication="001"
                    )
                    data = random.Random(i).randbytes(400 + 31 * i)
                    await upload_data(
                        session, ar.url, ar.fid, data, filename=f"c{i}.bin"
                    )
                    payloads[ar.fid] = data
            await mc.wait_connected()
            vids = {int(f.split(",")[0]) for f in payloads}
            for _ in range(100):
                if all(
                    len(mc.vid_map.lookup(v) or []) >= 2 for v in vids
                ):
                    break
                await asyncio.sleep(0.1)
            reader = ReplicaReader(http, mc.vid_map, hedge_cap_s=0.05)

            # healthy pass: replicated reads succeed
            for fid, data in payloads.items():
                st, body_ = await reader.read(fid)
                assert (st, body_) == (200, data)

            # brown out ONE replica: every GET to its address sheds
            sick = cluster.volume_servers[0].address
            plan = faults.FaultPlan(
                seed=0x1557,
                rules=[
                    faults.FaultRule(
                        op="http:GET",
                        target=sick,
                        fault="http_error",
                        status=503,
                        probability=1.0,
                    )
                ],
            )
            faults.install_plan(plan)
            try:
                for _round in range(12):
                    for fid, data in payloads.items():
                        st, body_ = await reader.read(fid)
                        assert (st, body_) == (200, data), (
                            f"read of {fid} failed during brownout"
                        )
                br = overload.BREAKERS.peek(sick)
                assert br is not None and br.opens >= 1, (
                    "shedding replica never tripped its breaker"
                )
                assert plan.fired("http:GET") > 0
                # while open, the sick peer is dropped from replica
                # ordering entirely (no wasted hop per read)
                if br.blocked():
                    order = reader._alive([sick, "other:1"])
                    assert sick not in order
            finally:
                faults.clear_plan()

            # heal: the half-open probe closes the breaker and the pool
            # re-balances (reads still correct throughout)
            await asyncio.sleep(0.3)
            for _ in range(6):
                for fid, data in payloads.items():
                    st, body_ = await reader.read(fid)
                    assert (st, body_) == (200, data)
        finally:
            await mc.stop()
            await http.close()
            await cluster.stop()

    asyncio.run(body())
