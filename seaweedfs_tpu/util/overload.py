"""Overload control plane: priority admission, adaptive concurrency
limits, fast shedding, per-peer circuit breakers, and a pressure signal
that throttles background maintenance.

Nothing in PRs 2/6/7 defends *goodput* when offered load exceeds
capacity: the serving paths are fast, but a 3x-capacity open-loop storm
(ops/loadgen.py can generate one) just grows queues until every request
times out — and retries/hedges amplify the collapse. This module is the
control plane layered over those fast paths:

- **AdmissionGate** (one per ServingCore, so master/volume/filer/S3 all
  inherit it): every fast-tier request is classified into a priority
  class (foreground reads > writes > gateway metadata > maintenance) and
  admitted, queued, or shed BEFORE any work happens. Two mechanisms:

  * a *queue-deadline*: the protocol stamps each request's arrival; a
    request whose wait (event-loop backlog + gate queue) already exceeds
    its class budget is shed instantly — the request was going to blow
    its caller's deadline anyway, so the µs 503 beats the doomed work.
    Lower classes get smaller budgets, so shedding is
    lowest-class-first by construction;
  * an *adaptive concurrency limit* (AdaptiveLimiter): AIMD on observed
    handler latency vs a tracked baseline, the gradient
    concurrency-limiting shape — requests past the limit queue (bounded,
    with per-class depth shares) instead of piling onto the loop.

  Shed responses are a pre-rendered 503 with ``Retry-After`` served in
  microseconds, counted in ``overload_shed_total{class,reason}`` and
  trace-flagged through the flight recorder's tail sampler.

- **CircuitBreaker** (per peer, shared by the HTTP and gRPC clients):
  closed → open on consecutive failures or a high shed rate, half-open
  probes after the open window (or the peer's own Retry-After). An open
  breaker fails calls in microseconds instead of burning a timeout per
  attempt, and tells the read fan-out to stop hedging into a peer that
  is already shedding.

- **Pressure signal**: gates export max(recent-shed, queue-fullness) in
  [0, 1]; `storage/maintenance.py` consults `global_pressure()` so
  scrub/vacuum/repair I/O yields while foreground traffic is being shed
  (the online-EC characterization result — arxiv 1709.05365 — is that
  background coding I/O visibly steals foreground throughput; the
  shared budget already caps the sum, this makes the cap *dynamic*).

Env knobs (all optional; docs/robustness.md "Overload plane"):
``SEAWEEDFS_TPU_ADMIT`` (0 disables admission, default on),
``SEAWEEDFS_TPU_ADMIT_LIMIT`` (initial concurrency limit),
``SEAWEEDFS_TPU_ADMIT_BUDGET_MS`` (foreground-read queue budget; other
classes scale from it), ``SEAWEEDFS_TPU_RETRY_AFTER_S`` (shed hint),
``SEAWEEDFS_TPU_BREAKER`` (0 disables circuit breakers).
"""

from __future__ import annotations

import asyncio
import math
import os
import time
from collections import deque
from itertools import count
from typing import Optional

from . import tenancy
from .metrics import (
    ADMISSION_LIMIT,
    ADMISSION_QUEUE_DEPTH,
    CIRCUIT_OPEN,
    CIRCUIT_TRANSITIONS,
    OVERLOAD_SHED,
    TENANT_ADMITTED,
    TENANT_ADMITTED_SECONDS,
    TENANT_QUEUE_DEPTH,
)

_DEFAULT_TENANT = tenancy.DEFAULT_TENANT
_POLICY_NOTE = tenancy.note_heat  # heat feed for the top-K label policy

# ------------------------------------------------------- priority classes --

CLASS_READ = 0  # foreground reads (GET/HEAD on the data plane)
CLASS_WRITE = 1  # writes (POST/PUT/DELETE)
CLASS_META = 2  # gateway/filer metadata, everything else HTTP
CLASS_MAINT = 3  # maintenance traffic (scrub/vacuum/repair riders)
N_CLASSES = 4
CLASS_NAMES = ("read", "write", "meta", "maint")

_CLASS_BY_METHOD = {
    "GET": CLASS_READ,
    "HEAD": CLASS_READ,
    "POST": CLASS_WRITE,
    "PUT": CLASS_WRITE,
    "DELETE": CLASS_WRITE,
}


def classify_method(method: str) -> int:
    """Default request classifier: reads above writes above the rest.
    Maintenance RPCs ride gRPC (not the HTTP gate) — their throttle is
    the pressure coupling in storage/maintenance.py."""
    return _CLASS_BY_METHOD.get(method, CLASS_META)


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


# ------------------------------------------------------- adaptive limiter --


class AdaptiveLimiter:
    """AIMD concurrency limit driven by observed latency vs a tracked
    baseline (the gradient concurrency-limiting shape, windowed):

    - every `window` samples, compare the window's mean latency against
      `baseline * tolerance`; above it → multiplicative decrease (the
      server is queueing internally), else, if the limit was actually
      the binding constraint this window, additive increase by 1;
    - the baseline tracks the *floor of windowed means* with a slow
      upward drift: it snaps down to any window that averages lower and
      drifts 10%/window toward higher ones, so it converges on the
      uncontended mean service time, survives regime changes (payload
      mix shifts) without locking in a congested measurement, and — the
      reason it is a mean, not a min — a bimodal service mix (µs cache
      hits beside ms disk reads) cannot pin the baseline at the fast
      mode and turn every window into a multiplicative decrease.
    """

    def __init__(
        self,
        initial: Optional[int] = None,
        min_limit: int = 8,
        max_limit: int = 1024,
        tolerance: float = 2.0,
        window: int = 64,
        decrease: float = 0.85,
    ):
        if initial is None:
            initial = int(_env_f("SEAWEEDFS_TPU_ADMIT_LIMIT", 128))
        self.limit = max(min_limit, int(initial))
        self.min_limit = min_limit
        self.max_limit = max_limit
        self.tolerance = tolerance
        self.window = window
        self.decrease = decrease
        self.baseline_s: Optional[float] = None
        self.decreases = 0  # multiplicative backoffs taken (observability)
        self.increases = 0
        self._n = 0
        self._sum = 0.0
        self._min = float("inf")
        self._hi_inflight = 0

    def on_sample(self, latency_s: float, inflight: int) -> None:
        self._n += 1
        self._sum += latency_s
        if latency_s < self._min:
            self._min = latency_s
        if inflight > self._hi_inflight:
            self._hi_inflight = inflight
        if self._n >= self.window:
            self._update()

    def _update(self) -> None:
        win_avg = self._sum / self._n
        saturated = self._hi_inflight >= self.limit - 1
        self._n = 0
        self._sum = 0.0
        self._min = float("inf")
        self._hi_inflight = 0
        b = self.baseline_s
        if b is None:
            self.baseline_s = win_avg
            return
        # track the floor of windowed means: snap down, drift up at
        # 10%/window (a min would let one µs cache hit define 'healthy')
        self.baseline_s = min(win_avg, b + (win_avg - b) * 0.1)
        if win_avg > self.baseline_s * self.tolerance:
            new = max(self.min_limit, int(self.limit * self.decrease))
            if new < self.limit:
                self.limit = new
                self.decreases += 1
        elif saturated and self.limit < self.max_limit:
            self.limit += 1
            self.increases += 1


# ------------------------------------------- admitted-latency histogram --

# log-bucketed (growth sqrt(2), base 1µs, 64 buckets -> ~4300s span):
# every percentile carries <= ~19% relative error, recording is one log
# + one list increment — cheap enough for the admitted fast path, and
# the per-server admitted p50/p99 it yields is the number an operator
# actually wants next to shed counts
_LAT_BASE = 1e-6
_LAT_LOG_G = math.log(math.sqrt(2.0))
_LAT_BUCKETS = 64


def latency_percentile(counts: list, p: float) -> float:
    """Seconds at percentile p in [0,100] of a bucket-count list (as
    `AdmissionGate.admitted_counts` snapshots/deltas); 0.0 when empty.

    Interpolates geometrically WITHIN the covering bucket by rank
    fraction: the raw bucket midpoint quantizes every answer to a
    sqrt(2) grid, which turns a p99 RATIO of two such numbers into
    steps of 1.41x — too coarse for the fairness leg's <= 2x
    acceptance bound (2.828 = sqrt(2)^3 is a three-bucket gap, wherever
    the truth lies between 2.0 and 4.0)."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    rank = total * p / 100.0
    seen = 0.0
    for i, c in enumerate(counts):
        if c and seen + c >= rank:
            frac = (rank - seen) / c
            return _LAT_BASE * math.exp(_LAT_LOG_G * (i + frac))
        seen += c
    return _LAT_BASE * math.exp(_LAT_LOG_G * _LAT_BUCKETS)


# -------------------------------------------------------- admission gate --

# per-class queue-wait budgets (seconds): a request that already waited
# longer than its class budget is shed before doing work. Lower classes
# get smaller budgets — shedding is lowest-class-first by construction.
_BUDGET_SCALE = (1.0, 0.8, 0.6, 0.2)
# per-class share of the bounded gate queue: when the queue is fuller
# than a class's share allows, that class sheds at arrival while higher
# classes may still queue.
_QUEUE_SHARE = (1.0, 0.5, 0.25, 0.1)


class _TenantState:
    """Per-tenant bookkeeping inside one gate: DRR weight, quota
    buckets, and counters. Metric children are bound per LABEL (not per
    tenant) at the gate level, because the bounded label policy can
    re-map a tenant to 'other' over its lifetime."""

    __slots__ = (
        "name", "weight", "quota", "admitted", "shed", "queued",
        "inflight", "pinned", "t_seen", "pub_label", "pub_queued",
    )

    def __init__(self, name: str, weight: float, quota):
        self.name = name
        self.weight = weight
        self.quota = quota
        self.admitted = 0
        self.shed = 0
        self.queued = 0
        self.inflight = 0  # admitted, release() not yet seen
        # operator-installed quota/weight (set_tenant_*): never evicted
        self.pinned = False
        self.t_seen = 0.0
        # the label this state's queued count is currently published
        # under, and the amount — per-LABEL depth gauges are aggregated
        # incrementally (many cold tenants share 'other'; last-writer-
        # wins per tenant would under-report and zero out real backlog)
        self.pub_label = None
        self.pub_queued = 0


class AdmissionGate:
    """Priority admission for one server's fast tier.

    `try_admit(cls, waited_s)` is the synchronous fast path: True =
    admitted (caller MUST pair with `release`), False = shed (caller
    answers 503 immediately), else a Future the caller awaits via
    `wait_queued`. Single-event-loop use only (no locking — ServingCore
    dispatch is the sole caller).

    Tenant QoS (ISSUE 12): within each priority class the queue is a
    set of per-tenant subqueues drained by deficit round robin — each
    rotation visit tops a tenant's deficit up by its weight
    (util/tenancy, default 1.0) and a grant costs 1, so over any
    backlogged window each tenant's admitted share tracks its weight
    share regardless of arrival order; an idle tenant's deficit resets
    (no banking), and a cancelled queued waiter is skipped without
    touching ANY tenant's deficit. Per-tenant token-bucket rate/byte
    quotas shed with reason="quota" before any queueing — the same
    pre-rendered µs 503 + Retry-After as every other shed."""

    def __init__(
        self,
        server: str,
        limiter: Optional[AdaptiveLimiter] = None,
        read_budget_s: Optional[float] = None,
        max_queue: int = 512,
        retry_after_s: Optional[float] = None,
        clock=time.monotonic,
    ):
        self.server = server
        # per-process unique identity: server NAMES repeat in in-process
        # clusters (three volume servers are all "volume") — the metric
        # series and the shell's cluster-wide merge must tell the gates
        # apart or distinct gates silently collapse into one
        self.gate_id = str(next(_GATE_SEQ))
        self.limiter = limiter or AdaptiveLimiter()
        if read_budget_s is None:
            read_budget_s = _env_f("SEAWEEDFS_TPU_ADMIT_BUDGET_MS", 50.0) / 1e3
        self.set_read_budget(read_budget_s)
        self.max_queue = max_queue
        self.retry_after_s = (
            retry_after_s
            if retry_after_s is not None
            else _env_f("SEAWEEDFS_TPU_RETRY_AFTER_S", 1.0)
        )
        self._clock = clock
        self.inflight = 0
        self.admitted_total = 0
        self.queued = 0
        # DRR state, per class: tenant -> subqueue of waiter futures,
        # the tenant rotation (a tenant is in the rotation iff its
        # subqueue is non-empty), and per-tenant deficits
        self._tq: tuple = tuple({} for _ in range(N_CLASSES))
        self._rrq: tuple = tuple(deque() for _ in range(N_CLASSES))
        self._deficit: tuple = tuple({} for _ in range(N_CLASSES))
        # live queued waiter -> (tenant name, quota-charged body bytes)
        self._fut_tenant: dict = {}
        self._tenants: dict = {}  # name -> _TenantState
        self._label_queued: dict = {}  # label -> aggregate queued
        self.shed_total = 0
        # per-label metric-child caches, all invalidated together when
        # the label policy purges a retirement (generation check): a
        # stale cached child would re-mint the purged series on its
        # next inc, and the caches would grow with CUMULATIVE label
        # churn instead of staying bounded by the live top-K
        self._children_gen = tenancy.purge_generation()
        self._shed_children: dict = {}
        self._tadm_children: dict = {}  # label -> TENANT_ADMITTED child
        self._tlat_children: dict = {}  # label -> latency hist child
        self.last_shed_t = 0.0
        self._depth_gauge = ADMISSION_QUEUE_DEPTH
        self._limit_gauge = ADMISSION_LIMIT
        self._limit_gauge.set(
            self.limiter.limit, server=server, gate=self.gate_id
        )
        # server-side latency of ADMITTED requests (admission wait +
        # service), log-bucketed — the number "admitted-request p99"
        # honestly means: a saturated open-loop *generator's* own client
        # backlog cannot pollute it
        self.admitted_counts = [0] * _LAT_BUCKETS

    def set_read_budget(self, read_budget_s: float) -> None:
        """Reset the per-class queue-wait budgets from the foreground-read
        budget (a caller may scale it from a measured baseline p99)."""
        self.queue_budget_s = tuple(
            read_budget_s * s for s in _BUDGET_SCALE
        )

    # -- tenants --
    def _tenant(self, name: str) -> _TenantState:
        ts = self._tenants.get(name)
        if ts is None:
            ts = self._tenants[name] = _TenantState(
                name,
                tenancy.CONFIG.weight(name),
                tenancy.CONFIG.quota_for(name, clock=self._clock),
            )
            # stamp recency BEFORE the prune can run: a fresh state at
            # t_seen=0.0 would sort first among the victims and the
            # insertion that triggered the prune would evict ITSELF —
            # the in-flight request would then book against an orphan
            # (and a set_tenant_quota call would silently lose its
            # quota before it could pin the state)
            ts.t_seen = self._clock()
            if len(self._tenants) > max(128, 8 * tenancy.POLICY.cap):
                self._prune_tenants(keep=ts)
        return ts

    def _prune_tenants(self, keep=None) -> None:
        """Bound the per-gate tenant table: principal names are
        client-controlled pre-auth (the header, a sprayed access key),
        so without eviction a million one-shot names is a memory DoS
        one layer below the bounded label policy. Evict the
        longest-idle states that hold NO live obligations — nothing
        queued (and nothing published into a depth gauge), nothing
        in flight (a released request must find its state to return
        the inflight count and charge response bytes), not pinned by
        an operator's set_tenant_* call, and — for quota'd states —
        idle past the bucket's refill horizon, so eviction grants
        nothing that natural refill would not have (a tenant cannot
        spray names to evict its own byte DEBT). A clean config-
        derived quota state is evictable: re-derived fresh on next
        sight, which a name-cycling client gets anyway under per-name
        quotas."""
        cap = max(128, 8 * tenancy.POLICY.cap)
        now = self._clock()
        victims = sorted(
            (
                ts
                for ts in self._tenants.values()
                if ts is not keep  # never the state being inserted:
                # when victims are scarce (everything else pinned or
                # busy) recency alone cannot protect it
                and not ts.pinned
                and ts.queued == 0
                and ts.pub_queued == 0
                and ts.inflight == 0
                and ts.name != _DEFAULT_TENANT
                and (
                    ts.quota is None
                    or now - ts.t_seen >= ts.quota.refill_horizon_s()
                )
            ),
            key=lambda ts: ts.t_seen,
        )
        drop = len(self._tenants) - cap // 2
        for ts in victims[:drop]:
            del self._tenants[ts.name]

    def set_tenant_quota(
        self, name: str, qps: float = 0.0, byte_ps: float = 0.0,
        burst_s: float = 1.0,
    ) -> None:
        """Install/replace one tenant's quota buckets (tests and shell
        tooling; env config covers the deployed path)."""
        ts = self._tenant(name)
        ts.pinned = True  # operator-installed: survives table pruning
        ts.quota = (
            tenancy.TenantQuota(
                qps=qps, byte_ps=byte_ps, burst_s=burst_s,
                clock=self._clock,
            )
            if (qps > 0.0 or byte_ps > 0.0)
            else None
        )

    def set_tenant_weight(self, name: str, weight: float) -> None:
        ts = self._tenant(name)
        ts.pinned = True  # operator-installed: survives table pruning
        ts.weight = min(100.0, max(0.1, weight))

    def _tenant_depth(self, ts: _TenantState) -> None:
        """Publish ts's queued count into the per-LABEL depth gauge.
        Labels collapse many tenants (everyone past top-K is 'other'),
        so the gauge must be the SUM over tenants sharing the label —
        a per-tenant set() would under-report and a drained tenant
        would zero out another's real backlog. Incremental O(1): each
        state remembers what it last published where."""
        label = tenancy.tenant_label(ts.name)
        lq = self._label_queued
        old = ts.pub_label
        if old is None or old == label:
            lq[label] = lq.get(label, 0) + ts.queued - ts.pub_queued
        else:
            # the tenant's label migrated (top-K retirement/admission):
            # move its published share between the aggregates. The OLD
            # label's series must never be re-MINTED here — after a
            # retirement the purge removed it, and a .set() (even to 0)
            # would re-insert it and grow cumulative cardinality with
            # every ever-retired name. Drained -> remove the series;
            # still-shared but retired -> leave it absent (internal
            # bookkeeping continues; the last publisher removes it).
            left = lq.get(old, 0) - ts.pub_queued
            if left > 0:
                lq[old] = left
                if (
                    old == tenancy.OTHER_LABEL
                    or tenancy.POLICY.peek_label(old) == old
                ):
                    TENANT_QUEUE_DEPTH.set(
                        left, server=self.server, gate=self.gate_id,
                        tenant=old,
                    )
            else:
                lq.pop(old, None)
                TENANT_QUEUE_DEPTH.remove(
                    server=self.server, gate=self.gate_id, tenant=old
                )
            lq[label] = lq.get(label, 0) + ts.queued
        ts.pub_label = label
        ts.pub_queued = ts.queued
        TENANT_QUEUE_DEPTH.set(
            lq[label], server=self.server, gate=self.gate_id,
            tenant=label,
        )

    def _check_children_gen(self) -> None:
        gen = tenancy.purge_generation()
        if gen != self._children_gen:
            self._children_gen = gen
            self._shed_children.clear()
            self._tadm_children.clear()
            self._tlat_children.clear()

    def _count_admitted(self, name: str) -> None:
        self._check_children_gen()
        label = tenancy.tenant_label(name)
        child = self._tadm_children.get(label)
        if child is None:
            child = self._tadm_children[label] = TENANT_ADMITTED.child(
                server=self.server, tenant=label
            )
        child.inc()

    # -- admission --
    def try_admit(
        self,
        cls: int,
        waited_s: float = 0.0,
        tenant: Optional[str] = None,
        cost_bytes: int = 0,
    ):
        name = tenant or _DEFAULT_TENANT
        ts = self._tenants.get(name)
        if ts is None:
            ts = self._tenant(name)
        _POLICY_NOTE(name)  # heat feeds the top-K label policy
        ts.t_seen = self._clock()  # recency for the table prune
        if waited_s > self.queue_budget_s[cls]:
            self._shed(cls, "deadline", name)
            return False
        if self.inflight < self.limiter.limit:
            # quota is consulted LAST, only for a request the gate
            # would otherwise take: charging a token and then shedding
            # for deadline/queue_full would bill a compliant tenant
            # twice for one overload
            if ts.quota is not None and not ts.quota.try_take(
                cost_bytes
            ):
                self._shed(cls, "quota", name)
                return False
            self.inflight += 1
            self.admitted_total += 1
            ts.admitted += 1
            ts.inflight += 1
            self._count_admitted(name)
            return True
        if self.queued >= self.max_queue * _QUEUE_SHARE[cls]:
            self._shed(cls, "queue_full", name)
            return False
        if ts.quota is not None and not ts.quota.try_take(cost_bytes):
            self._shed(cls, "quota", name)
            return False
        fut = asyncio.get_event_loop().create_future()
        tq = self._tq[cls]
        q = tq.get(name)
        if q is None:
            q = tq[name] = deque()
        if not q:
            # invariant: a tenant is in the class rotation iff its
            # subqueue is non-empty (husks included — they drain lazily)
            self._rrq[cls].append(name)
        q.append(fut)
        self._fut_tenant[fut] = (name, cost_bytes)
        self.queued += 1
        ts.queued += 1
        self._depth_gauge.set(
            self.queued, server=self.server, gate=self.gate_id
        )
        self._tenant_depth(ts)
        return fut

    def _drop_queued(self, fut) -> None:
        """A queued waiter stopped waiting (timeout/cancel): stop
        counting it NOW; the husk itself drains lazily in _next_queued
        without touching any tenant's deficit. The quota tokens charged
        at enqueue are REFUNDED — the request was never served, and a
        kept token would shed the tenant's next compliant request with
        reason=quota on top of the deadline shed it already paid."""
        info = self._fut_tenant.pop(fut, None)
        self.queued -= 1
        self._depth_gauge.set(
            self.queued, server=self.server, gate=self.gate_id
        )
        if info is not None:
            name, cost_bytes = info
            ts = self._tenants.get(name)
            if ts is not None:
                ts.queued -= 1
                if ts.quota is not None:
                    ts.quota.refund(cost_bytes)
                self._tenant_depth(ts)

    async def wait_queued(self, cls: int, fut, waited_s: float = 0.0) -> bool:
        """Await a queued admission inside the class's remaining budget;
        past it the request sheds (reason=deadline)."""
        left = max(self.queue_budget_s[cls] - waited_s, 0.001)
        try:
            await asyncio.wait_for(fut, left)
        except asyncio.TimeoutError:
            if fut.done() and not fut.cancelled():
                # _wake granted the slot in the same turn as the timeout
                # fired: the grant already counted it inflight and out of
                # the queue, so the request is admitted and releases as
                # any other (shedding it would leak the slot for good)
                self._fut_tenant.pop(fut, None)
                return True
            # wait_for cancelled the future; _next_queued skips
            # cancelled entries lazily — only the live count must drop
            # NOW
            info = self._fut_tenant.get(fut)
            self._drop_queued(fut)
            self._shed(cls, "deadline", info[0] if info else None)
            return False
        except asyncio.CancelledError:
            # the caller's task died while queued (client disconnect mid
            # overload — the exact regime this gate exists for). Undo the
            # bookkeeping or the gate leaks: if _wake granted the slot in
            # the race window before our cancellation landed, hand the
            # inflight slot back (release() will never run for us);
            # otherwise the future is a husk — stop counting it toward
            # the queue depth, same as the timeout path.
            if fut.done() and not fut.cancelled():
                # granted in the race window: hand back the gate slot
                # AND the per-tenant bookkeeping — a leaked ts.inflight
                # would pin the state unevictable forever (the prune
                # requires inflight == 0), and the quota token bought
                # no service
                self.inflight -= 1
                info = self._fut_tenant.pop(fut, None)
                if info is not None:
                    ts = self._tenants.get(info[0])
                    if ts is not None:
                        if ts.inflight > 0:
                            ts.inflight -= 1
                        if ts.quota is not None:
                            ts.quota.refund(info[1])
                self._wake()
            else:
                fut.cancel()
                self._drop_queued(fut)
            raise
        self._fut_tenant.pop(fut, None)
        return True

    async def admit(self, cls: int, waited_s: float = 0.0,
                    tenant: Optional[str] = None) -> bool:
        r = self.try_admit(cls, waited_s, tenant)
        if r is True or r is False:
            return r
        return await self.wait_queued(cls, r, waited_s)

    def release(
        self,
        latency_s: Optional[float] = None,
        total_s: Optional[float] = None,
        tenant: Optional[str] = None,
        resp_bytes: int = 0,
    ) -> None:
        """`latency_s` is the handler service wall (feeds the AIMD
        limiter), `total_s` the full server-side latency since parse
        completion (wait + service — feeds the admitted histograms);
        `tenant`/`resp_bytes` charge the response against the tenant's
        byte quota and its per-tenant latency series."""
        self.inflight -= 1
        if latency_s is not None:
            lim = self.limiter
            before = lim.limit
            lim.on_sample(latency_s, self.inflight + 1)
            if lim.limit != before:
                self._limit_gauge.set(
                    lim.limit, server=self.server, gate=self.gate_id
                )
        if total_s is not None:
            if total_s < _LAT_BASE:
                i = 0
            else:
                i = min(
                    int(math.log(total_s / _LAT_BASE) / _LAT_LOG_G),
                    _LAT_BUCKETS - 1,
                )
            self.admitted_counts[i] += 1
        # unattributed requests were ADMITTED under the default tenant
        # (try_admit's `tenant or _DEFAULT_TENANT`): release must book
        # them the same way, or a wildcard byte quota never sees the
        # default pool's response bytes and its latency series is
        # asymmetric with its admitted counter
        name = tenant or _DEFAULT_TENANT
        ts = self._tenants.get(name)
        if ts is not None:
            if ts.inflight > 0:
                ts.inflight -= 1
            if ts.quota is not None and resp_bytes:
                ts.quota.charge_bytes(resp_bytes)
        if total_s is not None:
            self._check_children_gen()
            label = tenancy.tenant_label(name)
            child = self._tlat_children.get(label)
            if child is None:
                child = self._tlat_children[label] = (
                    TENANT_ADMITTED_SECONDS.child(
                        server=self.server, tenant=label
                    )
                )
            child.observe(total_s)
        self._wake()

    def _next_queued(self):
        """The next waiter to grant: classes in priority order, tenants
        within a class by deficit round robin. Returns (fut, cls, name)
        or None. Cancelled husks are dropped WITHOUT touching deficits:
        tenant A's cancelled waiters can neither spend A's deficit nor
        leak B's (the PR 9 regression class, per-tenant edition)."""
        for cls in range(N_CLASSES):
            rr = self._rrq[cls]
            if not rr:
                continue
            tq = self._tq[cls]
            dq = self._deficit[cls]
            # bounded: each full rotation tops every tenant up by >= 0.1
            # (the clamped min weight), so <= 10 rotations reach a
            # deficit of 1; the +len guard absorbs husk-only drains
            guard = 12 * len(rr) + 16
            while rr and guard > 0:
                guard -= 1
                name = rr[0]
                q = tq.get(name)
                while q and q[0].done():
                    # husk (cancelled waiter): already uncounted by
                    # _drop_queued; deficits untouched
                    q.popleft()
                if not q:
                    # subqueue drained: out of the rotation, deficit
                    # resets — an idle tenant cannot bank credit
                    tq.pop(name, None)
                    dq.pop(name, None)
                    rr.popleft()
                    continue
                d = dq.get(name, 0.0)
                if d >= 1.0:
                    fut = q.popleft()
                    if q:
                        dq[name] = d - 1.0
                    else:
                        del tq[name]
                        dq.pop(name, None)
                        rr.popleft()
                    return fut, cls, name
                ts = self._tenants.get(name)
                dq[name] = d + (ts.weight if ts is not None else 1.0)
                rr.rotate(-1)
            if guard <= 0 and rr:
                # defensive: force progress rather than spin (cannot
                # happen with weights clamped >= 0.1, kept for safety)
                name = rr[0]
                q = tq.get(name)
                if q:
                    return q.popleft(), cls, name
        return None

    def _wake(self) -> None:
        """Hand freed slots to queued waiters: highest class first,
        weighted-fair across tenants within the class."""
        while self.inflight < self.limiter.limit and self.queued:
            nxt = self._next_queued()
            if nxt is None:
                return  # only cancelled husks remained
            fut, _cls, name = nxt
            # the map entry survives the grant: wait_queued pops it on
            # resume — the granted-then-cancelled race needs it to
            # return the tenant's inflight count and refund the quota
            self.queued -= 1
            self._depth_gauge.set(
                self.queued, server=self.server, gate=self.gate_id
            )
            ts = self._tenants.get(name)
            if ts is not None:
                ts.queued -= 1
                ts.admitted += 1
                ts.inflight += 1
                self._tenant_depth(ts)
            self.inflight += 1
            self.admitted_total += 1
            self._count_admitted(name)
            fut.set_result(True)

    # -- out-of-band byte attribution (ISSUE 13 satellites) --
    def charge_member_bytes(
        self,
        tenant: Optional[str],
        nbytes: int,
        carrier: Optional[str] = None,
    ) -> bool:
        """Re-attribute one member's share of an admitted MIXED-tenant
        batch frame (the filer's host-coalesced `!batch/put`) from the
        carrier principal to the member's own: consult + charge the
        member's byte bucket, then hand the same bytes back to the
        carrier's bucket (which paid for the whole frame body at
        admission) — each needle's bytes end up billed to exactly the
        principal that wrote it. False = the member is over its byte
        quota; the item declines item-wise (reason=quota counted here)
        and the client retries it through the single-needle path under
        the member's own principal, where the full admission path is
        authoritative."""
        name = tenant or _DEFAULT_TENANT
        ts = self._tenant(name)
        _POLICY_NOTE(name)
        ts.t_seen = self._clock()
        # the member pays its FULL quota — request token + bytes — the
        # same bill its needle would have paid as an unbatched volume
        # HTTP request (each chunk was one request before coalescing),
        # so host-coalesced batching cannot become a qps-quota bypass
        ok = ts.quota is None or ts.quota.try_take(nbytes)
        # the carrier is refunded EITHER way: on success the bytes now
        # bill the member; on decline the item is never written and a
        # kept charge would let one over-quota member's sustained
        # traffic drain the default pool's bucket and shed unrelated
        # anonymous writes (cross-tenant leakage through the carrier)
        cname = carrier or _DEFAULT_TENANT
        if cname != name:
            cts = self._tenants.get(cname)
            if cts is not None and cts.quota is not None:
                cts.quota.refund_bytes(nbytes)
        if not ok:
            self._shed(CLASS_WRITE, "quota", name)
            return False
        return True

    def charge_rpc_bytes(self, tenant: Optional[str], nbytes: int) -> bool:
        """gRPC request-message bytes against the tenant's byte quota —
        the pb/rpc.py handler seam (quotas were HTTP-only before; the
        gRPC plane moved volume copies and bulk reads for free). False
        = over quota: the handler refuses with RESOURCE_EXHAUSTED and
        the shed is counted class="rpc", reason="quota".

        UNTENANTED calls (no x-seaweed-tenant metadata) are exempt on
        purpose: the gRPC plane's anonymous traffic is the cluster's
        own control plane — master repair/vacuum/lifecycle dispatches,
        heartbeat side-calls — and a wildcard byte quota drained by
        tenant HTTP traffic must never shed cluster MAINTENANCE (the
        coupling would let foreground load starve repairs). A tenant
        principal only rides the metadata when a real request context
        flows through the hop, which is exactly the traffic the quota
        is for."""
        if tenant is None:
            return True
        ts = self._tenant(tenant)
        _POLICY_NOTE(tenant)
        ts.t_seen = self._clock()
        if ts.quota is not None and not ts.quota.try_take_bytes(nbytes):
            self._shed("rpc", "quota", tenant)
            return False
        return True

    def charge_rpc_response(
        self, tenant: Optional[str], nbytes: int
    ) -> None:
        """Response-message bytes at RPC completion (may drive the
        bucket negative, exactly like the HTTP release path). Exempt
        for untenanted control-plane calls like charge_rpc_bytes."""
        if tenant is None:
            return
        ts = self._tenants.get(tenant)
        if ts is not None and ts.quota is not None and nbytes:
            ts.quota.charge_bytes(nbytes)

    # -- shedding / pressure --
    def _shed(
        self, cls, reason: str, tenant: Optional[str] = None
    ) -> None:
        # cls: priority-class index, or a literal class label for
        # traffic outside the HTTP class lattice (e.g. "rpc")
        name = tenant or _DEFAULT_TENANT
        self.shed_total += 1
        self.last_shed_t = self._clock()
        ts = self._tenants.get(name)
        if ts is not None:
            ts.shed += 1
        self._check_children_gen()
        label = tenancy.tenant_label(name)
        key = (cls, reason, label)
        child = self._shed_children.get(key)
        if child is None:
            child = self._shed_children[key] = OVERLOAD_SHED.child(
                server=self.server,
                gate=self.gate_id,
                reason=reason,
                tenant=label,
                **{
                    "class": (
                        CLASS_NAMES[cls] if isinstance(cls, int) else cls
                    )
                },
            )
        child.inc()

    def pressure(self) -> float:
        """Foreground pressure in [0, 1]: 1.0 while shedding (within the
        last second), else queue fullness."""
        if self._clock() - self.last_shed_t < 1.0:
            return 1.0
        if self.queued:
            return min(1.0, self.queued / self.max_queue)
        return 0.0

    def stats(self) -> dict:
        lim = self.limiter
        return {
            "server": self.server,
            "gate": self.gate_id,
            "limit": lim.limit,
            "baseline_ms": (
                round(lim.baseline_s * 1e3, 3)
                if lim.baseline_s is not None
                else None
            ),
            "limit_decreases": lim.decreases,
            "limit_increases": lim.increases,
            "inflight": self.inflight,
            "queued": self.queued,
            "admitted_total": self.admitted_total,
            "shed_total": self.shed_total,
            "queue_budget_ms": [
                round(b * 1e3, 1) for b in self.queue_budget_s
            ],
            "admitted_p50_ms": round(
                latency_percentile(self.admitted_counts, 50) * 1e3, 3
            ),
            "admitted_p99_ms": round(
                latency_percentile(self.admitted_counts, 99) * 1e3, 3
            ),
            "pressure": round(self.pressure(), 3),
            "tenants": self.tenant_stats(),
        }

    def tenant_stats(self, limit: int = 24) -> dict:
        """Per-tenant view (top `limit` by admitted+shed — the stats
        payload must stay bounded on a million-principal box): weight,
        admitted/shed/queued counts, and quota bucket fill."""
        names = sorted(
            self._tenants,
            key=lambda n: -(
                self._tenants[n].admitted + self._tenants[n].shed
            ),
        )[:limit]
        out = {}
        for n in names:
            ts = self._tenants[n]
            row = {
                "weight": ts.weight,
                "admitted": ts.admitted,
                "shed": ts.shed,
                "queued": ts.queued,
                "label": tenancy.POLICY.peek_label(n),
            }
            if ts.quota is not None:
                row["quota"] = ts.quota.snapshot()
            out[n] = row
        return out


# ------------------------------------------------- gate registry/pressure --

_GATES: list = []
_GATE_SEQ = count(1)  # per-process unique gate ids (names repeat)


def admission_enabled() -> bool:
    return (os.environ.get("SEAWEEDFS_TPU_ADMIT", "1") or "1") not in (
        "0",
        "",
    )


def new_server_gate(server: str) -> Optional[AdmissionGate]:
    """An AdmissionGate for one ServingCore, registered into the global
    pressure signal — or None when admission is disabled by env."""
    if not admission_enabled():
        return None
    gate = AdmissionGate(server)
    _GATES.append(gate)
    return gate


def drop_gate(gate: Optional[AdmissionGate]) -> None:
    """Unregister a stopped server's gate so its last-shed window cannot
    keep pressuring maintenance after the server is gone."""
    if gate is not None:
        try:
            _GATES.remove(gate)
        except ValueError:
            pass


def global_pressure() -> float:
    """Max pressure over every live gate in this process — the signal
    storage/maintenance.py consults. Plain float reads: safe from worker
    threads."""
    p = 0.0
    for g in _GATES:
        gp = g.pressure()
        if gp > p:
            p = gp
            if p >= 1.0:
                break
    return p


def gate_stats() -> list:
    return [g.stats() for g in _GATES]


# ------------------------------------------------------- circuit breaker --

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"


class CircuitOpenError(ConnectionError):
    """Fast-fail for calls to a peer whose breaker is open. A
    ConnectionError on purpose: every existing retry/hedge/failover path
    already treats it as 'peer unavailable' and moves on."""


class CircuitBreaker:
    """Per-peer closed/open/half-open breaker.

    Opens on `fail_threshold` consecutive failures, or when at least
    half of the last `shed_window` outcomes were sheds (503 +
    Retry-After: the peer is alive but actively load-shedding — keep
    hammering it and you ARE the overload). Half-open admits one probe
    after the open window; the probe's outcome closes or re-opens. The
    probe slot leases for `probe_timeout_s`: a probe whose caller never
    reports (cancelled mid-flight, caller died) is reclaimed after the
    lease instead of wedging allow() shut until process restart."""

    def __init__(
        self,
        peer: str,
        fail_threshold: int = 6,
        shed_window: int = 20,
        shed_trip: float = 0.5,
        open_s: float = 0.25,
        probe_timeout_s: float = 5.0,
        clock=time.monotonic,
    ):
        self.peer = peer
        self.fail_threshold = fail_threshold
        self.shed_trip = shed_trip
        self.open_s = open_s
        self.probe_timeout_s = probe_timeout_s
        self._clock = clock
        self.state = CLOSED
        self.opens = 0  # times tripped
        self._consec_fail = 0
        self._ring: deque = deque(maxlen=shed_window)  # True = shed
        self._open_until = 0.0
        self._probe_out = False
        self._probe_deadline = 0.0
        self._last_shed_t = 0.0

    # -- gate --
    def allow(self) -> bool:
        """May a request go to this peer now? Consumes the half-open
        probe slot, so callers must report the outcome via record_*
        (record_cancelled when the request is abandoned outcome-less)."""
        if self.state == CLOSED:
            return True
        if self.state == OPEN:
            if self._clock() < self._open_until:
                return False
            self._transition(HALF_OPEN)
            return self._lease_probe()
        if self._probe_out and self._clock() < self._probe_deadline:
            return False  # half-open: one probe at a time
        # no probe out — or the in-flight probe outlived its lease
        # without reporting: reclaim the slot rather than refuse forever
        return self._lease_probe()

    def _lease_probe(self) -> bool:
        self._probe_out = True
        self._probe_deadline = self._clock() + self.probe_timeout_s
        return True

    def blocked(self) -> bool:
        """Non-consuming peek: would allow() refuse right now? (Replica
        ordering uses this so peeking never eats the half-open probe.)"""
        if self.state == CLOSED:
            return False
        if self.state == OPEN:
            return self._clock() < self._open_until
        return self._probe_out and self._clock() < self._probe_deadline

    def shedding(self) -> bool:
        """Is the peer actively load-shedding? True within ~1s of a shed
        answer — the read fan-out pauses hedging into such a pool (a
        hedge into a shedding peer is pure retry-storm fuel)."""
        return self._clock() - self._last_shed_t < 1.0

    # -- outcomes --
    def record_success(self) -> None:
        self._consec_fail = 0
        self._ring.append(False)
        self._probe_out = False
        if self.state != CLOSED:
            self._transition(CLOSED)

    def record_failure(self) -> None:
        self._consec_fail += 1
        self._ring.append(False)
        if self.state == HALF_OPEN:
            self._probe_out = False
            self._trip(self.open_s)  # failed probe: back to open
        elif self.state == CLOSED and (
            self._consec_fail >= self.fail_threshold
        ):
            self._trip(self.open_s)

    def record_cancelled(self) -> None:
        """The caller abandoned its request before an outcome was known
        (hedged reads losing their race are cancelled routinely). Says
        nothing about the peer's health — but if the request held the
        half-open probe slot it MUST be returned here, or allow()
        refuses the peer until the probe lease expires."""
        if self.state == HALF_OPEN:
            self._probe_out = False

    def record_shed(self, retry_after_s: Optional[float] = None) -> None:
        """A 503/429 shed answer (alive peer refusing load). Not a
        failure for the consecutive count — but a shed-heavy window
        trips the breaker for the peer's own Retry-After hint."""
        self._ring.append(True)
        self._last_shed_t = self._clock()
        if self.state == HALF_OPEN:
            self._probe_out = False
            self._trip(retry_after_s or self.open_s)
            return
        ring = self._ring
        if (
            self.state == CLOSED
            and len(ring) >= ring.maxlen // 2
            and sum(ring) >= len(ring) * self.shed_trip
        ):
            self._trip(retry_after_s or self.open_s)

    def _trip(self, open_for: float) -> None:
        self._transition(OPEN)
        self._open_until = self._clock() + open_for
        self.opens += 1
        self._consec_fail = 0
        self._ring.clear()

    def _transition(self, to: str) -> None:
        if to == self.state:
            return
        self.state = to
        CIRCUIT_TRANSITIONS.inc(peer=self.peer, to=to)
        CIRCUIT_OPEN.set(1.0 if to == OPEN else 0.0, peer=self.peer)


class BreakerRegistry:
    """Process-wide per-peer breakers, shared by the HTTP data-plane
    client and the gRPC stub so both views of one peer's health feed one
    breaker."""

    def __init__(self, **breaker_kwargs):
        self._kw = breaker_kwargs
        self._by_peer: dict[str, CircuitBreaker] = {}

    def get(self, peer: str) -> CircuitBreaker:
        br = self._by_peer.get(peer)
        if br is None:
            br = self._by_peer[peer] = CircuitBreaker(peer, **self._kw)
        return br

    def peek(self, peer: str) -> Optional[CircuitBreaker]:
        return self._by_peer.get(peer)

    def reset(self) -> None:
        self._by_peer.clear()

    def stats(self) -> dict:
        return {
            p: {"state": b.state, "opens": b.opens}
            for p, b in self._by_peer.items()
        }


BREAKERS = BreakerRegistry()


def breakers_enabled() -> bool:
    return (os.environ.get("SEAWEEDFS_TPU_BREAKER", "1") or "1") not in (
        "0",
        "",
    )


def peer_breaker(peer: str) -> Optional[CircuitBreaker]:
    """The shared breaker for a peer address, or None when breakers are
    disabled (env) — callers do `br is None or br.allow()`."""
    if not breakers_enabled():
        return None
    return BREAKERS.get(peer)
