"""Tenant identity, weights, quotas, and the bounded-cardinality label
policy for the tenant QoS plane (ISSUE 12).

Every serving surface used to run one implicitly-shared queue per
priority class: PR 9's admission gates order by request *class* only, so
one abusive tenant's reads sit in the same CLASS_READ pool as everyone
else's and starve them wholesale (the cross-workload contention hazard
measured for shared EC storage in arXiv 1709.05365). This module is the
identity half of the fix — `util/overload.py` consumes it for
weighted-fair dequeue and per-tenant quotas:

- **Identity derivation** (`tenant_from_request`): one principal shared
  by master/volume/filer/S3. Priority order:

  1. an explicit ``X-Seaweed-Tenant`` header (raw-tier clients, and the
     header our own FastHTTPClient propagates across in-cluster hops so
     a request keeps its principal from the S3 gateway down to the
     volume server);
  2. the ``collection`` query parameter (filer/volume/master surfaces —
     collections are the reference's native multi-tenancy unit);
  3. server-specific hooks layered on top: the S3 gateway maps the V4
     ``Credential=`` access key to its IAM identity name, the volume
     server maps a read path's vid to the volume's collection.

  No signal -> the ``default`` tenant (exactly the pre-ISSUE-12
  behavior: one shared pool).

- **Weights** (`tenant_weight`): relative shares for the deficit-round-
  robin dequeue inside each admission class, parsed once from
  ``SEAWEEDFS_TPU_TENANT_WEIGHTS`` ("alice:4,bob:2", default 1.0,
  clamped to [0.1, 100] so the DRR rotation terminates in a bounded
  number of visits).

- **Quotas** (`TenantQuota`, `tenant_quota`): per-tenant token buckets
  for request rate (``SEAWEEDFS_TPU_TENANT_QPS``) and bytes/s
  (``SEAWEEDFS_TPU_TENANT_BPS``), both "name:value" lists where ``*``
  sets a default for every tenant. A dry bucket sheds with
  ``reason=quota`` — the same pre-rendered ~2µs 503 + Retry-After the
  overload gate already serves. Byte buckets are charged request-body
  bytes at admission and response bytes at release, and may go
  negative: a tenant that just pulled a huge object pays it off before
  admitting more bytes.

- **Label policy** (`TenantLabelPolicy`, `tenant_label`): metric label
  values for tenants are BOUNDED — the top-K tenants by decayed heat
  get their own label, everyone else collapses into ``other``
  (cardinality on a million-tenant box must not be a million series).
  The bound is enforced at the registry seam: at most ``cap`` admitted
  names + ``other`` + ``default`` ever render, and when a hotter
  tenant displaces a colder one the retired tenant's series are purged
  from the tenant-labeled families (our registry, our rules — a purge
  resets that tenant's counters, disclosed in docs/robustness.md).

The current tenant rides a contextvar (`set_current`/`current`) so the
filer's internal chunk uploads/reads carry the gateway's principal to
the volume tier; `util/fasthttp.FastHTTPClient` injects the header from
it the same way it injects ``traceparent``.
"""

from __future__ import annotations

import os
import time
from contextvars import ContextVar
from typing import Optional

DEFAULT_TENANT = "default"
OTHER_LABEL = "other"
TENANT_HEADER = "X-Seaweed-Tenant"
TENANT_HEADER_B = b"x-seaweed-tenant"

# current tenant principal for this task tree (None = default): set by
# ServingCore._dispatch for non-default principals, read by the HTTP
# client for cross-hop propagation. Module-bound get/set below keep the
# per-request cost at one contextvar load (the trace plane's pattern).
_TENANT: ContextVar[Optional[str]] = ContextVar("swfs_tenant", default=None)
current = _TENANT.get
set_current = _TENANT.set
reset_current = _TENANT.reset


def _parse_kv_env(name: str) -> dict:
    """Parse "a:1,b:2.5" env lists; malformed entries are dropped (an
    operator typo must not take the serving plane down at import)."""
    out: dict = {}
    raw = os.environ.get(name, "") or ""
    for part in raw.split(","):
        part = part.strip()
        if not part or ":" not in part:
            continue
        k, _, v = part.rpartition(":")
        try:
            out[k.strip()] = float(v)
        except ValueError:
            continue
    return out


class TenancyConfig:
    """Weights + quota rates, env-parsed once and overridable for tests
    (`configure`)."""

    def __init__(self):
        self.reload()

    def reload(self) -> None:
        self.weights = _parse_kv_env("SEAWEEDFS_TPU_TENANT_WEIGHTS")
        self.qps = _parse_kv_env("SEAWEEDFS_TPU_TENANT_QPS")
        self.bps = _parse_kv_env("SEAWEEDFS_TPU_TENANT_BPS")

    def weight(self, tenant: str) -> float:
        w = self.weights.get(tenant)
        if w is None:
            w = self.weights.get("*", 1.0)
        # clamp: the DRR head-of-rotation top-up adds `weight` per visit
        # and serves at deficit >= 1, so weight >= 0.1 bounds the
        # rotation count before progress at 10
        return min(100.0, max(0.1, w))

    def quota_for(
        self, tenant: str, clock=time.monotonic
    ) -> Optional["TenantQuota"]:
        qps = self.qps.get(tenant, self.qps.get("*", 0.0))
        bps = self.bps.get(tenant, self.bps.get("*", 0.0))
        if qps <= 0.0 and bps <= 0.0:
            return None
        # the caller's clock (the gate's, possibly a test fake) drives
        # refills — a config-derived bucket on a different clock than
        # the gate that consults it would never refill under fakes
        return TenantQuota(qps=qps, byte_ps=bps, clock=clock)


CONFIG = TenancyConfig()


def configure(
    weights: Optional[dict] = None,
    qps: Optional[dict] = None,
    bps: Optional[dict] = None,
) -> None:
    """Install tenant weights/quota rates programmatically (tests, bench
    legs). Passing None for a field re-reads that field from env."""
    CONFIG.reload()
    if weights is not None:
        CONFIG.weights = dict(weights)
    if qps is not None:
        CONFIG.qps = dict(qps)
    if bps is not None:
        CONFIG.bps = dict(bps)


class TenantQuota:
    """Token buckets for one tenant: request rate + bytes/s.

    `burst_s` seconds of headroom; a rate of 0 disables that bucket.
    The byte bucket may go NEGATIVE (response sizes are only known at
    release), so `try_take` refuses while the tenant is paying off a
    prior burst. Single-event-loop use (the gate's discipline)."""

    __slots__ = (
        "qps", "byte_ps", "burst_s", "_rt", "_bt", "_last", "_clock"
    )

    def __init__(
        self,
        qps: float = 0.0,
        byte_ps: float = 0.0,
        burst_s: float = 1.0,
        clock=time.monotonic,
    ):
        self.qps = qps
        self.byte_ps = byte_ps
        self.burst_s = burst_s
        self._rt = qps * burst_s
        self._bt = byte_ps * burst_s
        self._clock = clock
        self._last = clock()

    def _refill(self) -> None:
        now = self._clock()
        dt = now - self._last
        if dt <= 0.0:
            return
        self._last = now
        if self.qps:
            self._rt = min(self.qps * self.burst_s, self._rt + dt * self.qps)
        if self.byte_ps:
            self._bt = min(
                self.byte_ps * self.burst_s, self._bt + dt * self.byte_ps
            )

    def try_take(self, cost_bytes: int = 0) -> bool:
        """One request (+ its known request-body bytes) against the
        buckets; False = over quota, shed with reason=quota. Both
        buckets are CHECKED before either is deducted — a refusal must
        not burn the request token of a request the dry byte bucket is
        about to refuse anyway."""
        self._refill()
        if self.qps and self._rt < 1.0:
            return False
        if self.byte_ps and self._bt <= 0.0:
            return False
        if self.qps:
            self._rt -= 1.0
        if self.byte_ps and cost_bytes:
            self._bt -= cost_bytes
        return True

    def charge_bytes(self, n: int) -> None:
        """Response bytes, charged at release (may drive the bucket
        negative — the next try_take refuses until it refills)."""
        if self.byte_ps and n:
            self._bt -= n

    def try_take_bytes(self, n: int) -> bool:
        """Byte-bucket-only consult + charge — no request token moves.
        The seam for traffic that is not a whole HTTP request of its
        own: a gRPC message's bytes, or one member's share of an
        admitted mixed-tenant batch frame. False = over byte quota."""
        self._refill()
        if self.byte_ps and self._bt <= 0.0:
            return False
        if self.byte_ps and n:
            self._bt -= n
        return True

    def refund_bytes(self, n: int) -> None:
        """Hand back bytes only (no request token): the carrier of a
        mixed-tenant batch was charged the whole frame at admission;
        each member's re-attribution returns the carrier's share."""
        if self.byte_ps and n:
            self._bt = min(self.byte_ps * self.burst_s, self._bt + n)

    def refill_horizon_s(self) -> float:
        """Seconds until the buckets refill to their fresh (full-burst)
        state. The gate's tenant-table prune only evicts a quota'd
        state after it has been idle at least this long: recreating the
        bucket then grants nothing natural refill would not have, so
        eviction cannot be exploited to erase byte debt."""
        self._refill()
        h = 0.0
        if self.qps:
            h = max(h, (self.qps * self.burst_s - self._rt) / self.qps)
        if self.byte_ps:
            h = max(
                h,
                (self.byte_ps * self.burst_s - self._bt) / self.byte_ps,
            )
        return h

    def refund(self, cost_bytes: int = 0) -> None:
        """Hand back one request token (+ its charged body bytes): the
        request was quota-charged at enqueue but never served (queue
        deadline, caller cancelled) — keeping the token would bill the
        tenant twice for one overload."""
        if self.qps:
            self._rt = min(self.qps * self.burst_s, self._rt + 1.0)
        if self.byte_ps and cost_bytes:
            self._bt = min(
                self.byte_ps * self.burst_s, self._bt + cost_bytes
            )

    def snapshot(self) -> dict:
        self._refill()
        return {
            "qps": self.qps,
            "byte_ps": self.byte_ps,
            "request_tokens": round(self._rt, 2),
            "byte_tokens": round(self._bt),
        }


# ------------------------------------------------- bounded label policy --


def _env_topk() -> int:
    try:
        return max(
            1, int(os.environ.get("SEAWEEDFS_TPU_TENANT_TOPK", "") or 16)
        )
    except ValueError:
        return 16


class TenantLabelPolicy:
    """Top-K-by-heat + ``other`` metric-label policy.

    `label(name)` returns `name` for at most `cap` distinct admitted
    tenants (plus the always-allowed ``default``), ``other`` for the
    rest — so tenant-labeled metric families hold <= cap + 2 distinct
    tenant values no matter how many principals a million-user box
    sees. Heat is a decayed per-tenant op count; when an unadmitted
    tenant's heat exceeds 2x the coldest admitted tenant's (hysteresis
    against label churn), the cold one is retired: its future ops label
    ``other`` and its existing series are PURGED from the registered
    tenant families via `on_retire` (the registry seam — purging is
    what keeps the cumulative distinct-value count bounded, not just
    the instantaneous one). Retirement checks are rate-limited to one
    per `swap_interval_s`."""

    def __init__(
        self,
        cap: Optional[int] = None,
        half_life_s: float = 60.0,
        swap_interval_s: float = 1.0,
        clock=time.monotonic,
        on_retire=None,
    ):
        self.cap = cap if cap is not None else _env_topk()
        self.half_life_s = half_life_s
        self.swap_interval_s = swap_interval_s
        self._clock = clock
        self.on_retire = on_retire
        self._heat: dict[str, float] = {}
        self._seen: dict[str, float] = {}  # last heat-update time
        self._admitted: set = set()
        self._last_swap = 0.0
        self.retired_total = 0

    def _decayed(self, name: str, now: float) -> float:
        h = self._heat.get(name, 0.0)
        t = self._seen.get(name, now)
        if h and now > t:
            h *= 0.5 ** ((now - t) / self.half_life_s)
        return h

    def note(self, name: str) -> None:
        """One op by `name` feeds its heat (fold-decayed in place)."""
        now = self._clock()
        self._heat[name] = self._decayed(name, now) + 1.0
        self._seen[name] = now
        if len(self._heat) > 8 * self.cap + 16:
            self._prune(now)

    def _prune(self, now: float) -> None:
        """Bound the heat table itself: keep admitted + the hottest
        non-admitted half (a million one-shot principals must not grow
        process memory without bound)."""
        keep = sorted(
            self._heat, key=lambda n: self._decayed(n, now), reverse=True
        )[: 4 * self.cap]
        keepset = set(keep) | self._admitted
        self._heat = {n: self._heat[n] for n in keepset if n in self._heat}
        self._seen = {n: self._seen[n] for n in keepset if n in self._seen}

    def label(self, name: str) -> str:
        if name == DEFAULT_TENANT or name in self._admitted:
            return name
        if len(self._admitted) < self.cap:
            self._admitted.add(name)
            return name
        now = self._clock()
        if now - self._last_swap >= self.swap_interval_s:
            self._last_swap = now
            mine = self._decayed(name, now)
            coldest = min(
                self._admitted, key=lambda n: self._decayed(n, now)
            )
            if mine > 2.0 * self._decayed(coldest, now):
                self._admitted.discard(coldest)
                self._admitted.add(name)
                self.retired_total += 1
                if self.on_retire is not None:
                    self.on_retire(coldest)
                return name
        return OTHER_LABEL

    def peek_label(self, name: str) -> str:
        """Non-mutating view of `label(name)` — status surfaces must not
        admit a tenant into the top-K as a side effect of rendering."""
        if name == DEFAULT_TENANT or name in self._admitted:
            return name
        return OTHER_LABEL

    def admitted(self) -> set:
        return set(self._admitted)


# bumped on every retirement purge: consumers caching per-label metric
# children (the admission gates) compare generations and drop their
# caches, or a cached child's next inc() would silently re-mint the
# purged series — and the caches themselves would grow with cumulative
# label churn instead of staying bounded by the live top-K
_PURGE_GEN = 0


def purge_generation() -> int:
    return _PURGE_GEN


def _purge_retired(name: str) -> None:
    """Registry-seam retirement: drop a retired tenant's series from
    every tenant-labeled family so the cumulative distinct-value count
    stays <= cap + 2 (counters restart at 0 if the tenant is ever
    re-admitted; the alternative is unbounded series growth)."""
    global _PURGE_GEN
    from . import metrics

    for fam in metrics.TENANT_LABELED_FAMILIES:
        fam.remove_label_value("tenant", name)
    _PURGE_GEN += 1


POLICY = TenantLabelPolicy(on_retire=_purge_retired)


def tenant_label(name: str) -> str:
    """The metric label value for a tenant principal (top-K + other)."""
    return POLICY.label(name)


def note_heat(name: str) -> None:
    """One op by `name` into the live policy's heat tracker (indirect on
    purpose: reset_policy swaps POLICY under long-lived callers)."""
    POLICY.note(name)


def reset_policy(cap: Optional[int] = None, **kw) -> None:
    """Fresh label policy (tests). The OLD policy's
    admitted labels are purged first: a swap that abandoned them would
    leave series no retirement can ever reach — permanently stale
    cardinality that breaks the cumulative cap invariant (and made the
    test suite order-dependent before this purge existed). Live
    counters of currently-admitted tenants restart; acceptable for a
    test hook."""
    global POLICY
    for name in POLICY.admitted():
        _purge_retired(name)
    POLICY = TenantLabelPolicy(cap=cap, on_retire=_purge_retired, **kw)


# ------------------------------------------------------------ derivation --


def tenant_from_request(req) -> Optional[str]:
    """Default fast-tier derivation: explicit header, else collection
    query parameter, else None (-> default tenant). `req` is a
    util/fasthttp.FastRequest (lower-cased byte header names)."""
    t = req.headers.get(TENANT_HEADER_B)
    if t:
        return t.decode("latin1")
    q = req.query
    if q:
        idx = q.find("collection=")
        while idx >= 0:
            # parameter-boundary guard — but keep SCANNING past a
            # rejected hit: "?mycollection=a&collection=beta" must find
            # the real parameter, not give up on the substring inside
            # "mycollection="
            if idx == 0 or q[idx - 1] == "&":
                end = q.find("&", idx)
                val = q[idx + 11: end if end >= 0 else len(q)]
                if val:
                    return val
            idx = q.find("collection=", idx + 1)
    return None
