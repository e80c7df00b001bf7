"""Remote attempts of an EC read (ISSUE 29): a holder is asked only where the
location table names one, a forced LookupEcVolume only follows a listed
holder that failed, and one lookup a volume is in flight at a time.

The harness drives EcHandlers._read_one_ec_interval against a real on-disk
EC volume with one shard lost. The wire is a counted fake: `Stub` is replaced
by a master that counts its LookupEcVolume calls and answers from a table the
test edits, and by holders that serve the real shard bytes or are down."""

import asyncio
import time
from dataclasses import dataclass, field

import pytest

from seaweedfs_tpu.pb import http_address
from seaweedfs_tpu.server import volume_ec
from seaweedfs_tpu.server.volume_ec import (
    EC_DEGRADED_SPAN,
    EC_REFRESH_ROUNDS,
    SHARD_LOCATION_TTL,
)
from seaweedfs_tpu.storage.erasure_coding import to_ext
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolumeShard
from seaweedfs_tpu.util.metrics import (
    EC_REMOTE_ATTEMPTS,
    EC_RECONSTRUCTIONS,
    RETRY_COUNTER,
)
from test_degraded_read_cache import _Host, _make_ec_volume

LOST = 0  # the one shard of the 1 MiB volume that holds the .dat's bytes
MASTER = "127.0.0.1:9333"
HOLDER = "127.0.0.1:8081"
SIZE = 1024


class _Wire:
    """The counted fake master and the volume servers it may name."""

    def __init__(self, base: str):
        self.base = base
        self.lookups = 0
        self.master_up = True
        self.holders: dict[int, list[str]] = {}  # what the master answers
        self.serving: set[str] = set()  # holders that are up
        self.lookup_seconds = 0.0
        self.shard_reads: list[str] = []

    def stub(self, address: str, service: str, channel=None) -> "_FakeStub":
        return _FakeStub(self, http_address(address), service)


class _FakeStub:
    def __init__(self, wire: _Wire, url: str, service: str):
        self.wire, self.url, self.service = wire, url, service

    async def call(self, method: str, request: dict, timeout=30) -> dict:
        assert (self.service, method, self.url) == ("master", "LookupEcVolume", MASTER)
        self.wire.lookups += 1
        await asyncio.sleep(self.wire.lookup_seconds)  # the round trip yields the loop
        if not self.wire.master_up:
            raise ConnectionError("master unreachable")
        return {
            "volume_id": request["volume_id"],
            "shard_id_locations": [
                {"shard_id": sid, "locations": [{"url": u} for u in urls]}
                for sid, urls in self.wire.holders.items()
            ],
        }

    async def server_stream(self, method: str, request: dict, timeout=None):
        assert (self.service, method) == ("volume", "VolumeEcShardRead")
        self.wire.shard_reads.append(self.url)
        if self.url not in self.wire.serving:
            raise ConnectionError("holder down")
        with open(self.wire.base + to_ext(request["shard_id"]), "rb") as f:
            f.seek(request["offset"])
            yield {"data": f.read(request["size"])}


def _counts(counter, label: str) -> dict:
    with counter._lock:
        return {dict(k).get(label, ""): v for k, v in counter._values.items()}


class _Moved:
    """Counter deltas since the harness was built."""

    def __init__(self):
        self._before = self._now()

    @staticmethod
    def _now() -> dict:
        return {
            "outcome": _counts(EC_REMOTE_ATTEMPTS, "outcome"),
            "kind": _counts(EC_RECONSTRUCTIONS, "kind"),
            "op": _counts(RETRY_COUNTER, "op"),
        }

    def __call__(self, family: str, child: str) -> float:
        return self._now()[family].get(child, 0) - self._before[family].get(child, 0)


@pytest.fixture
def lost_shard(tmp_path, monkeypatch):
    """An EC volume with every shard but LOST mounted, a host whose master
    and peers are the fake wire, and the counters' readings before."""
    base, ev = _make_ec_volume(tmp_path)
    for i in range(14):
        if i != LOST:
            ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, i))
    wire = _Wire(base)
    monkeypatch.setattr(volume_ec, "Stub", wire.stub)
    host = _Host()
    host.master = MASTER
    yield host, ev, wire, _Moved()
    ev.close()


def _shard_bytes(wire: _Wire, offset: int) -> bytes:
    with open(wire.base + to_ext(LOST), "rb") as f:
        f.seek(offset)
        return f.read(SIZE)


async def _read(host, ev, offset: int) -> bytes:
    return await host._read_one_ec_interval(ev, LOST, offset, SIZE, 7)


@dataclass
class Case:
    """One traffic against one state of the wire, and what it must cost."""

    reads: int = 1
    at_once: bool = False
    primed: bool = True  # the table holds the master's answer of this TTL
    warm_cache: bool = False  # the spans read were reconstructed before
    master_up: bool = True
    holders: list = field(default_factory=list)  # whom the master names for LOST
    # survivors this server does not hold either, and whose holder (HOLDER,
    # and of LOST too) the master comes to know only after the table was primed
    came_back: tuple = ()
    serving: bool = False
    lookups: int = 0
    outcome: str = "no_holder"
    forced: int = 0  # retries_total{op="ec_location_refresh"}
    cold: int = 0
    cache_hit: int = 0
    # VolumeEcShardRead streams opened to the holder: the fewest, and the
    # most (a failed stream is tried again while the shared retry budget,
    # which the tests before this one have drawn on, allows)
    asked: tuple = (0, 0)


CASES = {
    # (1) nobody to ask and the table says so: no lookup, no forced round
    "fresh_table_no_holder": Case(reads=5, cold=5),
    # (2) 16 requests meet an expired table: one lookup answers them all
    "expired_table_16_at_once": Case(reads=16, at_once=True, primed=False, lookups=1, cold=16),
    # (3) a listed holder fails: the list may be stale, so the forced rounds run
    "listed_holder_fails": Case(
        holders=[HOLDER], lookups=EC_REFRESH_ROUNDS, outcome="failed",
        forced=EC_REFRESH_ROUNDS, cold=1,
        asked=(
            1 + EC_REFRESH_ROUNDS,
            (1 + EC_REFRESH_ROUNDS) * volume_ec.EC_REMOTE_READ_POLICY.attempts,
        ),
    ),
    # (4) a listed holder serves: nothing forced, nothing reconstructed
    "listed_holder_serves": Case(holders=[HOLDER], serving=True, outcome="served", asked=(1, 1)),
    # (6) the master cannot be reached: no fresh table, so the path of before
    "master_unreachable": Case(
        primed=False, master_up=False, lookups=1 + EC_REFRESH_ROUNDS,
        outcome="failed", forced=EC_REFRESH_ROUNDS, cold=1,
    ),
    # (7) a hit in the degraded-read cache pays no lookup either
    "cache_hit_no_holder": Case(reads=3, warm_cache=True, cache_hit=3),
    # nobody listed and too few survivors in reach to reconstruct: the table
    # is up to a TTL old, so the path of a stale list — one forced lookup
    # finds the holder that came back since
    "no_holder_and_short_of_survivors": Case(
        came_back=(1, 2, 3, 4, 5), serving=True, lookups=1, outcome="served",
        forced=1, asked=(1, 1),
    ),
    # this server listed as the holder of a shard it lost is nobody to ask
    "only_itself_listed": Case(holders=[_Host.address, _Host.public_url], cold=1),
}


@pytest.mark.parametrize("name", CASES)
def test_remote_attempts(lost_shard, name):
    case = CASES[name]
    host, ev, wire, _ = lost_shard
    # the lost shard file is 8 spans long: 16 at once go two to a span
    stride = EC_DEGRADED_SPAN // 2 if case.at_once else EC_DEGRADED_SPAN
    offsets = [i * stride + 17 for i in range(case.reads)]
    if case.holders:
        wire.holders[LOST] = list(case.holders)
    if case.serving:
        wire.serving.add(HOLDER)
    wire.lookup_seconds = 0.01 if case.at_once else 0.0

    async def body():
        if case.warm_cache:
            for off in offsets:
                await _read(host, ev, off)
        if case.primed:
            await host._refresh_shard_locations(ev)
        for shard_id in case.came_back:
            ev.delete_shard(shard_id).close()
        for shard_id in case.came_back and (LOST, *case.came_back):
            wire.holders[shard_id] = [HOLDER]
        wire.master_up = case.master_up
        wire.lookups = 0
        wire.shard_reads.clear()
        moved = _Moved()
        if case.at_once:
            got = await asyncio.gather(*(_read(host, ev, off) for off in offsets))
        else:
            got = [await _read(host, ev, off) for off in offsets]
        return got, moved

    got, moved = asyncio.run(body())
    assert got == [_shard_bytes(wire, off) for off in offsets]
    assert wire.lookups == case.lookups
    for outcome in ("no_holder", "served", "failed"):
        want = case.reads if outcome == case.outcome else 0
        assert moved("outcome", outcome) == want, outcome
    assert moved("op", "ec_location_refresh") == case.forced
    if case.at_once:  # which of a span's two finds the other's work is a race
        assert moved("kind", "cold") + moved("kind", "cache_hit") == case.cold
    else:
        assert moved("kind", "cold") == case.cold
        assert moved("kind", "cache_hit") == case.cache_hit
    assert set(wire.shard_reads) <= {HOLDER}
    assert case.asked[0] <= len(wire.shard_reads) <= case.asked[1]


def test_a_holder_that_appears_is_used_by_the_first_read_after_the_ttl(
    lost_shard, monkeypatch
):
    """(5) ec.rebuild remounts the shard elsewhere: reads go on being
    reconstructed, exactly, until the table is a TTL old, and the first read
    after that asks the new holder. The clock is stepped, not slept."""
    host, ev, wire, moved = lost_shard
    step = [0.0]
    real = time.time
    monkeypatch.setattr(time, "time", lambda: real() + step[0])

    async def body():
        assert await _read(host, ev, 17) == _shard_bytes(wire, 17)
        assert (wire.lookups, moved("outcome", "no_holder")) == (1, 1)
        wire.holders[LOST] = [HOLDER]
        wire.serving.add(HOLDER)
        step[0] += SHARD_LOCATION_TTL / 2
        assert await _read(host, ev, EC_DEGRADED_SPAN) == _shard_bytes(wire, EC_DEGRADED_SPAN)
        assert (wire.lookups, wire.shard_reads) == (1, [])
        assert (moved("outcome", "no_holder"), moved("kind", "cold")) == (2, 2)
        step[0] += SHARD_LOCATION_TTL / 2 + 0.001
        off = 2 * EC_DEGRADED_SPAN
        assert await _read(host, ev, off) == _shard_bytes(wire, off)

    asyncio.run(body())
    assert (wire.lookups, wire.shard_reads) == (2, [HOLDER])
    assert (moved("outcome", "served"), moved("kind", "cold")) == (1, 2)
    assert moved("op", "ec_location_refresh") == 0


def test_a_cancelled_reader_leaves_the_lookup_to_the_others(lost_shard):
    """The lookup in flight is shared: the caller that sent it may be
    cancelled (its client hung up) and the one waiting beside it still gets
    the master's answer, from that one call."""
    host, ev, wire, moved = lost_shard
    wire.lookup_seconds = 0.05

    async def body():
        first = asyncio.ensure_future(_read(host, ev, 17))
        await asyncio.sleep(0.01)  # its lookup is out
        second = asyncio.ensure_future(_read(host, ev, 17))
        await asyncio.sleep(0.01)
        first.cancel()
        with pytest.raises(asyncio.CancelledError):
            await first
        return await second

    assert asyncio.run(body()) == _shard_bytes(wire, 17)
    assert wire.lookups == 1
    assert moved("outcome", "no_holder") == 1
    # the next loop (a test's next asyncio.run) starts its own lookup: the
    # one kept on the volume is done
    ev.shard_locations_refresh_time = 0.0
    assert asyncio.run(_read(host, ev, 17)) == _shard_bytes(wire, 17)
    assert wire.lookups == 2


def test_a_forced_refresh_joins_the_lookup_that_is_out(lost_shard):
    """Forced or not, one LookupEcVolume a volume at a time."""
    host, ev, wire, _ = lost_shard
    wire.lookup_seconds = 0.01

    async def body():
        await asyncio.gather(
            host._refresh_shard_locations(ev),
            *(host._refresh_shard_locations(ev, force=True) for _ in range(7)),
        )
        assert wire.lookups == 1
        await host._refresh_shard_locations(ev)  # fresh: nothing sent
        assert wire.lookups == 1
        await host._refresh_shard_locations(ev, force=True)  # forced: one more
        assert wire.lookups == 2

    asyncio.run(body())
