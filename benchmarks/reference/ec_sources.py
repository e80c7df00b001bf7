"""The benchmark's own plain rule for where each interval of a needle comes
from when a spread EC volume is read through one server with another server
lost. It imports nothing of the program, so no later change to the program
can move it.

It composes the two rules before it: `ec_spread.py`'s plan (the shards on the
server the reader reaches, `held`, and those nobody holds since the loss,
`lost`, as the master answers and `ec_spread.judge_spread` holds to the
balanced rule) and `ec_locate.py`'s intervals (a needle's record cut at every
block it touches, in order). Read (upstream weed/storage/store_ec.go:155-373
`readOneEcShardInterval`): an interval on a shard of the server itself is read
there (`local`); one on a shard a live server holds is asked of that holder
over VolumeEcShardRead (`served`); one on a lost shard is rebuilt from the
same span of any k survivors (`rebuilt`, whether decoded or found in a cache
of earlier decodes). Which of the live servers holds a `served` interval does
not matter here; that every interval is exactly one of the three does.
"""

from __future__ import annotations

SOURCES = ("local", "served", "rebuilt")


def source(shard: int, held: set, lost: set) -> str:
    """Where an interval on `shard` comes from."""
    if shard in held:
        return "local"
    if shard in lost:
        return "rebuilt"
    return "served"


def tally(intervals: list, held: set, lost: set) -> tuple:
    """`intervals`: [(shard, offset in the shard file, length)] of one needle
    (ec_locate.locate). (intervals, local, served, rebuilt, 1 if any interval
    is rebuilt else 0)."""
    by = dict.fromkeys(SOURCES, 0)
    for shard, _offset, _length in intervals:
        by[source(shard, held, lost)] += 1
    return len(intervals), by["local"], by["served"], by["rebuilt"], int(by["rebuilt"] > 0)
