"""The benchmark's own plain rule for where a needle's bytes lie in an EC
volume, and what the bytes of an interval on a lost shard are. It imports
nothing of the program, so no later change to the program can move it.

Layout (upstream weed/storage/erasure_coding/ec_encoder.go, ec_locate.go
`LocateData`): the .dat is cut into rows of k blocks, rows of large blocks
while more than one such row remains, rows of small blocks after; block j of a
row is shard j's, and a shard file is its large blocks, then its small ones.
So the bytes [offset, offset + size) of the .dat are one interval for every
block they touch, in order: the record of a 4 MiB chunk over 1 MiB blocks is
five, on five consecutive shards, the first and the last partial (six where
it starts within 40 bytes of a block's end).

Read (upstream weed/storage/store_ec.go `readEcShardIntervals`,
`recoverOneRemoteEcShardInterval`): the intervals are read one after another
and joined; one on a shard nobody holds is rebuilt from the same span of any k
survivors, so its bytes are the lost row of the code over those spans.
"""

from __future__ import annotations

import numpy as np

from .rs_codec import LARGE_BLOCK, SMALL_BLOCK


def large_rows(dat_bytes: int, k: int, large: int = LARGE_BLOCK) -> int:
    """Rows of large blocks: one for as long as more than a row remains."""
    n = 0
    while dat_bytes - n * large * k > large * k:
        n += 1
    return n


def record_bytes(body_bytes: int) -> int:
    """A version 3 needle record: 16 header, 4 length, body, 1 flags, 4
    checksum, 8 timestamp, then 1 to 8 bytes of padding to a multiple of 8."""
    return ((body_bytes + 33) // 8 + 1) * 8


def locate(offset: int, size: int, dat_bytes: int, k: int = 10,
           large: int = LARGE_BLOCK, small: int = SMALL_BLOCK) -> list:
    """[(shard, offset in the shard file, length)] of the bytes
    [offset, offset + size) of a .dat of `dat_bytes`, in the order read."""
    n_large = large_rows(dat_bytes, k, large)
    large_bytes = n_large * large * k
    out = []
    while size > 0:
        if offset < large_bytes:
            block, shard_base, into = large, 0, offset
        else:
            block, shard_base, into = small, n_large * large, offset - large_bytes
        row, in_row = divmod(into, block * k)
        shard, in_block = divmod(in_row, block)
        take = min(size, block - in_block)
        out.append((shard, shard_base + row * block + in_block, take))
        offset, size = offset + take, size - take
    return out


def tally(intervals: list, lost: set) -> tuple:
    """(intervals, intervals on a lost shard, 1 if any is on one else 0)."""
    on_lost = sum(1 for shard, _off, _n in intervals if shard in lost)
    return len(intervals), on_lost, int(on_lost > 0)


def rebuild(codec, read, lost_shard: int, shard_offset: int, length: int, survivors: list) -> bytes:
    """The bytes [shard_offset, + length) of `lost_shard`, from the same span
    of k of the `survivors`: `read(shard, offset, length)` gives a survivor's
    bytes, `codec` is a `rs_codec.Codec`."""
    spans = {
        s: np.frombuffer(read(s, shard_offset, length), dtype=np.uint8)
        for s in sorted(survivors)[: codec.k]
    }
    return codec.recover(spans, [lost_shard])[0].tobytes()
