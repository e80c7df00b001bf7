"""The benchmark's own plain rules for a spread EC volume: where the shards
may lie, which shard a needle lies on, and how many survivors a degraded read
has to fetch from other servers. It imports nothing of the program, so no
later change to the program can move it.

Spread (upstream weed/shell/command_ec_encode.go `balancedEcDistribution`):
after `ec.encode` every one of the n shards is on exactly one of the servers
the master lists, and no server holds more than one shard more than another
(14 over four servers: 4, 4, 3, 3 in some order). Which shard goes where is
the program's to choose; the rule is what a deployment relies on, since it
bounds what the loss of one server takes away: ceil(n / servers) shards, at
most the parity count from four servers up.

Locate (upstream weed/storage/erasure_coding/ec_locate.go): the .dat is laid
out in rows of k blocks, large blocks while more than one row of them
remains, small blocks after (`rs_codec.row_counts`); byte x of a row lies in
block (x // block) % k, which is the shard, at the row's offset in every shard
file plus x % block.
"""

from __future__ import annotations

from .rs_codec import LARGE_BLOCK, SMALL_BLOCK, row_counts


def balanced_counts(n_shards: int, n_nodes: int) -> list:
    """How many shards each node holds under the rule, largest first."""
    if n_nodes < 1:
        raise ValueError("no node to place a shard on")
    base, extra = divmod(n_shards, n_nodes)
    return [base + 1] * extra + [base] * (n_nodes - extra)


def judge_spread(holders: dict, nodes: list, n_shards: int) -> dict:
    """`holders`: {shard id: [node, ...]} as the master answers; `nodes`: every
    volume server the master lists. Three numbers, each 0 under the rule:
    shards nobody (or somebody who is not a listed node) holds, shards held
    more than once, and how far the fullest node is above the emptiest beyond
    the one shard the rule allows."""
    known = set(nodes)
    unplaced = doubled = 0
    count = {node: 0 for node in nodes}
    for shard in range(n_shards):
        at = [h for h in holders.get(shard, []) if h in known]
        if not at:
            unplaced += 1
        if len(holders.get(shard, [])) > 1:
            doubled += 1
        for h in at:
            count[h] += 1
    spread = max(count.values()) - min(count.values()) if count else n_shards
    return {"shards_unplaced": unplaced, "shards_doubled": doubled,
            "spread_uneven": max(0, spread - 1)}


def locate(offset: int, size: int, dat_bytes: int, k: int = 10) -> list:
    """[(shard, offset in the shard file, length)] of the bytes
    [offset, offset + size) of a .dat of `dat_bytes`, in order."""
    n_large, _n_small = row_counts(dat_bytes, k)
    large_bytes = n_large * LARGE_BLOCK * k
    out = []
    while size > 0:
        if offset < large_bytes:
            block, row_base, into = LARGE_BLOCK, 0, offset
        else:
            block, row_base, into = SMALL_BLOCK, n_large * LARGE_BLOCK, offset - large_bytes
        row, in_row = divmod(into, block * k)
        shard, in_block = divmod(in_row, block)
        take = min(size, block - in_block)
        out.append((shard, row_base + row * block + in_block, take))
        offset, size = offset + take, size - take
    return out


def least_remote_survivors(missing: int, held: set, lost: set, k: int = 10, total: int = 14):
    """A server that holds the shards `held` reconstructs shard `missing` while
    the shards `lost` are with nobody: the k survivors it needs come first from
    its own, so the least it has to fetch from others is k less those; None
    where fewer than k shards survive at all."""
    gone = set(lost) | {missing}
    own = len(set(held) - gone)
    elsewhere = total - len(gone | set(held))
    need = max(0, k - own)
    return need if elsewhere >= need else None
