"""What write_ec_files must make of a .dat, worked out without it: the file
laid out in rows in memory and each row's parity from CpuRSCodec.encode."""

import numpy as np

from seaweedfs_tpu.storage.erasure_coding import (
    EC_LARGE_BLOCK_SIZE,
    EC_SMALL_BLOCK_SIZE,
)
from seaweedfs_tpu.storage.erasure_coding.coder_cpu import CpuRSCodec


def oracle_shards(
    dat_path: str, k: int = 10, m: int = 4,
    large: int = EC_LARGE_BLOCK_SIZE, small: int = EC_SMALL_BLOCK_SIZE,
) -> list:
    """The k + m shard files' bytes: large rows while MORE than one is
    left, then small rows, the last zero-filled (ec_encoder.go:214-228)."""
    data = np.fromfile(dat_path, dtype=np.uint8)
    codec = CpuRSCodec(k, m)
    shards = [[] for _ in range(k + m)]
    pos = 0
    while pos < data.size:
        block = large if data.size - pos > large * k else small
        row = np.zeros(block * k, dtype=np.uint8)
        piece = data[pos : pos + block * k]
        row[: piece.size] = piece
        rows = row.reshape(k, block)
        for i, shard in enumerate(np.concatenate([rows, codec.encode(rows)])):
            shards[i].append(shard.tobytes())
        pos += block * k
    return [b"".join(s) for s in shards]
