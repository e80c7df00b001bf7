"""What a store builder needs of the program's storage library, in worker
processes that never import JAX: an empty volume (its super block), with the
clock the library stamps records with replaced by a counter, so the same seed
gives the same bytes.
"""

from __future__ import annotations

import os
import sys
import time

EPOCH_NS = 1_700_000_000 * 10**9


def stay_off_jax() -> None:
    if "jax" in sys.modules:
        raise RuntimeError("a store builder must stay off JAX")


def open_volume(directory: str, vid: int):
    stay_off_jax()
    from seaweedfs_tpu.storage.volume import Volume

    ticks = iter(range(EPOCH_NS, EPOCH_NS + 10**12))
    time.time_ns = lambda: next(ticks)
    os.makedirs(directory, exist_ok=True)
    return Volume(directory, "", vid, needle_map_kind="memory")
