"""Sharded bulk needle-index lookup over a device mesh.

Probe-parallel layout: the sorted index columns are replicated (a volume's
index fits one chip's HBM) and the probe batch is sharded across EVERY mesh
device (both axes flattened), so P probes run as n_devices independent
branchless searches with zero cross-device communication — the serving-side
scale-out of ops/index_kernel.py's single-chip kernel (ref: the per-request
CompactMap search this all replaces, weed/storage/needle_map/
compact_map.go:145-172).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..ops.index_kernel import _search_range, _split_u64


@functools.lru_cache(maxsize=32)
def _compiled_body(n: int, steps: int, mesh: Mesh):
    """Jitted shard_map body cached by (table size, step count, mesh):
    rebuilding the closure per call would miss jit's trace cache and pay a
    full XLA compile on every serving request."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(("vol", "blk")), P(("vol", "blk"))),
        out_specs=(
            P(("vol", "blk")),
            P(("vol", "blk")),
            P(("vol", "blk")),
        ),
    )
    def body(khi_g, klo_g, off_g, size_g, phi_l, plo_l):
        # derive the carry init from the sharded input so the fori_loop
        # carry has matching varying axes under shard_map
        lo = (phi_l ^ phi_l).astype(jnp.int32)
        hi = lo + n
        return _search_range(
            steps, khi_g, klo_g, off_g, size_g, phi_l, plo_l, lo, hi
        )

    return jax.jit(body)


def sharded_bulk_lookup(
    keys: np.ndarray,
    offsets: np.ndarray,
    sizes: np.ndarray,
    probes: np.ndarray,
    mesh: Mesh,
):
    """(sorted keys u64[M], offsets u32[M], sizes u32[M], probes u64[P])
    -> (offset_units u32[P], sizes u32[P], found bool[P]).

    Probe batches that don't divide the mesh size are zero-padded and the
    extras stripped from the result.
    """
    n = len(keys)
    n_devices = mesh.devices.size
    probes = np.ascontiguousarray(probes, dtype=np.uint64)
    p = len(probes)
    pad = (-p) % n_devices
    if pad:
        # zero-pad so uneven probe batches shard; extras are stripped below
        probes = np.concatenate(
            [probes, np.zeros(pad, dtype=np.uint64)]
        )
    steps = max(1, int(np.ceil(np.log2(max(n, 1)))) + 1)

    khi, klo = _split_u64(np.ascontiguousarray(keys, dtype=np.uint64))
    phi, plo = _split_u64(probes)

    off, size, found = _compiled_body(n, steps, mesh)(
        jnp.asarray(khi),
        jnp.asarray(klo),
        jnp.asarray(offsets.astype(np.uint32)),
        jnp.asarray(sizes.astype(np.uint32)),
        jnp.asarray(phi),
        jnp.asarray(plo),
    )
    return np.asarray(off)[:p], np.asarray(size)[:p], np.asarray(found)[:p]
