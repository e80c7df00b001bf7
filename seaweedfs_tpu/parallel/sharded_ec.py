"""Sharded erasure-coding over a device mesh.

Mesh axes:
- "vol": data parallel over volumes/stripes (the batch dimension — encoding
  1000 volumes at once is the north-star workload, BASELINE.json);
- "blk": sequence parallel over the byte stream inside each stripe (the
  long-context analogue — a 30GB volume's stripe does not fit one chip's HBM).

Encode/reconstruct are byte-local, so both axes shard without communication;
cross-device collectives appear in verification (psum of mismatch counts)
and in the degraded-read path (all_gather of survivor rows when shards are
sharded by shard-id, mirroring the reference's parallel remote-shard gather,
ref: weed/storage/store_ec.go:319-373).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.gf256 import gf_matmul_expr


def make_mesh(
    n_devices: int | None = None,
    vol_axis: int | None = None,
    devices=None,
) -> Mesh:
    """2-D (vol, blk) mesh over the available devices.

    `devices` overrides the default-backend device list — pass
    jax.devices("cpu") to build a virtual host mesh regardless of which
    accelerator backend is primary."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if vol_axis is None:
        # most-square factorization, vol >= blk
        vol_axis = 1
        for f in range(int(np.sqrt(n)), 0, -1):
            if n % f == 0:
                vol_axis = n // f
                break
    blk_axis = n // vol_axis
    mesh_devices = np.asarray(devices).reshape(vol_axis, blk_axis)
    return Mesh(mesh_devices, axis_names=("vol", "blk"))


def _encode_packed(matrix: np.ndarray, packed):
    """packed uint32[C, W] -> parity uint32[R, W]; pure function of one shard."""
    rows = [packed[j] for j in range(matrix.shape[1])]
    return jnp.stack(gf_matmul_expr(matrix, rows))


# per device id: [bytes held, of which zero padding of the vol axis], summed
# over the inputs and outputs of every sharded call since the last clear():
# a diagnostic that shows "everything on the first device" or "half the mesh
# encodes zeros" from outside, not part of the encode contract
DEVICE_BYTES: dict = {}


def _note_placement(arr, real_volumes: int) -> None:
    for sh in arr.addressable_shards:
        vols = sh.index[0].indices(arr.shape[0])
        n = max(1, vols[1] - vols[0])
        pad = n - max(0, min(vols[1], real_volumes) - vols[0])
        held = DEVICE_BYTES.setdefault(sh.device.id, [0, 0])
        held[0] += sh.data.nbytes
        held[1] += sh.data.nbytes * pad // n


def _put_words(data, mesh: Mesh):
    """Host uint8[V, C, N] -> device uint32[V', C, N/4] sharded (vol, -, blk),
    V' = V zero-padded to the mesh's vol axis (GF(2^8) is linear: zero
    stripes encode/verify to zero and are stripped from the result).

    The uint8->uint32 packing is a free numpy VIEW on the host, exactly as
    the one-chip served path packs (gf256.pack_bytes_host): on a TPU the
    same bitcast on the device is a relayout between tilings that the
    compiler pads 12.8x (the v5e compiler refused the 16 MB-per-row batch
    outright: 22 GB of HBM wanted). Returns (sharded words, V)."""
    data = np.ascontiguousarray(data, dtype=np.uint8)
    v, _c, n = data.shape
    blk = mesh.shape["blk"]
    assert n % (4 * blk) == 0, f"N={n} not divisible by {4 * blk}"
    pad = (-v) % mesh.shape["vol"]
    if pad:
        data = np.concatenate(
            [data, np.zeros((pad,) + data.shape[1:], dtype=np.uint8)]
        )
    words = jax.device_put(
        data.view(np.uint32), NamedSharding(mesh, P("vol", None, "blk"))
    )
    _note_placement(words, v)
    return words, v


@functools.lru_cache(maxsize=64)
def _apply_body(matrix_key, mesh: Mesh):
    """Jitted shard_map applying one static GF matrix to every local
    stripe; cached per (matrix, mesh) so steady traffic reuses the trace
    (and its compiled program) instead of rebuilding the closure."""
    matrix = np.asarray(matrix_key, dtype=np.uint8)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=P("vol", None, "blk"),
        out_specs=P("vol", None, "blk"),
    )
    def body(local):  # uint32[v_loc, C, w_loc]
        return jax.vmap(lambda p: _encode_packed(matrix, p))(local)

    return jax.jit(body)


@functools.lru_cache(maxsize=64)
def _verify_body(matrix_key, mesh: Mesh):
    matrix = np.asarray(matrix_key, dtype=np.uint8)
    k = matrix.shape[1]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=P("vol", None, "blk"),
        out_specs=P(),
    )
    def body(local):  # uint32[v_loc, C+R, w_loc]
        parity = jax.vmap(lambda p: _encode_packed(matrix, p[:k]))(local)
        mism = jnp.sum((parity != local[:, k:]).astype(jnp.int32))
        mism = jax.lax.psum(mism, axis_name="vol")
        return jax.lax.psum(mism, axis_name="blk")

    return jax.jit(body)


def _matrix_key(matrix) -> tuple:
    return tuple(map(tuple, np.asarray(matrix, dtype=np.uint8)))


def _apply(matrix, data, mesh: Mesh) -> np.ndarray:
    words, v = _put_words(data, mesh)
    out = _apply_body(_matrix_key(matrix), mesh)(words)
    _note_placement(out, v)
    return np.asarray(out).view(np.uint8)[:v]


def sharded_encode(matrix: np.ndarray, data, mesh: Mesh) -> np.ndarray:
    """data uint8[V, C, N] -> parity uint8[V, R, N], sharded (vol, -, blk).

    N must be divisible by 4 * mesh.shape['blk'] (uint32 packing per device).
    """
    return _apply(matrix, data, mesh)


def sharded_verify(matrix: np.ndarray, shards, mesh: Mesh) -> int:
    """shards uint8[V, C+R, N] -> global count of mismatching packed words
    (psum over the mesh)."""
    words, _v = _put_words(shards, mesh)
    return int(_verify_body(_matrix_key(matrix), mesh)(words))


def sharded_reconstruct_step(
    dec_rows: np.ndarray, survivors, mesh: Mesh
) -> np.ndarray:
    """Degraded-read analogue: survivor rows sharded across the mesh's "blk"
    axis are locally matmul'd by the (static) decode rows; the "vol" axis
    batches volumes. survivors: uint8[V, k, N] -> uint8[V, len(dec_rows), N].
    """
    return _apply(dec_rows, survivors, mesh)


def sharded_reconstruct_padded(
    dec_rows: np.ndarray, survivors: np.ndarray, mesh: Mesh
) -> np.ndarray:
    """sharded_reconstruct_step for arbitrary byte widths: pads the column
    axis up to the mesh's packing unit (4 bytes x blk devices — zero columns
    decode to zero under GF linearity) and slices the pad back off. The
    multi-chip leg rebuild_ec_files_multi dispatches survivor batches
    through."""
    survivors = np.ascontiguousarray(survivors, dtype=np.uint8)
    v, k, n = survivors.shape
    unit = 4 * mesh.shape["blk"]
    pad = (-n) % unit
    if pad:
        survivors = np.concatenate(
            [survivors, np.zeros((v, k, pad), dtype=np.uint8)], axis=2
        )
    out = sharded_reconstruct_step(dec_rows, survivors, mesh)
    return out[:, :, :n] if pad else out
