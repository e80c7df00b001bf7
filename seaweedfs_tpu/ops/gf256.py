"""GF(2^8) constant-matrix multiply over byte streams, TPU-native.

Math: the RS coding matrix is static at trace time, so multiplication by each
constant unrolls into xtime (multiply-by-2) chains shared across output rows:
for input row j we compute t_k = 2^k * data[j], and each output row
XOR-accumulates the t_k selected by the bits of its matrix entry.

Layout: Mosaic vectorizes i32, not i8, so bytes are packed 4-per-uint32 lane
and xtime runs byte-parallel inside each word with masks:

    msb     = x & 0x80808080
    doubled = (x << 1) & 0xFEFEFEFE       # per-byte shift, bit0 cleared
    r       = msb >> 7                     # 0x01 per overflowing byte
    xtime   = doubled ^ (r<<4 ^ r<<3 ^ r<<2 ^ r)   # r * 0x1D

~9 i32 ops per 4 bytes — no gathers, no tables; pure VPU work that replaces
the reference's table-driven SIMD GF multiply (klauspost/reedsolomon,
ref: ec_encoder.go:198). All byte positions are independent so the uint32
packing order never matters.

Measured 65 GB/s data throughput on one v5e chip — VPU-compute-bound at
~1.3e12 i32 ops/s. An MXU bit-slice formulation (GF(2) matmul of 80 bit
planes by a static 32x80 bit matrix via int8 dot_general) was prototyped and
is byte-correct but lands at the same ~63 GB/s: the bit unpack/repack is VPU
work of the same magnitude as the xtime chains, so the VPU remains the
bottleneck either way. Kept the packed formulation (simpler, no MXU).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..util import trace
from ..util.device import on_tpu
from ..util.metrics import (
    RS_DISPATCH_BYTES,
    RS_DISPATCH_SECONDS,
    RS_DISPATCHES,
)

# x^8 + x^4 + x^3 + x^2 + 1 (0x11D), matching the galois tables (galois.py).
# 0x1D = bits 4,3,2,0 — the shift set in _xtime.

LANE = 128
SUBLANE = 8  # i32 min tile sublane count
_MSB = np.uint32(0x80808080)
_LOW7 = np.uint32(0xFEFEFEFE)
_POLY = np.uint32(0x1D)

# xtime formulation: "mul" folds the 0x1D reduction into one byte-parallel
# i32 multiply (r is 0x00/0x01 per byte, and 1*0x1D = 29 < 256 so no byte
# crosses its lane) — 6 VPU ops vs the 11-op shift/xor chain. The kernel is
# VPU-op-bound, so fewer ops per word is directly throughput (measured in
# bench kernel_roofline; override with SEAWEED_GF_XTIME=shift to compare).
_XTIME_MODE = os.environ.get("SEAWEED_GF_XTIME", "mul")


def _xtime(x, mode: str | None = None):
    """Byte-parallel multiply-by-2 in GF(2^8) on packed uint32 words."""
    if (mode or _XTIME_MODE) == "mul":
        return ((x << 1) & _LOW7) ^ (((x & _MSB) >> 7) * _POLY)
    msb = x & _MSB
    doubled = (x << 1) & _LOW7
    r = msb >> 7
    return doubled ^ (r << 4) ^ (r << 3) ^ (r << 2) ^ r


def gf_matmul_expr(matrix: np.ndarray, rows: list, xtime_mode: str | None = None):
    """out[i] = XOR_j matrix[i,j] * rows[j] in GF(2^8), on packed uint32.

    matrix is a static numpy uint8 [R, C]; rows is a list of C equal-shaped
    packed-uint32 arrays (jnp values or pallas loads). Returns R arrays.
    Work is shared: one xtime chain per input row, reused by every output.
    """
    r_cnt, c_cnt = matrix.shape
    assert len(rows) == c_cnt
    acc: list = [None] * r_cnt
    for j in range(c_cnt):
        col = [int(matrix[i, j]) for i in range(r_cnt)]
        max_bits = max((c.bit_length() for c in col), default=0)
        if max_bits == 0:
            continue
        t = rows[j]
        for k in range(max_bits):
            for i in range(r_cnt):
                if (col[i] >> k) & 1:
                    acc[i] = t if acc[i] is None else acc[i] ^ t
            if k + 1 < max_bits:
                t = _xtime(t, xtime_mode)
    zero = jnp.zeros_like(rows[0])
    return [a if a is not None else zero for a in acc]


def count_expr_ops(matrix: np.ndarray, xtime_mode: str | None = None) -> int:
    """Static i32-op count of gf_matmul_expr per packed input WORD COLUMN
    (i.e. per 4 bytes of every input row together) — the numerator of the
    VPU roofline in bench kernel_roofline."""
    mode = xtime_mode or _XTIME_MODE
    per_xtime = 6 if mode == "mul" else 11
    matrix = np.asarray(matrix, dtype=np.uint8)
    r_cnt, c_cnt = matrix.shape
    ops = 0
    # acc in gf_matmul_expr is shared across COLUMNS: only each row's very
    # first contribution overall is free, not its first per column
    first = [True] * r_cnt
    for j in range(c_cnt):
        col = [int(matrix[i, j]) for i in range(r_cnt)]
        max_bits = max((c.bit_length() for c in col), default=0)
        if max_bits == 0:
            continue
        ops += (max_bits - 1) * per_xtime  # the shared chain
        for k in range(max_bits):
            for i in range(r_cnt):
                if (col[i] >> k) & 1:
                    if not first[i]:
                        ops += 1  # XOR-accumulate
                    first[i] = False
    return ops


# --- pure-jnp path (CPU fallback + reference for the kernel) ---
@functools.partial(jax.jit, static_argnums=(0, 2))
def _gf_matmul_jnp_packed(matrix_key, packed, xtime_mode: str | None = None):
    matrix = np.asarray(matrix_key, dtype=np.uint8)
    rows = [packed[j] for j in range(matrix.shape[1])]
    return jnp.stack(gf_matmul_expr(matrix, rows, xtime_mode))


# --- pallas kernel ---
def _gf_kernel(matrix: np.ndarray, xtime_mode, data_ref, out_ref):
    c_cnt = matrix.shape[1]
    rows = [data_ref[j] for j in range(c_cnt)]
    outs = gf_matmul_expr(matrix, rows, xtime_mode)
    for i, o in enumerate(outs):
        out_ref[i] = o


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _gf_matmul_pallas(
    matrix_key,
    packed3d,
    block_rows: int,
    interpret: bool,
    xtime_mode: str | None = None,
):
    """packed3d: uint32[C, S, LANE] with S % block_rows == 0 -> [R, S, LANE]."""
    matrix = np.asarray(matrix_key, dtype=np.uint8)
    r_cnt, c_cnt = matrix.shape
    _, s, lane = packed3d.shape
    return pl.pallas_call(
        functools.partial(_gf_kernel, matrix, xtime_mode),
        out_shape=jax.ShapeDtypeStruct((r_cnt, s, lane), jnp.uint32),
        grid=(s // block_rows,),
        in_specs=[
            pl.BlockSpec(
                (c_cnt, block_rows, lane),
                lambda b: (0, b, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (r_cnt, block_rows, lane),
            lambda b: (0, b, 0),
            memory_space=pltpu.VMEM,
        ),
        interpret=interpret,
    )(packed3d)


DEFAULT_BLOCK_ROWS = 512  # 512 x 128 lanes x 4B = 256KB per shard slice


def pack_bytes_host(data: np.ndarray, granule: int = 4) -> np.ndarray:
    """Host-side free packing: numpy uint8[C, n] -> uint32[C, padded_n/4].

    The only packing there is: on a TPU the same uint8->uint32 bitcast ON
    the device is a relayout between tilings (the v5e compiler wants 10.7 GB
    of temp for a 16 MB-per-row batch), so bytes are always packed here."""
    c, n = data.shape
    padded_n = ((n + granule - 1) // granule) * granule
    if padded_n != n:
        padded = np.zeros((c, padded_n), dtype=np.uint8)
        padded[:, :n] = data
        data = padded
    return np.ascontiguousarray(data).view(np.uint32)


def unpack_bytes_host(packed: np.ndarray, n: int) -> np.ndarray:
    """Host-side free unpacking: uint32[R, m] -> uint8[R, n]."""
    return np.ascontiguousarray(packed).view(np.uint8)[:, :n]


def gf_matmul_packed(
    matrix: np.ndarray,
    packed,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    force_pallas: bool | None = None,
    interpret: bool = False,
    xtime_mode: str | None = None,
):
    """GF(2^8) matmul on packed words: uint32[C, W] -> uint32[R, W].

    The native device API — keeps data uint32 end-to-end (the kernel is
    HBM-bound at this layout; measured ~450 GB/s data throughput on v5e).
    W must be a multiple of (block_rows * LANE) for the Pallas path; the
    jnp path takes any W.
    """
    matrix = np.asarray(matrix, dtype=np.uint8)
    packed = jnp.asarray(packed, dtype=jnp.uint32)
    assert packed.shape[0] == matrix.shape[1], (packed.shape, matrix.shape)
    use_pallas = force_pallas if force_pallas is not None else on_tpu()
    return _matmul_packed_on_device(
        tuple(map(tuple, matrix)), packed, block_rows, use_pallas,
        interpret, xtime_mode,
    )


def _matmul_packed_on_device(
    key, packed, block_rows: int, use_pallas: bool, interpret: bool,
    xtime_mode: str | None = None,
):
    """The dispatches of one call, as issued from the host, on words that
    are already on the device: pad / reshape / kernel / reshape / slice."""
    w = packed.shape[1]
    if not use_pallas and not interpret:
        return _gf_matmul_jnp_packed(key, packed, xtime_mode)
    granule = block_rows * LANE
    if w % granule:
        pad = granule - w % granule
        packed = jnp.pad(packed, ((0, 0), (0, pad)))
    packed3d = packed.reshape(packed.shape[0], -1, LANE)
    out = _gf_matmul_pallas(key, packed3d, block_rows, interpret, xtime_mode)
    out = out.reshape(out.shape[0], -1)
    return out if out.shape[1] == w else out[:, :w]


RS_OPS = ("encode", "decode", "apply")
RS_STAGES = ("stack", "pack", "put", "dispatch", "fetch")
# the stages of a TpuRSCodec call, bound once: RS_STAGE[op][stage]. Leaves
# of work, each an `rs.<stage>` event in a profiler trace; `rs.fetch`, the
# blocking fetch of the device's answer, is the host's side of upload +
# kernel + download and encloses the runtime's own `np.asarray(jax.Array)`
RS_STAGE = {
    op: {
        st: trace.stage(
            "rs." + st, RS_DISPATCH_SECONDS.child(op=op, stage=st), label=st
        )
        for st in RS_STAGES
    }
    for op in RS_OPS
}


@functools.lru_cache(maxsize=None)
def _rs_count_children(op: str, backend: str) -> tuple:
    return (
        RS_DISPATCHES.child(op=op, backend=backend),
        RS_DISPATCH_BYTES.child(op=op, backend=backend, kind="real"),
        RS_DISPATCH_BYTES.child(op=op, backend=backend, kind="padded"),
    )


def count_rs_dispatch(
    op: str, backend: str, rows: int, width: int, padded_width: int
) -> None:
    """One codec call under `backend` (TpuRSCodec.pipeline_dispatch_kind's
    vocabulary): `rows` = rows in + rows out, `width` the caller's bytes a
    row, `padded_width` what the kernel's granule made of it."""
    calls, real, padded = _rs_count_children(op, backend)
    calls.inc()
    real.inc(rows * width)
    padded.inc(rows * (padded_width - width))


def row_granule(
    block_rows: int = DEFAULT_BLOCK_ROWS,
    force_pallas: bool | None = None,
    interpret: bool = False,
) -> int:
    """Bytes gf_matmul_bytes pads a row to before the upload: the Pallas
    kernel's block (256 KiB), or one packed word off the chip."""
    use_pallas = force_pallas if force_pallas is not None else on_tpu()
    return block_rows * LANE * 4 if use_pallas or interpret else 4


def gf_matmul_bytes(
    matrix: np.ndarray,
    data,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    force_pallas: bool | None = None,
    interpret: bool = False,
    op: str = "apply",
    width: int | None = None,
):
    """GF(2^8) matmul over flat byte rows: uint8[C, N] -> uint8[R, N].

    Zero padding is exact (zero bytes yield zero parity columns, truncated on
    return). Bytes are packed and unpacked on the host with free views
    (pack_bytes_host); a device-resident input is pulled to the host first.
    For the Pallas kernel the pad to its block granule happens here too, so
    ragged widths (degraded-read spans, stream tails) all land on a few
    compiled shapes instead of each compiling its own device-side pad and
    slice.

    `width`: the caller's bytes a row where `data`'s rows may be wider,
    because the caller filled the rows of an array of its own. Where that
    array is a whole number of row_granule() wide it is uploaded as it is
    (packing copies nothing) and the columns past `width` may hold anything:
    the matmul is column by column, and they are cut off on return and
    counted as padding, as zero fill is. Rows of any other width are cut to
    `width` and padded like any.

    `op` (encode/decode/apply) labels the call's stages, dispatch and bytes
    on /metrics (RS_STAGE, count_rs_dispatch).
    """
    matrix = np.asarray(matrix, dtype=np.uint8)
    assert data.shape[0] == matrix.shape[1], (data.shape, matrix.shape)
    n = data.shape[1] if width is None else width
    use_pallas = force_pallas if force_pallas is not None else on_tpu()
    granule = row_granule(block_rows, force_pallas, interpret)
    if width is not None and data.shape[1] % granule:
        data = data[:, :width]
    stages = RS_STAGE[op]
    with stages["pack"]():
        packed = pack_bytes_host(
            np.asarray(data).astype(np.uint8, copy=False), granule
        )
    with stages["put"]():
        on_device = jnp.asarray(packed, dtype=jnp.uint32)
    with stages["dispatch"]():
        out = _matmul_packed_on_device(
            tuple(map(tuple, matrix)), on_device, block_rows, use_pallas,
            interpret,
        )
    with stages["fetch"]():
        out = np.asarray(out)
    out = unpack_bytes_host(out, n)
    count_rs_dispatch(
        op,
        "device" if use_pallas and on_tpu() else "device_emulated",
        matrix.shape[0] + matrix.shape[1], n, packed.shape[1] * 4,
    )
    return out


# --- MXU bit-slice prototype (VERDICT r4 item 5) ---
#
# GF(2^8) multiplication by a CONSTANT is GF(2)-linear on the 8 bits of
# the input byte, so the whole RS(10,4) encode is one binary matmul:
# out_bits[N, R*8] = in_bits[N, C*8] @ B[C*8, R*8]  (mod 2), which is MXU
# food (int8 dot + parity) instead of VPU shift/xor chains. The unpack/
# repack to bit-planes is the tax: 8x the data volume through HBM unless
# fused into the matmul kernel. This prototype keeps the jnp formulation
# (XLA decides the fusion) and exists to MEASURE that trade against the
# packed VPU kernel — bench leg `kernel_mxu_bitslice` — not to ship it.
# An earlier out-of-tree version measured ~63 GB/s on v5e, on par with the
# VPU formulation; in-tree now so the number is reproducible.


@functools.lru_cache(maxsize=None)
def _bitslice_matrix(matrix_key) -> np.ndarray:
    """B[C*8, R*8] over GF(2): column block r, bit b gets the b-th bit of
    matrix[r, c] * 2^k for input bit k of input byte c."""
    from ..storage.erasure_coding.galois import MUL_TABLE

    matrix = np.asarray(matrix_key, dtype=np.uint8)
    r_cnt, c_cnt = matrix.shape
    B = np.zeros((c_cnt * 8, r_cnt * 8), dtype=np.int8)
    for c in range(c_cnt):
        for k in range(8):
            for r in range(r_cnt):
                prod = int(MUL_TABLE[matrix[r, c], 1 << k])
                for b in range(8):
                    B[c * 8 + k, r * 8 + b] = (prod >> b) & 1
    return B


@functools.partial(jax.jit, static_argnums=(0,))
def _gf_matmul_bitsliced_jit(matrix_key, packed):
    matrix = np.asarray(matrix_key, dtype=np.uint8)
    r_cnt, c_cnt = matrix.shape
    B = jnp.asarray(_bitslice_matrix(matrix_key))
    w = packed.shape[1]
    # packed uint32[C, W] -> bytes uint8[C, W*4] -> bits int8[N, C*8]
    data = jax.lax.bitcast_convert_type(
        packed.reshape(c_cnt, w, 1), jnp.uint8
    ).reshape(c_cnt, w * 4)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (
        (data.T[:, :, None] >> shifts[None, None, :]) & 1
    ).astype(jnp.int8).reshape(w * 4, c_cnt * 8)
    # MXU: int8 x int8 -> int32 accumulation, then parity
    out_bits = (
        jax.lax.dot_general(
            bits, B, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        & 1
    ).astype(jnp.uint8).reshape(w * 4, r_cnt, 8)
    # repack: bits -> bytes -> uint32 words
    weights = (jnp.uint8(1) << shifts)[None, None, :]
    out_bytes = (out_bits * weights).sum(axis=2, dtype=jnp.uint8)
    return jax.lax.bitcast_convert_type(
        out_bytes.T.reshape(r_cnt, w, 4), jnp.uint32
    ).reshape(r_cnt, w)


def gf_matmul_bitsliced(matrix: np.ndarray, packed):
    """MXU bit-slice route: uint32[C, W] -> uint32[R, W], byte-identical
    to gf_matmul_packed. Prototype — see module note above."""
    matrix = np.asarray(matrix, dtype=np.uint8)
    key = tuple(map(tuple, matrix))
    packed = jnp.asarray(packed, dtype=jnp.uint32)
    assert packed.shape[0] == matrix.shape[1], (packed.shape, matrix.shape)
    return _gf_matmul_bitsliced_jit(key, packed)
