"""GF(2^8) constant-matrix multiply over byte streams, TPU-native.

Math: the RS coding matrix is static at trace time, so multiplication by each
constant unrolls into xtime (multiply-by-2) chains shared across output rows:
for input row j we compute t_k = 2^k * data[j], and each output row
XOR-accumulates the t_k selected by the bits of its matrix entry.

Layout: Mosaic vectorizes i32, not i8, so bytes are packed 4-per-uint32 lane
and xtime runs byte-parallel inside each word with masks:

    doubled = (x << 1) & 0xFEFEFEFE       # per-byte shift, bit0 cleared
    r       = (x & 0x80808080) >> 7        # 0x01 per overflowing byte
    xtime   = doubled ^ r * 0x1D           # 1 * 0x1D < 256: no byte carries

6 i32 ops per 4 bytes — no gathers, no tables; pure VPU work that replaces
the reference's table-driven SIMD GF multiply (klauspost/reedsolomon,
ref: ec_encoder.go:198). All byte positions are independent so the uint32
packing order never matters.

Speed on the chip: the benchmark's one reading of the kernel is
`rs_encode_roofline`, 38.83 % of a v5e's 819 GB/s HBM bandwidth
(PERF_LEDGER.jsonl); whether the VPU or HBM bounds it is not measured.

Dispatch: a served call (gf_matmul_bytes) is ONE device program. The host
packs the rows to uint32[C, W] with W a whole number of the kernel's blocks
and hands the jitted kernel a view of them as uint32[C, W/128, 128], its own
input shape, which it uploads within the same call (a separate device_put
first was one more call into the runtime, and slower on the chip): a 3-D
array whose second axis is a multiple of 8 has a tiled layout that is
exactly row-major, so the upload is a straight copy, where a 2-D [10, W] one
has its rows padded to 16 and shuffled into 8-row tiles, and needs a copy
and a slice on the device to become the kernel's input. The [R, W/128, 128]
result comes back as it is and is viewed as R rows of bytes on the host: no
reshape, pad, copy or slice program round the kernel, each of which would
be a launch (and a hand-over of the interpreter lock) of its own.
"""

from __future__ import annotations

import functools

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
    RS_DISPATCHES_BESIDE,
)

# x^8 + x^4 + x^3 + x^2 + 1 (0x11D), matching the galois tables (galois.py).
# 0x1D is the reduction _xtime multiplies each overflowing byte's 0x01 by.

LANE = 128
_MSB = np.uint32(0x80808080)
_LOW7 = np.uint32(0xFEFEFEFE)
_POLY = np.uint32(0x1D)


def _xtime(x):
    """Byte-parallel multiply-by-2 in GF(2^8) on packed uint32 words."""
    return ((x << 1) & _LOW7) ^ (((x & _MSB) >> 7) * _POLY)


def gf_matmul_expr(matrix: np.ndarray, rows: list):
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
                t = _xtime(t)
    zero = jnp.zeros_like(rows[0])
    return [a if a is not None else zero for a in acc]


# --- pure-jnp path (CPU fallback + reference for the kernel) ---
@functools.partial(jax.jit, static_argnums=(0,))
def _gf_matmul_jnp_packed(matrix_key, packed):
    matrix = np.asarray(matrix_key, dtype=np.uint8)
    rows = [packed[j] for j in range(matrix.shape[1])]
    return jnp.stack(gf_matmul_expr(matrix, rows))


# --- pallas kernel ---
def _gf_kernel(matrix: np.ndarray, data_ref, out_ref):
    c_cnt = matrix.shape[1]
    rows = [data_ref[j] for j in range(c_cnt)]
    outs = gf_matmul_expr(matrix, rows)
    for i, o in enumerate(outs):
        out_ref[i] = o


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _gf_matmul_pallas(matrix_key, packed3d, block_rows: int, interpret: bool):
    """packed3d: uint32[C, S, LANE] with S % block_rows == 0 -> [R, S, LANE]."""
    matrix = np.asarray(matrix_key, dtype=np.uint8)
    r_cnt, c_cnt = matrix.shape
    _, s, lane = packed3d.shape
    return pl.pallas_call(
        functools.partial(_gf_kernel, matrix),
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
    """Host-side free unpacking: uint32[R, m] (or the kernel's
    [R, S, LANE]) -> uint8[R, n], a view of the words."""
    packed = np.ascontiguousarray(packed)
    return packed.reshape(packed.shape[0], -1).view(np.uint8)[:, :n]


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


# the codec calls in flight, each from its dispatch to the end of its
# fetch: token -> (op, the ops of the other calls it met). A call enters
# itself, then marks itself and every call it finds with each other's op,
# so of two calls in flight at once the later one marks both. Each step is
# one dict or set operation under the interpreter lock: no lock of its own
_IN_FLIGHT: dict = {}


@functools.lru_cache(maxsize=None)
def _beside_child(op: str, beside: str):
    return RS_DISPATCHES_BESIDE.child(op=op, beside=beside)


def _count_beside(op: str, met: set) -> None:
    """`rs_dispatches_beside_total`: another op first, else the call's own."""
    beside = next((o for o in RS_OPS if o != op and o in met), None)
    _beside_child(op, beside or (op if op in met else "none")).inc()


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


def compile_gf_matmul_bytes(
    matrix: np.ndarray,
    max_width: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    force_pallas: bool | None = None,
    interpret: bool = False,
) -> int:
    """Compile the Pallas kernel of `matrix` at every width a
    gf_matmul_bytes call of up to `max_width` bytes a row is padded to,
    each a whole number of blocks, by running it once on zeros: a call
    counted nowhere. The jnp path takes any width and compiles nothing
    ahead. -> the widths run."""
    use_pallas = force_pallas if force_pallas is not None else on_tpu()
    if not (use_pallas or interpret):
        return 0
    granule = row_granule(block_rows, force_pallas, interpret)
    key = tuple(map(tuple, np.asarray(matrix, dtype=np.uint8)))
    widths = range(granule, max_width + 1, granule)
    for w in widths:
        words = np.zeros((len(key[0]), w // (4 * LANE), LANE), np.uint32)
        np.asarray(_gf_matmul_pallas(key, words, block_rows, interpret))
    return len(widths)


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

    One device program a call: for the Pallas kernel the packed
    uint32[C, W] are viewed as its own input, uint32[C, W/128, 128], and
    that host view is handed to the jitted kernel, which uploads it within
    the one call (`rs.dispatch` is upload + launch; the module note says
    why that upload is a straight copy); its [R, W/128, 128] result is
    fetched and viewed as uint8[R, N] on the host. The jnp path uploads
    (`rs.put`) and returns [C, W] / [R, W] words, one program too.

    `width`: the caller's bytes a row where `data`'s rows may be wider,
    because the caller filled the rows of an array of its own. Where that
    array is a whole number of row_granule() wide it is uploaded as it is
    (packing copies nothing) and the columns past `width` may hold anything:
    the matmul is column by column, and they are cut off on return and
    counted as padding, as zero fill is. Rows of any other width are cut to
    `width` and padded like any.

    `op` (encode/decode/apply) labels the call's stages, dispatch and bytes
    on /metrics (RS_STAGE, count_rs_dispatch), and which other op's call
    it met between its dispatch and the end of its fetch (_IN_FLIGHT,
    `rs_dispatches_beside_total`).
    """
    matrix = np.asarray(matrix, dtype=np.uint8)
    assert data.shape[0] == matrix.shape[1], (data.shape, matrix.shape)
    n = data.shape[1] if width is None else width
    use_pallas = force_pallas if force_pallas is not None else on_tpu()
    granule = row_granule(block_rows, force_pallas, interpret)
    if width is not None and data.shape[1] % granule:
        data = data[:, :width]
    key = tuple(map(tuple, matrix))
    stages = RS_STAGE[op]
    with stages["pack"]():
        packed = pack_bytes_host(
            np.asarray(data).astype(np.uint8, copy=False), granule
        )
    if use_pallas or interpret:
        # a whole number of blocks a row (the granule): a free view, which
        # the jitted kernel uploads itself, inside the one call
        words = packed.reshape(packed.shape[0], -1, LANE)
    else:
        with stages["put"]():
            words = jnp.asarray(packed, dtype=jnp.uint32)
    token, met = object(), set()
    _IN_FLIGHT[token] = (op, met)
    try:
        for other, other_met in list(_IN_FLIGHT.values()):
            if other_met is not met:
                met.add(other)
                other_met.add(op)
        with stages["dispatch"]():
            if use_pallas or interpret:
                out = _gf_matmul_pallas(key, words, block_rows, interpret)
            else:
                out = _gf_matmul_jnp_packed(key, words)
        with stages["fetch"]():
            out = np.asarray(out)
    finally:
        del _IN_FLIGHT[token]
    _count_beside(op, met)
    count_rs_dispatch(
        op,
        "device" if use_pallas and on_tpu() else "device_emulated",
        matrix.shape[0] + matrix.shape[1], n, packed.shape[1] * 4,
    )
    return unpack_bytes_host(out, n)
