"""Native (C++/SIMD) GF(2^8) kernel, compiled on demand and loaded via
ctypes. Provides the host-side fast path the reference gets from
klauspost/reedsolomon's assembly; falls back to None when no toolchain is
available (callers then use the numpy tables)."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "gf256.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_tier = "numpy"  # flag set of the loaded library; "numpy" = none loaded


def _cpu_flags() -> set:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return set(line.split(":", 1)[1].split())
    except OSError:
        pass
    return set()


def _flag_candidates() -> list:
    """Compiler-flag candidates for the SIMD this CPU actually has (the flag
    alone isn't enough — g++ accepts -mavx2 on any x86, then SIGILLs at
    runtime), best first."""
    have = _cpu_flags()
    candidates = []
    if {"gfni", "avx512f", "avx512bw"} <= have:
        candidates.append(["-mgfni", "-mavx512f", "-mavx512bw", "-mavx2"])
    if "avx2" in have:
        candidates.append(["-mavx2"])
    if "ssse3" in have or not have:
        # no /proc/cpuinfo (macOS, masked /proc): SSSE3 is universal on
        # x86-64, so keep attempting it rather than silently going scalar
        candidates.append(["-mssse3"])
    candidates.append([])  # scalar fallback (also the non-x86 path)
    return candidates


def _lib_path(flags: list) -> str:
    """The built file is named after the flag set it was built with, so a
    host only ever loads a library built for SIMD it has: a checkout that
    travels with its .so files (they are git-ignored, but a disk copy
    carries them) makes the next host build its own from gf256.cpp instead
    of loading another's GFNI/AVX-512 code."""
    return os.path.join(_HERE, f"libgf256_{_tier_name(flags)}.so")


def _tier_name(flags: list) -> str:
    return "-".join(f[2:] for f in flags) or "scalar"  # "-mavx2" -> "avx2"


def _build_and_open():
    """(CDLL, tier name) of the best flag set this CPU has that builds and
    loads, building from gf256.cpp when the named file is missing or older
    than the source; (None, "numpy") when no candidate does."""
    for flags in _flag_candidates():
        lib = _lib_path(flags)
        if not os.path.exists(lib) or os.path.getmtime(lib) < os.path.getmtime(
            _SRC
        ):
            # build beside the target and rename: a concurrent process
            # never dlopens a half-written file
            tmp = f"{lib}.{os.getpid()}.tmp"
            cmd = ["g++", "-O3", "-shared", "-fPIC", *flags, _SRC, "-o", tmp]
            try:
                subprocess.run(
                    cmd, check=True, capture_output=True, timeout=120
                )
                os.replace(tmp, lib)
            except (subprocess.SubprocessError, OSError):
                continue
        try:
            return ctypes.CDLL(lib, use_errno=True), _tier_name(flags)
        except OSError:
            continue
    return None, "numpy"


def load() -> Optional[ctypes.CDLL]:
    """The compiled library, building it first if necessary."""
    global _lib, _load_failed, _tier
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        lib, tier = _build_and_open()
        if lib is None:
            _load_failed = True
            return None
        lib.gf_matmul.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),  # matrix
            ctypes.c_int,  # rows
            ctypes.c_int,  # cols
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),  # data rows
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),  # out rows
            ctypes.c_size_t,  # n
        ]
        lib.gf_matmul.restype = None
        try:
            lib.gf_encode_copy.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),  # matrix
                ctypes.c_int,  # parity rows
                ctypes.c_int,  # data cols
                ctypes.POINTER(ctypes.c_void_p),  # src rows (NULL = zeros)
                ctypes.POINTER(ctypes.c_void_p),  # data dst (NULL = skip)
                ctypes.POINTER(ctypes.c_void_p),  # parity dst
                ctypes.c_size_t,  # n
                ctypes.c_int,  # nt stores
            ]
            lib.gf_encode_copy.restype = ctypes.c_int
        except AttributeError:  # stale .so without the symbol
            pass
        try:
            lib.write_all.argtypes = [
                ctypes.c_int,  # n
                ctypes.POINTER(ctypes.c_int),  # fds
                ctypes.POINTER(ctypes.c_void_p),  # buffers
                ctypes.POINTER(ctypes.c_size_t),  # lengths
            ]
            lib.write_all.restype = ctypes.c_long
        except AttributeError:
            pass
        try:
            lib.fill_rows.argtypes = [
                ctypes.c_int,  # n
                ctypes.POINTER(ctypes.c_int),  # fds (-1 = copy srcs[i])
                ctypes.POINTER(ctypes.c_int64),  # offset of each read
                ctypes.POINTER(ctypes.c_void_p),  # srcs
                ctypes.POINTER(ctypes.c_void_p),  # rows
                ctypes.POINTER(ctypes.c_size_t),  # lengths
                ctypes.POINTER(ctypes.c_int64),  # got
            ]
            lib.fill_rows.restype = None
        except AttributeError:
            pass
        _lib, _tier = lib, tier
        return _lib


def available() -> bool:
    return load() is not None


def tier() -> str:
    """Which host codec tier is live: the flag set of the loaded library
    (e.g. "gfni-avx512f-avx512bw-avx2", "avx2", "scalar"), or "numpy" when
    no compiler was found and the table codec serves."""
    load()
    return _tier


def encode_copy_available() -> bool:
    """True when the fused single-pass encode+copy (GFNI tier) is usable."""
    lib = load()
    if lib is None or not hasattr(lib, "gf_encode_copy"):
        return False
    # probe: the C entry returns 0 when built without GFNI
    z = np.zeros(64, np.uint8)
    out = np.empty(64, np.uint8)
    m = np.ones((1, 1), np.uint8)
    return bool(
        gf_encode_copy_native(m, [z.ctypes.data], [None], [out.ctypes.data], 64)
    )


def gf_encode_copy_native(
    matrix: np.ndarray,
    src_addrs,
    dst_addrs,
    parity_addrs,
    n: int,
    nt: bool = True,
) -> bool:
    """Fused one-pass encode+copy over raw buffer addresses.

    src_addrs: data-row addresses (None = implicit zero row — no copy, no
    parity contribution); dst_addrs: where each data row is copied (None =
    skip the copy); parity_addrs: where each parity row lands. With nt and
    64B-aligned destinations, all stores are non-temporal (no RFO traffic).
    Returns False when the library lacks the GFNI fused path.
    """
    lib = load()
    if lib is None or not hasattr(lib, "gf_encode_copy"):
        return False
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    prows, cols = matrix.shape
    assert len(src_addrs) == cols and len(dst_addrs) == cols
    assert len(parity_addrs) == prows
    src = (ctypes.c_void_p * cols)(*(a or None for a in src_addrs))
    dst = (ctypes.c_void_p * cols)(*(a or None for a in dst_addrs))
    pdst = (ctypes.c_void_p * prows)(*(a or None for a in parity_addrs))
    u8p = ctypes.POINTER(ctypes.c_uint8)
    rc = lib.gf_encode_copy(
        matrix.ctypes.data_as(u8p), prows, cols, src, dst, pdst,
        ctypes.c_size_t(n), 1 if nt else 0,
    )
    return bool(rc)


def gf_matmul_native(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """uint8[R,C] x uint8[C,N] -> uint8[R,N] via the native kernel."""
    data = np.ascontiguousarray(data, dtype=np.uint8)
    return gf_matmul_rows_native(matrix, list(data))


def gf_matmul_rows_native(
    matrix: np.ndarray, rows_in, out: Optional[np.ndarray] = None
) -> np.ndarray:
    """Same matmul, but over C separately-allocated contiguous 1-D rows of
    equal length (the kernel takes per-row pointers, so rows may be views
    into an mmapped file — no gather copy). `out`, when given, receives the
    result in place (hot loops recycle their output buffers instead of
    faulting fresh pages every call)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native gf256 library unavailable")
    return _matmul_rows(lib, matrix, rows_in, out=out)


def _matmul_rows(
    lib, matrix: np.ndarray, rows_in, out: Optional[np.ndarray] = None
) -> np.ndarray:
    """Shared ctypes marshalling for gf_matmul against any loaded tier."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    rows, cols = matrix.shape
    assert len(rows_in) == cols
    rows_in = [np.ascontiguousarray(r, dtype=np.uint8) for r in rows_in]
    n = rows_in[0].shape[0]
    assert all(r.shape == (n,) for r in rows_in)
    if out is None:
        out = np.empty((rows, n), dtype=np.uint8)
    else:
        assert out.shape == (rows, n) and out.dtype == np.uint8
        assert out.flags["C_CONTIGUOUS"] or all(
            row.flags["C_CONTIGUOUS"] for row in out
        )

    u8p = ctypes.POINTER(ctypes.c_uint8)
    data_ptrs = (u8p * cols)(*(r.ctypes.data_as(u8p) for r in rows_in))
    out_ptrs = (u8p * rows)(*(row.ctypes.data_as(u8p) for row in out))
    lib.gf_matmul(
        matrix.ctypes.data_as(u8p), rows, cols, data_ptrs, out_ptrs, n
    )
    return out


def write_all(files, rows) -> None:
    """Append each of `rows` (1-D uint8 arrays, C-contiguous) whole to
    the file object beside it, in one native call that gives the
    interpreter lock up once for all of them; without the library, one
    `write` a row. The files are written through their descriptors, so
    their Python buffers must hold nothing: every write to them comes
    here."""
    lib = load()
    if lib is None or not hasattr(lib, "write_all"):
        for f, row in zip(files, rows):
            f.write(row.data)
        return
    n = len(rows)
    rc = lib.write_all(
        n,
        (ctypes.c_int * n)(*(f.fileno() for f in files)),
        (ctypes.c_void_p * n)(*(row.ctypes.data for row in rows)),
        (ctypes.c_size_t * n)(*(row.nbytes for row in rows)),
    )
    if rc < 0:
        err = ctypes.get_errno()
        raise OSError(err, os.strerror(err), files[-rc - 1].name)


def fill_rows(rows, sources, offset) -> Optional[list]:
    """Fill each of `rows` (writable 1-D uint8 arrays, C-contiguous) from
    the source beside it, in one native call that gives the interpreter
    lock up once for all of them: an int is a file descriptor, read from
    its offset on until the row is full or the file ends; anything else
    is a buffer of at least the row's length, whose first bytes are
    copied. `offset` is every read's, or a sequence of one a row (a row
    that copies ignores its own). -> one int a row, the bytes read or
    copied, or -errno where the read failed (every row is tried); None
    without the library, where the caller reads row by row."""
    lib = load()
    if lib is None or not hasattr(lib, "fill_rows"):
        return None
    n = len(rows)
    if np.ndim(offset) == 0:
        offs = (ctypes.c_int64 * n)(*([int(offset)] * n))
    else:
        if len(offset) != n:
            raise ValueError(f"{len(offset)} offsets for {n} rows")
        offs = (ctypes.c_int64 * n)(*offset)
    fds = (ctypes.c_int * n)()
    srcs = (ctypes.c_void_p * n)()
    kept = []  # the copies' arrays, alive until the call returns
    for i, (row, src) in enumerate(zip(rows, sources)):
        if row.dtype != np.uint8 or not (row.flags.c_contiguous and row.flags.writeable):
            raise ValueError(f"row {i}: not a writable C-contiguous uint8 array")
        if isinstance(src, int):
            fds[i] = src
        else:
            buf = np.frombuffer(src, dtype=np.uint8)
            if buf.nbytes < row.nbytes:
                raise ValueError(f"row {i}: {buf.nbytes} bytes to copy into {row.nbytes}")
            kept.append(buf)
            fds[i], srcs[i] = -1, buf.ctypes.data
    got = (ctypes.c_int64 * n)()
    lib.fill_rows(
        n, fds, offs, srcs,
        (ctypes.c_void_p * n)(*(row.ctypes.data for row in rows)),
        (ctypes.c_size_t * n)(*(row.nbytes for row in rows)),
        got,
    )
    return list(got)
