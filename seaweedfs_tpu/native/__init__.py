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


def _flag_candidates(max_tier: str = "best") -> list:
    """Compiler-flag candidates for the SIMD this CPU actually has (the flag
    alone isn't enough — g++ accepts -mavx2 on any x86, then SIGILLs at
    runtime). max_tier="avx2" caps at the PSHUFB tier — the technique of the
    reference's vendored klauspost/reedsolomon v1.9.2 (pre-GFNI), used for
    honest baseline measurement."""
    have = _cpu_flags()
    candidates = []
    if max_tier == "best" and {"gfni", "avx512f", "avx512bw"} <= have:
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


def _build_and_open(max_tier: str = "best"):
    """(CDLL, tier name) of the best flag set this CPU has that builds and
    loads, building from gf256.cpp when the named file is missing or older
    than the source; (None, "numpy") when no candidate does."""
    for flags in _flag_candidates(max_tier):
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
            return ctypes.CDLL(lib), _tier_name(flags)
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


_base_lib = None
_base_failed = False


def load_baseline():
    """The PSHUFB-tier (AVX2-capped) build of the same kernel source — the
    technique of the reference's vendored klauspost/reedsolomon v1.9.2,
    which predates GFNI support. Bench CPU baselines measure against this
    so the GFNI tier registers as the technique win it is."""
    global _base_lib, _base_failed
    with _lock:
        if _base_lib is not None or _base_failed:
            return _base_lib
        lib, _ = _build_and_open(max_tier="avx2")
        if lib is None:
            _base_failed = True
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.gf_matmul.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(u8p), ctypes.POINTER(u8p), ctypes.c_size_t,
        ]
        lib.gf_matmul.restype = None
        _base_lib = lib
        return _base_lib


def gf_matmul_baseline(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """uint8[R,C] x uint8[C,N] -> uint8[R,N] via the PSHUFB-tier library."""
    lib = load_baseline()
    if lib is None:
        raise RuntimeError("baseline gf256 library unavailable")
    data = np.ascontiguousarray(data, dtype=np.uint8)
    return _matmul_rows(lib, matrix, list(data))


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
