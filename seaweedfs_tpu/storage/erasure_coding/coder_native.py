"""Native-SIMD RS codec: CpuRSCodec's interface over the C++ GF(2^8) kernel
(GFNI VGF2P8AFFINEQB where the CPU has it, PSHUFB nibble tables otherwise).

The production host-side codec (the numpy table path stays as the oracle);
decode matrices still come from the numpy galois module — only the bulk
byte-stream matmul runs natively.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

from .coder_cpu import CpuRSCodec


class NativeRSCodec(CpuRSCodec):
    # ctypes releases the GIL for the duration of the native matmul, so the
    # file pipeline's worker pool parallelizes encode across cores — the
    # multi-core equivalent of klauspost/reedsolomon's WithAutoGoroutines
    # (the reference's ec_encoder.go:120-136 stays single-threaded)
    preferred_chunk = 4 * 1024 * 1024
    zero_copy_rows = True  # encode_rows takes per-row pointers (mmap views)

    def __init__(self, data_shards: int = 10, parity_shards: int = 4):
        super().__init__(data_shards, parity_shards)
        from ... import native

        if not native.available():
            raise RuntimeError("native gf256 library unavailable")
        self._native = native
        from ...util import available_cpus

        self.pipeline_workers = max(2, min(8, available_cpus()))

    def _mat_apply(self, m: np.ndarray, data: np.ndarray) -> np.ndarray:
        return self._native.gf_matmul_native(m, data)

    def _apply_rows(self, m: np.ndarray, rows, out=None) -> np.ndarray:
        # decode-side analogue of encode_rows: the survivor chunks (read
        # buffers, mmap views) go to the kernel as row pointers and the
        # result lands in the caller's recycled `out` — reconstruct_rows
        # pays neither a k-row stack copy nor a fresh output allocation
        # per chunk
        return self._native.gf_matmul_rows_native(m, rows, out=out)

    def encode_rows(self, rows) -> np.ndarray:
        # per-row pointers straight into the kernel — mmap views encode
        # without ever being copied into a stacked buffer
        assert len(rows) == self.data_shards
        return self._native.gf_matmul_rows_native(self.parity_matrix, rows)
