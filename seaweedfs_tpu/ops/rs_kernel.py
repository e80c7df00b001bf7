"""TPU Reed-Solomon codec: same interface as CpuRSCodec, compute on TPU.

Encode, reconstruct and rebuild are all one primitive — a GF(2^8) constant-
matrix multiply (gf256.gf_matmul_bytes) — applied with the parity matrix, a
survivor-inverse matrix, or selected rows of either. Decode matrices are tiny
(k x k) and computed host-side in numpy per missing-shard pattern; kernels are
compiled per pattern and cached by jit.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..storage.erasure_coding.galois import (
    DECODE_ROWS_CACHE,
    build_matrix,
    mat_mul,
    reconstruction_matrix,
)
from ..util.device import on_tpu
from .gf256 import RS_STAGE, count_rs_dispatch, gf_matmul_bytes, row_granule


def rows_of_one_array(rows: Sequence) -> Optional[np.ndarray]:
    """uint8[len(rows), stride], a view and no copy, where `rows` are the
    equally wide starts of consecutive rows of ONE C-contiguous uint8 array
    (a caller read its survivors into an array of its own: `a[j, :n]`, or
    `a[j]` whole); None for rows that are anything else (separate arrays,
    views of a bytes object, a gap where a shard is missing). Seen from the
    rows' owner, addresses and widths, so no caller has to say it."""
    first = rows[0]
    owner = getattr(first, "base", None)
    if (
        not isinstance(owner, np.ndarray)
        or owner.dtype != np.uint8
        or not owner.flags.c_contiguous
    ):
        return None
    at = []
    for r in rows:
        if (
            getattr(r, "base", None) is not owner
            or r.dtype != np.uint8
            or r.shape != first.shape
            or r.strides != (1,)
        ):
            return None
        at.append(r.__array_interface__["data"][0])
    n = first.shape[0]
    stride = at[1] - at[0] if len(at) > 1 else n
    if stride < n or any(a != at[0] + j * stride for j, a in enumerate(at)):
        return None
    start = at[0] - owner.__array_interface__["data"][0]
    end = start + len(at) * stride
    if end > owner.size:
        return None  # the last row's stride would run past the owner's end
    return owner.reshape(-1)[start:end].reshape(len(at), stride)


class TpuRSCodec:
    """Drop-in for CpuRSCodec with JAX/Pallas compute.

    Accepts numpy or jax uint8 arrays of shape [shards, N]; returns numpy
    arrays (the storage pipeline writes them straight to shard files).
    """

    # the EC file pipeline overlaps disk IO with device encode (upload +
    # kernel + download per chunk are pipelined stages); large chunks
    # amortize per-dispatch/transfer latency
    preferred_chunk = 16 * 1024 * 1024
    is_device = True

    def __init__(
        self,
        data_shards: int = 10,
        parity_shards: int = 4,
        force_pallas: Optional[bool] = None,
        interpret: bool = False,
    ):
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.matrix = build_matrix(data_shards, self.total_shards)
        self.parity_matrix = self.matrix[data_shards:]
        self._force_pallas = force_pallas
        self._interpret = interpret
        self._standin = None  # lazy: host kernel the streamed pipeline
        # dispatches when no real accelerator backs the jax backend

    def _standin_codec(self):
        """The kernel the streamed file pipeline dispatches per staged
        chunk when the jax backend is the CPU STAND-IN: running the GF
        matmul through jax-on-CPU would only emulate the device at a
        fraction of the host kernel's rate, so the stand-in dispatches
        the native SIMD codec instead (self when native is unavailable —
        the jax path is then the best host kernel we have). On a real
        TPU this is never consulted. The pipeline structure (staging
        ring, overlap, stage walls) is identical either way; only the
        kernel stage's executor differs, and the run's route discloses it
        (`kernel`)."""
        if self._standin is None:
            try:
                from ..storage.erasure_coding.coder_native import (
                    NativeRSCodec,
                )

                self._standin = NativeRSCodec(
                    self.data_shards, self.parity_shards
                )
            except (RuntimeError, OSError):  # no compiler / no library
                self._standin = self
        return self._standin

    @property
    def pipeline_dispatch_kind(self) -> str:
        """What the streamed pipeline's kernel stage actually runs:
        "device" (host->device upload + MXU/VPU kernel + download),
        "host_standin" (native SIMD kernel substituted on the CPU
        stand-in), or "device_emulated" (jax-on-CPU — no native lib)."""
        if on_tpu():
            return "device"
        return (
            "device_emulated"
            if self._standin_codec() is self
            else "host_standin"
        )

    def pipeline_encode(self, data) -> np.ndarray:
        """Per-chunk encode for the streamed file pipeline (see
        _standin_codec for the stand-in substitution)."""
        if on_tpu():
            return self.encode(data)
        standin = self._standin_codec()
        if standin is self:
            return self.encode(data)
        data = np.asarray(data)
        # the stand-in has no sub-stages: its dispatches and bytes count
        # under its own backend, and the pipeline's `kernel` stage times it
        count_rs_dispatch(
            "encode", "host_standin", self.total_shards, data.shape[1],
            data.shape[1],
        )
        if hasattr(standin, "encode_rows"):
            # row pointers: a narrow tail view (contiguous rows, strided
            # 2D) encodes without a compaction copy
            return np.asarray(
                standin.encode_rows([data[i] for i in range(data.shape[0])])
            )
        return standin.encode(np.ascontiguousarray(data, dtype=np.uint8))

    def row_granule(self) -> int:
        """Bytes a row is padded to before its upload (CpuRSCodec.row_granule
        has the contract): rows a caller made that wide are not copied."""
        return row_granule(
            force_pallas=self._force_pallas, interpret=self._interpret
        )

    def _apply(
        self, matrix: np.ndarray, data, op: str, width: Optional[int] = None
    ) -> np.ndarray:
        return gf_matmul_bytes(
            matrix,
            data,
            force_pallas=self._force_pallas,
            interpret=self._interpret,
            op=op,
            width=width,
        )

    def encode(self, data) -> np.ndarray:
        """uint8[k, N] -> parity uint8[m, N]."""
        return self._apply(self.parity_matrix, data, "encode")

    def encode_all(self, data) -> np.ndarray:
        data_np = np.asarray(data, dtype=np.uint8)
        return np.concatenate([data_np, self.encode(data)], axis=0)

    def verify(self, shards) -> bool:
        shards = np.asarray(shards, dtype=np.uint8)
        return bool(
            np.array_equal(self.encode(shards[: self.data_shards]),
                           shards[self.data_shards :])
        )

    def reconstruct(
        self, shards: Sequence[Optional[np.ndarray]], data_only: bool = False
    ) -> list:
        shards = list(shards)
        if len(shards) != self.total_shards:
            raise ValueError(f"expected {self.total_shards} shard slots")
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) < self.data_shards:
            raise ValueError(f"too few shards: {len(present)} < {self.data_shards}")
        missing_data = [i for i in range(self.data_shards) if shards[i] is None]
        missing_parity = [
            i for i in range(self.data_shards, self.total_shards) if shards[i] is None
        ]
        if not missing_data and not missing_parity:
            return shards

        survivors = present[: self.data_shards]
        with RS_STAGE["decode"]["stack"]():
            sub = np.stack(
                [np.asarray(shards[i], dtype=np.uint8) for i in survivors]
            )

        if missing_data or (missing_parity and not data_only):
            dec = reconstruction_matrix(self.matrix, survivors)
            # one fused kernel: [missing_data rows; missing_parity rows] where
            # parity rows are (parity_matrix . dec) applied to the survivors
            rows = []
            if missing_data:
                rows.append(dec[np.asarray(missing_data)])
            if missing_parity and not data_only:
                par_rows = self.matrix[np.asarray(missing_parity)]
                rows.append(mat_mul(par_rows, dec))
            m = np.concatenate(rows, axis=0)
            recovered = self._apply(m, sub, "decode")
            targets = missing_data + (missing_parity if not data_only else [])
            for out_row, i in enumerate(targets):
                shards[i] = recovered[out_row]
        return shards

    def apply_matrix(self, m: np.ndarray, data) -> np.ndarray:
        """Public bulk GF(2^8) matmul on the device kernel (the primitive
        batched multi-volume rebuild dispatches through)."""
        return self._apply(np.asarray(m, dtype=np.uint8), data, "apply")

    def reconstruct_rows(
        self,
        shards: Sequence[Optional[np.ndarray]],
        wanted: Sequence[int],
        out: Optional[np.ndarray] = None,
    ) -> list:
        """Reconstruct ONLY the `wanted` shard ids from any k survivors —
        one device dispatch with the composed decode rows (data rows from
        the survivor inverse, parity rows pre-multiplied host-side), cached
        per (survivor set, wanted rows) in the shared DECODE_ROWS_CACHE so
        steady rebuild/degraded-read traffic reuses both the matrix AND its
        compiled kernel (jit caches per matrix shape). Survivors that are
        the consecutive rows of one array (rows_of_one_array) are uploaded as
        that array: no stack, and no pad either where its rows are
        row_granule() wide, whatever lies past the survivors' own width."""
        shards = list(shards)
        if len(shards) != self.total_shards:
            raise ValueError(f"expected {self.total_shards} shard slots")
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) < self.data_shards:
            raise ValueError(
                f"too few shards: {len(present)} < {self.data_shards}"
            )
        need = [i for i in wanted if shards[i] is None]
        recovered_by_id = {}
        if need:
            survivors = present[: self.data_shards]
            rows = DECODE_ROWS_CACHE.rows_for(self.matrix, survivors, need)
            given = [shards[i] for i in survivors]
            width = len(given[0])
            sub = rows_of_one_array(given)
            if sub is None:
                with RS_STAGE["decode"]["stack"]():
                    sub = np.stack(
                        [np.asarray(s, dtype=np.uint8) for s in given]
                    )
            recovered = self._apply(rows, sub, "decode", width)
            if out is not None and len(need) == len(wanted):
                out[:] = recovered  # device result lands in the recycled
                recovered = out  # caller buffer (interface parity with CPU)
            for out_row, i in enumerate(need):
                recovered_by_id[i] = recovered[out_row]
        return [
            shards[i] if shards[i] is not None else recovered_by_id[i]
            for i in wanted
        ]
