"""ErasureCoder selection: the `storage.backend=tpu` switch.

The reference hard-codes klauspost/reedsolomon (ref: ec_encoder.go:198);
here the codec is an injected dependency of the EC file pipeline and the
volume-server EC handlers, selected by configuration:

    [storage]
    backend = "tpu"     # or "cpu"

Both implementations expose the same interface (encode / encode_all /
verify / reconstruct / reconstruct_rows / apply_matrix over
uint8[shards, N]) and produce byte-identical output — reconstruct_rows is
the repair-plane primitive (decode matrix sliced to the wanted shard ids,
cached in galois.DECODE_ROWS_CACHE) that rebuild_ec_files and the
degraded-read path dispatch through.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

logger = logging.getLogger(__name__)

_adaptive_lock = threading.Lock()
_adaptive_cache: dict = {}


def get_codec(
    backend: str = "cpu",
    data_shards: int = 10,
    parity_shards: int = 4,
    interpret: bool = False,
):
    if backend == "adaptive":
        return adaptive_codec(data_shards, parity_shards, interpret=interpret)
    if backend == "tpu":
        from ..ops.rs_kernel import TpuRSCodec

        return TpuRSCodec(data_shards, parity_shards, interpret=interpret)
    if backend == "cpu":
        # prefer the native SIMD kernel (the klauspost-equivalent host path);
        # numpy tables are the always-available fallback and oracle
        try:
            from ..storage.erasure_coding.coder_native import NativeRSCodec

            return NativeRSCodec(data_shards, parity_shards)
        except (RuntimeError, OSError):
            pass
        from ..storage.erasure_coding.coder_cpu import CpuRSCodec

        return CpuRSCodec(data_shards, parity_shards)
    if backend == "numpy":
        from ..storage.erasure_coding.coder_cpu import CpuRSCodec

        return CpuRSCodec(data_shards, parity_shards)
    raise ValueError(
        f"unknown storage backend {backend!r} "
        "(want 'cpu', 'numpy', 'tpu' or 'adaptive')"
    )


def probe_roundtrip_seconds(codec, width: int = 1 << 20, reps: int = 2) -> float:
    """Best-of-reps wall time of one full encode round trip (host buffer in,
    parity bytes back on host) at `width` bytes per shard. For a device codec
    this includes upload + kernel + download — exactly the cost the file
    pipeline pays per chunk."""
    import numpy as np

    data = np.zeros((codec.data_shards, width), dtype=np.uint8)
    out = codec.encode(data)  # compile / warm outside the timed reps
    _ = bytes(memoryview(np.ascontiguousarray(out[0]))[:8])
    best = float("inf")
    for _i in range(reps):
        t0 = time.perf_counter()
        out = codec.encode(data)
        _ = bytes(memoryview(np.ascontiguousarray(out[0]))[:8])  # force host
        best = min(best, time.perf_counter() - t0)
    return best


def adaptive_codec(
    data_shards: int = 10,
    parity_shards: int = 4,
    interpret: bool = False,
):
    """The shipping-path codec selector: route to the device kernel only when
    the measured round trip (transfers included) actually beats the native
    host codec; otherwise serve the SIMD CPU path.

    This is the fix for the round-2 finding that the system shipped a
    transfer-bound device pipeline (0.14x baseline) while a 25x-faster host
    codec sat idle: the decision is made from a one-time measurement, not
    from `jax.devices()` optimism. On a CPU platform the host codec serves;
    with a device attached, a probe that fails raises.
    """
    key = (data_shards, parity_shards, interpret)
    with _adaptive_lock:
        cached = _adaptive_cache.get(key)
        if cached is not None:
            return cached
        codec = _pick_adaptive(data_shards, parity_shards, interpret)
        _adaptive_cache[key] = codec
        return codec


def _pick_adaptive(data_shards: int, parity_shards: int, interpret: bool):
    from ..util.device import platform

    cpu_codec = get_codec("cpu", data_shards, parity_shards)
    if platform() == "cpu":
        return cpu_codec
    # a device is attached: a probe that fails on it is an error to see,
    # not a reason to serve from the host in silence
    from ..ops.rs_kernel import TpuRSCodec

    tpu_codec = TpuRSCodec(data_shards, parity_shards, interpret=interpret)
    t_tpu = probe_roundtrip_seconds(tpu_codec)
    t_cpu = probe_roundtrip_seconds(cpu_codec)
    if t_tpu < t_cpu:
        logger.info(
            "adaptive codec: device path wins (%.1fms vs %.1fms/MB-stripe)",
            t_tpu * 1e3,
            t_cpu * 1e3,
        )
        return tpu_codec
    logger.info(
        "adaptive codec: device round trip transfer-bound "
        "(%.1fms vs %.1fms/MB-stripe) — serving native CPU codec",
        t_tpu * 1e3,
        t_cpu * 1e3,
    )
    return cpu_codec


def reset_adaptive_cache() -> None:
    with _adaptive_lock:
        _adaptive_cache.clear()
