"""EC file pipeline: .dat <-> .ec00-.ec13 (+ .ecx sorted index).

Byte-identical to the reference pipeline (ref: weed/storage/erasure_coding/
ec_encoder.go, ec_decoder.go):

- encode streams the .dat through the two-level block layout — shard i's
  bytes for a row starting at P come from dat[P + i*block : P + (i+1)*block],
  zero-filled past EOF (ec_encoder.go:162-192) — and appends one block per
  shard per row, so every shard file is large_rows*1GB + small_rows*1MB;
- rebuild reconstructs the missing shard files from >=10 survivors;
- decode interleave-copies .ec00-.ec09 back into a .dat
  (ec_decoder.go:157-195).

The codec is pluggable (CPU numpy or the TPU JAX kernel); chunking is
vectorized rather than the reference's 256KB scalar loop — the chunk is the
unit shipped to the TPU.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import numpy as np

from . import (
    DATA_SHARDS_COUNT,
    EC_LARGE_BLOCK_SIZE,
    EC_SMALL_BLOCK_SIZE,
    PARITY_SHARDS_COUNT,
    TOTAL_SHARDS_COUNT,
    to_ext,
)
from ...types import TOMBSTONE_FILE_SIZE, to_actual_offset
from ...util import trace
from ...util.metrics import (
    EC_ENCODE_BATCH_PIECES,
    EC_ENCODE_STAGE_CALLS,
    EC_ENCODE_STAGE_SECONDS,
)
from ..idx import iter_index, entry_to_bytes
from ..needle import get_actual_size
from ..needle_map import MemDb
from ..super_block import SuperBlock

DEFAULT_CHUNK = 4 * 1024 * 1024  # per-shard streaming chunk

class EncodeRun:
    """What one write_ec_files run took and which way it went: the sink
    of the run's stages (util/trace.Stage), added to from the main, pool
    and writer threads of THIS run only."""

    def __init__(self):
        self.route: dict = {}
        # what is no stage's wall: total_s, pipeline_depth, ...
        self.extra: dict = {}
        self._seconds: dict = {}
        self._lock = threading.Lock()

    def add(self, label: str, dt: float) -> None:
        with self._lock:
            self._seconds[label] = self._seconds.get(label, 0.0) + dt

    def seconds(self, *labels: str) -> float:
        with self._lock:
            return sum(self._seconds.get(k, 0.0) for k in labels)

    def stages(self) -> dict:
        """The run's budget: `<stage>_s` wall seconds, stage_s (= slot_wait_s
        + submit_s) and the extras. read / stage / sync / splice are
        main-thread walls that PARTITION the run (their sum over total_s is
        coverage_of_wall); kernel_s (pool) and write_s / parity_wait_s (the
        ordering writer thread) are overlapped walls; write_thread_s is
        summed over every thread that wrote shard files."""
        with self._lock:
            out = {k + "_s": v for k, v in self._seconds.items()}
        if "slot_wait_s" in out or "submit_s" in out:
            out["stage_s"] = out.get("slot_wait_s", 0.0) + out.get(
                "submit_s", 0.0
            )
        out.update(self.extra)
        return out


def _encode_stage(label: str, annotate: bool = True):
    return trace.stage(
        "ec.encode." + label,
        EC_ENCODE_STAGE_SECONDS.child(stage=label),
        EC_ENCODE_STAGE_CALLS.child(stage=label),
        annotate=annotate,
        label=label,
    )


# the encode plane's stages, bound once. Work at the leaves is annotated
# (an `ec.encode.<stage>` event in a profiler trace); waits are counters
# only; `kernel` has no event of its own: its leaves are the codec's
# `rs.*` stages. `sync` is the final flush + close + rename; the drain
# before it (the writer's join) is a wait that adds to the same counter.
# `write` is the ordering thread's wall round a chunk's shard writes, its
# helpers' included; `write_thread` is what each writing thread spent in
# its own write calls, so write_thread / write = threads writing at once.
_ST_SPLICE = _encode_stage("splice")
_ST_READ = _encode_stage("read")
_ST_SLOT_WAIT = _encode_stage("slot_wait", annotate=False)
_ST_SUBMIT = _encode_stage("submit", annotate=False)
_ST_KERNEL = _encode_stage("kernel", annotate=False)
_ST_PARITY_WAIT = _encode_stage("parity_wait", annotate=False)
_ST_WRITE = _encode_stage("write")
_ST_WRITE_THREAD = _encode_stage("write_thread", annotate=False)
_ST_SYNC = _encode_stage("sync")
_ST_SYNC_DRAIN = trace.stage(
    "ec.encode.sync_drain", EC_ENCODE_STAGE_SECONDS.child(stage="sync"),
    annotate=False, label="sync",
)

# the volume pieces each dispatch of the streamed pipeline carries: one
_BATCH_PIECES = EC_ENCODE_BATCH_PIECES.child()

# per-stage wall seconds of the last rebuild_ec_files run (read_s /
# decode_s / write_s / total_s). On the pipelined route the stages
# OVERLAP (decode_s is worker wall while the main thread reads/writes),
# so their sum can exceed total_s; each stage is still individually
# honest. Not synchronized across concurrent
# rebuild_ec_files_multi volumes.
LAST_REBUILD_STAGES: dict = {}
_REBUILD_STAGE_LOCK = threading.Lock()

# which structure the last rebuild_ec_files run took ("mmap" zero-copy
# survivor maps / "pread" buffered reads, pipelined or not)
LAST_REBUILD_ROUTE: dict = {}


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ[name])
    except (KeyError, ValueError):
        return default


def _sweep_stale_tmp(base_file_name: str, total_shards: int) -> None:
    """Remove .ecNN.tmp leftovers a crashed encode/rebuild left behind —
    a torn .tmp must never be mistaken for (or block) a fresh output."""
    for i in range(total_shards):
        tmp = base_file_name + to_ext(i) + ".tmp"
        if os.path.exists(tmp):
            os.remove(tmp)


def _rebuild_stage_add(key: str, dt: float) -> None:
    # decode runs on pool workers concurrently with the reader: lock
    with _REBUILD_STAGE_LOCK:
        LAST_REBUILD_STAGES[key] = LAST_REBUILD_STAGES.get(key, 0.0) + dt


def _get_codec(codec):
    if codec is None:
        from .coder_cpu import CpuRSCodec

        codec = CpuRSCodec(DATA_SHARDS_COUNT, PARITY_SHARDS_COUNT)
    return codec


def _read_into(f, out: np.ndarray, offset: int) -> None:
    """Read len(out) bytes at offset directly into `out` (no intermediate
    bytes allocation — preadv writes straight into the numpy buffer),
    zero-filling past EOF."""
    if not hasattr(f, "fileno"):
        out[:] = 0
        return
    fd = f.fileno()
    n = 0
    want = len(out)
    if hasattr(os, "preadv"):
        while n < want:
            got = os.preadv(fd, [memoryview(out)[n:]], offset + n)
            if got <= 0:
                break
            n += got
    else:  # macOS: no preadv — fall back to pread + copy
        b = os.pread(fd, want, offset)
        n = len(b)
        if n:
            out[:n] = np.frombuffer(b, dtype=np.uint8)
    if n < want:
        out[n:] = 0


def _read_exact(f, out: np.ndarray, offset: int) -> None:
    """_read_into that treats a short read as the IO error it is — the
    rebuild path must NOT zero-fill a truncated survivor into the decode
    (that would silently corrupt every rebuilt shard)."""
    if not hasattr(f, "fileno"):
        got = f.read(len(out))  # test doubles without a real fd
        out[: len(got)] = np.frombuffer(got, dtype=np.uint8)
        if len(got) != len(out):
            raise IOError(f"ec shard short read: {len(got)} != {len(out)}")
        return
    fd = f.fileno()
    n = 0
    want = len(out)
    while n < want:
        if hasattr(os, "preadv"):
            got = os.preadv(fd, [memoryview(out)[n:]], offset + n)
        else:
            b = os.pread(fd, want - n, offset + n)
            got = len(b)
            if got:
                out[n : n + got] = np.frombuffer(b, dtype=np.uint8)
        if got <= 0:
            raise IOError(f"ec shard short read: {n} != {want}")
        n += got


def _stream_items(
    n_large: int, large_block: int, n_small: int, small_block: int,
    chunk: int, k: int, group: bool = True,
) -> list:
    """The streamed pipeline's work list, in shard stream order:
    (start, block, done, width, g) where `start` is the .dat offset of the
    first covered row, `g` rows are grouped into one dispatch (small blocks
    only — GF columns are independent, so G concatenated blocks per shard
    encode identically to G per-row encodes, amortizing per-dispatch
    latency), and `done`/`width` chunk the inside of one large block.

    group=False emits one item per small-block row instead: the zero-copy
    mmap route dispatches strided (k, width) VIEWS of the source mapping,
    and a grouped item's per-shard bytes are not expressible as one such
    view (its g segments per shard are discontiguous)."""
    items = []
    offset = 0
    for rows, block in ((n_large, large_block), (n_small, small_block)):
        if block >= chunk or not group:
            for row in range(rows):
                row_start = offset + row * block * k
                done = 0
                while done < block:
                    width = min(chunk, block - done)
                    items.append((row_start, block, done, width, 1))
                    done += width
        else:
            g_max = max(1, chunk // block)
            row = 0
            while row < rows:
                g = min(g_max, rows - row)
                items.append((offset + row * block * k, block, 0, block, g))
                row += g
        offset += rows * block * k
    return items


# Raced on the chip's host (13 CPUs, shards to tmpfs, 1 GiB volumes; PERF.md
# section 6, PR 27): 1, 2, 3, 4, 5, 7, 14 writing threads gave 1.07, 1.67,
# 1.95, 2.04, 2.10, 2.07, 1.91 GB/s at 1.87, 1.95, 1.99, 2.14, 2.15, 2.50,
# 3.01 cpu-s/GB. Past three the copies only slow each other down (the
# threads' own write seconds grow as fast as their number), so a fourth
# buys 5 % of rate for 8 % more CPU and a sixth buys nothing.
_STREAM_WRITERS_MOST = 3


def _stream_writers(n_files: int, depth: int) -> int:
    """How many threads write a chunk's shard files in the streamed
    pipeline, the ordering thread included: one a file and one a CPU left
    beside the main thread and the pool's `depth` workers, and no more
    than the race above found useful. One or two CPUs give 1: the writer
    writes inline and starts no helper."""
    from ...util import available_cpus

    spare = available_cpus() - (1 + depth)
    return max(1, min(n_files, spare, _STREAM_WRITERS_MOST))


def _encode_streamed(
    run: EncodeRun,
    base_file_name: str,
    dat_f,
    codec,
    n_large: int,
    large_block: int,
    n_small: int,
    small_block: int,
    chunk: int,
    depth: int,
    splice_data,
    dat_path: str,
) -> tuple[bool, str, int]:
    """The streamed, depth-N double-buffered encode pipeline: the one way
    a .dat becomes shard files, whatever the codec.

    Chunked reads of the .dat feed a bounded ring of depth+2 REUSED host
    staging slots (the pinned-buffer pool a real device runtime would
    register for DMA). The input is the mapping of the .dat: each chunk is
    a zero-copy strided (k, width) VIEW of it — per-shard rows are
    contiguous segments `block` apart — prefetched with madvise(WILLNEED)
    one item ahead so page population overlaps compute; the ring slot is
    then only a backpressure token. Only an item whose source region
    crosses EOF stages through a copy (it needs the zero tail
    materialized). Where mmap refuses (an empty .dat, a file that cannot
    be mapped) every chunk is copied into a staging slot with preadv.

    Each chunk's kernel dispatch (host->device upload + matmul + download,
    or the host-kernel dispatch the codec substitutes on the CPU stand-in)
    runs on a pool of `depth` workers so it overlaps the NEXT chunk's disk
    read (main thread) and the PREVIOUS chunk's shard writes. Those are
    the write side's: one ordering thread (`ec-stream-writer`) takes the
    chunks in stream order and shares each chunk's writes with
    `_stream_writers(...)` - 1 helpers, every thread appending to its own
    fixed files (data shards before the chunk's parity is waited for,
    parity shards after), and gives the slot back when all are done.
    Output is in-order into .ecNN.tmp files renamed into
    place only when the whole stream succeeds — a mid-stream crash leaves
    only .tmp files for the next run's sweep, never a torn shard
    masquerading as complete.

    Per-stage walls land in `run` (and on /metrics, and as events in a
    profiler trace: the stages bound at the top of this module): read
    (main-thread preadv, or view construction + readahead on the mmap
    route), slot_wait (ring backpressure: the wait for a free slot),
    submit (set-up, pad, pool.submit, hand-off), sync (final drain +
    flush + rename) partition the main-thread wall — their sum over
    total_s is the disclosed coverage_of_wall; kernel (pool), parity_wait
    and write (the ordering thread: hand-out to all done, less its wait
    for parity) are the overlapped walls, and write_thread adds up what
    every writing thread spent in its write calls. Returns (spliced,
    input, writers) where `input` is the route that fed the ring ("mmap"
    or "pread") and `writers` the threads that wrote."""
    import concurrent.futures as cf
    import contextlib
    import mmap as mmap_mod
    import queue as queue_mod
    import time as _time

    k = codec.data_shards
    total = codec.total_shards

    _sweep_stale_tmp(base_file_name, total)

    spliced = False
    if splice_data is None or splice_data:
        with _ST_SPLICE(run):
            spliced = _splice_data_shards(
                dat_path, base_file_name, k,
                n_large, large_block, n_small, small_block,
                suffix=".tmp",
            )

    t_setup = _time.perf_counter()
    try:
        dat_size = os.fstat(dat_f.fileno()).st_size
    except (OSError, AttributeError):
        dat_size = 0
    mm = None
    mm_arr = None
    if dat_size > 0:
        try:
            mm = mmap_mod.mmap(
                dat_f.fileno(), 0, access=mmap_mod.ACCESS_READ
            )
            mm_arr = np.frombuffer(mm, dtype=np.uint8)
        except (ValueError, OSError, AttributeError):
            mm = None
            mm_arr = None

    items = _stream_items(
        n_large, large_block, n_small, small_block, chunk, k,
        group=mm_arr is None,
    )
    full_width = max((w * g for _s, _b, _d, w, g in items), default=0)
    dispatch = getattr(codec, "pipeline_encode", None) or codec.encode
    # the device dispatch keeps ONE compile shape (zero-padded tail, parity
    # sliced on write: zero columns encode to zero parity); host kernels
    # take the narrow tail directly
    pad_tail = getattr(codec, "pipeline_dispatch_kind", "host") == "device"

    def prefetch(index: int) -> None:
        """Async readahead for item `index`'s source range: on disk-backed
        files WILLNEED starts the IO while earlier chunks compute/write;
        on tmpfs it is a no-op-priced hint."""
        if mm is None or index >= len(items) or not hasattr(mm, "madvise"):
            return
        start, block, done, width, g = items[index]
        first = start + done
        span = (k - 1) * block + width * g
        first_pg = first - (first % mmap_mod.PAGESIZE)
        try:
            mm.madvise(
                mmap_mod.MADV_WILLNEED, first_pg,
                min(first + span, dat_size) - first_pg,
            )
        except (OSError, ValueError):
            pass

    outputs = [
        None if (spliced and i < k)
        else open(base_file_name + to_ext(i) + ".tmp", "wb")
        for i in range(total)
    ]
    n_slots = depth + 2
    freeq: queue_mod.Queue = queue_mod.Queue()
    for _ in range(n_slots):
        # slots materialize on first staging use: on the mmap route most
        # items are views and the token is pure backpressure
        freeq.put(None)
    outq: queue_mod.Queue = queue_mod.Queue()
    err: list = [None]

    def run_kernel(view: np.ndarray) -> np.ndarray:
        with _ST_KERNEL(run):
            return np.asarray(dispatch(view))

    # the write side: the ordering thread (`writer`, the one consumer of
    # outq) and n_writers - 1 helpers. A shard file belongs to ONE thread,
    # which appends to it in chunk order: no lock, no offsets to keep.
    # Dealt round-robin, so the parity files spread over the threads
    files = [i for i in range(total) if outputs[i] is not None]
    n_writers = _stream_writers(len(files), depth)
    shares = [
        (
            [i for i in files[t::n_writers] if i < k],
            [i for i in files[t::n_writers] if i >= k],
        )
        for t in range(n_writers)
    ]
    doneq: queue_mod.SimpleQueue = queue_mod.SimpleQueue()

    @contextlib.contextmanager
    def noted():
        """An exception in any writing thread lands in err[0]; the thread
        goes on consuming, so no slot is lost and nobody deadlocks."""
        try:
            yield
        except BaseException as e:
            if err[0] is None:
                err[0] = e

    def write_files(mine, rows, first: int, used: int) -> None:
        with _ST_WRITE_THREAD(run):
            for i in mine:
                outputs[i].write(rows[i - first, :used].data)

    def helper(data_files, parity_files, tasks) -> None:
        while True:
            task = tasks.get()
            if task is None:
                return
            buf, used, fut = task
            with noted():
                write_files(data_files, buf, 0, used)
                if parity_files:
                    write_files(parity_files, fut.result(), k, used)
            doneq.put(None)

    task_qs = [queue_mod.SimpleQueue() for _ in shares[1:]]
    helpers = [
        threading.Thread(
            target=helper, args=(*share, tasks),
            name=f"ec-stream-writer-{t}", daemon=True,
        )
        for t, (share, tasks) in enumerate(zip(shares[1:], task_qs), 1)
    ]

    def writer() -> None:
        data_files, parity_files = shares[0]
        for thread in helpers:
            thread.start()
        while True:
            entry = outq.get()
            if entry is None:
                break
            buf, used, fut, slot = entry
            parity = None
            # one task a thread a chunk; the data shards need no parity,
            # so they are written while the codec still works on it
            with _ST_WRITE(run), noted():
                for tasks in task_qs:
                    tasks.put((buf, used, fut))
                write_files(data_files, buf, 0, used)
            with _ST_PARITY_WAIT(run), noted():
                parity = fut.result()
            with _ST_WRITE(run):
                if parity is not None:
                    with noted():
                        write_files(parity_files, parity, k, used)
                for _ in helpers:
                    doneq.get()
            freeq.put(slot)
        for tasks in task_qs:
            tasks.put(None)
        for thread in helpers:
            thread.join()

    writer_t = threading.Thread(
        target=writer, name="ec-stream-writer", daemon=True
    )
    ok = False
    try:
        with cf.ThreadPoolExecutor(depth) as pool:
            writer_t.start()
            # pool/writer/ring setup charges to submit: the coverage
            # partition must account for every main-thread second
            _ST_SUBMIT.since(t_setup, run)
            prefetch(0)
            for idx, (start, block, done, width, g) in enumerate(items):
                if err[0] is not None:
                    break
                with _ST_SLOT_WAIT(run):
                    slot = freeq.get()
                used = width * g
                first = start + done
                view = None
                with _ST_READ(run):
                    if (
                        mm_arr is not None
                        and g == 1
                        and first + (k - 1) * block + width <= dat_size
                    ):
                        view = np.lib.stride_tricks.as_strided(
                            mm_arr[first:], shape=(k, width),
                            strides=(block, 1), writeable=False,
                        )
                        buf = view
                    else:
                        if slot is None:
                            slot = np.empty(
                                (k, max(full_width, 1)), dtype=np.uint8
                            )
                        for gi in range(g):
                            row_start = start + gi * block * k
                            sl = slice(gi * width, gi * width + width)
                            for i in range(k):
                                _read_into(
                                    dat_f, slot[i, sl],
                                    row_start + i * block + done,
                                )
                        buf = slot
                    prefetch(idx + 1)
                with _ST_SUBMIT(run):
                    if view is None:
                        if used < full_width and pad_tail:
                            slot[:, used:] = 0
                            kview = slot
                        else:
                            kview = (
                                slot if used == full_width
                                else slot[:, :used]
                            )
                    else:
                        kview = view
                    _BATCH_PIECES.inc()
                    outq.put(
                        (buf, used, pool.submit(run_kernel, kview), slot)
                    )
            with _ST_SYNC_DRAIN(run):
                outq.put(None)
                writer_t.join()
        if err[0] is not None:
            raise err[0]
        with _ST_SYNC(run):
            for f in outputs:
                if f is not None:
                    f.flush()
                    f.close()
            for i in range(total):
                os.replace(
                    base_file_name + to_ext(i) + ".tmp",
                    base_file_name + to_ext(i),
                )
        ok = True
    finally:
        if not ok:
            if writer_t.is_alive():
                outq.put(None)
                writer_t.join()
            for f in outputs:
                if f is not None:
                    try:
                        f.close()
                    except OSError:
                        pass
            _sweep_stale_tmp(base_file_name, total)
        if mm is not None:
            mm_arr = view = buf = kview = None  # drop buffer exports
            try:
                mm.close()
            except (BufferError, OSError):
                pass  # a straggling view still exports the buffer: the
                # mapping closes when it is collected
    return spliced, "mmap" if mm is not None else "pread", n_writers


def _fs_type_of(path: str) -> str:
    """Filesystem type of the mount containing `path` (Linux mountinfo);
    "" when undeterminable."""
    try:
        target = os.path.realpath(os.path.dirname(os.path.abspath(path)))
        best = ("", "")
        with open("/proc/self/mountinfo") as f:
            for line in f:
                parts = line.split(" - ")
                if len(parts) != 2:
                    continue
                mount_point = parts[0].split()[4]
                fstype = parts[1].split()[0]
                if (
                    target == mount_point
                    or target.startswith(mount_point.rstrip("/") + "/")
                ) and len(mount_point) > len(best[0]):
                    best = (mount_point, fstype)
        return best[1]
    except (OSError, IndexError, ValueError):
        return ""  # unparsable mount table: let the splice heuristic pass


def _splice_data_shards(
    dat_path: str,
    base_file_name: str,
    k: int,
    n_large: int,
    large_block: int,
    n_small: int,
    small_block: int,
    suffix: str = "",
) -> bool:
    """Assemble the k data-shard files as kernel-side copies of the .dat
    (copy_file_range) — their content is a pure interleaving of the source,
    so it never needs to transit user space; only parity does. Zero padding
    past EOF becomes file holes (byte-identical content, no page traffic).

    Returns False (with any partial files removed) when the kernel/filesystem
    refuses the splice; the caller then writes data shards inline. The
    reference streams every data byte back out through its user-space buffer
    (ref ec_encoder.go:120-136); this is the host-side analogue of keeping
    the MXU fed only with bytes that need compute.
    """
    if not hasattr(os, "copy_file_range"):
        return False
    if _fs_type_of(dat_path) in ("tmpfs", "ramfs"):
        # tmpfs has no reflink and its copy_file_range degrades to a pipe
        # splice — pure overhead over writing from the buffer we hold
        return False
    shard_size = n_large * large_block + n_small * small_block
    dat_size = os.path.getsize(dat_path)
    written = []
    try:
        with open(dat_path, "rb") as src:
            sfd = src.fileno()
            for i in range(k):
                path = base_file_name + to_ext(i) + suffix
                with open(path, "wb") as out:
                    written.append(path)
                    ofd = out.fileno()
                    out_pos = 0

                    def copy_block(src_off: int, length: int) -> None:
                        nonlocal out_pos
                        avail = max(0, min(length, dat_size - src_off))
                        done = 0
                        while done < avail:
                            got = os.copy_file_range(
                                sfd, ofd, avail - done, src_off + done,
                                out_pos + done,
                            )
                            if got <= 0:
                                raise OSError("copy_file_range stalled")
                            done += got
                        out_pos += length  # hole for the zero tail

                    for row in range(n_large):
                        copy_block(
                            (row * k + i) * large_block, large_block
                        )
                    small_base = n_large * k * large_block
                    for row in range(n_small):
                        copy_block(
                            small_base + (row * k + i) * small_block,
                            small_block,
                        )
                    os.ftruncate(ofd, shard_size)
        return True
    except OSError:
        for path in written:
            try:
                os.remove(path)
            except OSError:
                pass
        return False


def write_ec_files(
    base_file_name: str,
    codec=None,
    large_block_size: int = EC_LARGE_BLOCK_SIZE,
    small_block_size: int = EC_SMALL_BLOCK_SIZE,
    chunk: int = DEFAULT_CHUNK,
    splice_data: Optional[bool] = None,
) -> EncodeRun:
    """Generate .ec00-.ec13 from .dat (ref WriteEcFiles, ec_encoder.go:57).

    One pipeline for every codec (device, native, numpy): _encode_streamed,
    a bounded ring of reused staging buffers, overlapped read/kernel/write,
    in-order .ecNN.tmp outputs renamed when the volume is whole. Its chunk
    and depth are env-tunable: SEAWEEDFS_TPU_EC_PIPELINE_CHUNK (bytes,
    default codec.preferred_chunk) and SEAWEEDFS_TPU_EC_PIPELINE_DEPTH
    (default codec.pipeline_workers, else 2). splice_data=None tries the
    kernel-side data-shard splice and falls back to inline writes; False
    skips the attempt.

    Returns the run's own EncodeRun: the route taken and the stage budget.
    The .ecx is NOT written here (write_sorted_file_from_idx does that
    during volume->EC conversion): `ecx_s` is 0 in the budget so that it
    cannot be misread as omitted.
    """
    import time as _time

    t_enter = _time.perf_counter()
    run = EncodeRun()
    codec = _get_codec(codec)
    if chunk == DEFAULT_CHUNK:
        chunk = _env_int(
            "SEAWEEDFS_TPU_EC_PIPELINE_CHUNK",
            getattr(codec, "preferred_chunk", chunk),
        )
    depth = max(1, _env_int(
        "SEAWEEDFS_TPU_EC_PIPELINE_DEPTH",
        getattr(codec, "pipeline_workers", 2),
    ))
    dat_path = base_file_name + ".dat"
    n_large, n_small = _row_counts(
        os.path.getsize(dat_path), codec.data_shards,
        large_block_size, small_block_size,
    )
    try:
        with open(dat_path, "rb") as dat_f:
            spliced, input_kind, writers = _encode_streamed(
                run, base_file_name, dat_f, codec,
                n_large, large_block_size, n_small, small_block_size,
                chunk, depth, splice_data, dat_path,
            )
        run.route = {
            "route": "pipeline",
            "spliced": spliced,
            "input": input_kind,
            "kernel": getattr(codec, "pipeline_dispatch_kind", "host"),
            "pipeline_depth": depth,
            "writers": writers,
        }
    finally:
        total = _time.perf_counter() - t_enter
        run.extra["total_s"] = total
        run.extra["pipeline_depth"] = depth
        # coverage = the main-thread (blocking) stages over the wall:
        # kernel/write are overlapped walls and deliberately NOT
        # summed here — the PR 2 write-budget disclosure discipline
        blocking = run.seconds("read", "slot_wait", "submit", "sync", "splice")
        run.extra["coverage_of_wall"] = round(blocking / max(total, 1e-9), 3)
        run.extra["ecx_s"] = 0.0
    return run



def _row_counts(
    dat_size: int, k: int, large_block: int, small_block: int
) -> tuple[int, int]:
    """(n_large, n_small) rows for a .dat (ref ec_encoder.go:214-228)."""
    remaining = dat_size
    large_row = large_block * k
    n_large = 0
    while remaining - n_large * large_row > large_row:
        n_large += 1
    remaining -= n_large * large_row
    small_row = small_block * k
    n_small = 0
    while remaining > 0:
        n_small += 1
        remaining -= small_row
    return n_large, n_small


def _mesh_encode(codec, mesh, buf: np.ndarray) -> np.ndarray:
    """Encode one wide batch through the parallel/sharded_ec mesh path:
    columns pad to the mesh's 4*blk packing unit (zero columns encode to
    zero parity and are stripped), the batch rides as one [1, k, N]
    volume sharded over (vol, blk). The multi-chip leg of the encode
    plane — byte-identical to codec.encode by GF linearity."""
    from ...parallel.sharded_ec import sharded_encode

    n = buf.shape[1]
    unit = 4 * mesh.shape["blk"]
    pad = (-n) % unit
    if pad:
        buf = np.concatenate(
            [buf, np.zeros((buf.shape[0], pad), dtype=np.uint8)], axis=1
        )
    out = np.asarray(
        sharded_encode(codec.parity_matrix, buf[None], mesh)
    )[0]
    return out[:, :n] if pad else out


class _MeshCodec:
    """`codec` with its parity computed over `mesh`: what
    write_ec_files_multi(mesh=...) hands the pipeline. Everything but the
    dispatch is the wrapped codec's."""

    pipeline_dispatch_kind = "mesh"

    def __init__(self, codec, mesh):
        self._codec = codec
        self._mesh = mesh

    def __getattr__(self, name):
        return getattr(self._codec, name)

    def pipeline_encode(self, buf: np.ndarray) -> np.ndarray:
        return _mesh_encode(self._codec, self._mesh, buf)


def write_ec_files_multi(
    base_file_names,
    codec=None,
    large_block_size: int = EC_LARGE_BLOCK_SIZE,
    small_block_size: int = EC_SMALL_BLOCK_SIZE,
    chunk: int = DEFAULT_CHUNK,
    mesh=None,
) -> list:
    """Encode MANY volumes' .dat files in one call (BASELINE.json config 3 —
    batched multi-volume ec.encode). Returns the EncodeRun that encoded
    each volume, in the order given: its route says which kernel ran.

    Every volume goes through write_ec_files, whatever the codec: each is
    committed by rename when it is whole, with that pipeline's stages and
    counters. A device codec's go one after another: raced on the chip's
    host against wide batches of four volumes' pieces (13 CPUs, shards to
    tmpfs, the cell warm-rs10.4-maint.ec-encode-full4; PERF.md section 6,
    PR 28), in turn won 7 of 7: a wide batch saves dispatches but has to
    be staged by a copy, on a host whose writers are bound by the memory
    bus. A host codec's go at once, as many as there are CPUs: read by
    hand on the same host and job (PERF.md section 6, PR 30), in turn was
    16-19 % slower than at once had been. A failure raises with the runs
    of the volumes before the first that failed as its `encoded`, so a
    caller that falls back need not convert those again.

    With a mesh, each dispatch's parity is computed over it (_mesh_encode);
    the pipeline round the dispatch is the same.
    """
    import concurrent.futures as cf

    from ...util import available_cpus

    codec = _get_codec(codec)
    base_file_names = list(base_file_names)

    def one(base: str) -> EncodeRun:
        return write_ec_files(
            base, codec=_MeshCodec(codec, mesh) if mesh is not None else codec,
            large_block_size=large_block_size,
            small_block_size=small_block_size,
            chunk=chunk,
        )

    if mesh is None and not getattr(codec, "is_device", False):
        n_at_once = min(len(base_file_names), available_cpus())
        with cf.ThreadPoolExecutor(max(1, n_at_once)) as pool:
            pending = [pool.submit(one, base) for base in base_file_names]
        results = (f.result() for f in pending)
    else:
        results = map(one, base_file_names)
    runs: list = []
    try:
        for run in results:
            runs.append(run)
    except Exception as e:
        e.encoded = runs
        raise
    return runs


def write_sorted_file_from_idx(base_file_name: str, ext: str = ".ecx") -> None:
    """.idx log -> sorted index file (ref WriteSortedFileFromIdx,
    ec_encoder.go:27-54). Vectorized: one sequential read, one numpy
    newest-wins fold (needle_map/lsm_map.fold_live_columns — the same
    single owner of log-resolution the LSM map and the vacuum replay
    use), one serialized write — no per-entry Python dict on the way,
    so EC-encoding a multi-million-needle volume's index costs
    milliseconds, not a dict build.

    The file is replaced by rename, never truncated in place. An EcVolume
    that is mounted meanwhile keeps the index it opened, mapping and
    descriptor (the unlinked inode): it goes on serving the OLD index, and a
    delete it acknowledges after this reaches that old file and the .ecj
    only, not the new .ecx, until the volume is unmounted and mounted again
    (the .ecj is what carries such a delete over: rebuild_ecx_file replays
    it). Generate over a mounted EC volume, then remount."""
    from ..idx import NEEDLE_MAP_ENTRY_SIZE as _ENTRY  # noqa: N811
    from ..idx import entries_to_bytes, parse_index_bytes
    from ..needle_map.lsm_map import fold_live_columns

    with open(base_file_name + ".idx", "rb") as f:
        data = f.read()
    usable = len(data) - (len(data) % _ENTRY)
    keys, offs, sizes = parse_index_bytes(data[:usable])
    lk, lo, ls = fold_live_columns(keys, offs, sizes)
    # by rename, never in place: a mounted EC volume holds a mapping of its
    # .ecx, and a file truncated under a mapping kills the reader (SIGBUS).
    # The .sdx goes the same way, though nothing maps it: one writer, one
    # way, and a crash half way leaves the old .sdx whole where a write in
    # place left a short one that SortedFileNeedleMap's mtime check took
    # for fresh
    tmp = base_file_name + ext + ".tmp"
    with open(tmp, "wb") as f:
        f.write(entries_to_bytes(lk, lo, ls))
    os.replace(tmp, base_file_name + ext)


_REBUILD_HOST_ROUTE: Optional[str] = None
_REBUILD_ROUTE_LOCK = threading.Lock()

# one rebuild per volume base at a time (process-wide): a retry racing a
# still-running rebuild of the same volume (e.g. a client-side RPC timeout
# followed by a per-volume fallback while the server's executor thread is
# still decoding) must wait, re-survey, and find nothing missing — never
# interleave writes into the same .ecNN.tmp files
_BASE_REBUILD_LOCKS: dict = {}
_BASE_REBUILD_LOCKS_GUARD = threading.Lock()


def _base_rebuild_lock(base_file_name: str) -> threading.Lock:
    with _BASE_REBUILD_LOCKS_GUARD:
        lock = _BASE_REBUILD_LOCKS.get(base_file_name)
        if lock is None:
            lock = _BASE_REBUILD_LOCKS[base_file_name] = threading.Lock()
        return lock


def _calibrate_rebuild_route(codec) -> str:
    """Race the rebuild structures once per process and remember the winner:
    'onepass' (fused NT-store decode into mmapped outputs), 'mmap'
    (zero-copy survivor views + write() outputs) or 'pread' (buffered reads).

    The ranking is hardware-dependent (on hypervisors with a slow guest
    fault path anything mmap-backed degrades; on bare metal the fused
    sweep's halved memory traffic wins) and a ~100MB measured race picks reliably where a
    point probe flip-flops. Serialized so concurrent rebuilds can't cache a
    contention-skewed winner."""
    global _REBUILD_HOST_ROUTE
    if _REBUILD_HOST_ROUTE is not None:
        return _REBUILD_HOST_ROUTE
    with _REBUILD_ROUTE_LOCK:
        if _REBUILD_HOST_ROUTE is not None:
            return _REBUILD_HOST_ROUTE
        import shutil
        import tempfile
        import time

        from ... import native

        size = 96 << 20
        needed = size * 3  # .dat + shard set + rebuilt tmps
        use_dir = None
        if os.path.isdir("/dev/shm"):
            try:
                if shutil.disk_usage("/dev/shm").free >= needed:
                    use_dir = "/dev/shm"
            except OSError:
                pass
        if use_dir is None:
            try:
                if shutil.disk_usage(tempfile.gettempdir()).free < needed:
                    size = 16 << 20
            except OSError:
                pass
        routes = ["pread", "mmap"]
        if native.encode_copy_available():
            routes.append("onepass")
        d = None
        try:
            d = tempfile.mkdtemp(prefix="ec_rebuild_cal_", dir=use_dir)
            base = os.path.join(d, "c")
            block = b"\x5a\xa5\x3c" * (1 << 20)
            with open(base + ".dat", "wb") as f:
                left = size
                while left > 0:
                    f.write(block[: min(left, len(block))])
                    left -= len(block)
            write_ec_files(base, codec=codec)
            os.remove(base + ".dat")
            missing = [0, 1, codec.total_shards - 3, codec.total_shards - 1]
            best = ("pread", 0.0)
            for rep in range(2):
                order = routes if rep % 2 == 0 else routes[::-1]
                for name in order:
                    for i in missing:
                        try:
                            os.remove(base + to_ext(i))
                        except OSError:
                            pass
                    t0 = time.perf_counter()
                    try:
                        rebuild_ec_files(base, codec=codec, route=name)
                    except Exception:
                        continue
                    g = size / max(time.perf_counter() - t0, 1e-9)
                    if g > best[1]:
                        best = (name, g)
            _REBUILD_HOST_ROUTE = best[0]
        except Exception:
            _REBUILD_HOST_ROUTE = "pread"
        finally:
            if d is not None:
                shutil.rmtree(d, ignore_errors=True)
        return _REBUILD_HOST_ROUTE


def _rebuild_survey(base_file_name: str, codec) -> tuple[list[int], list[int]]:
    """(missing, present) shard ids for a rebuild, after sweeping any stale
    .ecNN.tmp torn outputs a crashed rebuild left behind. Raises when fewer
    than k survivors remain or survivors disagree on size (a truncated
    survivor would otherwise zero-fill into every rebuilt shard)."""
    k = codec.data_shards
    _sweep_stale_tmp(base_file_name, codec.total_shards)
    have = [
        os.path.exists(base_file_name + to_ext(i))
        for i in range(codec.total_shards)
    ]
    missing = [i for i, h in enumerate(have) if not h]
    present = [i for i, h in enumerate(have) if h]
    if missing and len(present) < k:
        raise ValueError(
            f"need at least {k} shards, only {len(present)} present"
        )
    sizes = {os.path.getsize(base_file_name + to_ext(i)) for i in present[:k]}
    if len(sizes) > 1:
        raise IOError(
            f"survivor shards disagree on size ({sorted(sizes)}): "
            "refusing to rebuild from a truncated survivor"
        )
    return missing, present


def rebuild_ec_files(
    base_file_name: str,
    codec=None,
    chunk: int = DEFAULT_CHUNK,
    pipeline: Optional[bool] = None,
    full_reconstruct: bool = False,
    route: Optional[str] = None,
) -> list[int]:
    """Reconstruct missing .ecNN files from survivors; returns the generated
    shard ids (ref RebuildEcFiles, ec_encoder.go:61,233-287).

    The repair-plane fast path (the decode analogue of the encode pipeline):

    - **missing-rows-only decode** — reconstruct_rows slices the decode
      matrix to the missing ids (4 output rows instead of 14 on a 4-loss
      rebuild; 1 on the common single-loss), with the composed matrix
      cached in galois.DECODE_ROWS_CACHE across chunks AND rebuilds;
    - **only k survivors read** — a single-loss rebuild reads 10 shards,
      not all 13 present;
    - **pipelined** (pipeline=None -> on with >1 CPU or a device codec):
      double-buffered reader / decode pool / in-order writer, mirroring
      _encode_streamed, with preadv into reused buffers (no per-chunk
      allocations) and zero-copy memoryview writes;
    - **atomic outputs** — rebuilt shards stream to .ecNN.tmp and are
      renamed into place only after the whole rebuild succeeds, so a
      failure (short read, ENOSPC, crash) can no longer leave a truncated
      .ecNN that later counts as a "present" survivor.

    Per-stage walls land in LAST_REBUILD_STAGES and the
    ec_rebuild_stage_seconds metric; the executed structure in
    LAST_REBUILD_ROUTE. route=None picks the host structure by a one-time
    measured race (_calibrate_rebuild_route: pread vs mmap vs fused
    onepass); route="pread"/"mmap"/"onepass" forces one.
    full_reconstruct=True keeps the old all-rows codec.reconstruct per
    chunk (the benchmark's reference leg).

    Serialized per volume base (process-wide): a concurrent second rebuild
    of the same volume waits, re-surveys, and returns [] — it can never
    interleave with the first one's .tmp outputs.
    """
    with _base_rebuild_lock(base_file_name):
        return _rebuild_ec_files_unlocked(
            base_file_name, codec, chunk, pipeline, full_reconstruct, route
        )


def _rebuild_ec_files_unlocked(
    base_file_name: str,
    codec,
    chunk: int,
    pipeline: Optional[bool],
    full_reconstruct: bool,
    route: Optional[str],
) -> list[int]:
    import time as _time

    codec = _get_codec(codec)
    LAST_REBUILD_STAGES.clear()
    t_enter = _time.perf_counter()
    missing, present = _rebuild_survey(base_file_name, codec)
    if not missing:
        return []
    k = codec.data_shards
    total = codec.total_shards
    survivors = present[:k]
    shard_size = os.path.getsize(base_file_name + to_ext(survivors[0]))
    if pipeline is None:
        from ...util import available_cpus

        pipeline = available_cpus() > 1 or getattr(codec, "is_device", False)
    # structure selection: route=None on a zero-copy host codec runs the
    # one-time measured race (_calibrate_rebuild_route) and remembers the
    # winner — "onepass" (fused NT-store sweep), "mmap" (zero-copy survivor
    # views + write() outputs) or "pread" (buffered reads); an explicit
    # route skips the race (the race's own legs, benchmarks, tests)
    if (
        route is None
        and not full_reconstruct
        and shard_size > 0
        and getattr(codec, "zero_copy_rows", False)
        and not getattr(codec, "is_device", False)
    ):
        _t_cal = _time.perf_counter()
        route = _calibrate_rebuild_route(codec)
        cal = _time.perf_counter() - _t_cal
        if cal > 1e-3:
            # first rebuild per process runs the race (whose legs wrote
            # their own stage walls): start the outer run's stages fresh
            # and disclose the race so sums still reconcile with total_s
            LAST_REBUILD_STAGES.clear()
            LAST_REBUILD_STAGES["calibrate_s"] = round(cal, 3)
    use_mmap = route == "mmap"

    if route == "onepass" and _rebuild_onepass(
        base_file_name, codec, survivors, missing, shard_size, chunk
    ):
        for i in missing:
            os.replace(
                base_file_name + to_ext(i) + ".tmp", base_file_name + to_ext(i)
            )
        LAST_REBUILD_ROUTE.clear()
        LAST_REBUILD_ROUTE.update({"route": "onepass", "pipeline": False})
        # fused kernel: read/decode/write interleave in one sweep
        LAST_REBUILD_STAGES["fused_s"] = _time.perf_counter() - t_enter
        LAST_REBUILD_STAGES["total_s"] = LAST_REBUILD_STAGES["fused_s"]
        try:
            from ...util.metrics import EC_REBUILD_STAGE_SECONDS

            EC_REBUILD_STAGE_SECONDS.observe(
                LAST_REBUILD_STAGES["total_s"], stage="total"
            )
        except ImportError:
            pass
        return missing

    def decode_slots(
        slots: list, width: int, out: Optional[np.ndarray] = None
    ) -> list[np.ndarray]:
        t0 = _time.perf_counter()
        if full_reconstruct:
            full = codec.reconstruct(slots)
            outs = [np.ascontiguousarray(full[i]) for i in missing]
        else:
            outs = [
                np.ascontiguousarray(o)
                for o in codec.reconstruct_rows(
                    slots, missing,
                    out=out[:, :width] if out is not None else None,
                )
            ]
        _rebuild_stage_add("decode_s", _time.perf_counter() - t0)
        return outs

    def decode_chunk(
        buf: np.ndarray, width: int, out: Optional[np.ndarray] = None
    ) -> list[np.ndarray]:
        slots: list[Optional[np.ndarray]] = [None] * total
        for j, i in enumerate(survivors):
            slots[i] = buf[j, :width]
        return decode_slots(slots, width, out)

    inputs = {i: open(base_file_name + to_ext(i), "rb") for i in survivors}
    outputs = {
        i: open(base_file_name + to_ext(i) + ".tmp", "wb") for i in missing
    }
    LAST_REBUILD_ROUTE.clear()
    LAST_REBUILD_ROUTE.update(
        {"route": "mmap" if use_mmap else "pread", "pipeline": bool(pipeline)}
    )
    ok = False
    try:
        if use_mmap:
            _rebuild_mmap(
                inputs, outputs, survivors, missing, total, shard_size,
                chunk, decode_slots, codec, pipeline,
            )
        elif pipeline and shard_size > chunk:
            _rebuild_pipelined(
                inputs, outputs, survivors, missing, shard_size, chunk,
                decode_chunk, codec,
            )
        else:
            buf_w = min(chunk, max(shard_size, 1))
            buf = np.empty((k, buf_w), dtype=np.uint8)
            out_buf = np.empty((len(missing), buf_w), dtype=np.uint8)
            offset = 0
            while offset < shard_size:
                width = min(chunk, shard_size - offset)
                t0 = _time.perf_counter()
                for j, i in enumerate(survivors):
                    _read_exact(inputs[i], buf[j, :width], offset)
                _rebuild_stage_add("read_s", _time.perf_counter() - t0)
                outs = decode_chunk(buf, width, out_buf)
                t0 = _time.perf_counter()
                for r, i in enumerate(missing):
                    outputs[i].write(outs[r].data)
                _rebuild_stage_add("write_s", _time.perf_counter() - t0)
                offset += width
        ok = True
    finally:
        for f in inputs.values():
            f.close()
        for f in outputs.values():
            f.close()
        if ok:
            for i in missing:
                os.replace(
                    base_file_name + to_ext(i) + ".tmp",
                    base_file_name + to_ext(i),
                )
        else:
            for i in missing:
                try:
                    os.remove(base_file_name + to_ext(i) + ".tmp")
                except OSError:
                    pass
        LAST_REBUILD_STAGES["total_s"] = _time.perf_counter() - t_enter
        if "sync_s" in LAST_REBUILD_STAGES:
            # streamed ring ran: the blocking (main-thread) stages
            # partition the wall — decode_s/write_s are overlapped walls.
            # On the mmap route read_s is worker-side view assembly (~0),
            # so the sum stays an honest main-thread account either way.
            blocking = ("read_s", "stage_s", "sync_s", "calibrate_s")
            if "pipeline_depth" in LAST_REBUILD_STAGES:
                LAST_REBUILD_ROUTE["pipeline_depth"] = LAST_REBUILD_STAGES[
                    "pipeline_depth"
                ]
        else:
            blocking = ("read_s", "decode_s", "write_s", "fused_s",
                        "calibrate_s")
        LAST_REBUILD_STAGES["coverage_of_wall"] = round(
            sum(LAST_REBUILD_STAGES.get(s, 0.0) for s in blocking)
            / max(LAST_REBUILD_STAGES["total_s"], 1e-9),
            3,
        )
        try:
            from ...util.metrics import EC_REBUILD_STAGE_SECONDS

            for stage in ("read_s", "decode_s", "write_s", "total_s"):
                if stage in LAST_REBUILD_STAGES:
                    EC_REBUILD_STAGE_SECONDS.observe(
                        LAST_REBUILD_STAGES[stage], stage=stage[:-2]
                    )
        except ImportError:
            pass
    return missing


def _rebuild_onepass(
    base_file_name: str,
    codec,
    survivors: list[int],
    missing: list[int],
    shard_size: int,
    chunk: int,
) -> bool:
    """Fused single-pass rebuild: ONE streaming read of the mmapped
    survivors produces every missing shard — each 64-byte survivor column
    is folded through the composed decode rows into non-temporal stores
    straight into the mmapped .ecNN.tmp outputs. gf_encode_copy with the
    data-copy destinations disabled IS the decode kernel: `matrix` is the
    (missing x k) decode-rows matrix instead of the parity generator, so
    the repair plane gets the encode plane's ~2.4-bytes-of-traffic-per-
    source-byte path (no read buffer, no write() copy, no RFO on stores).

    Writes land in .tmp files the caller renames on success. Returns False
    (with any partial .tmp removed) when the fused kernel is unavailable
    or refuses the geometry; the caller falls back to the split routes."""
    from ... import native

    if not native.encode_copy_available():
        return False
    from .galois import DECODE_ROWS_CACHE

    rows = DECODE_ROWS_CACHE.rows_for(codec.matrix, survivors, missing)
    k = rows.shape[1]
    if rows.shape[0] > 8 or k > 32:
        return False  # same register-blocking cap as the fused encode

    import mmap as mmap_mod

    matrix = np.ascontiguousarray(rows, dtype=np.uint8)
    in_files = []
    in_maps = []
    out_files = []
    out_maps = []
    ok = False
    try:
        src_base = []
        for i in survivors:
            f = open(base_file_name + to_ext(i), "rb")
            in_files.append(f)
            mm = mmap_mod.mmap(
                f.fileno(), shard_size, access=mmap_mod.ACCESS_READ
            )
            in_maps.append(mm)
            src_base.append(
                int(np.frombuffer(mm, dtype=np.uint8).ctypes.data)
            )
        out_base = []
        for i in missing:
            f = open(base_file_name + to_ext(i) + ".tmp", "wb+")
            out_files.append(f)
            try:
                os.posix_fallocate(f.fileno(), 0, shard_size)
            except OSError:
                return False  # fall back to write()-based routes (ENOSPC
                # surfaces as OSError there, not SIGBUS)
            mm = mmap_mod.mmap(
                f.fileno(), shard_size, access=mmap_mod.ACCESS_WRITE
            )
            out_maps.append(mm)
            out_base.append(
                int(np.frombuffer(mm, dtype=np.uint8).ctypes.data)
            )

        no_copy = [None] * k

        def run_range(offset: int, width: int) -> None:
            srcs = [b + offset for b in src_base]
            dsts = [b + offset for b in out_base]
            if not native.gf_encode_copy_native(
                matrix, srcs, no_copy, dsts, width
            ):
                raise RuntimeError("fused decode kernel refused the call")

        items = []
        offset = 0
        while offset < shard_size:
            width = min(chunk, shard_size - offset)
            items.append((offset, width))
            offset += width
        from ...util import available_cpus

        ncpu = available_cpus()
        if ncpu > 1 and len(items) > 1:
            import concurrent.futures as cf

            with cf.ThreadPoolExecutor(min(ncpu, 8)) as pool:
                for f in [pool.submit(run_range, *it) for it in items]:
                    f.result()
        else:
            for off, width in items:
                run_range(off, width)
        ok = True
        return True
    except Exception as e:
        from ...util.log import warning

        warning("onepass rebuild aborted (%s); using split routes", e)
        return False
    finally:
        for mm in out_maps + in_maps:
            try:
                mm.close()
            except (BufferError, ValueError):
                pass
        for f in out_files + in_files:
            f.close()
        if not ok:
            for i in missing:
                try:
                    os.remove(base_file_name + to_ext(i) + ".tmp")
                except OSError:
                    pass


def _rebuild_ring(
    shard_size: int, chunk: int, workers: int, allocate, stage, decode,
    write_outs,
) -> None:
    """The streamed ring both pipelined rebuild routes share (the rebuild
    mirror of _encode_streamed): `allocate()` builds one slot's buffers,
    `stage(offset, width, bufs)` runs in the MAIN thread (survivor reads;
    a no-op on the mmap route), `decode(offset, width, bufs)` runs on the
    pool, and a dedicated writer thread calls `write_outs(outs)` in stream
    order — so chunk i+1's survivor read overlaps chunk i's decode AND
    chunk i-1's shard writes. A slot recycles only after its decode result
    is written, bounding memory at (workers+2) slots with zero
    steady-state allocation. stage_s (free-slot waits + handoff) and
    sync_s (final drain) land in LAST_REBUILD_STAGES next to the
    read_s/decode_s/write_s the callbacks record; pipeline_depth too."""
    import concurrent.futures as cf
    import queue as queue_mod
    import time as _time

    depth = max(1, workers)
    freeq: queue_mod.Queue = queue_mod.Queue()
    for _ in range(depth + 2):
        freeq.put(allocate())
    outq: queue_mod.Queue = queue_mod.Queue()
    err: list = [None]

    def writer() -> None:
        while True:
            entry = outq.get()
            if entry is None:
                return
            bufs, fut = entry
            try:
                write_outs(fut.result())
            except BaseException as e:  # keep consuming: the main thread
                # must never deadlock on a dead writer's unreturned slots
                if err[0] is None:
                    err[0] = e
            finally:
                freeq.put(bufs)

    writer_t = threading.Thread(
        target=writer, name="ec-rebuild-writer", daemon=True
    )
    writer_t.start()
    with _REBUILD_STAGE_LOCK:
        LAST_REBUILD_STAGES["pipeline_depth"] = depth
    try:
        with cf.ThreadPoolExecutor(depth) as pool:
            offset = 0
            while offset < shard_size and err[0] is None:
                width = min(chunk, shard_size - offset)
                t0 = _time.perf_counter()
                bufs = freeq.get()
                _rebuild_stage_add("stage_s", _time.perf_counter() - t0)
                stage(offset, width, bufs)
                t0 = _time.perf_counter()
                outq.put((bufs, pool.submit(decode, offset, width, bufs)))
                _rebuild_stage_add("stage_s", _time.perf_counter() - t0)
                offset += width
            t0 = _time.perf_counter()
            outq.put(None)
            writer_t.join()
        _rebuild_stage_add("sync_s", _time.perf_counter() - t0)
    finally:
        if writer_t.is_alive():
            outq.put(None)
            writer_t.join()
    if err[0] is not None:
        raise err[0]


def _rebuild_mmap(
    inputs: dict,
    outputs: dict,
    survivors: list[int],
    missing: list[int],
    total: int,
    shard_size: int,
    chunk: int,
    decode_slots,
    codec,
    pipeline: bool,
) -> None:
    """Rebuild with mmapped survivors: decode consumes zero-copy row views
    of the shard files (page-cache pages go straight into the row-pointer
    matmul — no read buffer, no read copy), the writer streams outputs in
    order. read_s stays ~0 by construction: source page faults are taken
    INSIDE decode_s, the same disclosure the encode mmap route makes."""
    import mmap as mmap_mod
    import time as _time

    maps = []
    arrs: dict = {}
    try:
        for i in survivors:
            mm = mmap_mod.mmap(
                inputs[i].fileno(), shard_size, access=mmap_mod.ACCESS_READ
            )
            maps.append(mm)
            arrs[i] = np.frombuffer(mm, dtype=np.uint8)

        n_miss = len(missing)

        def decode_at(offset: int, width: int, out) -> list[np.ndarray]:
            t0 = _time.perf_counter()
            slots: list = [None] * total
            for i in survivors:
                slots[i] = arrs[i][offset : offset + width]
            _rebuild_stage_add("read_s", _time.perf_counter() - t0)
            return decode_slots(slots, width, out)

        def write_outs(outs: list) -> None:
            t0 = _time.perf_counter()
            for r, i in enumerate(missing):
                outputs[i].write(outs[r].data)
            _rebuild_stage_add("write_s", _time.perf_counter() - t0)

        if pipeline and shard_size > chunk:
            _rebuild_ring(
                shard_size, chunk,
                max(2, getattr(codec, "pipeline_workers", 2)),
                allocate=lambda: np.empty((n_miss, chunk), dtype=np.uint8),
                stage=lambda offset, width, out: None,  # reads are the
                # decode's own zero-copy view access
                decode=decode_at,
                write_outs=write_outs,
            )
        else:
            out = np.empty((n_miss, min(chunk, shard_size)), dtype=np.uint8)
            offset = 0
            while offset < shard_size:
                width = min(chunk, shard_size - offset)
                write_outs(decode_at(offset, width, out))
                offset += width
    finally:
        arrs = None
        for mm in maps:
            try:
                mm.close()
            except (BufferError, ValueError):
                pass


def _rebuild_pipelined(
    inputs: dict,
    outputs: dict,
    survivors: list[int],
    missing: list[int],
    shard_size: int,
    chunk: int,
    decode_chunk,
    codec,
) -> None:
    """Double-buffered rebuild loop: the main thread streams survivor reads
    (preadv into a recycled buffer ring) and in-order shard writes while a
    small pool runs the decode matmul — the structure _encode_streamed
    proved out, pointed at the decode matrix (ring discipline shared with
    the mmap route via _rebuild_ring)."""
    import time as _time

    k = len(survivors)

    def allocate():
        return (
            np.empty((k, chunk), dtype=np.uint8),
            np.empty((len(missing), chunk), dtype=np.uint8),
        )

    def stage(offset: int, width: int, bufs) -> None:
        buf, _out = bufs
        t0 = _time.perf_counter()
        for j, i in enumerate(survivors):
            _read_exact(inputs[i], buf[j, :width], offset)
        _rebuild_stage_add("read_s", _time.perf_counter() - t0)

    def decode(offset: int, width: int, bufs):
        buf, out = bufs
        return decode_chunk(buf, width, out)

    def write_outs(outs) -> None:
        t0 = _time.perf_counter()
        for r, i in enumerate(missing):
            outputs[i].write(outs[r].data)
        _rebuild_stage_add("write_s", _time.perf_counter() - t0)

    _rebuild_ring(
        shard_size, chunk, max(2, getattr(codec, "pipeline_workers", 2)),
        allocate, stage, decode, write_outs,
    )


def rebuild_ec_files_multi(
    base_file_names,
    codec=None,
    chunk: int = DEFAULT_CHUNK,
    workers: Optional[int] = None,
    mesh=None,
) -> dict:
    """Rebuild MANY volumes' missing shards; returns {base: rebuilt ids}.

    The repair-plane analogue of write_ec_files_multi: host codecs rebuild
    whole volumes concurrently across cores (each on the single-thread
    fast path); device codecs concatenate same-decode-matrix chunks from
    different volumes along the column axis into ONE wide dispatch — after
    a node death every volume that lost the same shard ids shares one
    matrix, so a single device launch serves the whole fleet's round.
    `mesh` routes those batches through the (vol, blk) device mesh
    (parallel.sharded_ec.sharded_reconstruct_padded), the multi-chip leg.
    """
    import concurrent.futures as cf
    from collections import deque

    codec = _get_codec(codec)
    k = codec.data_shards
    results: dict = {}
    if mesh is None and not getattr(codec, "is_device", False):
        from ...util import available_cpus

        n_workers = max(
            1, min(len(base_file_names), workers or available_cpus())
        )

        # several volumes: one single-thread rebuild per core (parallelism
        # comes from the volume axis); a LONE volume keeps the per-volume
        # pipelined fast path — it has no sibling to share cores with
        per_vol_pipeline = None if len(base_file_names) == 1 else False

        def one(base: str):
            return base, rebuild_ec_files(
                base, codec=codec, chunk=chunk, pipeline=per_vol_pipeline
            )

        if n_workers == 1:
            for base in base_file_names:
                results[base] = one(base)[1]
            return results
        with cf.ThreadPoolExecutor(n_workers) as pool:
            for base, ids in pool.map(one, base_file_names):
                results[base] = ids
        return results

    width_cap = max(chunk, getattr(codec, "preferred_chunk", chunk))
    vols = []  # mutable per-volume state dicts
    ok = False
    import contextlib

    locks = contextlib.ExitStack()
    # sorted acquisition: two concurrent multi-rebuilds over overlapping
    # volume sets take the per-base locks in the same order
    for base in sorted(set(base_file_names)):
        locks.enter_context(_base_rebuild_lock(base))
    try:
        for base in base_file_names:
            missing, present = _rebuild_survey(base, codec)
            if not missing:
                results[base] = []
                continue
            survivors = present[:k]
            vols.append(
                {
                    "base": base,
                    "missing": missing,
                    "survivors": survivors,
                    "shard_size": os.path.getsize(base + to_ext(survivors[0])),
                    "offset": 0,
                    "inputs": {
                        i: open(base + to_ext(i), "rb") for i in survivors
                    },
                    "outputs": {
                        i: open(base + to_ext(i) + ".tmp", "wb")
                        for i in missing
                    },
                }
            )

        def rounds():
            active = list(vols)
            while active:
                produced = []
                for v in active:
                    if v["offset"] < v["shard_size"]:
                        width = min(chunk, v["shard_size"] - v["offset"])
                        produced.append((v, v["offset"], width))
                        v["offset"] += width
                if not produced:
                    return
                # one decode matrix per (survivor set, missing set): only
                # same-matrix same-width pieces can share a dispatch
                groups: dict = {}
                for v, off, width in produced:
                    key = (tuple(v["survivors"]), tuple(v["missing"]), width)
                    groups.setdefault(key, []).append((v, off))
                for (surv, miss, width), items in sorted(groups.items()):
                    per_batch = max(1, width_cap // width)
                    for s in range(0, len(items), per_batch):
                        yield surv, miss, width, items[s : s + per_batch]
                active = [v for v, _off, _w in produced]

        def read_batch(surv, width, items) -> np.ndarray:
            buf = np.empty((k, len(items) * width), dtype=np.uint8)
            for j, (v, off) in enumerate(items):
                c0 = j * width
                for row, i in enumerate(surv):
                    _read_exact(
                        v["inputs"][i], buf[row, c0 : c0 + width], off
                    )
            return buf

        def decode_batch(rows: np.ndarray, buf: np.ndarray, width: int):
            if mesh is not None:
                from ...parallel.sharded_ec import sharded_reconstruct_padded

                g = buf.shape[1] // width
                stacked = np.ascontiguousarray(
                    buf.reshape(k, g, width).transpose(1, 0, 2)
                )
                out = sharded_reconstruct_padded(rows, stacked, mesh)
                # back to [R, G*width] column-concat layout for the writer
                return np.ascontiguousarray(
                    out.transpose(1, 0, 2).reshape(rows.shape[0], -1)
                )
            return np.ascontiguousarray(codec.apply_matrix(rows, buf))

        from .galois import DECODE_ROWS_CACHE

        depth = max(1, workers or 2)  # device pipeline depth
        with cf.ThreadPoolExecutor(depth) as pool:
            pending: deque = deque()

            def drain() -> None:
                miss, width, items, fut = pending.popleft()
                out = fut.result()
                for j, (v, _off) in enumerate(items):
                    sl = slice(j * width, (j + 1) * width)
                    for r, i in enumerate(miss):
                        v["outputs"][i].write(out[r, sl].data)

            for surv, miss, width, items in rounds():
                rows = DECODE_ROWS_CACHE.rows_for(
                    codec.matrix, list(surv), list(miss)
                )
                buf = read_batch(surv, width, items)
                pending.append(
                    (miss, width, items,
                     pool.submit(decode_batch, rows, buf, width))
                )
                while len(pending) > depth:
                    drain()
            while pending:
                drain()
        ok = True
    finally:
        for v in vols:
            for f in v["inputs"].values():
                f.close()
            for f in v["outputs"].values():
                f.close()
            for i in v["missing"]:
                tmp = v["base"] + to_ext(i) + ".tmp"
                if ok:
                    os.replace(tmp, v["base"] + to_ext(i))
                else:
                    try:
                        os.remove(tmp)
                    except OSError:
                        pass
        locks.close()
    for v in vols:
        results[v["base"]] = v["missing"]
    return results


def write_dat_file(
    base_file_name: str, dat_file_size: int, data_shards: int = DATA_SHARDS_COUNT
) -> None:
    """Interleave-copy the data shards -> .dat (ref WriteDatFile,
    ec_decoder.go:157-195)."""
    inputs = [
        open(base_file_name + to_ext(i), "rb") for i in range(data_shards)
    ]
    # one reused copy buffer for the whole decode: readinto + memoryview
    # writes, so the interleave copy allocates nothing per 4MiB chunk
    # (the old read() path allocated a fresh bytes object for every one)
    buf = memoryview(bytearray(4 * 1024 * 1024))
    try:
        with open(base_file_name + ".dat", "wb") as dat:
            remaining = dat_file_size
            while remaining >= data_shards * EC_LARGE_BLOCK_SIZE:
                for i in range(data_shards):
                    _copy_n(inputs[i], dat, EC_LARGE_BLOCK_SIZE, buf=buf)
                    remaining -= EC_LARGE_BLOCK_SIZE
            while remaining > 0:
                for i in range(data_shards):
                    to_read = min(remaining, EC_SMALL_BLOCK_SIZE)
                    if to_read <= 0:
                        break
                    _copy_n(inputs[i], dat, to_read, buf=buf)
                    remaining -= to_read
                    # skip the zero padding of this small block
                    if to_read < EC_SMALL_BLOCK_SIZE:
                        inputs[i].seek(EC_SMALL_BLOCK_SIZE - to_read, 1)
    finally:
        for f in inputs:
            f.close()


def _copy_n(
    src, dst, n: int, bufsize: int = 4 * 1024 * 1024, buf=None
) -> None:
    """Copy exactly n bytes src -> dst through `buf` (a reusable memoryview;
    allocated here when the caller doesn't pass one)."""
    if buf is None:
        buf = memoryview(bytearray(min(bufsize, n)))
    while n > 0:
        want = min(len(buf), n)
        if hasattr(src, "readinto"):
            got = src.readinto(buf[:want])
        else:
            b = src.read(want)
            got = len(b)
            buf[:got] = b
        if not got:
            raise IOError("short read during ec decode copy")
        dst.write(buf[:got])
        n -= got


def iterate_ecj_file(base_file_name: str):
    """Yield deleted needle ids from the .ecj journal
    (ref iterateEcjFile, ec_decoder.go:123-150)."""
    path = base_file_name + ".ecj"
    if not os.path.exists(path):
        return
    from ...types import bytes_to_u64, NEEDLE_ID_SIZE

    with open(path, "rb") as f:
        while True:
            b = f.read(NEEDLE_ID_SIZE)
            if len(b) != NEEDLE_ID_SIZE:
                return
            yield bytes_to_u64(b)


def write_idx_file_from_ec_index(base_file_name: str) -> None:
    """.ecx + .ecj -> .idx (ref WriteIdxFileFromEcIndex, ec_decoder.go:18-43)."""
    with open(base_file_name + ".ecx", "rb") as src, open(
        base_file_name + ".idx", "wb"
    ) as dst:
        while True:
            b = src.read(1 << 20)
            if not b:
                break
            dst.write(b)
        for key in iterate_ecj_file(base_file_name):
            dst.write(entry_to_bytes(key, 0, TOMBSTONE_FILE_SIZE))


def read_ec_volume_version(base_file_name: str) -> int:
    """Volume version from the .ec00 super block (ref readEcVolumeVersion)."""
    with open(base_file_name + ".ec00", "rb") as f:
        return SuperBlock.parse(f.read(8)).version


def find_dat_file_size(base_file_name: str) -> int:
    """Original .dat size = max end-offset over live .ecx entries
    (ref FindDatFileSize, ec_decoder.go:48-70)."""
    version = read_ec_volume_version(base_file_name)
    dat_size = 0
    with open(base_file_name + ".ecx", "rb") as f:
        for key, offset_units, size in iter_index(f):
            if size == TOMBSTONE_FILE_SIZE:
                continue
            stop = to_actual_offset(offset_units) + get_actual_size(size, version)
            if stop > dat_size:
                dat_size = stop
    return dat_size
