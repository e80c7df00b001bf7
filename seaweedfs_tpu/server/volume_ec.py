"""Volume-server EC handlers: the 9 EC RPCs + the distributed EC read path.

RPC surface mirrors the reference (ref: weed/server/
volume_grpc_erasure_coding.go:39-391): Generate / Rebuild / Copy / Delete /
Mount / Unmount / ShardRead(stream) / BlobDelete / ShardsToVolume.

Read path (ref: weed/storage/store_ec.go:119-373): locate the needle via the
local sorted .ecx, map to shard intervals, read each interval from a local
shard, else a remote shard holder (VolumeEcShardRead stream), else
reconstruct on the fly from any 10 shards through the RS codec (the TPU
kernel when storage.backend=tpu). Shard locations come from the master's
LookupEcVolume, cached with a TTL refresh.
"""

from __future__ import annotations

import asyncio
import contextvars
import os
import random
import threading
import time
from typing import Optional

import numpy as np

from ..pb import grpc_address
from ..pb.rpc import Stub
from ..util.backoff import (
    BackoffPolicy,
    deadline_after,
    remaining,
    shared_retry_budget,
)
from ..util import trace
from ..util.metrics import (
    EC_DEGRADED_READ_SECONDS,
    EC_DEGRADED_READ_STAGE_SECONDS,
    EC_DEGRADED_READ_WORKER_SECONDS,
    EC_ENCODE_BATCH_FALLBACKS,
    EC_ENCODE_BYTES,
    EC_GENERATE_SECONDS,
    EC_NEEDLE_READS,
    EC_READ_INTERVALS,
    EC_READ_STAGE_SECONDS,
    EC_RECONSTRUCT_LOCAL_READS,
    EC_RECONSTRUCT_SURVIVOR_BYTES,
    EC_RECONSTRUCTIONS,
    EC_REMOTE_ATTEMPTS,
    EC_REMOTE_SHARD_READ_BYTES,
    EC_REMOTE_SHARD_READ_STREAMS,
    EC_REMOTE_SHARD_READS,
    EC_SHARD_COPY_BYTES,
    EC_SHARD_COPY_SECONDS,
    EC_SHARD_READ_SERVED_BYTES,
    REQUEST_HISTOGRAM,
    RETRY_COUNTER,
)
from ..storage.erasure_coding import (
    DATA_SHARDS_COUNT,
    EC_LARGE_BLOCK_SIZE,
    EC_SMALL_BLOCK_SIZE,
    TOTAL_SHARDS_COUNT,
    rebuild_ec_files,
    rebuild_ec_files_multi,
    to_ext,
    write_dat_file,
    write_ec_files,
    write_idx_file_from_ec_index,
    write_sorted_file_from_idx,
    find_dat_file_size,
)
from ..storage.erasure_coding.ec_volume import (
    EcVolume,
    EcVolumeShard,
    NeedleNotFound,
    ShardBits,
    rebuild_ecx_file,
)
from ..storage.needle import Needle, get_actual_size
from ..storage.volume import volume_base_name
from ..storage.volume_info import VolumeInfo, save_volume_info
from ..types import TOMBSTONE_FILE_SIZE, to_actual_offset

# seconds between LookupEcVolume refreshes; upstream keeps the table 11 s
# while fewer than 10 shards are known (ref store_ec.go:218-259)
SHARD_LOCATION_TTL = 10.0


def _locations_fresh(ev: EcVolume) -> bool:
    """The location table is the master's answer of the last TTL."""
    return time.time() - ev.shard_locations_refresh_time < SHARD_LOCATION_TTL


def _read_stage(label: str, annotate: bool = True):
    return trace.stage(
        "ec.read." + label,
        EC_DEGRADED_READ_STAGE_SECONDS.child(stage=label),
        annotate=annotate,
        label=label,
    )


# the stages of a cold degraded read (_recover_one_interval), bound once;
# their count is ec_reconstructions_total{kind="cold"}. Counters only
# where an await or a thread hand-off lets other requests run inside:
# survivor_read is the wall of the gathers of remote survivors on the loop
# (none where every survivor is local) plus the worker's wall filling the
# decode's input rows (its leaf, each shard read into a row, is the
# `ec.read.pread` event, on the worker's thread), executor_wait runs from
# run_in_executor to the worker's first line, decode is the worker's wall
# around reconstruct_rows alone (its leaves are the codec's `rs.*` events),
# loop_resume runs from the worker's last line to the coroutine's first line
# after the hop (the loop coming round to the finished future). With
# cache_put they add up to ec_degraded_read_seconds{result="cold"}.
# Before a reconstruct is tried at all, remote_attempts: the TTL'd
# location refresh (one LookupEcVolume a SHARD_LOCATION_TTL, a dict look-up
# otherwise) and, only where the table names a holder of the shard, the
# holders asked and the forced refreshes after they failed. A shard the
# fresh table gives nobody costs a read the look-up alone; how often that
# is, is ec_remote_attempts_total{outcome}. Inside survivor_read,
# remote_read: the wall of each survivor fetched from another server (from
# the asking for its VolumeEcShardRead stream to a holder the table lists
# until its span is in, retries included), counted a survivor by
# ec_remote_shard_reads_total{outcome} whichever stream carried it; the
# streams themselves are ec_remote_shard_read_streams_total{shape}, and
# under a sampled request each is the child span `ec.read.remote_read`,
# which names its shards and its holder
_ST_REMOTE_ATTEMPTS = _read_stage("remote_attempts", annotate=False)
_ST_SURVIVOR_READ = _read_stage("survivor_read", annotate=False)
_ST_REMOTE_READ = _read_stage("remote_read", annotate=False)
_STREAMS_GROUPED = EC_REMOTE_SHARD_READ_STREAMS.child(shape="grouped")
_STREAMS_SINGLE = EC_REMOTE_SHARD_READ_STREAMS.child(shape="single")
_STREAMS_REGROUPED = EC_REMOTE_SHARD_READ_STREAMS.child(shape="regrouped")
_SHARD_READ_SERVED = REQUEST_HISTOGRAM.child(
    server="volume", operation="VolumeEcShardRead"
)
_ST_PREAD = trace.stage("ec.read.pread")
_ST_EXECUTOR_WAIT = _read_stage("executor_wait", annotate=False)
_ST_DECODE = _read_stage("decode", annotate=False)
_ST_LOOP_RESUME = _read_stage("loop_resume", annotate=False)
_ST_CACHE_PUT = _read_stage("cache_put")
# the worker's whole wall beside its CPU: wall without CPU is the worker
# waiting (the interpreter lock, the device, the disk)
_WORKER_WALL = EC_DEGRADED_READ_WORKER_SECONDS.child(clock="wall")
_WORKER_CPU = EC_DEGRADED_READ_WORKER_SECONDS.child(clock="cpu")
# a needle read whole, outside any reconstruct: each interval counted once
# by what served it (_INTERVAL[source]), the needle once by kind, and the
# three pieces of work the loop does itself: the .ecx binary search that
# locates the needle (in the index's mapping: ec_index_lookups_total says
# so), the synchronous pread of an interval on a local shard (up to a
# block: 1 MiB of a chunk needle) and the assembly (join, parse, CRC over
# the whole record). All are annotated leaves; under a sampled request each
# is a child span
INTERVAL_SOURCES = ("local", "cold_tier", "remote", "reconstructed", "cache")
_INTERVAL = {
    source: EC_READ_INTERVALS.child(source=source) for source in INTERVAL_SOURCES
}
_NEEDLE_HEALTHY = EC_NEEDLE_READS.child(kind="healthy")
_NEEDLE_DEGRADED = EC_NEEDLE_READS.child(kind="degraded")
_SURVIVOR_BYTES_LOCAL = EC_RECONSTRUCT_SURVIVOR_BYTES.child(origin="local")
_SURVIVOR_BYTES_REMOTE = EC_RECONSTRUCT_SURVIVOR_BYTES.child(origin="remote")
# a local survivor span read for a cold reconstruct, by the thread the read
# ran on, as the read itself sees it: one that runs an event loop, or not
_LOCAL_READS = {
    where: EC_RECONSTRUCT_LOCAL_READS.child(where=where)
    for where in ("worker", "loop")
}
_ST_LOCATE = trace.stage(
    "ec.read.locate",
    EC_READ_STAGE_SECONDS.child(stage="locate"),
    label="locate",
)
_ST_LOCAL_INTERVAL = trace.stage(
    "ec.read.local_interval",
    EC_READ_STAGE_SECONDS.child(stage="local_interval"),
    label="local_interval",
)
_ST_ASSEMBLE = trace.stage(
    "ec.read.assemble",
    EC_READ_STAGE_SECONDS.child(stage="assemble"),
    label="assemble",
)


def _count_remote_survivor(t0: float, data: Optional[bytes], size: int) -> None:
    """One survivor asked of another server at `t0`, by what came back."""
    _ST_REMOTE_READ.since(t0)
    if data is None:
        EC_REMOTE_SHARD_READS.inc(outcome="failed")
    elif len(data) != size:
        EC_REMOTE_SHARD_READS.inc(outcome="short")
    else:
        EC_REMOTE_SHARD_READS.inc(outcome="ok")
        EC_REMOTE_SHARD_READ_BYTES.inc(size)


# total wall-clock budget for one EC needle read, across every interval,
# remote attempt, location refresh and reconstruction; each remote RPC gets
# the REMAINDER of this budget as its timeout instead of a bare 30s
EC_READ_DEADLINE_SECONDS = float(
    os.environ.get("SEAWEEDFS_TPU_EC_READ_DEADLINE", "15.0")
)
# per-url remote-read retry: quick second chance for transient resets; the
# deadline, not the attempt count, is the real bound
EC_REMOTE_READ_POLICY = BackoffPolicy(base=0.02, cap=0.25, attempts=2)
# rounds of (force-refresh locations, re-attempt remote reads) after every
# LISTED holder failed, before falling back to reconstruction: the list may
# be stale. A shard nobody is listed for never starts them
EC_REFRESH_ROUNDS = 2

# degraded-read interval cache: reconstructed spans kept per server so
# repeated reads of a dead shard stop re-paying the survivor fetch + decode
EC_DEGRADED_CACHE_BYTES = (
    int(os.environ.get("SEAWEEDFS_TPU_EC_DEGRADED_CACHE_MB", "16")) << 20
)
# reconstruction granularity: intervals are widened to this alignment
# (readahead — neighbouring needles on the same dead shard land in one
# reconstructed span)
EC_DEGRADED_SPAN = 128 * 1024
# the alignment of a reconstruct that has to fetch survivors from other
# servers: every byte of readahead then crosses gRPC once a remote survivor
# (six times a span of a 4/4/3/3 spread), where a local survivor's is a
# page-cache pread; the reference reads the interval alone (store_ec.go:319)
EC_REMOTE_SPAN = 16 * 1024


_WORKER = threading.local()


def _survivor_rows(k: int, width: int) -> np.ndarray:
    """uint8[k, width], C-contiguous, for the calling worker thread to read
    a reconstruct's survivors into and hand to the codec, which uploads it
    as it is. The thread's own array, used again by its next reconstruct (a
    decode has fetched its answer before it returns, so nothing reads the
    rows after that; memory the runtime has seen before uploads faster than
    fresh pages): what it held stays in it, so a row is valid as far as it
    was filled and no further. Rows wider than a small block (an interval
    inside a large block) get an array of their own, so that a thread keeps
    k small blocks at most."""
    if width > EC_SMALL_BLOCK_SIZE:
        return np.empty((k, width), dtype=np.uint8)
    buf = getattr(_WORKER, "rows", None)
    if buf is None or buf.size < k * width:
        buf = _WORKER.rows = np.empty(k * width, dtype=np.uint8)
    return buf[: k * width].reshape(k, width)


class DegradedIntervalCache:
    """Byte-bounded LRU of reconstructed shard spans, keyed by
    (volume_id, shard_id, span_start).

    A degraded read widens its interval to EC_DEGRADED_SPAN alignment
    (EC_REMOTE_SPAN where survivors come from other servers) before
    reconstructing, caches the whole span, and serves any later
    interval that falls inside a cached span — so a hot dead shard costs
    one fetch+decode per span instead of per needle. Tombstones invalidate
    the volume's spans (reconstructed bytes may include the deleted
    needle's data; correctness of the tombstone answer comes from the .ecx
    check upstream, but the cache must not outlive the journal write).
    """

    def __init__(self, capacity_bytes: int = EC_DEGRADED_CACHE_BYTES):
        import threading
        from collections import OrderedDict

        self.capacity = capacity_bytes
        self._spans: "OrderedDict[tuple, bytes]" = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()

    @staticmethod
    def span_for(
        offset: int, size: int, shard_size: Optional[int],
        align: int = EC_DEGRADED_SPAN,
    ) -> tuple[int, int]:
        """Aligned (span_start, span_size) covering [offset, offset+size);
        no readahead when the shard size is unknown (an over-long survivor
        fetch past EOF would read short and poison the reconstruction)."""
        if not shard_size or offset + size > shard_size:
            return offset, size
        start = offset - (offset % align)
        end = offset + size
        end += (-end) % align
        return start, min(end, shard_size) - start

    def get(
        self, vid: int, shard_id: int, offset: int, size: int
    ) -> Optional[bytes]:
        starts = (
            offset - (offset % EC_DEGRADED_SPAN),
            offset - (offset % EC_REMOTE_SPAN),
            offset,
        )
        with self._lock:
            for key in ((vid, shard_id, start) for start in starts):
                span = self._spans.get(key)
                if span is not None and key[2] + len(span) >= offset + size:
                    self._spans.move_to_end(key)
                    return span[offset - key[2] : offset - key[2] + size]
        return None

    def put(self, vid: int, shard_id: int, span_start: int, data: bytes) -> None:
        key = (vid, shard_id, span_start)
        with self._lock:
            old = self._spans.pop(key, None)
            if old is not None:
                self._bytes -= len(old)
            self._spans[key] = data
            self._bytes += len(data)
            while self._bytes > self.capacity and self._spans:
                _k, v = self._spans.popitem(last=False)
                self._bytes -= len(v)

    def invalidate(self, vid: int) -> int:
        """Drop every cached span of a volume (on .ecj tombstone writes);
        returns how many spans were dropped."""
        with self._lock:
            doomed = [k for k in self._spans if k[0] == vid]
            for k in doomed:
                self._bytes -= len(self._spans.pop(k))
            return len(doomed)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


class EcHandlers:
    """Mixin for VolumeServer (expects .store, .master, .codec, .address)."""

    def register_ec_rpcs(self, svc) -> None:
        svc.unary("VolumeEcShardsGenerate")(self._grpc_ec_generate)
        svc.unary("VolumeEcShardsGenerateBatch")(self._grpc_ec_generate_batch)
        svc.unary("VolumeEcShardsRebuild")(self._grpc_ec_rebuild)
        svc.unary("VolumeEcShardsRebuildBatch")(self._grpc_ec_rebuild_batch)
        svc.unary("VolumeEcShardsCopy")(self._grpc_ec_copy)
        svc.unary("VolumeEcShardsDelete")(self._grpc_ec_delete)
        svc.unary("VolumeEcShardsMount")(self._grpc_ec_mount)
        svc.unary("VolumeEcShardsUnmount")(self._grpc_ec_unmount)
        svc.server_stream("VolumeEcShardRead")(self._grpc_ec_shard_read)
        svc.unary("VolumeEcBlobDelete")(self._grpc_ec_blob_delete)
        svc.unary("VolumeEcShardsToVolume")(self._grpc_ec_shards_to_volume)
        svc.unary("VolumeEcShardsInfo")(self._grpc_ec_info)
        svc.unary("VolumeEcShardsOffload")(self._grpc_ec_offload)
        svc.unary("VolumeEcShardsRecall")(self._grpc_ec_recall)

    def _base_name(self, collection: str, vid: int) -> Optional[str]:
        v = self.store.find_volume(vid)
        if v is not None:
            return v.file_name()
        for loc in self.store.locations:
            base = volume_base_name(loc.directory, collection, vid)
            if any(
                os.path.exists(base + ext) for ext in (".ecx", ".dat", ".ec00")
            ):
                return base
        return None

    # ---------------- RPCs ----------------
    async def _grpc_ec_generate(self, req, context) -> dict:
        """.dat/.idx -> .ecNN + .ecx + .vif (ref :39-75).

        Optional data_shards/parity_shards select an alternate RS geometry
        (6.3 / 12.4); the geometry is persisted in the .vif so readers and
        rebuilds recover it (our extension — the reference fixes 10.4 at
        compile time, ec_encoder.go:17-23)."""
        t0 = time.perf_counter()
        try:
            return await self._ec_generate(req)
        finally:
            EC_GENERATE_SECONDS.inc(time.perf_counter() - t0, rpc="single")

    async def _ec_generate(self, req) -> dict:
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        data_shards = int(req.get("data_shards", 0))
        parity_shards = int(req.get("parity_shards", 0))
        base = self._base_name(collection, vid)
        if base is None:
            return {"error": f"volume {vid} not found"}
        codec = (
            self.codec_for(data_shards, parity_shards)
            if data_shards
            else self.codec
        )
        loop = asyncio.get_event_loop()
        try:
            # background-plane callers (lifecycle auto-EC) tag the request
            # with their plane: the encode's read volume is charged to the
            # shared maintenance budget BEFORE the I/O burst, so encode
            # traffic competes with scrub/vacuum/repair under one cap and
            # yields to foreground pressure (arxiv 1709.05365)
            if req.get("plane"):
                try:
                    dat_size = os.path.getsize(base + ".dat")
                except OSError:
                    dat_size = 0
                await self._charge_maintenance(dat_size, plane=req["plane"])
            run = await loop.run_in_executor(
                None, lambda: write_ec_files(base, codec=codec)
            )
            await loop.run_in_executor(None, write_sorted_file_from_idx, base)
            v = self.store.find_volume(vid)
            save_volume_info(
                base + ".vif",
                VolumeInfo(
                    version=v.version if v else 3,
                    data_shards=data_shards,
                    parity_shards=parity_shards,
                ),
            )
            # which executor ran the kernel stage, from THIS run's own
            # route ("device" only when a TPU ran it): two encodes in
            # flight never label each other's bytes. Counted once the
            # volume is whole: one that reports an error is not counted
            EC_ENCODE_BYTES.inc(
                os.path.getsize(base + ".dat"),
                backend=run.route.get("kernel", "host"),
            )
            return {}
        except Exception as e:
            return {"error": str(e)}

    async def _grpc_ec_generate_batch(self, req, context) -> dict:
        """Batched multi-volume encode: all requested local volumes are
        converted by ONE call of write_ec_files_multi, each through the
        encode pipeline (write_ec_files): a device codec's one after
        another, a host codec's at once.
        Our extension; the reference encodes volumes serially, one
        RPC each (command_ec_encode.go:110-135). Returns per-volume errors
        keyed by id; volumes absent from `errors` succeeded.

        What the one-volume RPC guarantees holds here: the shard files
        appear under their final names only when
        whole (commit by rename, as in write_ec_files), every volume's
        bytes are counted under the backend of the run that encoded THEM,
        and a batch that fails is counted
        (ec_encode_batch_fallback_total{reason}) and logged before the
        volumes it had not finished are converted one by one, so that one
        broken volume reports its own error under its own id and its
        neighbours finish."""
        t0 = time.perf_counter()
        try:
            return await self._ec_generate_batch(req)
        finally:
            EC_GENERATE_SECONDS.inc(time.perf_counter() - t0, rpc="batch")

    async def _ec_generate_batch(self, req) -> dict:
        vids = [int(v) for v in req.get("volume_ids", [])]
        collection = req.get("collection", "")
        data_shards = int(req.get("data_shards", 0))
        parity_shards = int(req.get("parity_shards", 0))
        errors: dict = {}
        bases = []
        for vid in vids:
            base = self._base_name(collection, vid)
            if base is None:
                errors[str(vid)] = f"volume {vid} not found"
            else:
                bases.append((vid, base))
        if not bases:
            return {"errors": errors}
        codec = (
            self.codec_for(data_shards, parity_shards)
            if data_shards
            else self.codec
        )
        from ..storage.erasure_coding import write_ec_files_multi

        loop = asyncio.get_event_loop()
        if req.get("plane"):
            total = 0
            for _vid, b in bases:
                try:
                    total += os.path.getsize(b + ".dat")
                except OSError:
                    pass
            await self._charge_maintenance(total, plane=req["plane"])
        runs: dict = {}  # vid -> the EncodeRun that encoded it
        try:
            got = await loop.run_in_executor(
                None,
                lambda: write_ec_files_multi(
                    [b for _vid, b in bases], codec=codec
                ),
            )
            runs = {vid: run for (vid, _b), run in zip(bases, got)}
        except Exception as e:
            # never silent: a device error in the batch shows on /metrics
            # and in the log before the volumes go one by one
            EC_ENCODE_BATCH_FALLBACKS.inc(
                reason="io" if isinstance(e, OSError) else "codec"
            )
            from ..util.log import warning

            warning(
                "ec generate batch %s failed (%s: %s); converting its "
                "volumes one by one", [v for v, _b in bases],
                type(e).__name__, e,
            )
            # the volumes the batch finished before it failed are whole
            runs = {
                vid: run
                for (vid, _b), run in zip(bases, getattr(e, "encoded", []))
            }
            for vid, base in bases[len(runs):]:
                try:
                    runs[vid] = await loop.run_in_executor(
                        None, lambda b=base: write_ec_files(b, codec=codec)
                    )
                except Exception as e1:
                    errors[str(vid)] = str(e1)
        for vid, base in bases:
            run = runs.get(vid)
            if run is None:
                continue
            try:
                await loop.run_in_executor(
                    None, write_sorted_file_from_idx, base
                )
                v = self.store.find_volume(vid)
                save_volume_info(
                    base + ".vif",
                    VolumeInfo(
                        version=v.version if v else 3,
                        data_shards=data_shards,
                        parity_shards=parity_shards,
                    ),
                )
                EC_ENCODE_BYTES.inc(
                    os.path.getsize(base + ".dat"),
                    backend=run.route.get("kernel", "host"),
                )
            except Exception as e:
                errors[str(vid)] = str(e)
        return {"errors": errors}

    async def _grpc_ec_rebuild(self, req, context) -> dict:
        """Rebuild missing local shards from >=10 present (ref :77-106)."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        base = self._base_name(collection, vid)
        if base is None:
            return {"error": f"volume {vid} not found"}
        codec = self._codec_from_vif(base)
        # survey BEFORE rebuilding: if a concurrent rebuild of this volume
        # (e.g. a retried batch) commits first, rebuild_ec_files waits on
        # the per-base lock and returns [] — the caller must still learn
        # which of ITS missing shards now exist so it can mount them
        pre_missing = [
            i
            for i in range(codec.total_shards)
            if not os.path.exists(base + to_ext(i))
        ]
        loop = asyncio.get_event_loop()
        try:
            await loop.run_in_executor(
                None, lambda: rebuild_ec_files(base, codec=codec)
            )
            rebuilt = [
                i for i in pre_missing if os.path.exists(base + to_ext(i))
            ]
            return {"rebuilt_shard_ids": rebuilt}
        except Exception as e:
            return {"error": str(e)}

    async def _grpc_ec_rebuild_batch(self, req, context) -> dict:
        """Rebuild missing shards of MANY local EC volumes in one call:
        volumes sharing an RS geometry stream through rebuild_ec_files_multi
        (device codecs batch same-decode-matrix chunks across volumes into
        wide dispatches; host codecs rebuild volumes across cores). Our
        extension — the reference rebuilds one volume per RPC
        (command_ec_rebuild.go:97-244). Returns per-volume results/errors;
        a volume that fails batched is retried alone so one broken survivor
        set cannot sink its neighbours."""
        vids = [int(v) for v in req.get("volume_ids", [])]
        collection = req.get("collection", "")
        results: dict = {}
        errors: dict = {}
        by_codec: dict = {}
        for vid in vids:
            base = self._base_name(collection, vid)
            if base is None:
                errors[str(vid)] = f"volume {vid} not found"
                continue
            codec = self._codec_from_vif(base)
            by_codec.setdefault(id(codec), (codec, []))[1].append((vid, base))
        loop = asyncio.get_event_loop()
        for codec, group in by_codec.values():
            # survey the missing sets BEFORE rebuilding: a partially
            # committed batch (per-volume atomic renames) followed by a
            # per-volume retry would otherwise report [] for the volumes
            # the batch already fixed, and the caller would never mount
            # their rebuilt shards
            pre_missing = {
                vid: [
                    i
                    for i in range(codec.total_shards)
                    if not os.path.exists(base + to_ext(i))
                ]
                for vid, base in group
            }
            try:
                await loop.run_in_executor(
                    None,
                    lambda c=codec, g=group: rebuild_ec_files_multi(
                        [b for _vid, b in g], codec=c
                    ),
                )
                for vid, base in group:
                    results[str(vid)] = {"rebuilt_shard_ids": pre_missing[vid]}
            except Exception:
                for vid, base in group:
                    try:
                        await loop.run_in_executor(
                            None,
                            lambda b=base, c=codec: rebuild_ec_files(b, codec=c),
                        )
                        results[str(vid)] = {
                            "rebuilt_shard_ids": pre_missing[vid]
                        }
                    except Exception as e:
                        errors[str(vid)] = str(e)
        return {"results": results, "errors": errors}

    async def _grpc_ec_info(self, req, context) -> dict:
        """RS geometry of a local EC volume from its .vif (our extension;
        heartbeats carry only shard bitmaps, so geometry-aware shell
        commands ask a shard holder)."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        base = self._base_name(collection, vid)
        if base is None:
            return {"error": f"volume {vid} not found"}
        from ..storage.volume_info import load_volume_info

        info = load_volume_info(base + ".vif")
        k = info.data_shards if info and info.data_shards else DATA_SHARDS_COUNT
        m = (
            info.parity_shards
            if info and info.data_shards
            else TOTAL_SHARDS_COUNT - DATA_SHARDS_COUNT
        )
        return {"data_shards": k, "parity_shards": m}

    def _codec_from_vif(self, base: str):
        """Codec matching the geometry persisted in the .vif (10.4 default)."""
        from ..storage.volume_info import load_volume_info

        info = load_volume_info(base + ".vif")
        if info is not None and info.data_shards:
            return self.codec_for(info.data_shards, info.parity_shards)
        return self.codec

    async def _grpc_ec_copy(self, req, context) -> dict:
        """Pull shards (+ index files) from a source server via its CopyFile
        stream (ref :108-164)."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        shard_ids = [int(s) for s in req.get("shard_ids", [])]
        source = req["source_data_node"]
        # repair pulls by default; the lifecycle dispatcher tags its
        # spread/collect copies plane="lifecycle" for budget attribution
        plane = req.get("plane") or "repair"
        loc = max(
            self.store.locations,
            key=lambda l: l.max_volume_count - len(l.volumes),
        )
        base = volume_base_name(loc.directory, collection, vid)
        stub = Stub(grpc_address(source), "volume")
        t0 = time.perf_counter()

        async def pull(ext: str) -> None:
            tmp = base + ext + ".tmp"
            with open(tmp, "wb") as f:
                async for msg in stub.server_stream(
                    "CopyFile",
                    {"volume_id": vid, "collection": collection, "ext": ext,
                     "is_ec_volume": True},
                ):
                    if msg.get("error"):
                        raise IOError(msg["error"])
                    chunk = msg.get("file_content", b"")
                    # survivor-shard pulls share the maintenance budget
                    # with scrub + vacuum (one cap over all planes)
                    await self._charge_maintenance(len(chunk), plane=plane)
                    f.write(chunk)
                    EC_SHARD_COPY_BYTES.inc(len(chunk))
            os.replace(tmp, base + ext)

        try:
            for shard_id in shard_ids:
                await pull(to_ext(shard_id))
            if req.get("copy_ecx_file", True):
                await pull(".ecx")
                try:
                    await pull(".ecj")
                except Exception:
                    with open(base + ".ecj", "wb"):
                        pass
                try:
                    await pull(".vif")
                except Exception:
                    save_volume_info(base + ".vif", VolumeInfo(version=3))
            return {}
        except Exception as e:
            return {"error": str(e)}
        finally:
            EC_SHARD_COPY_SECONDS.inc(time.perf_counter() - t0)

    async def _grpc_ec_delete(self, req, context) -> dict:
        """Remove local shard files; drop index files with the last shard
        (ref :166-216)."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        shard_ids = [int(s) for s in req.get("shard_ids", [])]
        base = self._base_name(collection, vid)
        if base is None:
            return {}
        # cached degraded-read spans may embed this generation's bytes
        self._ec_degraded_cache().invalidate(vid)
        self._cold_cache().invalidate(vid)
        # cold tier: an explicitly deleted OFFLOADED shard must drop its
        # remote object and manifest entry too (manifest uncommit FIRST —
        # a crash between the two leaves an orphaned remote blob, never a
        # manifest naming a deleted one)
        from ..storage import cold_tier, tier_backend

        manifest = cold_tier.load_manifest(base)
        ev = self.store.find_ec_volume(vid)
        doomed = [sid for sid in shard_ids if sid in manifest]
        for sid in doomed:
            ent = manifest.pop(sid)
            cold_tier.save_manifest(base, manifest)
            if ev is not None:
                ev.note_shard_recalled(sid)  # drops the in-memory entry
            backend = tier_backend.get_backend(ent.get("backend", ""))
            if backend is not None:
                try:
                    await asyncio.get_event_loop().run_in_executor(
                        None, backend.delete_file, ent["key"]
                    )
                except Exception:
                    pass  # an orphaned blob is bytes, never lost data
        for shard_id in shard_ids:
            try:
                os.remove(base + to_ext(shard_id))
            except FileNotFoundError:
                pass
        remaining = [
            i for i in range(32) if os.path.exists(base + to_ext(i))
        ] or sorted(manifest)
        if not remaining:
            for ext in (".ecx", ".ecj", ".vif", ".ctm"):
                try:
                    os.remove(base + ext)
                except FileNotFoundError:
                    pass
        return {}

    async def _grpc_ec_mount(self, req, context) -> dict:
        """(ref :218-244)"""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        shard_ids = [int(s) for s in req.get("shard_ids", [])]
        added = ShardBits()
        try:
            for shard_id in shard_ids:
                for loc in self.store.locations:
                    base = volume_base_name(loc.directory, collection, vid)
                    if os.path.exists(base + to_ext(shard_id)):
                        loc.load_ec_shard(collection, vid, shard_id)
                        added = added.add(shard_id)
                        break
            if added.bits:
                self.store.note_ec_shards_changed(
                    vid, collection, added, ShardBits()
                )
            return {}
        except Exception as e:
            return {"error": str(e)}

    async def _grpc_ec_unmount(self, req, context) -> dict:
        """(ref :246-268)"""
        vid = int(req["volume_id"])
        shard_ids = [int(s) for s in req.get("shard_ids", [])]
        self._ec_degraded_cache().invalidate(vid)
        self._cold_cache().invalidate(vid)
        removed = ShardBits()
        for shard_id in shard_ids:
            for loc in self.store.locations:
                if loc.unload_ec_shard(vid, shard_id):
                    removed = removed.add(shard_id)
                    break
        if removed.bits:
            self.store.note_ec_shards_changed(vid, "", ShardBits(), removed)
        return {}

    def _served_shard(self, vid: int, shard_id: int) -> tuple:
        """(shard, cold_ev) of a shard this server streams to others: the
        mounted shard, or the volume of one it offloaded to the cold tier —
        read through the read-through cache, so a repairing or
        degraded-reading neighbour doesn't force a recall; (None, None)
        where it has neither."""
        shard = self.store.find_ec_shard(vid, shard_id)
        if shard is not None:
            return shard, None
        ev = self.store.find_ec_volume(vid)
        if ev is not None and ev.remote_shard(shard_id) is not None:
            return None, ev
        return None, None

    async def _grpc_ec_shard_read(self, req, context):
        """Stream bytes of one local shard (ref :270-325). With `shard_ids`
        (our extension: the survivors of one reconstruct that this server
        holds) the same [offset, offset+size) of each shard in turn over the
        one stream, after one liveness check; every message then carries its
        `shard_id`, and a shard this server does not hold is answered with
        an `error` of its own and the stream goes on to the next."""
        t0 = time.perf_counter()
        try:
            vid = int(req["volume_id"])
            offset = int(req.get("offset", 0))
            size = int(req.get("size", 0))
            group = req.get("shard_ids")
            served = [
                (int(s), self._served_shard(vid, int(s)))
                for s in group or (req["shard_id"],)
            ]
            if not group and served[0][1] == (None, None):
                yield {"error": f"ec shard {vid}.{served[0][0]} not found"}
                return
            # optional liveness check of the whole needle (ref :283-298)
            if req.get("file_key"):
                ev = self.store.find_ec_volume(vid)
                if ev is not None:
                    try:
                        _, nsize = ev.find_needle_from_ecx(int(req["file_key"]))
                        if nsize == TOMBSTONE_FILE_SIZE:
                            yield {"is_deleted": True}
                            return
                    except NeedleNotFound:
                        pass
            for shard_id, (shard, cold_ev) in served:
                tag = {"shard_id": shard_id} if group else {}
                if shard is None and cold_ev is None:
                    yield {**tag, "error": f"ec shard {vid}.{shard_id} not found"}
                    continue
                remaining = size
                pos = offset
                while remaining > 0:
                    if cold_ev is not None:
                        chunk = await self._read_cold_interval(
                            cold_ev, shard_id, pos, min(1 << 20, remaining)
                        )
                        if chunk is None:
                            yield {
                                **tag,
                                "error": f"ec shard {vid}.{shard_id}: remote "
                                "tier read failed",
                            }
                            break
                    else:
                        chunk = shard.read_at(min(1 << 20, remaining), pos)
                    if not chunk:
                        break
                    EC_SHARD_READ_SERVED_BYTES.inc(len(chunk))
                    yield {**tag, "data": chunk}
                    pos += len(chunk)
                    remaining -= len(chunk)
        finally:
            _SHARD_READ_SERVED.observe(time.perf_counter() - t0)

    async def _grpc_ec_blob_delete(self, req, context) -> dict:
        """Tombstone a needle in the local .ecx/.ecj (ref :327-352)."""
        vid = int(req["volume_id"])
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            return {}
        loop = asyncio.get_event_loop()
        await loop.run_in_executor(
            None, ev.delete_needle_from_ecx, int(req["file_key"])
        )
        self._note_ec_tombstone(ev)
        return {}

    async def _grpc_ec_shards_to_volume(self, req, context) -> dict:
        """Decode local data shards back into a normal volume (ref :354-391)."""
        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        base = self._base_name(collection, vid)
        if base is None or not os.path.exists(base + ".ecx"):
            return {"error": f"ec volume {vid} not found"}
        # the vid returns to (and may later re-leave) the normal-volume
        # world: cached spans must not survive into the next generation
        self._ec_degraded_cache().invalidate(vid)
        self._cold_cache().invalidate(vid)
        codec = self._codec_from_vif(base)
        missing = [
            i
            for i in range(codec.data_shards)
            if not os.path.exists(base + to_ext(i))
        ]
        if missing:
            return {"error": f"need all data shards locally to decode, missing {missing}"}
        loop = asyncio.get_event_loop()
        try:
            dat_size = await loop.run_in_executor(None, find_dat_file_size, base)
            # re-inflation I/O rides the shared maintenance budget when a
            # background plane dispatched it (decode reads ~dat_size of
            # shards and writes dat_size back)
            if req.get("plane"):
                await self._charge_maintenance(
                    2 * dat_size, plane=req["plane"]
                )
            await loop.run_in_executor(
                None, write_dat_file, base, dat_size, codec.data_shards
            )
            await loop.run_in_executor(None, write_idx_file_from_ec_index, base)
            return {}
        except Exception as e:
            return {"error": str(e)}

    # ---------------- EC read path (ref store_ec.go:119-373) ----------------
    async def _refresh_shard_locations(
        self, ev: EcVolume, force: bool = False
    ) -> None:
        """Bring ev.shard_locations up to the master's answer: unforced
        only once the table is older than SHARD_LOCATION_TTL. At most one
        LookupEcVolume is in flight a volume: a caller that arrives while
        one is out awaits its answer instead of sending its own."""
        if not force and _locations_fresh(ev):
            return
        lookup = ev.shard_locations_lookup
        if lookup is None or lookup.done():
            lookup = ev.shard_locations_lookup = asyncio.ensure_future(
                self._lookup_shard_locations(ev)
            )
        # shielded: a caller that is cancelled (its client hung up) must not
        # take the answer away from the others waiting on it
        await asyncio.shield(lookup)

    async def _lookup_shard_locations(self, ev: EcVolume) -> None:
        now = time.time()
        stub = Stub(grpc_address(self.master), "master")
        try:
            resp = await stub.call("LookupEcVolume", {"volume_id": ev.volume_id})
        except Exception:
            return
        if resp.get("error"):
            return
        with ev.shard_locations_lock:
            ev.shard_locations.clear()
            for entry in resp.get("shard_id_locations", []):
                ev.shard_locations[int(entry["shard_id"])] = [
                    l["url"] for l in entry["locations"]
                ]
            ev.shard_locations_refresh_time = now

    def _remote_holders(self, ev: EcVolume, shard_id: int) -> list[str]:
        """Who the location table names for the shard, less this server."""
        with ev.shard_locations_lock:
            return [
                url
                for url in ev.shard_locations.get(shard_id, ())
                if url not in (self.address, self.public_url)
            ]

    class _Deleted(Exception):
        """Needle tombstoned on a remote holder: a definitive answer, not
        a failure — must short-circuit retries and reconstruction."""

    async def _read_remote_shard_once(
        self, ev: EcVolume, url: str, shard_id: int, offset: int, size: int,
        file_key: int, deadline: Optional[float], sent=None,
    ) -> bytes:
        stub = Stub(grpc_address(url), "volume")
        buf = bytearray()
        if sent is not None:
            sent.inc()
        async for msg in stub.server_stream(
            "VolumeEcShardRead",
            {
                "volume_id": ev.volume_id,
                "shard_id": shard_id,
                "offset": offset,
                "size": size,
                "file_key": file_key,
            },
            timeout=remaining(deadline, 30.0),
        ):
            if msg.get("error"):
                raise IOError(msg["error"])
            if msg.get("is_deleted"):
                raise EcHandlers._Deleted()
            buf.extend(msg.get("data", b""))
        return bytes(buf)

    async def _read_remote_shard_interval(
        self,
        ev: EcVolume,
        shard_id: int,
        offset: int,
        size: int,
        file_key: int,
        deadline: Optional[float] = None,
        sent=None,
    ) -> Optional[bytes]:
        """Try each known holder of the shard; per-url transient failures
        get one jittered retry, and every RPC's timeout is the remaining
        read deadline (a stalled holder can no longer eat a bare 30s of a
        15s read budget). Raises _Deleted on a tombstone answer. `sent`
        (a counter child) moves once a stream sent."""
        rng = getattr(self, "_backoff_rng", None)
        budget = shared_retry_budget()
        for url in self._remote_holders(ev, shard_id):
            for attempt in range(EC_REMOTE_READ_POLICY.attempts):
                if deadline is not None and time.monotonic() >= deadline:
                    return None
                try:
                    result = await self._read_remote_shard_once(
                        ev, url, shard_id, offset, size, file_key, deadline,
                        sent,
                    )
                except EcHandlers._Deleted:
                    raise
                except Exception:
                    if budget is not None:
                        budget.on_failure()
                    if attempt == EC_REMOTE_READ_POLICY.attempts - 1:
                        break  # next url
                    if budget is not None and not budget.allow(
                        "ec_remote_read"
                    ):
                        break  # budget dry: no second chance, next url
                    RETRY_COUNTER.inc(op="ec_remote_read")
                    d = EC_REMOTE_READ_POLICY.delay(
                        attempt, rng if rng is not None else random
                    )
                    if deadline is not None:
                        d = min(d, max(0.0, deadline - time.monotonic()))
                    await asyncio.sleep(d)
                else:
                    if budget is not None:
                        budget.on_success()
                    return result
        return None

    async def _read_remote_survivor(
        self, ev: EcVolume, shard_id: int, offset: int, size: int,
        file_key: int, deadline: Optional[float],
        sent=_STREAMS_SINGLE, t0: Optional[float] = None,
    ) -> Optional[bytes]:
        """One survivor span of a reconstruct from whoever the location
        table lists for the shard, counted by what came back; `sent` counts
        its streams. `t0` is when the survivor was first asked for, where
        that was on a grouped stream and it did not arrive whole."""
        holders = self._remote_holders(ev, shard_id)
        if t0 is None:
            t0 = time.perf_counter()
        with trace.span(
            "ec.read.remote_read", shards=str(shard_id),
            holder=",".join(holders),
        ):
            try:
                data = await self._read_remote_shard_interval(
                    ev, shard_id, offset, size, file_key, deadline,
                    sent=sent,
                )
            except EcHandlers._Deleted:
                data = None
        if data is None and not holders:
            # nobody to ask, nothing sent: no wait to count
            EC_REMOTE_SHARD_READS.inc(outcome="no_holder")
            return None
        _count_remote_survivor(t0, data, size)
        return data

    async def _read_remote_survivor_group(
        self, ev: EcVolume, url: str, shard_ids: list[int], offset: int,
        size: int, file_key: int, deadline: Optional[float],
    ) -> dict[int, Optional[bytes]]:
        """The same span of the survivors of a reconstruct that ONE holder
        lists, over one VolumeEcShardRead stream (`shard_ids`: the call
        costs a holder more than its bytes do), each counted as a survivor
        of its own by what came back for it. `shard_id` is the first of
        them, so a holder that does not know `shard_ids` serves that one as
        it always did; a shard that came back with an error, short or not at
        all, or whose stream raised, is asked for again alone
        (_read_remote_survivor: every listed holder, the retry, the
        deadline), and the ones that arrived whole are used. A tombstone
        answers for them all."""
        t0 = time.perf_counter()
        parts: dict[int, list] = {s: [] for s in shard_ids}
        deleted = False
        budget = shared_retry_budget()
        _STREAMS_GROUPED.inc()
        with trace.span(
            "ec.read.remote_read",
            shards=",".join(map(str, shard_ids)), holder=url,
        ):
            try:
                async for msg in Stub(grpc_address(url), "volume").server_stream(
                    "VolumeEcShardRead",
                    {
                        "volume_id": ev.volume_id,
                        "shard_id": shard_ids[0],
                        "shard_ids": shard_ids,
                        "offset": offset,
                        "size": size,
                        "file_key": file_key,
                    },
                    timeout=remaining(deadline, 30.0),
                ):
                    if msg.get("is_deleted"):
                        deleted = True
                        break
                    # an untagged message is of `shard_id`: the holder does
                    # not know `shard_ids`
                    got = parts.get(int(msg.get("shard_id", shard_ids[0])))
                    if got is not None:
                        # None: the shard is not whole, whatever else came
                        got.append(
                            None if msg.get("error") else msg.get("data", b"")
                        )
            except Exception:
                # what arrived whole before the stream broke is used; the
                # rest is asked for again, each alone
                if budget is not None:
                    budget.on_failure()
            else:
                if budget is not None:
                    budget.on_success()
        out: dict[int, Optional[bytes]] = {}
        again = []
        for shard_id, got in parts.items():
            whole = None not in got and sum(map(len, got)) == size
            if whole or deleted:
                # one message a span up to 1 MiB: the join is that message
                out[shard_id] = b"".join(got) if whole else None
                _count_remote_survivor(t0, out[shard_id], size)
            else:
                again.append(shard_id)
        out.update(zip(again, await asyncio.gather(*(
            self._read_remote_survivor(
                ev, s, offset, size, file_key, deadline,
                sent=_STREAMS_REGROUPED, t0=t0,
            )
            for s in again
        ))))
        return out

    async def _read_one_ec_interval(
        self,
        ev: EcVolume,
        shard_id: int,
        offset: int,
        size: int,
        file_key: int,
        deadline: Optional[float] = None,
        recovered: Optional[list] = None,
    ) -> Optional[bytes]:
        """One interval of a needle, counted by what served it; a shard id
        is appended to `recovered` where the bytes came out of
        `_recover_one_interval` (a reconstruct or its cache)."""
        shard = ev.find_shard(shard_id)
        if shard is not None:
            try:
                with _ST_LOCAL_INTERVAL() as st:
                    st.tag("shard", shard_id)
                    st.tag("bytes", size)
                    data = shard.read_at(size, offset)
                _INTERVAL["local"].inc()
                return data
            except OSError:
                # offload race: the shard moved to the remote tier between
                # find_shard and the pread (fd closed) — fall through to
                # the cold-tier read instead of erroring the request
                if ev.remote_shard(shard_id) is None:
                    raise
        # cold tier: a shard THIS server offloaded serves through the
        # byte-range read-through cache (one ranged remote GET per
        # readahead span, then page-cache-priced hits)
        data = await self._read_cold_interval(ev, shard_id, offset, size)
        if data is not None:
            _INTERVAL["cold_tier"].inc()
            return data
        if deadline is None:
            deadline = deadline_after(EC_READ_DEADLINE_SECONDS)
        with _ST_REMOTE_ATTEMPTS():
            await self._refresh_shard_locations(ev)
            nobody = _locations_fresh(ev) and not self._remote_holders(
                ev, shard_id
            )
        if nobody:
            # the master's fresh answer names nobody to ask: asking it again
            # cannot name one (ref store_ec.go:211-259 never looks up again
            # for want of a holder). One that appears is seen at the next
            # TTL refresh; until then reads reconstruct
            data = await self._recover_one_interval(
                ev, shard_id, offset, size, file_key, deadline
            )
            if data is not None:
                EC_REMOTE_ATTEMPTS.inc(outcome="no_holder")
                if recovered is not None:
                    recovered.append(shard_id)
                return data
            # short of survivors as well: the table is up to a TTL old, and
            # a holder of this shard or of a survivor may have come back
            # since. What is left is the path of a stale list
        with _ST_REMOTE_ATTEMPTS():
            try:
                data = await self._read_remote_shard_interval(
                    ev, shard_id, offset, size, file_key, deadline
                )
                # every listed holder failed (or the table could not be
                # refreshed): the cached locations may be stale (ref
                # store_ec.go:211 forgets failed shard locations);
                # force-refresh and retry in bounded rounds while the
                # deadline allows
                for _ in range(EC_REFRESH_ROUNDS):
                    if data is not None or time.monotonic() >= deadline:
                        break
                    RETRY_COUNTER.inc(op="ec_location_refresh")
                    await self._refresh_shard_locations(ev, force=True)
                    data = await self._read_remote_shard_interval(
                        ev, shard_id, offset, size, file_key, deadline
                    )
            except EcHandlers._Deleted:
                # a holder's definitive answer: the needle is gone
                EC_REMOTE_ATTEMPTS.inc(outcome="served")
                return None
            EC_REMOTE_ATTEMPTS.inc(
                outcome="failed" if data is None else "served"
            )
            if data is not None:
                _INTERVAL["remote"].inc()
                return data
        # degraded: reconstruct from any DATA_SHARDS_COUNT other shards
        # (ref store_ec.go:319-373)
        data = await self._recover_one_interval(
            ev, shard_id, offset, size, file_key, deadline
        )
        if data is not None and recovered is not None:
            recovered.append(shard_id)
        return data

    def codec_for(self, data_shards: int, parity_shards: int):
        """Geometry-specific codec on the configured backend, cached per
        (k, m) — the default self.codec stays the 10.4 instance."""
        if (
            data_shards == self.codec.data_shards
            and parity_shards == self.codec.parity_shards
        ):
            return self.codec
        cache = getattr(self, "_geometry_codecs", None)
        if cache is None:
            cache = self._geometry_codecs = {}
        key = (data_shards, parity_shards)
        if key not in cache:
            from ..tpu.coder import get_codec

            cache[key] = get_codec(self.codec_backend, data_shards, parity_shards)
        return cache[key]

    def _ec_degraded_cache(self) -> DegradedIntervalCache:
        cache = getattr(self, "_degraded_cache", None)
        if cache is None:
            cache = self._degraded_cache = DegradedIntervalCache()
        return cache

    # ---------------- cold tier (ISSUE 14) ----------------
    def _cold_cache(self):
        """Per-server byte-range read-through cache over offloaded shard
        extents (the DegradedIntervalCache pattern applied to the remote
        tier)."""
        cache = getattr(self, "_cold_extent_cache", None)
        if cache is None:
            from ..storage.cold_tier import RemoteExtentCache

            cache = self._cold_extent_cache = RemoteExtentCache()
        return cache

    async def _read_cold_interval(
        self, ev: EcVolume, shard_id: int, offset: int, size: int
    ) -> Optional[bytes]:
        """Read [offset, offset+size) of an OFFLOADED shard through the
        read-through cache; the blocking remote GET (urllib) runs in the
        executor. Returns None when the shard is not offloaded / backend
        unknown; remote failures surface as None too so the caller falls
        through to remote holders and reconstruction."""
        from ..storage import cold_tier, tier_backend

        if ev.remote_shard(shard_id) is None:
            return None
        loop = asyncio.get_event_loop()
        try:
            return await loop.run_in_executor(
                None,
                lambda: cold_tier.read_remote_extent(
                    ev,
                    shard_id,
                    offset,
                    size,
                    self._cold_cache(),
                    tier_backend.get_backend,
                ),
            )
        except Exception:
            return None

    async def _grpc_ec_offload(self, req, context) -> dict:
        """Move this server's LOCAL shard files of an EC volume onto the
        named remote backend (cold tier): upload → crash-safe manifest
        commit → unlink, per shard — no kill point loses the only copy.
        Transfer bytes are charged to the shared maintenance budget
        BEFORE the burst (plane from the request, lifecycle by default),
        so offload I/O yields under foreground pressure like every other
        background plane."""
        from ..storage import cold_tier, tier_backend

        vid = int(req["volume_id"])
        backend_name = req.get("backend", "")
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            return {"error": f"ec volume {vid} not found"}
        backend = tier_backend.get_backend(backend_name)
        if backend is None:
            return {
                "error": f"backend {backend_name!r} not registered, "
                f"supported: {sorted(tier_backend.BACKEND_STORAGES)}"
            }
        local = ev.shard_ids()
        if not local:
            return {"offloaded_shard_ids": [], "bytes": 0}
        # per-SHARD budget pacing (not one pre-burst lump): the transfer
        # itself is spread at the budget rate, so a multi-shard offload
        # cannot slam the serving loops with one unthrottled burst after
        # paying its whole charge up front
        from ..storage.maintenance import plane_bucket

        bucket = plane_bucket(req.get("plane") or "lifecycle")
        throttle = bucket.consume if bucket is not None else None
        loop = asyncio.get_event_loop()
        try:
            out = await loop.run_in_executor(
                None,
                lambda: cold_tier.offload_shards(
                    ev, backend, throttle=throttle
                ),
            )
        except Exception as e:
            return {"error": str(e)}
        # the union of (local | offloaded) bits is unchanged, so no
        # shard delta rides the heartbeat; the per-pulse ec_heat tick
        # carries the new split to the planner within seconds
        return {
            "offloaded_shard_ids": sorted(out),
            "bytes": sum(out.values()),
        }

    async def _grpc_ec_recall(self, req, context) -> dict:
        """Bring every offloaded shard of an EC volume back to local disk
        (download → atomic rename → manifest uncommit → remote delete,
        per shard), remount the shard files, and drop the volume's
        read-through spans. Recall I/O is budget-charged like offload."""
        from ..storage import cold_tier, tier_backend
        from ..util.metrics import TIER_RECALL_SECONDS

        vid = int(req["volume_id"])
        collection = req.get("collection", "")
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            return {"error": f"ec volume {vid} not found"}
        remote = dict(ev.remote_shards)
        if not remote:
            return {"recalled_shard_ids": [], "bytes": 0}
        t0 = time.perf_counter()
        from ..storage.maintenance import plane_bucket

        bucket = plane_bucket(req.get("plane") or "lifecycle")
        throttle = bucket.consume if bucket is not None else None
        loop = asyncio.get_event_loop()
        recall_err: Optional[Exception] = None
        out: dict = {}
        try:
            out = await loop.run_in_executor(
                None,
                lambda: cold_tier.recall_shards(
                    ev,
                    tier_backend.get_backend,
                    throttle=throttle,
                    delete_remote=bool(req.get("delete_remote", True)),
                ),
            )
        except Exception as e:
            recall_err = e
        # remount EVERY on-disk shard file that lacks a live
        # EcVolumeShard — not just this call's downloads: a PARTIAL
        # recall (failure after some shards landed) already dropped
        # those sids from the manifest, so a remount keyed off the
        # current call's result would leave them invisible (out of
        # ev.shards AND ev.remote_shards) until a server restart
        mount_errs = []
        for loc in self.store.locations:
            if loc.find_ec_volume(vid) is ev:
                for sid in range(32):
                    if ev.find_shard(sid) is not None:
                        continue
                    if not os.path.exists(ev.file_name() + to_ext(sid)):
                        continue
                    try:
                        ev.add_shard(
                            EcVolumeShard(
                                loc.directory, ev.collection, vid, sid
                            )
                        )
                    except OSError as e:
                        mount_errs.append(f"shard {sid}: {e}")
                break
        self._cold_cache().invalidate(vid)
        if recall_err is not None:
            return {"error": str(recall_err)}
        if mount_errs:
            return {"error": "remount " + "; ".join(mount_errs)}
        wall = time.perf_counter() - t0
        TIER_RECALL_SECONDS.observe(wall)
        return {
            "recalled_shard_ids": sorted(out),
            "bytes": sum(out.values()),
            "recall_s": round(wall, 4),
        }

    def _note_ec_tombstone(self, ev: EcVolume) -> None:
        """A needle was tombstoned in this volume's .ecx/.ecj: reconstructed
        spans may embed its bytes — drop them."""
        self._ec_degraded_cache().invalidate(ev.volume_id)

    async def _recover_one_interval(
        self, ev: EcVolume, missing_shard: int, offset: int, size: int,
        file_key: int, deadline: Optional[float] = None,
    ) -> Optional[bytes]:
        """Reconstruct [offset, offset+size) of a shard nobody can serve
        from the data_shards survivors with the lowest ids that can be had.
        Those on other servers or the cold tier are fetched CONCURRENTLY on
        the loop (one gather — wall clock is the slowest survivor, not the
        sum; the survivors one holder lists share one stream, a holder's
        call costing more than its bytes). Those on this server's disks are
        read by the worker thread that decodes, each span straight into its
        row of the array the codec uploads: no local read runs on the loop,
        none is a task, no spare is read unless a read fails, and no copy
        stands between the shard file and the upload. The decode is
        missing-row-only through the shared decode-matrix LRU, and the
        whole readahead-widened span is kept in the degraded-read cache so
        the next needle on this dead shard skips the fetch+decode entirely
        (ref store_ec.go:319-373 fetches, then reconstructs all rows, every
        time)."""
        t_start = time.perf_counter()
        cache = self._ec_degraded_cache()
        hit = cache.get(ev.volume_id, missing_shard, offset, size)
        if hit is not None:
            EC_RECONSTRUCTIONS.inc(kind="cache_hit")
            _INTERVAL["cache"].inc()
            EC_DEGRADED_READ_SECONDS.observe(
                time.perf_counter() - t_start, result="cache_hit"
            )
            return hit
        total, k = ev.total_shards, ev.data_shards
        candidates = [i for i in range(total) if i != missing_shard]
        local = [i for i in candidates if ev.find_shard(i) is not None]
        # survivors with somewhere to come from (the cold tier, a holder
        # the location table lists) before those the table gives nobody:
        # a lost shard among the first asked would cost a second round
        holders = {
            i: self._remote_holders(ev, i) for i in candidates if i not in local
        }
        remote = sorted(
            holders,
            key=lambda i: not (ev.remote_shard(i) is not None or holders[i]),
        )
        # local survivors count toward the data_shards first and are read
        # by the worker below, the lowest ids as far as they are needed
        # (ten spans with one disk lost, the two spares only where a read
        # comes short or raises). A remote survivor costs its span's bytes
        # over gRPC and its holder's CPU, and the gather waits for the
        # slowest of those asked: ask as many as the decode needs, none to
        # spare, widen to the rest only on a shortfall, and read ahead only
        # as far as EC_REMOTE_SPAN
        needed = max(0, k - len(local))
        span_start, span_size = cache.span_for(
            offset, size, ev.shard_size() or None,
            EC_REMOTE_SPAN if needed else EC_DEGRADED_SPAN,
        )
        fetched: dict[int, bytes] = {}  # whole spans that came from elsewhere

        def keep(shard_id: int, b: Optional[bytes]) -> None:
            if b is not None:
                _SURVIVOR_BYTES_REMOTE.inc(len(b))
                if len(b) == span_size:
                    fetched[shard_id] = b

        async def fetch(shard_id: int) -> None:
            if ev.remote_shard(shard_id) is not None:
                # cold tier: an offloaded survivor feeds reconstruction
                # through the read-through cache (one ranged remote GET)
                b = await self._read_cold_interval(
                    ev, shard_id, span_start, span_size
                )
            else:
                b = await self._read_remote_survivor(
                    ev, shard_id, span_start, span_size, file_key, deadline
                )
            keep(shard_id, b)

        async def fetch_group(url: str, shard_ids: list[int]) -> None:
            got = await self._read_remote_survivor_group(
                ev, url, shard_ids, span_start, span_size, file_key, deadline
            )
            for shard_id, b in got.items():
                keep(shard_id, b)

        first, rest = remote[:needed], remote[needed:]
        if first:
            # the survivors to fetch from other servers, by the holder the
            # table names first for each: two or more of one holder ride
            # one stream
            by_holder: dict[str, list[int]] = {}
            for i in first:
                if holders[i] and ev.remote_shard(i) is None:
                    by_holder.setdefault(holders[i][0], []).append(i)
            groups = {u: g for u, g in by_holder.items() if len(g) > 1}
            grouped = {i for g in groups.values() for i in g}
            with _ST_SURVIVOR_READ():
                await asyncio.gather(
                    *(fetch(i) for i in first if i not in grouped),
                    *(fetch_group(url, g) for url, g in groups.items()),
                )
        codec = self.codec_for(k, ev.parity_shards)
        loop = asyncio.get_event_loop()

        def fill() -> Optional[list]:
            """The k survivors with the lowest ids among the local shards
            and the fetched spans, row by row into the array the decode
            uploads, as reconstruct_rows takes them: a slot a shard id.
            None where fewer than k can be had: a local read that raises or
            comes short leaves its row to the next survivor."""
            granule = codec.row_granule()
            rows = _survivor_rows(k, -(-span_size // granule) * granule)
            shards: list[Optional[np.ndarray]] = [None] * total
            where = _LOCAL_READS[
                "worker" if asyncio._get_running_loop() is None else "loop"
            ]
            filled = read = 0
            for shard_id in sorted(fetched.keys() | set(local)):
                row = rows[filled, :span_size]
                b = fetched.get(shard_id)
                if b is not None:
                    row[:] = np.frombuffer(b, dtype=np.uint8)
                else:
                    shard = ev.find_shard(shard_id)
                    if shard is None:
                        continue  # unmounted since the plan was made
                    where.inc()
                    try:
                        with _ST_PREAD():
                            got = shard.read_into(row, span_start)
                    except OSError:
                        continue
                    read += got
                    if got != span_size:
                        continue
                shards[shard_id] = row
                filled += 1
                if filled == k:
                    break
            if read:
                _SURVIVOR_BYTES_LOCAL.inc(read)
            return shards if filled == k else None

        def rebuild(t_submit: float) -> tuple:
            """-> (the decoded rows, or None short of survivors; when the
            worker was done, which is where loop_resume starts)."""
            t0 = _ST_EXECUTOR_WAIT.since(t_submit)
            cpu0 = time.thread_time()
            rows = None
            with _ST_SURVIVOR_READ():
                shards = fill()
            if shards is not None:
                with _ST_DECODE():
                    rows = codec.reconstruct_rows(shards, [missing_shard])
            _WORKER_CPU.inc(time.thread_time() - cpu0)
            t_done = time.perf_counter()
            _WORKER_WALL.inc(t_done - t0)
            return rows, t_done

        decoded = None
        for more in (rest, ()):
            if len(local) + len(fetched) >= k:
                # in the request's context, so that under a sampled request
                # the worker's stages are child spans as the loop's are
                decoded, t_done = await loop.run_in_executor(
                    None, contextvars.copy_context().run, rebuild,
                    time.perf_counter(),
                )
                _ST_LOOP_RESUME.since(t_done)
                if decoded is not None:
                    break
            if not more:
                return None
            # short of survivors: ask whoever has not been asked yet
            with _ST_SURVIVOR_READ():
                await asyncio.gather(*(fetch(i) for i in more))
        out = decoded[0]
        if out is None:
            return None
        with _ST_CACHE_PUT():
            # the span alone: what lay past it in the worker's rows was
            # cut off by the codec
            span = np.ascontiguousarray(out).tobytes()
            cache.put(ev.volume_id, missing_shard, span_start, span)
        EC_RECONSTRUCTIONS.inc(kind="cold")
        _INTERVAL["reconstructed"].inc()
        EC_DEGRADED_READ_SECONDS.observe(
            time.perf_counter() - t_start, result="cold"
        )
        return span[offset - span_start : offset - span_start + size]

    async def read_ec_needle(self, ev: EcVolume, key: int) -> Optional[Needle]:
        try:
            with _ST_LOCATE():
                offset_units, size = ev.find_needle_from_ecx(key)
        except NeedleNotFound:
            return None
        if size == TOMBSTONE_FILE_SIZE:
            return None
        return await self.read_ec_needle_at(ev, key, offset_units, size)

    async def read_ec_needle_at(
        self, ev: EcVolume, key: int, offset_units: int, size: int
    ) -> Optional[Needle]:
        """Interval reads for an already-located needle (the bulk path hands
        in offsets from EcVolume.bulk_locate instead of re-searching). One
        deadline covers the WHOLE needle — retries on interval 1 shrink the
        budget intervals 2..n may spend."""
        # lifecycle heat: one EC needle read = one heat unit on whichever
        # server serves it (the master sums across holders)
        ev.heat.note_read()
        intervals = ev.intervals_for(offset_units, size)
        deadline = deadline_after(EC_READ_DEADLINE_SECONDS)
        chunks = []
        recovered: list[int] = []
        for iv in intervals:
            shard_id, shard_offset = iv.to_shard_id_and_offset(
                EC_LARGE_BLOCK_SIZE, EC_SMALL_BLOCK_SIZE
            )
            data = await self._read_one_ec_interval(
                ev, shard_id, shard_offset, iv.size, key, deadline, recovered
            )
            if data is None or len(data) != iv.size:
                return None
            chunks.append(data)
        with _ST_ASSEMBLE() as st:
            blob = b"".join(chunks)
            st.tag("intervals", len(chunks))
            st.tag("bytes", len(blob))
            n = Needle()
            n.read_bytes(blob, to_actual_offset(offset_units), size, ev.version)
        (_NEEDLE_DEGRADED if recovered else _NEEDLE_HEALTHY).inc()
        return n

    async def delete_ec_needle(self, ev: EcVolume, key: int) -> int:
        """Tombstone locally + fan out to every shard holder
        (ref store_ec_delete.go:15-110)."""
        try:
            _, size = ev.find_needle_from_ecx(key)
        except NeedleNotFound:
            return 0
        if size == TOMBSTONE_FILE_SIZE:
            return 0
        loop = asyncio.get_event_loop()
        await loop.run_in_executor(None, ev.delete_needle_from_ecx, key)
        self._note_ec_tombstone(ev)
        await self._refresh_shard_locations(ev)
        urls = set()
        with ev.shard_locations_lock:
            for shard_urls in ev.shard_locations.values():
                urls.update(shard_urls)
        urls.discard(self.address)
        urls.discard(self.public_url)

        async def one(url: str) -> None:
            stub = Stub(grpc_address(url), "volume")
            try:
                await stub.call(
                    "VolumeEcBlobDelete",
                    {
                        "volume_id": ev.volume_id,
                        "collection": ev.collection,
                        "file_key": key,
                        "version": ev.version,
                    },
                )
            except Exception:
                pass

        await asyncio.gather(*(one(u) for u in urls))
        return size
