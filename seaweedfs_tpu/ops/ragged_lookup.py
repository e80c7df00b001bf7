"""Ragged-batch device lookups over a paged, HBM-resident column arena.

One gate wakeup delivers a RAGGED probe batch: needle-map probes spanning
many volumes' LSM runs, or filer path-spine ancestor chains of varying
depth. Instead of one `IndexSnapshot.lookup` dispatch per volume (per
segment!), the whole wakeup runs as ONE device dispatch — the ragged
paging idiom from "Ragged Paged Attention" (arxiv 2604.15464) applied to
the metadata hot path: flat probe keys + per-probe (row-range, segment
end, bloom word) coordinates into a paged column arena that stays
device-resident across dispatches (arxiv 2112.09017's keep-it-on-HBM
lesson; re-uploading a 10M-row run per batch would drown the kernel).

Layout (one immutable _Generation per refresh):

    khi/klo/offs/sizes : u32[N]  sealed-run columns, concatenated, each
                                 segment base aligned to PAGE rows
    bloom              : u32[W]  bloom-sidecar bitmaps, concatenated as
                                 LE words; word 0 is a sentinel so
                                 filterless probes can address it
    per probe (host-packed, ISSUE-18 kernel inputs):
        phi/plo   u32  key split in (hi, lo) planes (no 64-bit lanes)
        lo/hi     i32  absolute row range from the segment's
                       interpolation-bucket table (host u64 math, the
                       index_kernel discipline)
        end       i32  segment's absolute end row: a search that walks
                       off its segment can never match the NEXT
                       segment's first row (_search_range_bounded)
        bw/bm     i32/u32 ×2  bloom word index + bit mask (k=2, same
                       premixed murmur3 hash as the host probe path);
                       mask 0 = no filter = always present

The search body is the existing bucketed interpolation search
(`index_kernel._search_range_bounded`) — per-segment bucket tables are
host-side, per-generation columns device-side, exactly the split the
single-table kernel uses.

`DeviceColumnArena` pins sealed segments HBM-resident with LRU eviction
(budget `SEAWEEDFS_TPU_ARENA_MB`) and DOUBLE-BUFFERED uploads: a refresh
builds the next generation on a background thread while in-flight
dispatches keep their reference to the old one (generations are
immutable; the swap is one pointer under a lock), so the serving path
never stalls on a transfer. Every caller must treat `ensure()` returning
None — arena cold, arena killed — as an instruction to serve from the
host maps instead; the arena is an accelerator, never an authority. A
device that fails is neither: a failed upload or dispatch is counted as
`device_error` and logged, never folded into "cold".
"""

from __future__ import annotations

import functools
import itertools
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..util.device import platform
from .index_kernel import _search_range_bounded

logger = logging.getLogger(__name__)

PAGE = 2048  # rows; segment bases are page-aligned in the arena
MIN_ROWS = 4096  # generations pad to pow2 row counts ≥ this (jit reuse)

ARENA_BYTES = int(
    float(os.environ.get("SEAWEEDFS_TPU_ARENA_MB", "256") or 256) * (1 << 20)
)

_HANDLES = itertools.count(1)

def _metrics():
    try:
        from ..util import metrics as m

        return m
    except ImportError:  # stripped builds
        return None


class ArenaSegment:
    """One immutable sorted segment offered to the arena: columnar
    (keys u64, offs u32, sizes u32) views — typically straight off a
    sealed run's mmap — plus an optional bloom bitmap as LE u32 words.
    Content-immutable by contract: the handle is the identity the arena
    caches residency under, so a mutated segment MUST be a new handle
    (LSM runs and filer .sst segments satisfy this by construction)."""

    __slots__ = (
        "handle", "keys", "offs", "sizes", "bloom_words", "bloom_mbits",
        "count", "nbytes", "source", "alive",
        "_starts", "kmin", "bstep", "nb", "steps", "_buckets_built",
    )

    MIN_BUCKETED = 4096
    MAX_BUCKETS = 1 << 25

    def __init__(
        self,
        keys: np.ndarray,
        offs: np.ndarray,
        sizes: np.ndarray,
        bloom_words: Optional[np.ndarray] = None,
        bloom_mbits: int = 0,
        source=None,
        alive=None,
    ):
        self.handle = next(_HANDLES)
        self.keys = keys
        self.offs = offs
        self.sizes = sizes
        self.bloom_words = bloom_words
        self.bloom_mbits = int(bloom_mbits)
        self.count = len(keys)
        self.nbytes = self.count * 16 + (
            len(bloom_words) * 4 if bloom_words is not None else 0
        )
        self.source = source
        self.alive = alive if alive is not None else (lambda: True)
        self._starts = None
        self._buckets_built = False
        self.kmin = 0
        self.bstep = 1
        self.nb = 0
        # search steps must cover the worst row range a probe can get;
        # refined to bucket occupancy when the bucket table is built
        self.steps = max(1, int(np.ceil(np.log2(max(self.count, 1)))) + 1)

    def buckets(self):
        """Host-side interpolation-bucket table (IndexSnapshot's exact
        construction), built once per segment and cached — refreshes
        re-upload columns but never redo this searchsorted."""
        if self._buckets_built:
            return self._starts
        self._buckets_built = True
        n = self.count
        if n < self.MIN_BUCKETED:
            return None
        keys = np.asarray(self.keys, dtype=np.uint64)
        kmin = int(keys[0])
        kmax = int(keys[-1])
        span = kmax - kmin + 1
        if not (0 < span < 1 << 62) or kmax + 1 + self.MAX_BUCKETS >= 1 << 64:
            return None
        nb = 1 << max(10, int(np.ceil(np.log2(n))) + 1)
        nb = min(nb, self.MAX_BUCKETS)
        self.kmin = kmin
        self.nb = nb
        self.bstep = max(1, -(-span // nb))
        boundaries = np.uint64(kmin) + np.arange(
            nb, dtype=np.uint64
        ) * np.uint64(self.bstep)
        starts = np.searchsorted(keys, boundaries).astype(np.int32)
        starts = np.append(starts, np.int32(n))
        max_occ = int(np.max(np.diff(starts))) if nb else n
        self.steps = max(1, int(np.ceil(np.log2(max(max_occ, 1)))) + 1)
        self._starts = starts
        return starts


@functools.partial(jax.jit, static_argnums=(0,))
def _ragged_dispatch(steps, khi, klo, offs, sizes, bloom, u32p, i32p):
    """One-dispatch ragged probe batch: device-side bloom pre-filter
    (2 word gathers + bit tests per probe) collapses absent-run probes'
    search ranges to empty, then the shared bounded interpolation search
    answers every surviving probe against its own segment's row range.

    Probe-side inputs arrive as TWO stacked planes — u32p rows are
    (phi, plo, bm0, bm1), i32p rows are (lo, hi, end, bw0, bw1) — so a
    dispatch pays 2 host->device transfers, not 9 (per-array jnp.asarray
    overhead dominated small-wakeup latency on the CPU stand-in)."""
    phi, plo, bm0, bm1 = u32p[0], u32p[1], u32p[2], u32p[3]
    lo, hi, end, bw0, bw1 = (
        i32p[0], i32p[1], i32p[2], i32p[3], i32p[4],
    )
    w0 = bloom[bw0]
    w1 = bloom[bw1]
    present = ((w0 & bm0) == bm0) & ((w1 & bm1) == bm1)
    hi = jnp.where(present, hi, lo)  # filtered-out: empty range
    off, size, found = _search_range_bounded(
        steps, khi, klo, offs, sizes, phi, plo, lo, hi, end
    )
    return off, size, found & present


class _Generation:
    """One immutable device-resident arena build. Dispatches capture a
    reference and keep using it even if the arena swaps underneath —
    correctness of the double-buffer race reduces to jax array
    immutability plus this object's."""

    __slots__ = (
        "gen_id", "khi", "klo", "offs", "sizes", "bloom", "steps",
        "seg", "rows", "nbytes", "built_s",
    )

    def __init__(self, gen_id, segments):
        t0 = time.perf_counter()
        self.gen_id = gen_id
        self.seg = {}  # handle -> (ArenaSegment, base_row, bloom_base_word)
        rows = 0
        bloom_words = 1  # word 0 = sentinel for filterless probes
        steps = 1
        for s in segments:
            base = rows
            bbase = -1
            if s.bloom_words is not None and s.bloom_mbits:
                bbase = bloom_words
                bloom_words += len(s.bloom_words)
            s.buckets()  # refine s.steps before taking the max
            steps = max(steps, s.steps)
            self.seg[s.handle] = (s, base, bbase)
            rows += -(-max(s.count, 1) // PAGE) * PAGE  # page-aligned
        self.rows = rows
        n = max(MIN_ROWS, 1 << max(0, (rows - 1)).bit_length())
        w = 1 << max(0, (bloom_words - 1)).bit_length()
        khi = np.zeros(n, dtype=np.uint32)
        klo = np.zeros(n, dtype=np.uint32)
        offs = np.zeros(n, dtype=np.uint32)
        sizes = np.zeros(n, dtype=np.uint32)
        bloom = np.zeros(w, dtype=np.uint32)
        for s, base, bbase in self.seg.values():
            k = np.ascontiguousarray(s.keys, dtype=np.uint64)
            khi[base : base + s.count] = (k >> np.uint64(32)).astype(
                np.uint32
            )
            klo[base : base + s.count] = (
                k & np.uint64(0xFFFFFFFF)
            ).astype(np.uint32)
            offs[base : base + s.count] = np.asarray(s.offs, dtype=np.uint32)
            sizes[base : base + s.count] = np.asarray(
                s.sizes, dtype=np.uint32
            )
            if bbase >= 0:
                bloom[bbase : bbase + len(s.bloom_words)] = s.bloom_words
        self.khi = jnp.asarray(khi)
        self.klo = jnp.asarray(klo)
        self.offs = jnp.asarray(offs)
        self.sizes = jnp.asarray(sizes)
        self.bloom = jnp.asarray(bloom)
        for a in (self.khi, self.klo, self.offs, self.sizes, self.bloom):
            a.block_until_ready()
        self.steps = steps
        self.nbytes = (4 * n) * 4 + 4 * w
        self.built_s = time.perf_counter() - t0


class DeviceColumnArena:
    """Pins sealed segments HBM-resident; LRU-evicts past the byte
    budget; refreshes double-buffered on a background thread. All public
    methods are thread-safe; `ensure`/`probe_groups` never block on an
    upload — a cold arena answers None and the caller serves host-side
    while the refresh runs."""

    def __init__(self, budget_bytes: int = 0):
        self.budget = budget_bytes or ARENA_BYTES
        self._lock = threading.Lock()
        self._gen: Optional[_Generation] = None
        self._gen_seq = 0
        self._sources: dict[int, ArenaSegment] = {}
        self._last_used: dict[int, int] = {}
        self._tick = 0
        self._dead = False
        self._refresh_queued = False
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="arena-refresh"
        )
        self.counters = {
            "dispatches": 0,
            "probes": 0,
            "uploads": 0,
            "evictions": 0,
            "cold_misses": 0,
            "dead_refusals": 0,
            "device_error": 0,
        }

    # ---------------- residency ----------------
    def ensure(self, segments) -> Optional[_Generation]:
        """All `segments` resident in the CURRENT generation -> that
        generation (LRU bumped). Otherwise registers them, queues one
        background refresh, and returns None (caller: host fallback)."""
        if self._dead:
            self.counters["dead_refusals"] += 1
            return None
        with self._lock:
            self._tick += 1
            tick = self._tick
            gen = self._gen
            missing = False
            for s in segments:
                self._last_used[s.handle] = tick
                if s.handle not in self._sources:
                    self._sources[s.handle] = s
                if gen is None or s.handle not in gen.seg:
                    missing = True
            if not missing:
                return gen
            self.counters["cold_misses"] += 1
            queue = not self._refresh_queued
            if queue:
                self._refresh_queued = True
        if queue:
            self._pool.submit(self._refresh)
        return None

    def _refresh(self) -> None:
        """Build generation N+1 while N keeps serving; swap is one
        pointer. LRU eviction happens here: most-recently-ensured
        segments win the byte budget."""
        try:
            with self._lock:
                self._refresh_queued = False
                live = [
                    s for s in self._sources.values() if s.alive()
                ]
                dead_handles = [
                    h for h, s in self._sources.items() if not s.alive()
                ]
                for h in dead_handles:
                    del self._sources[h]
                    self._last_used.pop(h, None)
                order = sorted(
                    live,
                    key=lambda s: self._last_used.get(s.handle, 0),
                    reverse=True,
                )
                chosen = []
                total = 0
                for s in order:
                    if chosen and total + s.nbytes > self.budget:
                        self.counters["evictions"] += 1
                        continue
                    chosen.append(s)
                    total += s.nbytes
                self._gen_seq += 1
                gen_id = self._gen_seq
            gen = _Generation(gen_id, chosen)
            with self._lock:
                if self._gen is None or self._gen.gen_id < gen_id:
                    self._gen = gen
                self.counters["uploads"] += 1
            m = _metrics()
            if m is not None:
                m.NEEDLE_MAP_DEVICE_RESIDENT.set(gen.nbytes)
                m.NEEDLE_MAP_DEVICE_SEGMENTS.set(len(gen.seg))
                m.NEEDLE_MAP_DEVICE_UPLOADS.inc()
        except Exception:
            # a failed upload must never take serving down — callers keep
            # host-serving — but it is a DEVICE ERROR, not a cold arena:
            # counted under its own name and logged once with its traceback
            with self._lock:
                self._refresh_queued = False
                first = not self.counters["device_error"]
                self.counters["device_error"] += 1
            if first:
                logger.exception("arena refresh failed on the device")

    def prefetch(self, segment: ArenaSegment) -> str:
        """Flush-path residency hint (ISSUE 20 satellite): the LSM store
        offers a NEWLY SEALED run right when it seals, so the background
        refresh uploads it before the first probe would cold-miss on it.
        Never blocks, never counts as a probe-path cold miss. Returns the
        outcome for the `arena_prefetch_total{result}` counter."""
        if self._dead:
            return "unavailable"
        with self._lock:
            self._tick += 1
            self._last_used[segment.handle] = self._tick
            if segment.handle not in self._sources:
                self._sources[segment.handle] = segment
            gen = self._gen
            if gen is not None and segment.handle in gen.seg:
                return "resident"
            queue = not self._refresh_queued
            if queue:
                self._refresh_queued = True
        if queue:
            self._pool.submit(self._refresh)
            return "queued"
        return "piggybacked"

    def refresh_sync(self) -> None:
        """Block until a refresh including everything registered so far
        has landed (tests/bench warm-up — serving paths never call it)."""
        self._pool.submit(self._refresh).result()

    def kill(self) -> None:
        """Fault hook (chaos soak): drop dead. Every subsequent ensure/
        probe answers None and the gates degrade to host lookups."""
        self._dead = True

    def revive(self) -> None:
        self._dead = False

    def stats(self) -> dict:
        with self._lock:
            gen = self._gen
            out = {
                "generation": gen.gen_id if gen else 0,
                "resident_segments": len(gen.seg) if gen else 0,
                "resident_bytes": gen.nbytes if gen else 0,
                "resident_rows": gen.rows if gen else 0,
                "registered_segments": len(self._sources),
                "budget_bytes": self.budget,
                "dead": self._dead,
                "platform": platform(),
            }
            out.update(self.counters)
        return out

    # ---------------- the one-dispatch probe ----------------
    def probe_groups(self, groups, timings: Optional[dict] = None):
        """groups: [(segments_newest_first, keys_u64)] — one entry per
        (volume | path-spine) contributor of the wakeup. Returns a list
        aligned with groups: None where this group must be host-served
        (cold/dead), else {found, rank, off, size} numpy
        arrays aligned with the group's keys; `rank` indexes the group's
        newest-first segment list (the caller applies its own
        newest-wins + tombstone semantics)."""
        t0 = time.perf_counter()
        results: list = [None] * len(groups)
        plan = []  # (group index, segments, keys, gen)
        if self._dead:
            self.counters["dead_refusals"] += 1
            return results
        for gi, (segments, keys) in enumerate(groups):
            if len(keys) == 0:
                results[gi] = _empty_result()
                continue
            if len(segments) == 0:
                results[gi] = _empty_result(len(keys))
                continue
            gen = self.ensure(segments)
            if gen is None:
                continue
            plan.append((gi, segments, keys, gen))
        if not plan:
            if timings is not None:
                timings["pack_s"] = timings.get("pack_s", 0.0) + (
                    time.perf_counter() - t0
                )
            return results
        # dispatch groups sharing a generation together (normal case:
        # everything is on the current one)
        by_gen: dict[int, list] = {}
        gens: dict[int, _Generation] = {}
        for gi, segments, keys, gen in plan:
            by_gen.setdefault(gen.gen_id, []).append((gi, segments, keys))
            gens[gen.gen_id] = gen
        if timings is not None:
            timings["pack_s"] = timings.get("pack_s", 0.0) + (
                time.perf_counter() - t0
            )
        for gen_id, members in by_gen.items():
            self._dispatch_members(gens[gen_id], members, results, timings)
        return results

    def _dispatch_members(self, gen, members, results, timings) -> None:
        from ..storage.needle_map.lsm_map import mix64_batch

        t0 = time.perf_counter()
        blocks = []  # (gi, base_slot, K, R)
        total = 0
        for gi, segments, keys in members:
            K = len(keys)
            R = len(segments)
            blocks.append((gi, total, K, R))
            total += K * R
        p2 = max(64, 1 << (total - 1).bit_length())
        u32p = np.zeros((4, p2), dtype=np.uint32)
        i32p = np.zeros((5, p2), dtype=np.int32)
        phi, plo, bm0, bm1 = u32p[0], u32p[1], u32p[2], u32p[3]
        lo, hi, end, bw0, bw1 = (
            i32p[0], i32p[1], i32p[2], i32p[3], i32p[4],
        )
        for (gi, base_slot, K, R), (_, segments, keys) in zip(
            blocks, members
        ):
            keys = np.ascontiguousarray(keys, dtype=np.uint64)
            g_hi = (keys >> np.uint64(32)).astype(np.uint32)
            g_lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            mixed = None
            for rj, s in enumerate(segments):
                sl = slice(base_slot + rj * K, base_slot + (rj + 1) * K)
                seg, base, bbase = gen.seg[s.handle]
                phi[sl] = g_hi
                plo[sl] = g_lo
                end[sl] = base + seg.count
                starts = seg.buckets()
                if starts is None:
                    lo[sl] = base
                    hi[sl] = base + seg.count
                else:
                    b = np.maximum(keys, np.uint64(seg.kmin))
                    b = (b - np.uint64(seg.kmin)) // np.uint64(seg.bstep)
                    b = np.minimum(b, np.uint64(seg.nb - 1)).astype(
                        np.int64
                    )
                    lo[sl] = base + starts[b]
                    hi[sl] = base + starts[b + 1]
                if bbase >= 0:
                    if mixed is None:
                        mixed = mix64_batch(keys)
                    mask = np.uint64(seg.bloom_mbits - 1)
                    pos0 = mixed & mask
                    pos1 = (pos0 + ((mixed >> np.uint64(32)) | np.uint64(1))) & mask
                    bw0[sl] = bbase + (pos0 >> np.uint64(5)).astype(
                        np.int64
                    )
                    bm0[sl] = (
                        np.uint32(1)
                        << (pos0 & np.uint64(31)).astype(np.uint32)
                    )
                    bw1[sl] = bbase + (pos1 >> np.uint64(5)).astype(
                        np.int64
                    )
                    bm1[sl] = (
                        np.uint32(1)
                        << (pos1 & np.uint64(31)).astype(np.uint32)
                    )
        t1 = time.perf_counter()
        u32_d = jnp.asarray(u32p)
        i32_d = jnp.asarray(i32p)
        if timings is not None:
            # barrier only when stage walls are being measured: the
            # serving path lets upload and dispatch overlap freely
            u32_d.block_until_ready()
            i32_d.block_until_ready()
        t2 = time.perf_counter()
        off_d, size_d, found_d = _ragged_dispatch(
            gen.steps, gen.khi, gen.klo, gen.offs, gen.sizes, gen.bloom,
            u32_d, i32_d,
        )
        found_d.block_until_ready()
        t3 = time.perf_counter()
        off_h = np.asarray(off_d)
        size_h = np.asarray(size_d)
        found_h = np.asarray(found_d)
        for gi, base_slot, K, R in blocks:
            fm = found_h[base_slot : base_slot + K * R].reshape(R, K)
            om = off_h[base_slot : base_slot + K * R].reshape(R, K)
            sm = size_h[base_slot : base_slot + K * R].reshape(R, K)
            rank = np.argmax(fm, axis=0)  # first (newest) hit
            cols = np.arange(K)
            results[gi] = {
                "found": fm.any(axis=0),
                "rank": rank.astype(np.int32),
                "off": om[rank, cols],
                "size": sm[rank, cols],
            }
        t4 = time.perf_counter()
        self.counters["dispatches"] += 1
        self.counters["probes"] += total
        m = _metrics()
        if m is not None:
            m.NEEDLE_MAP_DEVICE_DISPATCHES.inc()
            m.NEEDLE_MAP_DEVICE_PROBES.inc(total)
        if timings is not None:
            timings["pack_s"] = timings.get("pack_s", 0.0) + (t1 - t0)
            timings["upload_s"] = timings.get("upload_s", 0.0) + (t2 - t1)
            timings["dispatch_s"] = timings.get("dispatch_s", 0.0) + (
                t3 - t2
            )
            timings["readback_s"] = timings.get("readback_s", 0.0) + (
                t4 - t3
            )

    def close(self) -> None:
        self._pool.shutdown(wait=False)
        with self._lock:
            self._gen = None
            self._sources = {}
            self._last_used = {}


def _empty_result(k: int = 0) -> dict:
    return {
        "found": np.zeros(k, dtype=bool),
        "rank": np.zeros(k, dtype=np.int32),
        "off": np.zeros(k, dtype=np.uint32),
        "size": np.zeros(k, dtype=np.uint32),
    }


_DEFAULT: Optional[DeviceColumnArena] = None
_DEFAULT_LOCK = threading.Lock()


def get_default_arena() -> DeviceColumnArena:
    """Process-wide arena shared by every gate backend (one HBM budget,
    one residency plane — per-gate arenas would fight over the chip)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = DeviceColumnArena()
        return _DEFAULT


def peek_default_arena() -> Optional[DeviceColumnArena]:
    """The process-wide arena IF one has been created, else None. The
    flush-path prefetch hint rides this instead of get_default_arena():
    a store running without any device gate must stay arena-free — a
    hint must never be what first allocates the HBM budget."""
    return _DEFAULT
