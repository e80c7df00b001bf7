"""EcVolume: serving reads from erasure-coded shards.

Holds the sorted .ecx index (mapped at mount and binary-searched in the
mapping: a probe touches a page and makes no system call), the .ecj
deletion journal, and whichever local .ecNN shard files exist
(ref: weed/storage/erasure_coding/ec_volume.go, ec_shard.go,
ec_volume_delete.go).
"""

from __future__ import annotations

import mmap
import os
import struct
import threading
from typing import Callable, Optional

from . import (
    DATA_SHARDS_COUNT,
    EC_LARGE_BLOCK_SIZE,
    EC_SMALL_BLOCK_SIZE,
    TOTAL_SHARDS_COUNT,
    to_ext,
)
from ...types import (
    NEEDLE_ID_SIZE,
    NEEDLE_MAP_ENTRY_SIZE,
    TOMBSTONE_FILE_SIZE,
    VERSION3,
    needle_id_to_bytes,
    to_actual_offset,
    u32_to_bytes,
)
from ...util.metrics import EC_INDEX_LOOKUPS
from ..idx import parse_entry
from ..needle import get_actual_size
from .locate import Interval, locate_data

# a search of a mounted volume's .ecx (EcVolume._locate_entry), once, by
# what it searched
_LOOKUPS_MAPPING = EC_INDEX_LOOKUPS.child(via="mapping")
_LOOKUPS_PREAD = EC_INDEX_LOOKUPS.child(via="pread")
_KEY_AT = struct.Struct(">Q").unpack_from  # an entry starts with its key


class NeedleNotFound(Exception):
    pass


def ec_shard_file_name(collection: str, directory: str, vid: int) -> str:
    if collection:
        return os.path.join(directory, f"{collection}_{vid}")
    return os.path.join(directory, str(vid))


def ec_shard_base_file_name(collection: str, vid: int) -> str:
    if collection:
        return f"{collection}_{vid}"
    return str(vid)


class ShardBits:
    """uint32 bitmask of present shard ids (ref: ec_volume_info.go:61-110);
    iteration spans the full 32 bits so alternate geometries with more than
    14 shards (e.g. 12.4) are representable."""

    def __init__(self, bits: int = 0):
        self.bits = bits & 0xFFFFFFFF

    def add(self, shard_id: int) -> "ShardBits":
        return ShardBits(self.bits | (1 << shard_id))

    def remove(self, shard_id: int) -> "ShardBits":
        return ShardBits(self.bits & ~(1 << shard_id))

    def has(self, shard_id: int) -> bool:
        return bool(self.bits & (1 << shard_id))

    def shard_ids(self) -> list[int]:
        return [i for i in range(32) if self.has(i)]

    def count(self) -> int:
        return bin(self.bits).count("1")

    def minus(self, other: "ShardBits") -> "ShardBits":
        return ShardBits(self.bits & ~other.bits)

    def plus(self, other: "ShardBits") -> "ShardBits":
        return ShardBits(self.bits | other.bits)

    def minus_parity_shards(
        self, data_shards: int = DATA_SHARDS_COUNT
    ) -> "ShardBits":
        b = self
        for i in range(data_shards, 32):
            b = b.remove(i)
        return b

    def __eq__(self, other) -> bool:
        return isinstance(other, ShardBits) and self.bits == other.bits

    def __repr__(self) -> str:
        return f"ShardBits({self.shard_ids()})"


class EcVolumeShard:
    """One local .ecNN file (ref: ec_shard.go:16-110)."""

    def __init__(self, directory: str, collection: str, vid: int, shard_id: int):
        self.dir = directory
        self.collection = collection
        self.volume_id = vid
        self.shard_id = shard_id
        path = self.file_name() + to_ext(shard_id)
        self._f = open(path, "rb")
        self.size = os.path.getsize(path)

    def file_name(self) -> str:
        return ec_shard_file_name(self.collection, self.dir, self.volume_id)

    def read_at(self, size: int, offset: int) -> bytes:
        return os.pread(self._f.fileno(), size, offset)

    def read_into(self, buf, offset: int) -> int:
        """Fill the writable buffer `buf` from `offset` on; the bytes read,
        fewer than `len(buf)` at the file's end. For a caller that owns
        the array the bytes are wanted in (a reconstruct's survivor rows)."""
        return os.preadv(self._f.fileno(), [buf], offset)

    def close(self) -> None:
        self._f.close()

    def destroy(self) -> None:
        self.close()
        os.remove(self.file_name() + to_ext(self.shard_id))


def search_needle_from_sorted_index(
    ecx_f,
    ecx_file_size: int,
    needle_id: int,
    process_fn: Optional[Callable[[object, int], None]] = None,
) -> tuple[int, int]:
    """Binary search the on-disk sorted .ecx; returns (offset_units, size).
    process_fn(file, entry_offset) runs on the matched entry while positioned
    (ref SearchNeedleFromSortedIndex, ec_volume.go:210-235)."""
    lo, hi = 0, ecx_file_size // NEEDLE_MAP_ENTRY_SIZE
    while lo < hi:
        mid = (lo + hi) // 2
        entry = os.pread(
            ecx_f.fileno(), NEEDLE_MAP_ENTRY_SIZE, mid * NEEDLE_MAP_ENTRY_SIZE
        )
        if len(entry) != NEEDLE_MAP_ENTRY_SIZE:
            raise IOError(f"ecx short read at {mid * NEEDLE_MAP_ENTRY_SIZE}")
        key, offset_units, size = parse_entry(entry)
        if key == needle_id:
            if process_fn is not None:
                process_fn(ecx_f, mid * NEEDLE_MAP_ENTRY_SIZE)
            return offset_units, size
        if key < needle_id:
            lo = mid + 1
        else:
            hi = mid
    raise NeedleNotFound(f"needle {needle_id} not found in ecx")


def search_mapped_sorted_index(index, needle_id: int) -> int:
    """Binary search a sorted index held in a buffer (the mapping of an
    .ecx); returns the byte offset of the matching entry. Reads the eight
    key bytes of each probe in place: no system call, no copy, and no view
    of the buffer outlives the call."""
    lo, hi = 0, len(index) // NEEDLE_MAP_ENTRY_SIZE
    while lo < hi:
        mid = (lo + hi) >> 1
        key = _KEY_AT(index, mid * NEEDLE_MAP_ENTRY_SIZE)[0]
        if key == needle_id:
            return mid * NEEDLE_MAP_ENTRY_SIZE
        if key < needle_id:
            lo = mid + 1
        else:
            hi = mid
    raise NeedleNotFound(f"needle {needle_id} not found in ecx")


def mark_needle_deleted(f, entry_offset: int) -> None:
    """Tombstone the size field of an .ecx entry in place
    (ref MarkNeedleDeleted, ec_volume_delete.go:13-25)."""
    from ...types import OFFSET_SIZE

    os.pwrite(
        f.fileno(),
        u32_to_bytes(TOMBSTONE_FILE_SIZE),
        entry_offset + NEEDLE_ID_SIZE + OFFSET_SIZE,  # key + offset come first
    )


class EcVolume:
    def __init__(self, directory: str, collection: str, vid: int):
        self.dir = directory
        self.collection = collection
        self.volume_id = vid
        base = self.file_name()
        if not os.path.exists(base + ".ecx"):
            raise FileNotFoundError(f"cannot open ec volume index {base}.ecx")
        self._ecx = open(base + ".ecx", "r+b")
        self.ecx_file_size = os.path.getsize(base + ".ecx")
        # the index mapped once, shared and read-only: a search reads the
        # file's pages in place, and a tombstone's pwrite shows through.
        # An empty index has nothing to map; a filesystem that refuses a
        # mapping leaves None too, and the searches go by pread
        self._ecx_map: Optional[mmap.mmap] = None
        if self.ecx_file_size:
            try:
                self._ecx_map = mmap.mmap(
                    self._ecx.fileno(),
                    self.ecx_file_size,
                    access=mmap.ACCESS_READ,
                )
            except (OSError, ValueError):
                pass
        self._ecj = open(base + ".ecj", "a+b")
        self._ecj_lock = threading.Lock()
        self.version = VERSION3
        # RS geometry: default 10.4, overridable per volume via .vif
        self.data_shards = DATA_SHARDS_COUNT
        self.parity_shards = TOTAL_SHARDS_COUNT - DATA_SHARDS_COUNT
        vif = base + ".vif"
        if os.path.exists(vif):
            from ..volume_info import load_volume_info

            info = load_volume_info(vif)
            if info is not None and info.version:
                self.version = info.version
            if info is not None and info.data_shards:
                self.data_shards = info.data_shards
                self.parity_shards = info.parity_shards
        self.shards: list[EcVolumeShard] = []
        # cold tier (ISSUE 14): shard_id -> {key, size, backend} for shard
        # files offloaded to a remote backend (the crash-safe `.ctm`
        # manifest is the authority; torn shadows/recall tmps are swept
        # here exactly like the vacuum .cpd sweep at volume load)
        from ..cold_tier import load_manifest, sweep_recall_tmps

        sweep_recall_tmps(base)
        self.remote_shards: dict[int, dict] = load_manifest(base)
        # shard_id -> list of server addresses, refreshed from master
        self.shard_locations: dict[int, list[str]] = {}
        self.shard_locations_lock = threading.RLock()
        self.shard_locations_refresh_time = 0.0
        # the LookupEcVolume now out for this volume, if any (an asyncio
        # future of the volume server's loop): refreshes share its answer
        self.shard_locations_lookup = None
        # device-resident .ecx snapshot for bulk probes; invalidated on
        # tombstone writes (see bulk_locate)
        from ...ops.snapshot_cache import SnapshotCache

        self._ecx_cache = SnapshotCache()
        self._ecx_mutations = 0
        # lifecycle plane: EC read heat (the re-inflation sensor). The
        # sidecar shares the volume's base name, so a conversion on the
        # same node carries the temperature across the format change.
        from ..heat import HeatTracker

        self.heat = HeatTracker.load(base + ".heat")

    def file_name(self) -> str:
        return ec_shard_file_name(self.collection, self.dir, self.volume_id)

    # --- shard registry ---
    def add_shard(self, shard: EcVolumeShard) -> bool:
        if any(s.shard_id == shard.shard_id for s in self.shards):
            return False
        self.shards.append(shard)
        self.shards.sort(key=lambda s: (s.volume_id, s.shard_id))
        return True

    def delete_shard(self, shard_id: int) -> Optional[EcVolumeShard]:
        for i, s in enumerate(self.shards):
            if s.shard_id == shard_id:
                return self.shards.pop(i)
        return None

    def find_shard(self, shard_id: int) -> Optional[EcVolumeShard]:
        for s in self.shards:
            if s.shard_id == shard_id:
                return s
        return None

    def shard_ids(self) -> list[int]:
        return [s.shard_id for s in self.shards]

    def shard_bits(self) -> ShardBits:
        b = ShardBits()
        for s in self.shards:
            b = b.add(s.shard_id)
        return b

    def shard_size(self) -> int:
        if self.shards:
            return self.shards[0].size
        # fully offloaded volume: interval math still needs the sealed
        # shard size — the manifest recorded it at offload time
        for ent in self.remote_shards.values():
            if ent.get("size"):
                return int(ent["size"])
        return 0

    def size(self) -> int:
        return sum(s.size for s in self.shards) + sum(
            int(e.get("size", 0)) for e in self.remote_shards.values()
        )

    # --- cold tier (offloaded shards) ---
    def remote_shard(self, shard_id: int) -> Optional[dict]:
        return self.remote_shards.get(shard_id)

    def offloaded_bits(self) -> ShardBits:
        b = ShardBits()
        for sid in self.remote_shards:
            b = b.add(sid)
        return b

    def note_shard_offloaded(self, shard_id: int, ent: dict) -> None:
        """Bookkeeping hook fired by cold_tier.offload_shards after the
        manifest commit (the in-memory view mirrors the durable one)."""
        self.remote_shards[shard_id] = dict(ent)

    def note_shard_recalled(self, shard_id: int) -> None:
        self.remote_shards.pop(shard_id, None)

    # --- lookup ---
    def _locate_entry(self, needle_id: int) -> tuple[int, int, int]:
        """-> (byte offset of the needle's .ecx entry, offset_units, size):
        by the mapping, or by pread where the mount got none. The one place
        a mounted volume's index is searched, and where the counter moves."""
        index = self._ecx_map
        if index is not None:
            _LOOKUPS_MAPPING.inc()
            at = search_mapped_sorted_index(index, needle_id)
            _, offset_units, size = parse_entry(
                index[at : at + NEEDLE_MAP_ENTRY_SIZE]
            )
            return at, offset_units, size
        if not self.ecx_file_size:
            raise NeedleNotFound(f"needle {needle_id} not found in ecx")
        _LOOKUPS_PREAD.inc()
        found_at: list[int] = []
        offset_units, size = search_needle_from_sorted_index(
            self._ecx,
            self.ecx_file_size,
            needle_id,
            lambda _f, entry_offset: found_at.append(entry_offset),
        )
        return found_at[0], offset_units, size

    def find_needle_from_ecx(self, needle_id: int) -> tuple[int, int]:
        _, offset_units, size = self._locate_entry(needle_id)
        return offset_units, size

    def ecx_snapshot(self):
        """Live .ecx entries as sorted numpy columns
        (keys u64[n], offset_units u32[n], sizes u32[n]) — the probe table
        for the bulk-lookup kernel. Tombstoned entries are excluded."""
        from ..idx import parse_index_bytes

        keys, offsets, sizes = parse_index_bytes(
            os.pread(self._ecx.fileno(), self.ecx_file_size, 0)
        )
        live = sizes != TOMBSTONE_FILE_SIZE
        return keys[live], offsets[live], sizes[live]

    def bulk_locate(self, needle_ids, use_device: Optional[bool] = None):
        """Batched .ecx probes -> (offset_units u32[P], sizes u32[P],
        found bool[P]).

        The bulk analogue of find_needle_from_ecx: one vectorized binary
        search on a cached device-resident snapshot instead of P on-disk
        searches (ref SearchNeedleFromSortedIndex, ec_volume.go:210-235).
        """
        import numpy as np

        needle_ids = np.asarray(needle_ids, dtype=np.uint64)
        if use_device is None:
            # tiny batches aren't worth a device dispatch / first-use
            # compile; 5-byte offsets exceed the kernel's u32 columns
            from ...types import OFFSET_SIZE

            use_device = OFFSET_SIZE == 4 and len(needle_ids) >= 64
        if not use_device:
            from ...types import OFFSET_SIZE

            off_dtype = np.uint64 if OFFSET_SIZE == 5 else np.uint32
            offsets = np.zeros(len(needle_ids), dtype=off_dtype)
            sizes = np.zeros(len(needle_ids), dtype=np.uint32)
            found = np.zeros(len(needle_ids), dtype=bool)
            for i, k in enumerate(needle_ids):
                try:
                    o, s = self.find_needle_from_ecx(int(k))
                except NeedleNotFound:
                    continue
                if s != TOMBSTONE_FILE_SIZE:
                    offsets[i], sizes[i], found[i] = o, s, True
            return offsets, sizes, found

        accel = self._ecx_cache.get(
            lambda: self._ecx_mutations, self.ecx_snapshot
        )
        return accel.lookup(needle_ids)

    @property
    def total_shards(self) -> int:
        return self.data_shards + self.parity_shards

    def intervals_for(self, offset_units: int, size: int) -> list[Interval]:
        """Shard intervals for an already-located needle."""
        shard_size = self.shard_size()
        return locate_data(
            EC_LARGE_BLOCK_SIZE,
            EC_SMALL_BLOCK_SIZE,
            self.data_shards * shard_size,
            to_actual_offset(offset_units),
            get_actual_size(size, self.version),
            data_shards=self.data_shards,
        )

    def locate_needle(self, needle_id: int) -> tuple[int, int, list[Interval]]:
        """-> (offset_units, size, intervals)
        (ref LocateEcShardNeedle, ec_volume.go:190-206)."""
        offset_units, size = self.find_needle_from_ecx(needle_id)
        return offset_units, size, self.intervals_for(offset_units, size)

    # --- delete ---
    def delete_needle_from_ecx(self, needle_id: int) -> None:
        """Tombstone in .ecx + journal to .ecj
        (ref DeleteNeedleFromEcx, ec_volume_delete.go:27-49)."""
        try:
            at, _, _ = self._locate_entry(needle_id)
        except NeedleNotFound:
            return
        mark_needle_deleted(self._ecx, at)
        self._ecx_mutations += 1
        with self._ecj_lock:
            self._ecj.seek(0, 2)
            self._ecj.write(needle_id_to_bytes(needle_id))
            self._ecj.flush()

    def close(self) -> None:
        try:
            self.heat.save(self.file_name() + ".heat")
        except Exception:
            pass
        for s in self.shards:
            s.close()
        with self._ecj_lock:
            self._ecj.close()
        if self._ecx_map is not None:
            self._ecx_map.close()  # before its descriptor
        self._ecx.close()

    def destroy(self) -> None:
        self.close()
        for s in self.shards:
            try:
                os.remove(s.file_name() + to_ext(s.shard_id))
            except FileNotFoundError:
                pass
        base = self.file_name()
        # .ctm last: destroying a volume drops the local index files; the
        # remote objects it names become orphaned BYTES, never lost data
        # (the delete RPC path deletes them explicitly before this)
        for ext in (".ecx", ".ecj", ".vif", ".heat", ".ctm"):
            try:
                os.remove(base + ext)
            except FileNotFoundError:
                pass


def rebuild_ecx_file(base_file_name: str) -> None:
    """Replay the .ecj journal into .ecx tombstones, then drop the journal
    (ref RebuildEcxFile, ec_volume_delete.go:51-96)."""
    if not os.path.exists(base_file_name + ".ecj"):
        return
    with open(base_file_name + ".ecx", "r+b") as ecx:
        size = os.path.getsize(base_file_name + ".ecx")
        with open(base_file_name + ".ecj", "rb") as ecj:
            while True:
                b = ecj.read(NEEDLE_ID_SIZE)
                if len(b) != NEEDLE_ID_SIZE:
                    break
                from ...types import bytes_to_u64

                try:
                    search_needle_from_sorted_index(
                        ecx, size, bytes_to_u64(b), mark_needle_deleted
                    )
                except NeedleNotFound:
                    pass
    os.remove(base_file_name + ".ecj")
