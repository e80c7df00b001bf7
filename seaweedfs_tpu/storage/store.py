"""Store: per-server facade over disk locations; assembles heartbeats and
delta change queues (ref: weed/storage/store.go, store_ec.go)."""

from __future__ import annotations

import threading
from typing import Optional

from ..storage.erasure_coding.ec_volume import ShardBits
from .disk_location import DiskLocation
from .needle import Needle
from .super_block import ReplicaPlacement
from .ttl import TTL
from .volume import Volume


class Store:
    def __init__(
        self,
        ip: str,
        port: int,
        public_url: str,
        directories: list[str],
        max_volume_counts: list[int],
        needle_map_kind: str = "memory",
    ):
        self.ip = ip
        self.port = port
        self.public_url = public_url
        self.needle_map_kind = needle_map_kind
        self.locations = [
            DiskLocation(d, m, needle_map_kind=needle_map_kind)
            for d, m in zip(directories, max_volume_counts)
        ]
        self.volume_size_limit = 0  # set by master heartbeat response
        self._lock = threading.RLock()
        # delta queues drained into heartbeats (ref store.go:41-44)
        self.new_volumes: list[dict] = []
        self.deleted_volumes: list[dict] = []
        self.new_ec_shards: list[dict] = []
        self.deleted_ec_shards: list[dict] = []

    # --- lifecycle ---
    def load(self) -> None:
        for loc in self.locations:
            loc.load_existing_volumes()
            loc.load_all_ec_shards()

    def close(self) -> None:
        for loc in self.locations:
            loc.close()

    # --- volumes ---
    def find_volume(self, vid: int) -> Optional[Volume]:
        for loc in self.locations:
            v = loc.find_volume(vid)
            if v is not None:
                return v
        return None

    def has_volume(self, vid: int) -> bool:
        return self.find_volume(vid) is not None

    def add_volume(
        self,
        vid: int,
        collection: str,
        replication: str = "000",
        ttl_string: str = "",
        preallocate: int = 0,
    ) -> Volume:
        if self.find_volume(vid) is not None:
            raise ValueError(f"volume id {vid} already exists")
        location = max(
            self.locations, key=lambda l: l.max_volume_count - len(l.volumes)
        )
        v = Volume(
            location.directory,
            collection,
            vid,
            replica_placement=ReplicaPlacement.parse(replication),
            ttl=TTL.read(ttl_string),
            needle_map_kind=self.needle_map_kind,
        )
        location.add_volume(v)
        with self._lock:
            self.new_volumes.append(self._volume_message(v))
        return v

    def delete_volume(self, vid: int, keep_ec_files: bool = False) -> bool:
        v = self.find_volume(vid)
        if v is None:
            return False
        msg = self._volume_message(v)
        for loc in self.locations:
            if loc.delete_volume(vid, keep_ec_files=keep_ec_files):
                with self._lock:
                    self.deleted_volumes.append(msg)
                return True
        return False

    def unmount_volume(self, vid: int) -> bool:
        v = self.find_volume(vid)
        if v is None:
            return False
        msg = self._volume_message(v)
        for loc in self.locations:
            if loc.unmount_volume(vid):
                with self._lock:
                    self.deleted_volumes.append(msg)
                return True
        return False

    def mount_volume(self, vid: int) -> bool:
        for loc in self.locations:
            count = loc.load_existing_volumes()
            v = loc.find_volume(vid)
            if v is not None:
                with self._lock:
                    self.new_volumes.append(self._volume_message(v))
                return True
        return False

    def mark_volume_readonly(self, vid: int) -> bool:
        v = self.find_volume(vid)
        if v is None:
            return False
        v.no_write_or_delete = True
        return True

    # --- data path ---
    def write_volume_needle(self, vid: int, n: Needle, sync: bool = False):
        v = self.find_volume(vid)
        if v is None:
            raise LookupError(f"volume {vid} not found")
        if v.is_read_only():
            raise PermissionError(f"volume {vid} is read only")
        result = v.write_needle(n, sync=sync)
        if (
            self.volume_size_limit
            and v.data_file_size() > self.volume_size_limit
        ):
            # report full volume at next heartbeat via size field
            pass
        return result

    def read_volume_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is None:
            raise LookupError(f"volume {vid} not found")
        return v.read_needle(n)

    def delete_volume_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is None:
            return 0
        return v.delete_needle(n)

    # --- heartbeat assembly (ref store.go:194-254) ---
    def _volume_message(self, v: Volume) -> dict:
        return {
            "id": v.id,
            "size": v.data_file_size(),
            "collection": v.collection,
            "file_count": v.file_count(),
            "delete_count": v.deleted_count(),
            "deleted_byte_count": v.deleted_size(),
            "read_only": v.is_read_only(),
            "replica_placement": v.super_block.replica_placement.to_byte(),
            "version": v.version,
            "ttl": v.super_block.ttl.to_u32(),
            "compact_revision": v.super_block.compaction_revision,
            "modified_at_second": int(v.last_modified_ts_seconds),
            # anti-entropy fields: order-independent live-content digest +
            # append frontier let the master spot diverged/stale replicas
            # from heartbeats alone; scrub_corrupt marks a quarantined copy
            "content_digest": v.content_digest(),
            "append_at_ns": v.last_append_at_ns,
            "scrub_corrupt": v.scrub_corrupt,
            # vacuum plane: the garbage ratio rides every heartbeat so the
            # master's vacuum scheduler can rank candidates without an RPC
            # sweep (the per-dispatch VacuumVolumeCheck stays the
            # authoritative re-check)
            "garbage_ratio": round(v.garbage_level(), 4),
            # lifecycle plane: decayed access heat rides the same way, so
            # the master's lifecycle planner ranks hot/cold candidates
            # straight off heartbeats (VolumeLifecycleCheck re-checks
            # authoritatively at dispatch)
            "read_heat": round(v.heat.read_heat(), 4),
            "write_heat": round(v.heat.write_heat(), 4),
        }

    def collect_volume_digests(self) -> list[dict]:
        """Lightweight per-pulse digest refresh: full volume messages only
        travel at stream connect and on add/remove deltas, so steady-state
        writes would leave the master comparing stale digests. This slim
        message (id + digest + frontier + corrupt flag) rides every few
        heartbeat ticks instead."""
        out = []
        read_total = write_total = 0.0
        for loc in self.locations:
            for v in list(loc.volumes.values()):
                rh, wh = v.heat.read_heat(), v.heat.write_heat()
                read_total += rh
                write_total += wh
                out.append(
                    {
                        "id": v.id,
                        "content_digest": v.content_digest(),
                        "append_at_ns": v.last_append_at_ns,
                        "read_only": v.is_read_only(),
                        "scrub_corrupt": v.scrub_corrupt,
                        "garbage_ratio": round(v.garbage_level(), 4),
                        # lifecycle refresh: heat + size must stay current
                        # between full volume messages or the planner
                        # compares temperatures frozen at stream connect
                        "read_heat": round(rh, 4),
                        "write_heat": round(wh, 4),
                        "size": v.data_file_size(),
                        # ec.encode -quietFor reads it off the master: a
                        # volume taking writes must not look quiet there
                        "modified_at_second": int(
                            v.last_modified_ts_seconds
                        ),
                    }
                )
        try:
            from ..util.metrics import VOLUME_HEAT

            VOLUME_HEAT.set(round(read_total, 4), kind="read")
            VOLUME_HEAT.set(round(write_total, 4), kind="write")
        except ImportError:
            pass
        return out

    def collect_heartbeat(self) -> dict:
        volume_messages = []
        max_volume_count = 0
        max_file_key = 0
        for loc in self.locations:
            max_volume_count += loc.max_volume_count
            for v in list(loc.volumes.values()):
                if v.max_file_key() > max_file_key:
                    max_file_key = v.max_file_key()
                volume_messages.append(self._volume_message(v))
        return {
            "ip": self.ip,
            "port": self.port,
            "public_url": self.public_url,
            "max_volume_count": max_volume_count,
            "max_file_key": max_file_key,
            "volumes": volume_messages,
            "has_no_volumes": len(volume_messages) == 0,
        }

    def collect_ec_heartbeat(self) -> dict:
        shard_messages = []
        for loc in self.locations:
            for vid, ev in loc.ec_volumes.items():
                # cold tier: ec_index_bits = local | offloaded — this
                # server still SERVES an offloaded shard (through the
                # remote read-through path), so lookup/read routing is
                # unchanged; the split rides alongside for the planner
                local = ev.shard_bits()
                offloaded = ev.offloaded_bits()
                shard_messages.append(
                    {
                        "id": vid,
                        "collection": ev.collection,
                        "ec_index_bits": local.plus(offloaded).bits,
                        "ec_local_bits": local.bits,
                        "ec_offloaded_bits": offloaded.bits,
                        "read_heat": round(ev.heat.read_heat(), 4),
                    }
                )
        return {
            "ec_shards": shard_messages,
            "has_no_ec_shards": len(shard_messages) == 0,
        }

    def collect_tier_manifest_keys(self) -> dict:
        """{backend_name: set(remote keys)} this server's durable tier
        records still name: EC `.ctm` manifest entries plus tiered
        volumes' .vif remote files — the orphan sweep's reference set
        (a remote object NO live manifest names is a leak, never data)."""
        out: dict[str, set] = {}
        for loc in self.locations:
            for ev in loc.ec_volumes.values():
                for ent in ev.remote_shards.values():
                    name = ent.get("backend", "")
                    key = ent.get("key", "")
                    if name and key:
                        out.setdefault(name, set()).add(key)
            for v in loc.volumes.values():
                info = getattr(v, "volume_info", None)
                if info is None:
                    continue
                for rf in getattr(info, "files", []):
                    name = f"{rf.backend_type}.{rf.backend_id}"
                    if rf.key:
                        out.setdefault(name, set()).add(rf.key)
        return out

    def collect_ec_heat(self) -> list[dict]:
        """Slim per-pulse EC heat refresh (the EC analogue of
        collect_volume_digests): full EC messages only travel every ~17
        ticks, far too slow for the lifecycle planner to notice a warm
        volume turning hot. One (id, read_heat) pair per local EC volume
        rides the anti-entropy tick instead."""
        out = []
        total = 0.0
        for loc in self.locations:
            for vid, ev in loc.ec_volumes.items():
                h = ev.heat.read_heat()
                total += h
                out.append(
                    {
                        "id": vid,
                        "collection": ev.collection,
                        "read_heat": round(h, 4),
                        # cold tier: the offload/recall planners rank off
                        # this same slim refresh (seconds-fresh, like the
                        # re-inflation sensor)
                        "ec_local_bits": ev.shard_bits().bits,
                        "ec_offloaded_bits": ev.offloaded_bits().bits,
                    }
                )
        try:
            from ..util.metrics import VOLUME_HEAT

            VOLUME_HEAT.set(round(total, 4), kind="ec_read")
        except ImportError:
            pass
        return out

    def note_volume_changed(self, old_msg: dict, new_msg: dict) -> None:
        """Queue an in-place layout change (e.g. replica placement rewrite)
        as a deleted(old)+new(new) delta pair; the master moves the volume
        between VolumeLayouts on the next pulse."""
        with self._lock:
            self.deleted_volumes.append(old_msg)
            self.new_volumes.append(new_msg)

    def drain_deltas(self) -> dict:
        with self._lock:
            # collapse same-vid churn within one pulse so the master's
            # delete-then-add processing can't resurrect ghosts:
            # - a volume we no longer hold must not appear as new
            #   (created+deleted within the tick)
            # - keep only the FIRST deleted msg (the layout the master has
            #   registered) and the LAST new msg (the current layout)
            held = {
                vid for loc in self.locations for vid in loc.volumes
            }
            new_by_vid: dict = {}
            for msg in self.new_volumes:
                if int(msg["id"]) in held:
                    new_by_vid[int(msg["id"])] = msg
            deleted_by_vid: dict = {}
            for msg in self.deleted_volumes:
                deleted_by_vid.setdefault(int(msg["id"]), msg)
            out = {
                "new_volumes": list(new_by_vid.values()),
                "deleted_volumes": list(deleted_by_vid.values()),
                "new_ec_shards": self.new_ec_shards,
                "deleted_ec_shards": self.deleted_ec_shards,
            }
            self.new_volumes = []
            self.deleted_volumes = []
            self.new_ec_shards = []
            self.deleted_ec_shards = []
            return out

    def note_ec_shards_changed(
        self, vid: int, collection: str, added: ShardBits, removed: ShardBits
    ) -> None:
        with self._lock:
            if added.bits:
                self.new_ec_shards.append(
                    {"id": vid, "collection": collection, "ec_index_bits": added.bits}
                )
            if removed.bits:
                self.deleted_ec_shards.append(
                    {"id": vid, "collection": collection, "ec_index_bits": removed.bits}
                )


# --- EC volume access (ref store_ec.go) ---
def _store_find_ec_volume(self, vid: int):
    for loc in self.locations:
        ev = loc.find_ec_volume(vid)
        if ev is not None:
            return ev
    return None


def _store_find_ec_shard(self, vid: int, shard_id: int):
    ev = self.find_ec_volume(vid)
    if ev is None:
        return None
    return ev.find_shard(shard_id)


Store.find_ec_volume = _store_find_ec_volume
Store.find_ec_shard = _store_find_ec_shard
