"""The `.ecx` of a mounted EC volume is mapped at mount and searched in the
mapping (ISSUE 38): a probe makes no system call, a delete's tombstone shows
through at once, `close()` leaves no mapping and no descriptor, and a
filesystem that refuses a mapping leaves the `pread` search.

The yardstick is `search_needle_from_sorted_index`, the `pread` search the
mapped one took the place of, on the same file. Everything runs on the CPU:
answers, bytes, counters and descriptors are checked, never a time."""

import asyncio
import os
import random
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.storage.erasure_coding import (
    write_ec_files,
    write_sorted_file_from_idx,
)
from seaweedfs_tpu.storage.erasure_coding import ec_volume as ecv
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (
    EcVolume,
    NeedleNotFound,
    search_needle_from_sorted_index,
)
from seaweedfs_tpu.storage.idx import entry_to_bytes
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.types import (
    NEEDLE_ID_SIZE,
    NEEDLE_MAP_ENTRY_SIZE,
    OFFSET_SIZE,
    TOMBSTONE_FILE_SIZE,
)

from benchmarks.lib import common, metrics as layer_metrics
from test_stage_tracing import CHUNK_CELL, SPREAD_CELL, moved, scrape

LOOKUPS = "seaweedfs_tpu_ec_index_lookups_total"
VID = 7


# ------------------------------------------------- an index alone, by hand
def seeded_index(n: int, seed: int = 38) -> dict:
    """key -> (offset_units, size) of n entries; keys leave room below,
    between and above."""
    rng = random.Random(seed * 1000 + n)
    keys = sorted(rng.sample(range(10, 10 + 50 * max(n, 1)), n))
    return {k: (rng.randrange(1, 1 << 31), rng.randrange(1, 1 << 20)) for k in keys}


def index_bytes(entries: dict) -> bytes:
    return b"".join(entry_to_bytes(k, o, s) for k, (o, s) in sorted(entries.items()))


def an_index_volume(directory, entries: dict) -> EcVolume:
    """An EcVolume over an `.ecx` written by hand: no shard is needed to
    search or to delete."""
    with open(os.path.join(directory, f"{VID}.ecx"), "wb") as f:
        f.write(index_bytes(entries))
    return EcVolume(str(directory), "", VID)


def by_pread(ev: EcVolume, key: int):
    """The yardstick's answer: (offset_units, size), or None for not found."""
    try:
        return search_needle_from_sorted_index(ev._ecx, ev.ecx_file_size, key)
    except NeedleNotFound:
        return None


def by_mapping(ev: EcVolume, key: int):
    try:
        return ev.find_needle_from_ecx(key)
    except NeedleNotFound:
        return None


def absent_keys(entries: dict) -> list:
    keys = sorted(entries)
    if not keys:
        return [0, 1, 1 << 40, (1 << 64) - 1]
    between = [k + 1 for k in keys if k + 1 not in entries][:200]
    return [0, keys[0] - 1, keys[-1] + 1, (1 << 64) - 1] + between


@pytest.mark.parametrize("n", [1, 2, 1000])
def test_every_key_of_the_index_is_found_as_the_pread_search_finds_it(tmp_path, n):
    entries = seeded_index(n)
    ev = an_index_volume(tmp_path, entries)
    try:
        assert ev._ecx_map is not None and len(ev._ecx_map) == n * NEEDLE_MAP_ENTRY_SIZE
        for key, want in entries.items():
            assert by_mapping(ev, key) == by_pread(ev, key) == want, key
    finally:
        ev.close()


@pytest.mark.parametrize("n", [0, 1, 2, 1000])
def test_a_key_below_between_or_above_is_not_found(tmp_path, n):
    entries = seeded_index(n)
    ev = an_index_volume(tmp_path, entries)
    try:
        assert (ev._ecx_map is None) == (n == 0)  # an empty file has no mapping
        for key in absent_keys(entries):
            assert by_mapping(ev, key) is None and by_pread(ev, key) is None, key
    finally:
        ev.close()


# ------------------------------------- no pread of the index, and the counter
def ecx_descriptors() -> list:
    """Paths of this process's open descriptors that end in `.ecx`."""
    found = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            path = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if path.endswith(".ecx") or path.endswith(".ecx (deleted)"):
            found.append(path)
    return found


def ecx_mappings() -> list:
    with open("/proc/self/maps") as f:
        return [line.split()[-1] for line in f if ".ecx" in line]


def no_pread_of(ev: EcVolume, monkeypatch):
    """`os.pread` raises on the volume's `.ecx` (its shard files are read
    as ever)."""
    real, ecx_fd = os.pread, ev._ecx.fileno()

    def pread(fd, n, offset):
        if fd == ecx_fd:
            raise AssertionError("a pread of the mapped .ecx")
        return real(fd, n, offset)

    monkeypatch.setattr(os, "pread", pread)


class Served:
    """A volume server that was never started, over one EC volume of 39
    needles built offline: all 14 shard files, the `.ecx`, no `.dat`."""

    def __init__(self, directory):
        self.dir = str(directory)
        self.base = os.path.join(self.dir, str(VID))
        v = Volume(self.dir, "", VID)
        self.body = {}
        for key in range(1, 40):
            self.body[key] = bytes([key]) * (500 + 7 * key)
            v.write_needle(Needle(id=key, cookie=0x3800 + key, data=self.body[key]))
        v.close()
        write_ec_files(self.base)
        write_sorted_file_from_idx(self.base)
        os.remove(self.base + ".dat")
        os.remove(self.base + ".idx")
        self.vs = VolumeServer(master="127.0.0.1:1", directories=[self.dir], port=0)

    @property
    def ev(self) -> EcVolume:
        return self.vs.store.find_ec_volume(VID)

    def rpc(self, name: str, **req):
        req.setdefault("volume_id", VID)
        reply = asyncio.run(getattr(self.vs, name)(req, None))
        assert not reply.get("error"), reply
        return reply

    def read(self, key: int):
        n = asyncio.run(self.vs.read_ec_needle(self.ev, key))
        return None if n is None else bytes(n.data)

    def close(self):
        self.vs.store.close()


@pytest.fixture
def served(tmp_path):
    s = Served(tmp_path)
    yield s
    s.close()


def test_a_search_makes_no_pread_and_counts_itself_once(served, monkeypatch):
    ev = served.ev
    no_pread_of(ev, monkeypatch)
    before = scrape()
    for key in (1, 20, 39):
        offset_units, size = ev.find_needle_from_ecx(key)
        assert size != TOMBSTONE_FILE_SIZE and offset_units > 0
    with pytest.raises(NeedleNotFound):
        ev.find_needle_from_ecx(4000)
    after = scrape()
    assert moved(before, after, LOOKUPS, via="mapping") == 4
    assert moved(before, after, LOOKUPS) == 4


def test_a_needle_read_locates_through_the_mapping(served, monkeypatch):
    no_pread_of(served.ev, monkeypatch)
    before = scrape()
    assert served.read(5) == served.body[5]
    assert served.read(4000) is None
    after = scrape()
    assert moved(before, after, LOOKUPS, via="mapping") == 2
    assert moved(before, after, LOOKUPS, via="pread") == 0


def test_the_cells_metric_file_reads_the_family_as_a_run_does(served):
    """`ec_read.mapped_locate_share`: 100 where every search went through
    the mapping, left out of the line by a program without the family."""
    before = scrape()
    assert served.read(5) == served.body[5]
    served.ev.find_needle_from_ecx(6)
    after = scrape()
    spec = common.load("layer_metrics", "ec_read.mapped_locate_share.json")
    entry = common.benchmark_json()["per_layer"][-1]
    assert entry["name"] == spec["name"]
    assert entry["workloads"] == ["warm-rs10.4.degraded-get-c16", SPREAD_CELL, CHUNK_CELL]
    for field in ("unit", "better", "source", "layer", "moves"):
        assert entry[field] == spec[field], field
    assert layer_metrics.Observed(before, after, {}, {}, {}, {}, None, None, {}).value(spec) == 100.0
    parents = [{k: v for k, v in page.items() if not k.startswith(LOOKUPS)} for page in (before, after)]
    assert layer_metrics.Observed(*parents, {}, {}, {}, {}, None, None, {}).value(spec) is None


# ------------------------------------------- a delete shows through at once
GONE = 17


def a_second_volume_sees(served) -> int:
    other = EcVolume(served.dir, "", VID)
    try:
        return other.find_needle_from_ecx(GONE)[1]
    finally:
        other.close()


def the_journal(served) -> bytes:
    with open(served.base + ".ecj", "rb") as f:
        return f.read()


def bulk_found(served, use_device: bool) -> list:
    probes = np.array([GONE, GONE + 1] + [GONE] * 70, dtype=np.uint64)
    _off, _size, found = served.ev.bulk_locate(probes, use_device=use_device)
    return [bool(found[0]), bool(found[1]), bool(found[2:].any())]


@pytest.mark.parametrize(
    "seen_by,want",
    [
        (lambda s: s.ev.find_needle_from_ecx(GONE)[1], TOMBSTONE_FILE_SIZE),
        (a_second_volume_sees, TOMBSTONE_FILE_SIZE),
        (the_journal, GONE.to_bytes(NEEDLE_ID_SIZE, "big")),
        (lambda s: s.read(GONE), None),
        (lambda s: bulk_found(s, use_device=False), [False, True, False]),
        (lambda s: bulk_found(s, use_device=True), [False, True, False]),
    ],
    ids=["the_same_volume", "a_second_volume", "the_ecj", "a_needle_read",
         "bulk_locate_on_the_host", "bulk_locate_on_the_device"],
)
def test_an_acknowledged_delete_reads_back_deleted_at_once(served, seen_by, want):
    assert seen_by(served) != want  # alive before
    served.ev.delete_needle_from_ecx(GONE)
    assert seen_by(served) == want  # no refresh step between


def test_a_deletes_bytes_are_four_in_the_ecx_and_the_key_in_the_ecj(served):
    """What the `pread` search's delete wrote: the entry's size field
    tombstoned in place, the rest of the `.ecx` as it was, the key appended
    to the `.ecj`; a key that is not there writes nothing."""
    with open(served.base + ".ecx", "rb") as f:
        was = f.read()
    at = next(
        i for i in range(0, len(was), NEEDLE_MAP_ENTRY_SIZE)
        if int.from_bytes(was[i : i + NEEDLE_ID_SIZE], "big") == GONE
    )
    size_at = at + NEEDLE_ID_SIZE + OFFSET_SIZE
    want = was[:size_at] + b"\xff\xff\xff\xff" + was[size_at + 4 :]
    mutations = served.ev._ecx_mutations
    served.ev.delete_needle_from_ecx(4000)
    served.ev.delete_needle_from_ecx(GONE)
    with open(served.base + ".ecx", "rb") as f:
        assert f.read() == want
    assert the_journal(served) == GONE.to_bytes(NEEDLE_ID_SIZE, "big")
    assert served.ev._ecx_mutations == mutations + 1


def test_searches_beside_a_deleting_thread_see_the_size_or_the_tombstone(tmp_path):
    """The loop searches while an executor thread deletes: a search reads
    the entry's live size or its tombstone and never a third thing, and one
    that starts after the delete returned reads the tombstone."""
    entries = seeded_index(1000)
    ev = an_index_volume(tmp_path, entries)
    keys = sorted(entries)
    deleted: set = set()
    wrong: list = []
    stop = threading.Event()

    def search(seed):
        rng = random.Random(seed)
        while not stop.is_set():
            key = rng.choice(keys)
            gone_before = key in deleted
            offset_units, size = ev.find_needle_from_ecx(key)
            live = (offset_units, size) == entries[key]
            dead = (offset_units, size) == (entries[key][0], TOMBSTONE_FILE_SIZE)
            if not (dead or (live and not gone_before)):
                wrong.append((key, offset_units, size))

    threads = [threading.Thread(target=search, args=(s,)) for s in range(12)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        end = time.monotonic() + 0.5
        for key in random.Random(38).sample(keys, 300):
            ev.delete_needle_from_ecx(key)
            deleted.add(key)
            if time.monotonic() > end:
                break
    finally:
        stop.set()
        for t in threads:
            t.join(20)
        sys.setswitchinterval(old)
        alive = [t for t in threads if t.is_alive()]
        ev.close()
    assert not alive and not wrong, wrong[:5]
    assert len(deleted) > 10


# --------------------------- a filesystem that refuses the mapping at mount
@pytest.mark.parametrize("error", [OSError(19, "No such device"), ValueError("refused")],
                         ids=["OSError", "ValueError"])
def test_where_no_mapping_can_be_made_the_volume_mounts_and_searches_by_pread(
    served, monkeypatch, error
):
    def refuse(*a, **kw):
        raise error

    served.rpc("_grpc_ec_unmount", shard_ids=list(range(14)))
    monkeypatch.setattr(ecv.mmap, "mmap", refuse)
    served.rpc("_grpc_ec_mount", shard_ids=list(range(14)))
    ev = served.ev
    assert ev is not None and ev._ecx_map is None and not ecx_mappings()
    before = scrape()
    assert ev.find_needle_from_ecx(5) == by_pread(ev, 5)
    assert served.read(5) == served.body[5]
    with pytest.raises(NeedleNotFound):
        ev.find_needle_from_ecx(4000)
    ev.delete_needle_from_ecx(GONE)
    assert ev.find_needle_from_ecx(GONE)[1] == TOMBSTONE_FILE_SIZE
    assert the_journal(served) == GONE.to_bytes(NEEDLE_ID_SIZE, "big")
    after = scrape()
    assert moved(before, after, LOOKUPS, via="pread") == 5  # the delete searches too
    assert moved(before, after, LOOKUPS, via="mapping") == 0


# ------------------------------------------------------------- the lifetime
def test_a_mounted_volume_holds_one_mapping_of_its_ecx(served):
    """And two descriptors: Python's `mmap` keeps a duplicate of the one it
    was given until the mapping is closed."""
    assert ecx_descriptors() == [served.base + ".ecx"] * 2
    assert ecx_mappings() == [served.base + ".ecx"]


@pytest.mark.parametrize("how", ["close", "destroy", "unmount", "store_close"])
def test_nothing_of_the_ecx_stays_open_or_mapped(served, how):
    ev = served.ev
    if how == "close":
        ev.close()
    elif how == "destroy":
        ev.destroy()
        assert not os.path.exists(served.base + ".ecx")
    elif how == "unmount":
        served.rpc("_grpc_ec_unmount", shard_ids=list(range(14)))
        assert served.ev is None
    else:
        served.vs.store.close()
    assert ev._ecx_map.closed and ev._ecx.closed
    assert ecx_descriptors() == [] and ecx_mappings() == []
    with pytest.raises(ValueError):
        ev.find_needle_from_ecx(5)  # a closed mapping, not a fault
    ev.close()  # a second close is quiet


def test_mounted_again_the_volume_maps_the_file_that_is_there_now(served):
    """Unmount, the `.ecx` replaced by rename (as `VolumeEcShardsCopy`'s pull
    replaces it), mount: the search reads the new file."""
    shards = list(range(14))
    first = served.ev
    assert first.find_needle_from_ecx(5)[1] != TOMBSTONE_FILE_SIZE
    served.rpc("_grpc_ec_unmount", shard_ids=shards)
    # the new index: needle 5 gone, and one more needle than before
    other = EcVolume(served.dir, "", VID)
    other.delete_needle_from_ecx(5)
    other.close()
    with open(served.base + ".ecx", "rb") as f:
        entries = f.read() + entry_to_bytes(4000, 9, 99)
    with open(served.base + ".ecx.tmp", "wb") as f:
        f.write(entries)
    os.replace(served.base + ".ecx.tmp", served.base + ".ecx")
    served.rpc("_grpc_ec_mount", shard_ids=shards)
    again = served.ev
    assert again is not first and again.ecx_file_size == len(entries)
    assert again.find_needle_from_ecx(5)[1] == TOMBSTONE_FILE_SIZE
    assert again.find_needle_from_ecx(4000) == (9, 99)
    assert set(ecx_descriptors()) == {served.base + ".ecx"}
    assert ecx_mappings() == [served.base + ".ecx"]


def test_an_index_written_anew_beside_a_mounted_volume_replaces_the_file(served):
    """`write_sorted_file_from_idx` never truncates an `.ecx` in place (a
    file cut short under a mapping kills the reader). What that costs, as
    its docstring says: a volume mounted meanwhile stays on the OLD index,
    its deletes reach the old file and the `.ecj` only, and it takes an
    unmount and a mount to serve the new one."""
    ev = served.ev
    was = os.stat(served.base + ".ecx").st_ino
    with open(served.base + ".idx", "wb") as f:
        f.write(entry_to_bytes(4000, 9, 99))
    write_sorted_file_from_idx(served.base)
    assert os.stat(served.base + ".ecx").st_ino != was
    assert not os.path.exists(served.base + ".ecx.tmp")
    assert ev.find_needle_from_ecx(5)[1] != TOMBSTONE_FILE_SIZE  # the old file, whole
    ev.delete_needle_from_ecx(GONE)  # acknowledged on the old index ...
    assert ev.find_needle_from_ecx(GONE)[1] == TOMBSTONE_FILE_SIZE
    assert the_journal(served) == GONE.to_bytes(NEEDLE_ID_SIZE, "big")  # ... and journalled
    with open(served.base + ".ecx", "rb") as f:
        assert f.read() == entry_to_bytes(4000, 9, 99)  # the new file never saw it
    shards = list(range(14))
    served.rpc("_grpc_ec_unmount", shard_ids=shards)
    served.rpc("_grpc_ec_mount", shard_ids=shards)
    assert served.ev.find_needle_from_ecx(4000) == (9, 99)
    with pytest.raises(NeedleNotFound):
        served.ev.find_needle_from_ecx(5)
