"""One sealed volume as a filer fills it: files of file_bytes_min..file_bytes_max
bytes (log-uniform, a stratified draw from the seed: `file_sizes`), each cut into chunk needles of exactly
chunk_bytes with the file's tail shorter (upstream `weed filer -maxMB`,
filer_server_handlers_write_autochunk.go), the chunks of a file appended one
after another, files appended until the .dat has reached fill_to_bytes (the
write that carries a volume over its limit is its last). Bodies are cut from a
seeded pool. Built offline as `sealed_template` builds its volume: every
worker process serialises its share of the needles with the program's needle
format (`Needle.to_bytes`, version 3) and writes it at its own offset of one
file; the traffic lays it out with `sealed_template.link_volume`.

The `Reader` draws uniformly among ALL chunk needles: which shards a needle's
record lies on is the layout's to say, not the draw's. It hands a body out as
a view of the pool, never a copy, and says with the plain reference
(benchmarks/reference/ec_locate.py) what a needle's intervals are.
"""

from __future__ import annotations

import functools
import math
import os

import numpy as np

from . import _volume_writer
from .sealed_template import IDX_ENTRY, SUPER_BLOCK, VERSION, record_bytes
from ...reference import ec_locate

POOL_BYTES = 64 << 20


def file_sizes(recipe: dict, rng):
    """File sizes of the law (log-uniform between file_bytes_min and
    file_bytes_max), a round at a time: a round is as many files as fill the
    volume at the law's mean, one from each of as many equal slices of the law,
    in an order drawn from the seed. A stratified draw: a volume of ~70 files
    then holds the law's mix of sizes as a volume of thousands does, whatever
    the seed. A plain draw of ~70 left 61 to 75 files and 3.50 to 3.85 MB a
    needle between seeds, which moved `get_rate` by more than the server did."""
    lo, hi = math.log(recipe["file_bytes_min"]), math.log(recipe["file_bytes_max"])
    mean = (recipe["file_bytes_max"] - recipe["file_bytes_min"]) / (hi - lo)
    n = max(1, round(int(recipe["fill_to_bytes"]) / mean))
    while True:
        slices = (rng.permutation(n) + rng.uniform(size=n)) / n
        for u in slices:
            yield max(1, int(round(math.exp(lo + u * (hi - lo)))))


def plan(recipe: dict, seed: int) -> dict:
    """Sizes, file numbers, pool offsets, cookies and file offsets of every
    chunk needle, in the order appended."""
    rng = np.random.default_rng([seed, 0xC4A2])
    chunk, fill = int(recipe["chunk_bytes"]), int(recipe["fill_to_bytes"])
    size, file_of, files, total = [], [], 0, SUPER_BLOCK
    for file_bytes in file_sizes(recipe, rng):
        if total >= fill:
            break
        full, tail = divmod(file_bytes, chunk)
        parts = [chunk] * full + ([tail] if tail else [])
        size += parts
        file_of += [files] * len(parts)
        total += int(record_bytes(np.array(parts)).sum())
        files += 1
    size = np.array(size, dtype=np.int64)
    rec = record_bytes(size)
    return {
        "size": size, "file": np.array(file_of), "files": files,
        "start": rng.integers(0, POOL_BYTES - chunk, len(size)),
        "cookie": rng.integers(1, 1 << 32, len(size)),
        "offset": SUPER_BLOCK + np.concatenate([[0], np.cumsum(rec)[:-1]]),
        "record": rec, "dat_bytes": SUPER_BLOCK + int(rec.sum()),
    }


def pool(seed: int) -> bytes:
    return np.random.default_rng([seed, 0xB0D2]).integers(
        0, 256, POOL_BYTES, dtype=np.uint8
    ).tobytes()


class Reader:
    """What a load client needs of this store, laid out as volume `volume`."""

    def __init__(self, store: dict, seed: int, pick: dict):
        self.p, self.seed = plan(store["recipe"], seed), seed
        self.volume = int(pick.get("volume", 1))
        self.lost = set(pick.get("lost_shards", []))
        self.k = int(pick.get("data_shards", 10))
        self.count = len(self.p["size"])

    def target(self, i: int) -> str:
        return f"{self.volume},{i + 1:x}{int(self.p['cookie'][i]):08x}"

    @functools.cached_property
    def pool(self) -> memoryview:
        return memoryview(pool(self.seed))

    def body(self, i: int) -> memoryview:
        s = int(self.p["start"][i])
        return self.pool[s : s + int(self.p["size"][i])]

    def draw_index(self, rng) -> int:
        return rng.randrange(self.count)

    def draw(self, rng) -> tuple:
        i = self.draw_index(rng)
        return self.target(i), self.body(i)

    def intervals(self, i: int) -> list:
        """Needle i's [(shard, offset in the shard file, length)] by the plain rule."""
        return ec_locate.locate(int(self.p["offset"][i]), int(self.p["record"][i]),
                                self.p["dat_bytes"], self.k)

    def tallies(self) -> np.ndarray:
        """int[needles, 3]: intervals, intervals on a lost shard, 1 if any."""
        return np.array([ec_locate.tally(self.intervals(i), self.lost)
                         for i in range(self.count)], dtype=np.int64)


def build_part(job: tuple) -> np.ndarray:
    recipe, seed, dat, lo, hi = job
    _volume_writer.stay_off_jax()
    from seaweedfs_tpu.storage.needle import Needle

    p, body_pool = plan(recipe, seed), memoryview(pool(seed))
    entries = np.zeros(hi - lo, dtype=IDX_ENTRY)
    fd = os.open(dat, os.O_WRONLY)
    try:
        for i in range(lo, hi):
            s = int(p["start"][i])
            n = Needle(cookie=int(p["cookie"][i]), id=i + 1,
                       data=bytes(body_pool[s : s + int(p["size"][i])]),
                       append_at_ns=_volume_writer.EPOCH_NS + i)
            blob, _, _actual = n.to_bytes(VERSION)
            if len(blob) != int(p["record"][i]):
                raise RuntimeError("the needle format's record length is not the planned one")
            os.pwrite(fd, blob, int(p["offset"][i]))
            entries[i - lo] = (i + 1, int(p["offset"][i]) // 8, n.size)
    finally:
        os.close(fd)
    return entries


def build(recipe: dict, dirs, seed: int, pool_map, workers: int) -> dict:
    """Writes template.dat and .idx under the run's directory, and returns
    what was built."""
    out_dir = os.path.join(dirs.scratch, "template")
    p = plan(recipe, seed)
    total = len(p["size"])
    # an empty volume from the storage library gives the super block
    v = _volume_writer.open_volume(out_dir, 1)
    v.close()
    dat, idx = os.path.join(out_dir, "template.dat"), os.path.join(out_dir, "template.idx")
    os.replace(os.path.join(out_dir, "1.dat"), dat)
    for name in os.listdir(out_dir):
        if name.startswith("1."):
            os.unlink(os.path.join(out_dir, name))
    if os.path.getsize(dat) != SUPER_BLOCK:
        raise RuntimeError(f"super block of {os.path.getsize(dat)} bytes, want {SUPER_BLOCK}")
    os.truncate(dat, p["dat_bytes"])
    cuts = np.searchsorted(
        p["offset"], [p["dat_bytes"] * j / workers for j in range(1, workers)]
    ).tolist()
    cuts = sorted({0, total, *cuts})
    jobs = [(recipe, seed, dat, cuts[j], cuts[j + 1]) for j in range(len(cuts) - 1)]
    # concatenate hands back native byte order; the .idx is big-endian
    np.concatenate(pool_map(build_part, jobs)).astype(IDX_ENTRY).tofile(idx)
    chunk = int(recipe["chunk_bytes"])
    return {
        "kind": "sealed_chunks", "dat": dat, "idx": idx, "recipe": recipe,
        "dat_bytes": p["dat_bytes"], "needles": total, "files": p["files"],
        "full_chunks": int((p["size"] == chunk).sum()), "volumes": 1,
    }
