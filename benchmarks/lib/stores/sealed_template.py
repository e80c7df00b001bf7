"""One sealed volume built from the seed: `needles` bodies of size_min..size_max
bytes (uniform), cut from a seeded pool, then needles of large_needle_bytes
until the .dat reaches fill_to_bytes. Record lengths follow from the sizes, so
every worker process serialises its share of the needles with the program's
needle format (`Needle.to_bytes`, version 3) and writes it at its own offset of
one file: set-up pays seconds, not the minute a load over HTTP takes."""

from __future__ import annotations

import os

import numpy as np

from . import _volume_writer

POOL_BYTES = 32 << 20
SUPER_BLOCK = 8
VERSION = 3
IDX_ENTRY = np.dtype([("key", ">u8"), ("off", ">u4"), ("size", ">u4")])
CHUNK = 2048  # needles per pwrite


def plan(recipe: dict, seed: int) -> dict:
    """Sizes, pool offsets, cookies and file offsets of every needle."""
    rng = np.random.default_rng([seed, 0x5EA1])
    n = int(recipe["needles"])
    size = rng.integers(recipe["size_min"], recipe["size_max"] + 1, n)
    large = int(recipe["large_needle_bytes"])
    short = int(recipe["fill_to_bytes"]) - SUPER_BLOCK - int(record_bytes(size).sum())
    extra = max(0, -(-short // int(record_bytes(np.array([large]))[0])))
    size = np.concatenate([size, np.full(extra, large, dtype=size.dtype)])
    rec = record_bytes(size)
    return {
        "size": size, "small": n,
        "start": rng.integers(0, POOL_BYTES - large, len(size)),
        "cookie": rng.integers(1, 1 << 32, len(size)),
        "offset": SUPER_BLOCK + np.concatenate([[0], np.cumsum(rec)[:-1]]),
        "dat_bytes": SUPER_BLOCK + int(rec.sum()),
    }


def record_bytes(body: np.ndarray) -> np.ndarray:
    """A version 3 record: 16 header, 4 length, body, 1 flags, 4 checksum,
    8 timestamp, then 1 to 8 bytes of padding to a multiple of 8."""
    return ((body + 33) // 8 + 1) * 8


def pool(seed: int) -> bytes:
    return np.random.default_rng([seed, 0xB0D1]).integers(
        0, 256, POOL_BYTES, dtype=np.uint8
    ).tobytes()


def body_of(p: dict, body_pool: bytes, i: int) -> bytes:
    s = int(p["start"][i])
    return body_pool[s : s + int(p["size"][i])]


class Reader:
    """What a load client needs of this store, laid out as volume `volume`: a
    needle drawn uniformly from the seed among the small ones, or among those
    whose record starts on EC data shard `on_shard` (byte x of a 1 GiB .dat
    lies on shard (x // 1 MiB) % 10), as the target of a GET and its body."""

    def __init__(self, store: dict, seed: int, pick: dict):
        self.p, self.pool = plan(store["recipe"], seed), pool(seed)
        self.volume = int(pick.get("volume", 1))
        small = np.arange(self.p["small"])
        if "on_shard" in pick:
            shard = (self.p["offset"][: self.p["small"]] // (1 << 20)) % 10
            small = small[shard == int(pick["on_shard"])]
        self.candidates = small.tolist()

    def draw(self, rng) -> tuple:
        i = self.candidates[rng.randrange(len(self.candidates))]
        target = f"{self.volume},{i + 1:x}{int(self.p['cookie'][i]):08x}"
        return target, body_of(self.p, self.pool, i)


def link_volume(store: dict, data_dir: str, vid: int) -> None:
    """Volume `vid` of the server's directory as a symbolic link of the template
    (the server never writes to a sealed .dat, and dropping it unlinks the link)."""
    for ext in ("dat", "idx"):
        os.symlink(store[ext], os.path.join(data_dir, f"{vid}.{ext}"))


def build_part(job: tuple) -> np.ndarray:
    recipe, seed, dat, lo, hi = job
    _volume_writer.stay_off_jax()
    from seaweedfs_tpu.storage.needle import Needle

    p, body_pool = plan(recipe, seed), pool(seed)
    entries = np.zeros(hi - lo, dtype=IDX_ENTRY)
    fd = os.open(dat, os.O_WRONLY)
    try:
        for a in range(lo, hi, CHUNK):
            b = min(hi, a + CHUNK)
            blobs = []
            for i in range(a, b):
                n = Needle(
                    cookie=int(p["cookie"][i]), id=i + 1,
                    data=body_of(p, body_pool, i),
                    append_at_ns=_volume_writer.EPOCH_NS + i,
                )
                blob, _, actual = n.to_bytes(VERSION)
                blobs.append(blob)
                entries[i - lo] = (i + 1, int(p["offset"][i]) // 8, n.size)
            blob = b"".join(blobs)
            if len(blob) != int(p["offset"][b - 1] + record_bytes(p["size"][b - 1]) - p["offset"][a]):
                raise RuntimeError("the needle format's record length is not the planned one")
            os.pwrite(fd, blob, int(p["offset"][a]))
    finally:
        os.close(fd)
    return entries


def build(recipe: dict, dirs, seed: int, pool_map, workers: int) -> dict:
    """Writes template.dat and .idx under the run's directory. The traffic lays
    the server's volumes out as links of the two files. Returns what was built."""
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
    # parts of equal bytes, not of equal counts: the large needles come last
    cuts = np.searchsorted(
        p["offset"], [p["dat_bytes"] * j / workers for j in range(1, workers)]
    ).tolist()
    cuts = sorted({0, total, *cuts})
    jobs = [(recipe, seed, dat, cuts[j], cuts[j + 1]) for j in range(len(cuts) - 1)]
    # concatenate hands back native byte order; the .idx is big-endian
    np.concatenate(pool_map(build_part, jobs)).astype(IDX_ENTRY).tofile(idx)
    return {
        "kind": "sealed_template", "dat": dat, "idx": idx, "recipe": recipe,
        "dat_bytes": p["dat_bytes"], "needles": total, "small": p["small"], "volumes": 1,
    }
