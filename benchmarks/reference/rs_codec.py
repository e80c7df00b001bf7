"""The benchmark's own plain Reed-Solomon codec over GF(2^8): the yardstick
the shard files of a conversion are held to. It imports nothing of the
program, so no later change to the program can move it.

Field: x^8 + x^4 + x^3 + x^2 + 1 (0x11D), generator 2 — klauspost/reedsolomon's
field, which upstream SeaweedFS encodes with. The systematic matrix is a
Vandermonde matrix times the inverse of its top k rows: identity on top,
parity generator rows below. Encoding is a table gather per matrix constant.

File layout (upstream weed/storage/erasure_coding/ec_encoder.go): while more
than k large blocks remain, a row of k large blocks; then rows of k small
blocks until the .dat is used up, the last row padded with zeros. Shard i is
block i of every row, one after another.
"""

from __future__ import annotations

import numpy as np

LARGE_BLOCK = 1 << 30
SMALL_BLOCK = 1 << 20


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    exp[255:] = exp[:255]
    return exp, log


EXP, LOG = _tables()
MUL = EXP[(LOG[:, None] + LOG[None, :]) % 255].astype(np.uint8)
MUL[0, :] = 0
MUL[:, 0] = 0


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("zero has no inverse in GF(2^8)")
    return int(EXP[(255 - LOG[a]) % 255])


def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for r in range(a.shape[0]):
        for j in range(a.shape[1]):
            out[r] ^= MUL[a[r, j]][b[j]]
    return out


def mat_inv(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    aug = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if aug[r, col]), None)
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        aug[col] = MUL[gf_inv(int(aug[col, col]))][aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= MUL[int(aug[r, col])][aug[col]]
    return aug[:, n:].copy()


def build_matrix(k: int, m: int) -> np.ndarray:
    """uint8[k + m, k]: identity rows, then the parity generator rows."""
    vm = np.zeros((k + m, k), dtype=np.uint8)
    for r in range(k + m):
        for c in range(k):
            vm[r, c] = 1 if c == 0 else (0 if r == 0 else EXP[(LOG[r] * c) % 255])
    return mat_mul(vm, mat_inv(vm[:k]))


def apply_matrix(m: np.ndarray, data: np.ndarray) -> np.ndarray:
    """uint8[R, C] x uint8[C, N] -> uint8[R, N] over GF(2^8)."""
    out = np.zeros((m.shape[0], data.shape[1]), dtype=np.uint8)
    for i in range(m.shape[0]):
        for j in range(m.shape[1]):
            c = int(m[i, j])
            if c == 1:
                out[i] ^= data[j]
            elif c:
                out[i] ^= MUL[c][data[j]]
    return out


class Codec:
    def __init__(self, k: int = 10, m: int = 4):
        self.k, self.m = k, m
        self.matrix = build_matrix(k, m)
        self.parity_matrix = self.matrix[k:]

    def encode(self, data: np.ndarray) -> np.ndarray:
        """uint8[k, N] -> parity uint8[m, N]."""
        return apply_matrix(self.parity_matrix, data)

    def recover(self, shards: dict, want: list) -> np.ndarray:
        """Rows `want` of the full shard set from any k surviving shards
        ({shard id: uint8[N]}): the survivors' rows of the matrix inverted,
        then the wanted rows of the matrix applied."""
        ids = sorted(shards)[: self.k]
        if len(ids) < self.k:
            raise ValueError(f"{len(ids)} shards survive, {self.k} are needed")
        decode = mat_inv(self.matrix[ids])
        rows = mat_mul(self.matrix[list(want)], decode)
        return apply_matrix(rows, np.stack([shards[i] for i in ids]))


def row_counts(dat_size: int, k: int) -> tuple[int, int]:
    """(rows of large blocks, rows of small blocks) for a .dat of that size."""
    n_large = 0
    while dat_size - n_large * LARGE_BLOCK * k > LARGE_BLOCK * k:
        n_large += 1
    rest = dat_size - n_large * LARGE_BLOCK * k
    n_small = -(-rest // (SMALL_BLOCK * k)) if rest > 0 else 0
    return n_large, n_small


def shard_size(dat_size: int, k: int) -> int:
    n_large, n_small = row_counts(dat_size, k)
    return n_large * LARGE_BLOCK + n_small * SMALL_BLOCK


def row_spans(dat_size: int, k: int) -> list:
    """[(offset in the .dat, offset in every shard file, block size)] per row."""
    n_large, n_small = row_counts(dat_size, k)
    spans, dat_off, shard_off = [], 0, 0
    for rows, block in ((n_large, LARGE_BLOCK), (n_small, SMALL_BLOCK)):
        for _ in range(rows):
            spans.append((dat_off, shard_off, block))
            dat_off += block * k
            shard_off += block
    return spans


def data_rows(dat: np.ndarray, dat_off: int, block: int, k: int) -> np.ndarray:
    """The k data blocks of one row as uint8[k, block], zero past the end."""
    flat = np.zeros(block * k, dtype=np.uint8)
    piece = dat[dat_off : dat_off + block * k]
    flat[: len(piece)] = piece
    return flat.reshape(k, block)
