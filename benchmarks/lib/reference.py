"""The plain reference: RS(k, m) over GF(2^8) on the on-disk striping rule.

Nothing here imports the program. The field is GF(2^8) with polynomial
0x11D and generator 2; the coding matrix is the systematic Vandermonde
matrix SeaweedFS v1.71 gets from klauspost/reedsolomon (rows r, columns c:
r**c, times the inverse of its top k x k square). A sealed volume's `.dat`
is striped in rows of k blocks, block j of a row goes to shard j, the tail
row is zero-padded; rows are 1 GiB blocks while more than k x 1 GiB remain,
1 MiB blocks after (weed/storage/erasure_coding/ec_encoder.go:17-23).
"""

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FIELD_POLY = 0x11D
LARGE_BLOCK = 1 << 30
SMALL_BLOCK = 1 << 20


def _tables():
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= FIELD_POLY
    exp[255:510] = exp[:255]
    a = np.arange(256)
    mul = exp[(log[a][:, None] + log[a][None, :]) % 255].astype(np.uint8)
    mul[0, :] = 0
    mul[:, 0] = 0
    return exp, log, mul


EXP, LOG, MUL = _tables()


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(int(LOG[a]) * n) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - int(LOG[a])])


def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for t in range(a.shape[1]):
                acc ^= int(MUL[a[i, t], b[t, j]])
            out[i, j] = acc
    return out


def mat_inv(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8)."""
    n = m.shape[0]
    work = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)],
                          axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if work[r, col]), None)
        if pivot is None:
            raise ValueError("singular matrix")
        if pivot != col:
            work[[col, pivot]] = work[[pivot, col]]
        work[col] = MUL[gf_inv(int(work[col, col]))][work[col]]
        for r in range(n):
            if r != col and work[r, col]:
                work[r] ^= MUL[int(work[r, col])][work[col]]
    return work[:, n:]


def coding_matrix(k: int, m: int) -> np.ndarray:
    """(k+m, k): identity on top, the m parity rows below."""
    vm = np.array([[gf_pow(r, c) for c in range(k)] for r in range(k + m)],
                  dtype=np.uint8)
    return mat_mul(vm, mat_inv(vm[:k]))


def encode_rows(matrix: np.ndarray, data: np.ndarray,
                pool: ThreadPoolExecutor = None) -> np.ndarray:
    """Parity rows (m, w) of data (k, w), by table look-up and XOR."""
    k = data.shape[0]
    parity = matrix[k:]

    def one(i):
        acc = np.zeros(data.shape[1], dtype=np.uint8)
        tmp = np.empty_like(acc)
        for j in range(k):
            np.take(MUL[int(parity[i, j])], data[j], out=tmp)
            acc ^= tmp
        return acc

    rows = range(parity.shape[0])
    return np.stack(list(pool.map(one, rows) if pool else map(one, rows)))


def shard_shas(dat_path: str, k: int, m: int, matrix: np.ndarray = None,
               large_block: int = LARGE_BLOCK,
               small_block: int = SMALL_BLOCK) -> list:
    """sha256 of each of the k+m shard files the `.dat` must encode to.
    `matrix` is for the control: a coding matrix with one coefficient
    changed must come out different."""
    if matrix is None:
        matrix = coding_matrix(k, m)
    hashers = [hashlib.sha256() for _ in range(k + m)]
    remaining = os.path.getsize(dat_path)

    with open(dat_path, "rb") as f, ThreadPoolExecutor(k + m) as pool:
        def code_row(block: int):
            data = np.zeros((k, block), dtype=np.uint8)
            raw = np.frombuffer(f.read(k * block), dtype=np.uint8)
            data.reshape(-1)[:raw.size] = raw
            rows = list(data) + list(encode_rows(matrix, data, pool))
            list(pool.map(lambda hr: hr[0].update(hr[1]),
                          zip(hashers, rows)))

        while remaining > k * large_block:
            code_row(large_block)
            remaining -= k * large_block
        while remaining > 0:
            code_row(small_block)
            remaining -= k * small_block
    return [h.hexdigest() for h in hashers]


def shard_bytes(dat_bytes: int, k: int, large_block: int = LARGE_BLOCK,
                small_block: int = SMALL_BLOCK) -> int:
    """The size of each shard file of a `.dat` of dat_bytes: one block a
    row, the tail row whole."""
    size, remaining = 0, dat_bytes
    while remaining > k * large_block:
        size += large_block
        remaining -= k * large_block
    return size + -(-remaining // (k * small_block)) * small_block


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(8 << 20), b""):
            h.update(block)
    return h.hexdigest()


def sha256_files(paths: list) -> list:
    """Hash several files at once (hashlib releases the GIL)."""
    with ThreadPoolExecutor(max(1, min(len(paths), 8))) as pool:
        return list(pool.map(sha256_file, paths))
