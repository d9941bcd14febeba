"""The plain reference of the piggybacked layout: RS(k, m) whose parity
shards carry, sub-chunk by sub-chunk, a second term of a paired sub-chunk.

Nothing here imports the program. Field, flat coding matrix, striping rule
and the data shards are `reference.py`'s (benchmark code). A shard file is
cut into windows of `small_block` bytes, a window into alpha = 2**pairs
sub-chunks; z counts the sub-chunks of a window, `s_i[z]` is sub-chunk z of
data shard i and `a[j, i]` the flat parity coefficient. Data shard i < 2 *
pairs belongs to pair p = i >> 1 on side b = i & 1. Parity shard j holds

    P_j[z] = XOR_i a[j,i] * s_i[z]
             ^ [bit p of z == b] * theta_j * a[j,i] * s_i[z ^ 2**p]

with theta_j = EXP[((theta_seed * m + j) * 11) mod 255] (RS(10,4): seed 5,
five pairs, alpha 32; DESIGN.md, "Piggybacked sub-chunk layout"). It is
computed here in that sparse form: the flat row over every sub-chunk, then
for each coupled shard the theta-scaled term on the half of the sub-chunks
whose gate is open. No (m * alpha, k * alpha) block matrix is ever built,
so a wrong one in the program cannot agree with a wrong one here.
"""

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from lib import reference
from lib.reference import EXP, LARGE_BLOCK, MUL, SMALL_BLOCK

PAIRS = 5           # the program's default (SW_EC_PIGGYBACK_PAIRS)
THETA_SEED = {(10, 4): 5}   # the seed the program pins for a geometry


def thetas(m: int, theta_seed: int) -> list:
    return [int(EXP[((theta_seed * m + j) * 11) % 255]) for j in range(m)]


def gate_open(z: int, i: int) -> bool:
    """Does sub-chunk z of a parity shard carry data shard i's second
    term? Bit p = i >> 1 of z has to equal the shard's side b = i & 1."""
    return (z >> (i >> 1)) & 1 == i & 1


def encode_rows(matrix: np.ndarray, data: np.ndarray, window: int,
                pairs: int, theta: list,
                pool: ThreadPoolExecutor = None) -> np.ndarray:
    """Parity rows (m, w) of data (k, w), w a whole number of windows."""
    k, width = data.shape
    alpha = 1 << pairs
    if width % window or window % alpha:
        raise ValueError(f"width {width}, window {window}, alpha {alpha}")
    coupled = min(k, 2 * pairs)
    a = matrix[k:]
    parity = reference.encode_rows(matrix, data, pool)     # the flat rows

    def by_bit(row: np.ndarray, p: int) -> np.ndarray:
        """A shard row as (windows, z above bit p, bit p of z, z below
        bit p, bytes of a sub-chunk): a view, nothing is copied."""
        return row.reshape(width // window, alpha >> (p + 1), 2, 1 << p,
                           window // alpha)

    def one(j):
        for i in range(coupled):
            p, b = i >> 1, i & 1
            coeff = int(MUL[theta[j], a[j, i]])
            # the gate is open where bit p of z is b; the partner
            # sub-chunk z ^ 2**p is the one whose bit p is 1 - b
            gated = by_bit(parity[j], p)[:, :, b]
            term = np.take(MUL[coeff], by_bit(data[i], p)[:, :, 1 - b])
            np.bitwise_xor(gated, term, out=gated)
        return j

    rows = range(a.shape[0])
    list(pool.map(one, rows) if pool else map(one, rows))
    return parity


def shard_shas(dat_path: str, k: int, m: int, matrix: np.ndarray = None,
               large_block: int = LARGE_BLOCK,
               small_block: int = SMALL_BLOCK, pairs: int = PAIRS,
               theta_seed: int = None) -> list:
    """sha256 of each of the k+m shard files the `.dat` must encode to
    under the piggybacked layout; the window is the small block."""
    if matrix is None:
        matrix = reference.coding_matrix(k, m)
    if theta_seed is None:
        theta_seed = THETA_SEED[(k, m)]
    pairs = min(pairs, k // 2)
    theta = thetas(m, theta_seed)
    hashers = [hashlib.sha256() for _ in range(k + m)]
    remaining = os.path.getsize(dat_path)

    with open(dat_path, "rb") as f, ThreadPoolExecutor(k + m) as pool:
        def code_row(block: int):
            data = np.zeros((k, block), dtype=np.uint8)
            raw = np.frombuffer(f.read(k * block), dtype=np.uint8)
            data.reshape(-1)[:raw.size] = raw
            rows = list(data) + list(encode_rows(
                matrix, data, small_block, pairs, theta, pool))
            list(pool.map(lambda hr: hr[0].update(hr[1]),
                          zip(hashers, rows)))

        while remaining > k * large_block:
            code_row(large_block)
            remaining -= k * large_block
        while remaining > 0:
            code_row(small_block)
            remaining -= k * small_block
    return [h.hexdigest() for h in hashers]


shard_bytes = reference.shard_bytes
sha256_files = reference.sha256_files
