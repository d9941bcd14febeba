"""Fused Pallas GF(2^8) kernel: bit-exactness vs the numpy oracle.

Runs in interpreter mode on the CPU test mesh — every call passes
interpret=True itself; the kernel has no implicit interpret default. The
arithmetic is identical either way, so these pin the layout/permutation
logic — the part that could silently corrupt shards. Mirrors the
reference's conformance posture (ec_test.go byte-compares shard bytes;
here the kernel itself is the unit). The real (non-interpret) compiles
for the chip are in tests/test_tpu_compile.py.
"""

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.codec import NumpyCodec
from seaweedfs_tpu.ops.rs_pallas import (fuse_bitmat, fused_matmul,
                                         make_fused_encode_fn, pick_tile)

RNG = np.random.default_rng(7)


@pytest.fixture(autouse=True, scope="module")
def _own_device_stats():
    """These tests jit exact ragged widths on purpose. Keep that churn
    out of the process-global recompile sentinel, which
    tests/test_device_stats.py asserts is unlatched when it shares an
    xdist worker with this file."""
    from seaweedfs_tpu.ops import device_stats, rs_pallas
    saved = device_stats.DEVICE_STATS
    device_stats.DEVICE_STATS = device_stats.DeviceStats()
    rs_pallas._fused_fn.cache_clear()
    yield
    device_stats.DEVICE_STATS = saved
    rs_pallas._fused_fn.cache_clear()


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (20, 4), (3, 2), (1, 1)])
def test_encode_matches_oracle(k, m):
    n = 2048
    data = RNG.integers(0, 256, (k, n), dtype=np.uint8)
    oracle = NumpyCodec(k, m)
    got = np.asarray(fused_matmul(oracle.matrix[k:], data, interpret=True))
    assert np.array_equal(got, oracle.encode(data))


@pytest.mark.parametrize("n", [1, 127, 128, 129, 1000, 4096 + 311])
def test_ragged_widths(n):
    """Grid-edge columns are discarded, never polluted (column
    independence of the contraction)."""
    k, m = 10, 4
    data = RNG.integers(0, 256, (k, n), dtype=np.uint8)
    oracle = NumpyCodec(k, m)
    got = np.asarray(fused_matmul(oracle.matrix[k:], data, interpret=True))
    assert got.shape == (m, n)
    assert np.array_equal(got, oracle.encode(data))


def test_decode_rows_match_oracle():
    """The kernel serves rebuild too: arbitrary coefficient rows (decode
    plans are inverses, not the encode matrix)."""
    k, m = 6, 3
    oracle = NumpyCodec(k, m)
    data = RNG.integers(0, 256, (k, 512), dtype=np.uint8)
    shards = oracle.encode_to_all(data)
    # drop shards 1 and 7, plan the decode
    present = tuple(i not in (1, 7) for i in range(k + m))
    src, inv = oracle._decode_coeffs(present)
    survivors = shards[list(src)]
    got = np.asarray(fused_matmul(inv[1:2], survivors, interpret=True))
    assert np.array_equal(got[0], data[1])


def test_fuse_bitmat_permutation():
    """fuse_bitmat is exactly the (bit,shard)-major re-grouping of the
    documented gf256.bit_matrix layout."""
    coeffs = RNG.integers(0, 256, (4, 10), dtype=np.uint8)
    b0 = gf256.bit_matrix(coeffs)  # (k*8, r*8)
    bp = fuse_bitmat(coeffs)       # (8r, 8k)
    r, k = coeffs.shape
    for j in range(k):
        for l in range(8):
            for i in range(r):
                for b in range(8):
                    assert bp[b * r + i, l * k + j] == b0[j * 8 + l, i * 8 + b]


def test_pick_tile_bounds():
    for k, m in [(10, 4), (20, 4), (1, 1)]:
        t = pick_tile(k, m, 10 << 20)
        assert t % 128 == 0 and 128 <= t <= 64 << 10
        # working set within budget
        assert t * (9 * k + 41 * m + 2 * (k + m)) <= 8 << 20
    assert pick_tile(10, 4, 300) == 384  # small n rounds up to 128-multiple


def test_make_fused_encode_fn_roundtrip():
    import jax.numpy as jnp
    k, m, n = 10, 4, 1024
    fn, bitmat = make_fused_encode_fn(k, m, n, interpret=True)
    data = RNG.integers(0, 256, (k, n), dtype=np.uint8)
    got = np.asarray(fn(jnp.asarray(bitmat), data))
    assert np.array_equal(got, NumpyCodec(k, m).encode(data))
