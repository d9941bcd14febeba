"""Fused Pallas TPU kernel for GF(2^8) Reed-Solomon coding.

The round-2 XLA kernel (ops/rs_tpu.py) materialized the 8x bit-plane
expansion and the 4-byte-per-bit int32 matmul result in HBM around a
skinny matmul — bandwidth-bound on its own temporaries.
This kernel fuses unpack -> matmul -> pack into one pallas_call so the
only HBM traffic is the uint8 payload in and the uint8 code rows out
((k + r)/k bytes moved per payload byte); the bit-planes and int32
products live and die in VMEM, tile by tile.

Layout trick that keeps the kernel reshape-free: bit-plane rows are
ordered (bit, shard) — row l*k + j is bit l of input shard j — so the
in-kernel expansion is a plain sublane-axis concatenation of the eight
shifted-AND planes, and the pack side slices eight (r, tile) blocks
back out of the (8r, tile) matmul result. The GF(2) lift of the byte
coefficient matrix (ops/gf256.bit_matrix, input rows (shard, bit),
output cols (shard, bit)) is permuted once on the host to match
(fuse_bitmat below).

Exactness: everything is integer — the (8r, 8k) 0/1 matrix times 0/1
planes accumulates in int32 (row sums <= 8k <= 2048), & 1 recovers the
GF(2) sum, and the byte pack is an OR of disjoint bits — so output is
bit-identical to the numpy oracle / native AVX2 path for every matrix
and geometry (tests/test_rs_pallas.py pins this, incl. ragged widths).

Column independence makes grid-edge padding safe: the matmul contracts
over sublanes only, so garbage lanes in a ragged final tile never leak
into valid output columns. Any n >= 1 works without host-side padding.

Replaces the hot loop of reference ec_encoder.go:118-134 (klauspost
AVX2 GF multiply) — same contract, MXU execution.
"""

from __future__ import annotations

import functools

import numpy as np

from . import gf256


def _pl():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..util.jax_platform import configure_compile_cache
    configure_compile_cache()
    return jax, jnp, pl, pltpu


@functools.lru_cache(maxsize=64)
def _fused_bitmat_cached(coeff_bytes: bytes, r: int, k: int) -> np.ndarray:
    coeffs = np.frombuffer(coeff_bytes, dtype=np.uint8).reshape(r, k)
    b0 = gf256.bit_matrix(coeffs)  # (k*8, r*8): in row j*8+l, out col i*8+b
    # -> (8r, 8k): out row b*r+i, in col l*k+j  (transposed for the MXU,
    # both axes re-grouped plane-major)
    return np.ascontiguousarray(
        b0.reshape(k, 8, r, 8).transpose(3, 2, 1, 0).reshape(8 * r, 8 * k)
    ).astype(np.int8)


def fuse_bitmat(coeffs: np.ndarray) -> np.ndarray:
    """(r, k) GF(2^8) byte matrix -> (8r, 8k) int8 plane-major GF(2) lift."""
    coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
    r, k = coeffs.shape
    return _fused_bitmat_cached(coeffs.tobytes(), r, k)


def pick_tile(k: int, r: int, n: int, vmem_budget: int = 8 << 20) -> int:
    """Largest lane-tile (multiple of 128, <= 64K) whose working set fits
    the VMEM budget: payload tile (k), 8 planes (8k), int32 products
    (32r), unpacked bits (8r), packed out (r), plus pallas's double
    buffering of the in/out blocks (2(k+r))."""
    per_lane = 9 * k + 41 * r + 2 * (k + r)
    tile = (vmem_budget // per_lane) // 128 * 128
    tile = max(128, min(tile, 64 << 10))
    if n < tile:
        tile = max(128, (n + 127) // 128 * 128)
    return tile


# the Mosaic kernel's name, stable across refactors: a profiler trace
# is reduced by it, not by the name XLA gives the custom call
KERNEL_NAME = "sw_rs_fused"


@functools.lru_cache(maxsize=256)
def _fused_fn(k: int, r: int, n: int, tile: int, interpret: bool):
    """Jitted (bitmat (8r, 8k) int8, data (k, n) uint8) -> (r, n) uint8."""
    jax, jnp, pl, pltpu = _pl()

    def kernel(bitmat_ref, data_ref, out_ref):
        data = data_ref[...]  # (k, tile) uint8
        # unpack: eight mask-and-compare planes, stacked plane-major
        # along sublanes -> (8k, tile) in {0,1}. (Mask+compare, not
        # shifts: Mosaic has no uint8 shrui legalization. The masks and
        # the payload view are int8 — bit-identical for bitwise AND,
        # and Mosaic can't materialize uint8 constants.)
        bits = jax.lax.bitcast_convert_type(data, jnp.int8)
        masks = (1, 2, 4, 8, 16, 32, 64, -128)
        x = jnp.concatenate(
            [((bits & jnp.int8(m)) != 0).astype(jnp.int8) for m in masks],
            axis=0)
        # MXU: exact 0/1 arithmetic, int32 accumulation
        y = jax.lax.dot_general(
            bitmat_ref[...], x,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        # pack: bit b of output shard i is row b*r+i; multiply-accumulate
        # in int32 (disjoint bits), downcast once
        acc = y[0:r, :] & 1
        for b in range(1, 8):
            acc = acc + (y[b * r:(b + 1) * r, :] & 1) * (1 << b)
        out_ref[...] = acc.astype(jnp.uint8)

    grid = (n + tile - 1) // tile
    call = pl.pallas_call(
        kernel,
        name=KERNEL_NAME,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((8 * r, 8 * k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.uint8),
        interpret=interpret,
    )

    def sw_rs_fused(bitmat, data):      # the module is jit_sw_rs_fused
        return call(bitmat, data)

    from . import device_stats
    return device_stats.wrap(jax.jit(sw_rs_fused), "rs_pallas._fused_fn")


from . import device_stats as _device_stats  # noqa: E402

_device_stats.register_jit_factory("rs_pallas._fused_fn", _fused_fn)
_device_stats.register_jit_factory("rs_pallas._fused_bitmat_cached",
                                   _fused_bitmat_cached)


def fused_matmul(coeffs: np.ndarray, data, interpret: bool):
    """coeffs (r, k) GF(2^8) x data (k, n) uint8 -> (r, n) uint8 (device
    array). `data` may be a numpy or device array; transfer is implicit.
    `interpret` is the caller's explicit choice: False compiles for the
    TPU, True runs the Pallas interpreter (CPU tests only)."""
    import jax.numpy as jnp
    coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
    r, k = coeffs.shape
    n = data.shape[1]
    bitmat = jnp.asarray(fuse_bitmat(coeffs))
    fn = _fused_fn(k, r, n, pick_tile(k, r, n), interpret)
    return fn(bitmat, data)


def make_fused_encode_fn(k: int, m: int, n: int,
                         matrix_kind: str = "vandermonde", *,
                         interpret: bool):
    """(jitted fn(bitmat, data (k,n) uint8) -> (m,n) uint8, bitmat (8m,8k)).

    Direct Pallas-path handle with an explicit interpret switch — the
    production entry point is rs_tpu.make_encode_fn / fn_and_bitmat,
    which dispatches here automatically on TPU.
    """
    matrix = gf256.build_matrix(k, k + m, matrix_kind)
    bitmat = fuse_bitmat(matrix[k:])
    return _fused_fn(k, m, n, pick_tile(k, m, n), interpret), bitmat
