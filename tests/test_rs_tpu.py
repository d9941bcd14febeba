"""TPU (JAX bit-plane matmul) backend conformance — bit-identical to numpy."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.codec import NumpyCodec, host_matmul
from seaweedfs_tpu.ops.rs_tpu import TpuCodec, bitplane_program


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (20, 4)])
@pytest.mark.parametrize("kind", ["vandermonde", "cauchy"])
def test_encode_bit_identical(k, m, kind):
    rng = np.random.default_rng(k + m)
    data = rng.integers(0, 256, (k, 4096)).astype(np.uint8)
    ref = NumpyCodec(k, m, kind).encode(data)
    got = TpuCodec(k, m, kind).encode(data)
    assert np.array_equal(ref, got)


def test_encode_chunked_with_tail():
    """Chunking + zero-padded tail must not change output."""
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (10, 10_000)).astype(np.uint8)
    ref = NumpyCodec(10, 4).encode(data)
    got = TpuCodec(10, 4, chunk_bytes=4096).encode(data)
    assert np.array_equal(ref, got)


def test_reconstruct_bit_identical():
    rng = np.random.default_rng(2)
    c_ref = NumpyCodec(10, 4)
    c_tpu = TpuCodec(10, 4)
    data = rng.integers(0, 256, (10, 1000)).astype(np.uint8)
    full = c_ref.encode_to_all(data)
    for trial in range(5):
        lost = rng.choice(14, 4, replace=False)
        shards = [None if i in lost else full[i].copy() for i in range(14)]
        out = c_tpu.reconstruct(shards)
        for i in range(14):
            assert np.array_equal(out[i], full[i]), f"shard {i} trial {trial}"


def test_multi_slab_chunking_exact_multiple():
    """n an exact multiple of chunk_bytes: the no-pad branch of the
    multi-slab loop (rs_tpu._matmul) for every slab."""
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, (10, 3 * 2048)).astype(np.uint8)
    ref = NumpyCodec(10, 4).encode(data)
    got = TpuCodec(10, 4, chunk_bytes=2048).encode(data)
    assert np.array_equal(ref, got)


def test_multi_slab_reconstruct():
    """Reconstruct routed through the chunked matmul path (wide payload,
    small chunk_bytes) — decode-plan rows, not the encode matrix."""
    rng = np.random.default_rng(5)
    c_ref = NumpyCodec(10, 4)
    c_tpu = TpuCodec(10, 4, chunk_bytes=1024)
    data = rng.integers(0, 256, (10, 5000)).astype(np.uint8)
    full = c_ref.encode_to_all(data)
    shards = [None if i in (2, 3, 10, 12) else full[i].copy()
              for i in range(14)]
    out = c_tpu.reconstruct(shards)
    for i in range(14):
        assert np.array_equal(out[i], full[i])


def test_odd_sizes():
    c_ref = NumpyCodec(10, 4)
    c_tpu = TpuCodec(10, 4)
    rng = np.random.default_rng(3)
    for n in (1, 7, 127, 129, 1000003 % 2048):
        data = rng.integers(0, 256, (10, n)).astype(np.uint8)
        assert np.array_equal(c_ref.encode(data), c_tpu.encode(data))


def _cell_operands():
    """What the benchmark's cells and the next configurations dispatch."""
    rs = NumpyCodec(10, 4)
    lost4 = tuple(i not in (0, 3, 10, 12) for i in range(14))
    lost1 = tuple(i != 6 for i in range(14))
    rng = np.random.default_rng(56)
    return [
        pytest.param(rs.matrix[10:], id="encode-4x10"),
        pytest.param(rs.decode_plan(lost4)[2], id="decode-4x10"),
        pytest.param(rs.decode_plan(lost1)[2], id="decode-1x10"),
        pytest.param(NumpyCodec(6, 3).matrix[6:], id="rs6-3-encode-3x6"),
        pytest.param(NumpyCodec(20, 4).matrix[20:], id="rs20-4-encode-4x20"),
        # the trace repair's combine: {0,1} coefficients, 56 symbol rows
        pytest.param(rng.integers(0, 2, (8, 56), dtype=np.uint8),
                     id="trace-combine-8x56"),
    ]


@pytest.mark.parametrize("n", [2048, 1000 + 37])
@pytest.mark.parametrize("coeffs", _cell_operands())
def test_bitplane_program_matches_oracle(coeffs, n, request):
    """The bit-plane dot — the program the mesh cell runs on four chips,
    which JAX_PLATFORMS=cpu never picks — jitted on the CPU, against the
    host product, for each operand the cells dispatch."""
    import jax
    r, k = coeffs.shape
    assert f"-{r}x{k}-" in request.node.name    # the id says the operand
    rng = np.random.default_rng(n + r)
    data = rng.integers(0, 256, (k, n), dtype=np.uint8)
    bitmat = gf256.bit_matrix(coeffs).astype(np.int8)
    out = jax.jit(bitplane_program(k, r, n))(bitmat, data)
    assert out.dtype == np.uint8 and out.shape == (r, n)
    assert np.array_equal(np.asarray(out), host_matmul(coeffs, data))
