"""MeshCodec: multi-chip EC as a serving-path backend (SURVEY §2.6
device tier) — bit-identical to the numpy oracle on the virtual
8-device CPU mesh."""

import contextlib
import os

import numpy as np
import pytest

from seaweedfs_tpu.ops.codec import NumpyCodec, get_codec
from seaweedfs_tpu.ops.telemetry import STATS, delta
from seaweedfs_tpu.parallel.mesh_codec import MeshCodec


def test_get_codec_mesh_backend():
    c = get_codec(10, 4, backend="mesh")
    assert isinstance(c, MeshCodec) and c.backend == "mesh"


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (20, 4)])
def test_encode_matches_oracle(k, m):
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (k, 4096 + 37), dtype=np.uint8)
    assert np.array_equal(MeshCodec(k, m).encode(data),
                          NumpyCodec(k, m).encode(data))


def test_reconstruct_matches_oracle():
    k, m = 10, 4
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, (k, 3000), dtype=np.uint8)
    codec = MeshCodec(k, m)
    shards = list(codec.encode_to_all(data))
    for sid in (0, 3, 11, 13):
        shards[sid] = None
    rebuilt = codec.reconstruct(shards)
    ref = NumpyCodec(k, m).encode_to_all(data)
    for sid in range(k + m):
        assert np.array_equal(rebuilt[sid], ref[sid]), sid


@contextlib.contextmanager
def _private_programs():
    """A second mesh of the process compiles a bucket the first one has
    already compiled: that is what the recompile sentinel latches on,
    and tests/test_device_stats.py asserts the global one is unlatched.
    Programs of a mesh that is not the process's own go to a cache and
    a DeviceStats of the test's."""
    from seaweedfs_tpu.ops import device_stats
    from seaweedfs_tpu.parallel import mesh_codec
    saved = device_stats.DEVICE_STATS, mesh_codec._FNS
    device_stats.DEVICE_STATS = device_stats.DeviceStats()
    mesh_codec._FNS = {}
    try:
        yield
    finally:
        device_stats.DEVICE_STATS, mesh_codec._FNS = saved


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3)])
@pytest.mark.parametrize("width_devices", [1, 2, 3, 5, 8])
def test_mesh_widths_and_geometries(width_devices, k, m):
    """Every width of the codec mesh, both geometries, a payload that is
    no multiple of the 128 lanes nor of the device count: encode equals
    the oracle, and with m shards dropped (data and parity) reconstruct
    gives the data back exactly — on the device, not the host path that
    small reads take."""
    from seaweedfs_tpu.parallel.mesh import make_codec_mesh
    n = 333 * width_devices + 7
    rng = np.random.default_rng(100 * width_devices + k)
    data = rng.integers(0, 256, (k, n), dtype=np.uint8)
    # one device takes the single-device kernel of the process (shared
    # with every other test: no private copy of that)
    with _private_programs() if width_devices > 1 \
            else contextlib.nullcontext():
        mesh = make_codec_mesh(width_devices=width_devices)
        assert dict(mesh.shape) == {"data": width_devices, "shard": 1}
        codec = MeshCodec(k, m, mesh=mesh, mesh_shard_min_bytes=0,
                          small_dispatch_bytes=0)
        before = STATS.snapshot()
        shards = list(codec.encode_to_all(data))
        ref = NumpyCodec(k, m).encode_to_all(data)
        assert all(np.array_equal(a, b) for a, b in zip(shards, ref))
        lost = [0, k - 1, k, k + m - 1][:m]
        for sid in lost:
            shards[sid] = None
        rebuilt = codec.reconstruct(shards)
        d = delta(before)
    for sid in range(k + m):
        assert np.array_equal(rebuilt[sid], ref[sid]), sid
    assert d["dispatches"] == 2 and d["host_fallbacks"] == 0
    assert d["mesh_dispatches"] == (2 if width_devices > 1 else 0)


@pytest.mark.parametrize("r,k", [(32, 80), (128, 320)])
def test_rolled_packed_program_on_the_mesh(r, k):
    """An operand over _PACKED_UNROLL_LIMIT — (32, 80), and the coupled
    encode of the piggyback layout, (m*alpha, k*alpha) = (128, 320) —
    takes the rolled form of the packed program on the CPU mesh as it
    does on one device (unrolled, it is ~10^5 ops and minutes of XLA
    compile), and equals the host product."""
    from seaweedfs_tpu.ops import rs_tpu
    from seaweedfs_tpu.ops.codec import host_matmul
    assert r * 8 * ((k * 8 + 31) // 32) > rs_tpu._PACKED_UNROLL_LIMIT
    rng = np.random.default_rng(r)
    coeffs = rng.integers(0, 256, (r, k), dtype=np.uint8)
    data = rng.integers(0, 256, (k, 2048 + 24), dtype=np.uint8)
    codec = MeshCodec(10, 4, mesh_shard_min_bytes=0)
    before = STATS.snapshot()
    out = codec._matmul(coeffs, data)
    assert delta(before)["mesh_dispatches"] == 1
    assert np.array_equal(out, host_matmul(coeffs, data))


def test_multi_chunk_widths():
    """Payload spanning several chunk_bytes windows, with a ragged tail
    narrower than the data axis."""
    codec = MeshCodec(10, 4, chunk_bytes=2048)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (10, 2048 * 3 + 5), dtype=np.uint8)
    assert np.array_equal(codec.encode(data),
                          NumpyCodec(10, 4).encode(data))


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (20, 4)])
@pytest.mark.parametrize("width", [4096, 4096 + 37, 8 * 513 + 3])
def test_sharded_vs_single_bit_identity(k, m, width):
    """The mesh-sharded dispatch (width axis split over every device)
    and the forced single-device dispatch produce byte-identical
    output, including tail widths that do not divide the device count
    — and both match the numpy oracle."""
    rng = np.random.default_rng(k * 1000 + width)
    data = rng.integers(0, 256, (k, width), dtype=np.uint8)
    sharded = MeshCodec(k, m, mesh_shard_min_bytes=0).encode(data)
    single = MeshCodec(k, m, mesh_shard_min_bytes=1 << 60).encode(data)
    oracle = NumpyCodec(k, m).encode(data)
    assert np.array_equal(sharded, single)
    assert np.array_equal(sharded, oracle)


def test_sharded_slab_is_one_dispatch():
    """Dispatch discipline on the sharded path: a warm slab costs
    exactly ONE device dispatch (mesh-sharded, bitmat already
    resident) whose width spans every mesh device."""
    k, m, width = 10, 4, 8 * 512
    codec = MeshCodec(k, m, mesh_shard_min_bytes=0)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (k, width), dtype=np.uint8)
    codec.encode(data)  # warm: compile + bitmat upload
    before = STATS.snapshot()
    codec.encode(data)
    d = delta(before)
    assert d["dispatches"] == 1
    assert d["mesh_dispatches"] == 1
    assert d["bitmat_uploads"] == 0
    want_width = codec.mesh.shape["data"]
    assert want_width > 1, "virtual 8-device mesh required (conftest)"
    assert d["dispatch_width_devices"] == want_width
    assert set(d["device_byte_share"]) == set(d["mesh_device_bytes"])
    assert max(d["device_byte_share"].values()) == 1.0


def test_small_slab_crosses_over_to_single_device():
    """Below SW_EC_MESH_SHARD_MIN_BYTES the codec dispatches on one
    device: no mesh dispatch, reported width 1 — and still
    bit-identical to the oracle."""
    k, m, width = 10, 4, 2048
    codec = MeshCodec(k, m, mesh_shard_min_bytes=1 << 60)
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, (k, width), dtype=np.uint8)
    codec.encode(data)  # warm
    before = STATS.snapshot()
    out = codec.encode(data)
    d = delta(before)
    assert d["dispatches"] == 1
    assert d["mesh_dispatches"] == 0
    assert d["dispatch_width_devices"] == 1
    assert d["device_byte_share"] == {}
    assert np.array_equal(out, NumpyCodec(k, m).encode(data))


def test_drain_pieces_reassembles_device_resident_output():
    """drain_pieces yields per-device (col_offset, piece) stripes that
    tile the logical width exactly — the device-resident handoff the
    streaming transports consume without staging the full slab."""
    import jax.numpy as jnp
    from seaweedfs_tpu.ops import gf256

    k, m, w = 10, 4, 4000
    codec = MeshCodec(k, m, mesh_shard_min_bytes=0)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, (k, w), dtype=np.uint8)
    coeffs = gf256.build_matrix(k, k + m)[k:]
    bucket = codec.pipeline_width_bucket(w, codec.chunk_bytes)
    fn, bitmat, put = codec.device_fn(coeffs, bucket)
    padded = np.zeros((k, bucket), dtype=np.uint8)
    padded[:, :w] = data
    out_dev = fn(bitmat, put(padded))
    pieces = codec.drain_pieces(out_dev, w)
    assert len(pieces) == codec.mesh.shape["data"]
    cursor = 0
    for lo, piece in pieces:
        assert lo == cursor
        cursor += piece.shape[1]
    assert cursor == w
    assembled = np.concatenate([p for _, p in pieces], axis=1)
    assert np.array_equal(assembled, NumpyCodec(k, m).encode(data))


def test_write_ec_files_digest_parity(tmp_path):
    """Volume encode through the mesh backend produces shard files
    byte-identical to the numpy path."""
    from seaweedfs_tpu.ec import to_ext, write_ec_files
    rng = np.random.default_rng(4)
    base = str(tmp_path / "1")
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 3 << 20, dtype=np.uint8).tobytes())

    def digests():
        from seaweedfs_tpu.util import file_sha256
        out = []
        for i in range(14):
            with open(base + to_ext(i), "rb") as f:
                out.append(file_sha256(f))
        return out

    write_ec_files(base, codec=NumpyCodec(10, 4), large_block=1 << 20,
                   small_block=64 << 10, slab=256 << 10, pipelined=False)
    ref = digests()
    for i in range(14):
        os.remove(base + to_ext(i))
    write_ec_files(base, codec=MeshCodec(10, 4, chunk_bytes=512 << 10),
                   large_block=1 << 20, small_block=64 << 10,
                   slab=256 << 10, pipelined=False)
    assert digests() == ref
