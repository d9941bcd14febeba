"""The served kernels, compiled for the chip without the chip.

The TPU's compiler is installed with libtpu and compiles for a *described*
v5e 2x2 topology (no device attached): a kernel Mosaic would refuse on the
chip — a slice off the tiling, too much VMEM, a program that does not fit
HBM — is refused HERE, at the real served widths, at no chip time. Nothing
runs, so this says nothing about results or speed; tests/test_rs_pallas.py
pins the bytes (interpret mode) and chip_smoke.py runs the real thing.

Rules this file keeps (they are why it is ONE file with fixtures):
the topology is described inside a module-scoped fixture, never at import
and never in skipif/parametrize arguments — only one process at a time may
load libtpu, and every xdist worker imports every test file; the compiles
run in this process (a child could not load the library either); the
persistent compile cache is off around them (a described-device entry can
be written but never read back).
"""

import os

import pytest

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter", "collective-broadcast")
MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_fused(one_chip, k, r, n):
    """Real .lower().compile() of the fused Pallas kernel, not interpret."""
    import jax
    import jax.numpy as jnp
    from seaweedfs_tpu.ops.rs_pallas import _fused_fn, pick_tile
    fn = _fused_fn(k, r, n, pick_tile(k, r, n), False)
    compiled = fn.raw_jit.lower(
        jax.ShapeDtypeStruct((8 * r, 8 * k), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((k, n), jnp.uint8, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel did not compile through Mosaic"
    # the names a profiler trace is reduced by: the module, and the
    # custom call itself (an 'XLA Ops' event's name is its HLO line)
    assert "HloModule jit_sw_rs_fused" in text
    assert "%sw_rs_fused" in text and "/sw_rs_fused/pallas_call" in text
    return compiled


# the three geometries tests/test_rs_pallas.py used to only *lower*
# (jax.export never ran the chip's compiler); 8 MiB is the served slab
@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (20, 4)])
def test_fused_encode_compiles_at_served_slab(one_chip, k, m):
    from seaweedfs_tpu.ec.encoder import DEFAULT_SLAB
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.rs_pallas import fuse_bitmat
    matrix = gf256.build_matrix(k, k + m, "vandermonde")
    assert fuse_bitmat(matrix[k:]).shape == (8 * m, 8 * k)
    assert DEFAULT_SLAB == 8 * MIB
    mem = _compile_fused(one_chip, k, m, DEFAULT_SLAB).memory_analysis()
    # payload in, parity out, nothing 8x in HBM: that is the fusion
    assert mem.argument_size_in_bytes >= k * DEFAULT_SLAB
    assert mem.temp_size_in_bytes < DEFAULT_SLAB


@pytest.mark.parametrize("n", [32 * MIB, 128 << 10],
                         ids=["chunk-32MiB", "bucket-128KiB"])
def test_fused_encode_compiles_at_other_served_widths(one_chip, n):
    """TpuCodec._matmul's 32 MiB chunk and a small power-of-two bucket."""
    _compile_fused(one_chip, 10, 4, n)


@pytest.mark.parametrize("k,r", [(10, 4), (14, 4)],
                         ids=["decode-4-lost", "syndrome-H-4x14"])
def test_fused_decode_and_syndrome_compile(one_chip, k, r):
    """Decode rows of a 4-shard loss (4 x 10) and the scrub's syndrome
    matrix H (4 x 14), at the 1 MiB scrub slab."""
    _compile_fused(one_chip, k, r, MIB)


@pytest.mark.parametrize("r", [4, 3], ids=["encode-or-4-lost", "3-lost"])
def test_fused_compiles_at_a_small_volumes_stripe_width(one_chip, r):
    """A 123 MiB volume of small needles (BASELINE config 3) has 13 MiB
    shards, which `ec/gather.auto_slab` cuts into four stripes of
    3.25 MiB: the encode's three-row dispatches are padded to that width
    (`width_bucket` caps the 4 MiB bucket at the stream's slab) and the
    rebuild's stripes have it. Not a power of two, and no multiple of
    the tile `pick_tile` gives it."""
    from seaweedfs_tpu.ec.gather import auto_slab
    from seaweedfs_tpu.ops.rs_pallas import pick_tile
    from seaweedfs_tpu.ops.rs_tpu import width_bucket
    n = auto_slab(13 * MIB)
    assert n == 13 * MIB // 4 == 3407872
    assert width_bucket(3 * MIB, n) == n and width_bucket(MIB, n) == MIB
    assert n % pick_tile(10, r, n) != 0
    _compile_fused(one_chip, 10, r, n)


def test_fused_piggyback_encode_matrix_compiles(one_chip):
    """The piggyback layout's sub-chunk encode matrix is (m*alpha,
    k*alpha) = (128, 320): the widest contraction the kernel serves, at
    the tile pick_tile gives it."""
    from seaweedfs_tpu.ops import codec as ops_codec
    from seaweedfs_tpu.ops.rs_pallas import pick_tile
    pplan = ops_codec.piggyback_plan(10, 4)
    r, k = pplan.emat.shape
    assert (r, k) == (128, 320)
    assert pick_tile(k, r, MIB) == 896
    _compile_fused(one_chip, k, r, MIB)


@pytest.mark.parametrize("lost", [[0, 4, 8, 12], [2, 6, 10]],
                         ids=["holder-of-4", "holder-of-3"])
def test_fused_coupled_decode_operands_compile(one_chip, lost):
    """A lost holder's full coupled decode: a dense (32 x lost, 320)
    operand against (320, 8 MiB / 32) stripes, the served width."""
    from seaweedfs_tpu.ec.encoder import DEFAULT_SLAB
    from seaweedfs_tpu.ops import codec as ops_codec
    _, _, coeffs = ops_codec.piggyback_decode_plan(
        10, 4, tuple(i not in lost for i in range(14)))
    r, k = coeffs.shape
    assert (r, k) == (32 * len(lost), 320)
    _compile_fused(one_chip, k, r, DEFAULT_SLAB // 32)


@pytest.mark.parametrize("lost", [[0, 3, 6], [1, 4, 7], [2, 5, 8]],
                         ids=["holder-A", "holder-B", "holder-C"])
def test_fused_rs6_3_holder_decode_operand_compiles(one_chip, lost):
    """A lost holder of an RS(6,3) volume on three servers (two data
    shards and a parity shard): a dense (3, 6) block of the inverse,
    lifted to the (24, 48) bit operand the encode of that geometry runs
    too, against (6, 8 MiB) stripes: 48 MiB a dispatch where RS(10,4)
    sends 80."""
    import numpy as np
    from seaweedfs_tpu.ec.encoder import DEFAULT_SLAB
    from seaweedfs_tpu.ops.codec import NumpyCodec
    from seaweedfs_tpu.ops.rs_pallas import fuse_bitmat
    src, missing, coeffs = NumpyCodec(6, 3).decode_plan(
        tuple(i not in lost for i in range(9)))
    assert missing == lost and len(src) == 6
    assert coeffs.shape == (3, 6) and np.all(coeffs != 0)   # dense
    assert fuse_bitmat(coeffs).shape == (24, 48)
    mem = _compile_fused(one_chip, 6, 3, DEFAULT_SLAB).memory_analysis()
    assert mem.argument_size_in_bytes >= 6 * DEFAULT_SLAB
    assert mem.temp_size_in_bytes < DEFAULT_SLAB


@pytest.mark.parametrize("rows_in,rows_out,n", [
    (10, 4, 8 * MIB), (10, 4, 32 * MIB), (320, 128, MIB)],
    ids=["slab-8MiB", "chunk-32MiB", "piggyback-1MiB"])
def test_mesh_program_compiles_on_four_chips(topo, rows_in, rows_out, n):
    """MeshCodec._fn's TPU branch over a 4-device ('data',) mesh: the
    payload splits four ways and the partitioner adds no collective."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from seaweedfs_tpu.parallel.mesh import make_codec_mesh
    from seaweedfs_tpu.parallel.mesh_codec import MeshCodec
    mesh = make_codec_mesh(devices=topo.devices, width_devices=4)
    assert mesh.shape["data"] == 4
    codec = MeshCodec(10, 4, mesh=mesh)
    assert codec._on_tpu_mesh()
    compiled = codec._fn(rows_in, rows_out, n).raw_jit.lower(
        jax.ShapeDtypeStruct((rows_in * 8, rows_out * 8), jnp.int8,
                             sharding=NamedSharding(mesh, P(None, None))),
        jax.ShapeDtypeStruct((rows_in, n), jnp.uint8,
                             sharding=NamedSharding(mesh, P(None, "data")))
    ).compile()
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    # the module's name and the scope every op of the program sits in
    assert "HloModule jit_sw_rs_mesh" in text
    assert 'op_name="jit(sw_rs_mesh)/sw_rs_mesh/' in text
    # each device is handed a quarter of the payload columns ...
    _, data_sharding = compiled.input_shardings[0]
    assert data_sharding.shard_shape((rows_in, n)) == (rows_in, n // 4)
    # ... and holds that quarter, not a replica: argument bytes per
    # device are the quarter plus the bit-matrix, up to the HBM tiling's
    # row padding (a 10-row uint8 array is laid out as 16 rows)
    quarter = rows_in * n // 4
    per_device = compiled.memory_analysis().argument_size_in_bytes \
        - rows_in * 8 * rows_out * 8
    assert quarter <= per_device <= 1.7 * quarter


@pytest.mark.parametrize("r", [4, 3], ids=["holder-of-4", "holder-of-3"])
def test_fused_decode_compiles_for_each_chip_of_the_host(topo, r):
    """`-ec.backend tpu-own`: a server's codec lowers the same jitted
    program with operands committed to its own chip, so the fanned
    rebuild's warm-up compiles the (lost, 10) decode at the 8 MiB slab
    once a chip. For the described host's four devices: four compiles of
    one program, each through Mosaic, each for its one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from seaweedfs_tpu.ec.encoder import DEFAULT_SLAB
    from seaweedfs_tpu.ops.rs_pallas import _fused_fn, pick_tile
    k, n = 10, DEFAULT_SLAB
    assert len(topo.devices) == 4
    fn = _fused_fn(k, r, n, pick_tile(k, r, n), False)
    for index, device in enumerate(topo.devices):
        own = fn.on_device(index)
        assert own.raw_jit is fn.raw_jit and own.device == index
        chip = SingleDeviceSharding(device)
        compiled = own.raw_jit.lower(
            jax.ShapeDtypeStruct((8 * r, 8 * k), jnp.int8, sharding=chip),
            jax.ShapeDtypeStruct((k, n), jnp.uint8, sharding=chip)
        ).compile()
        assert "tpu_custom_call" in compiled.as_text()
        (const_at, data_at), _ = compiled.input_shardings
        assert const_at.device_set == data_at.device_set == {device}
        assert compiled.output_shardings.device_set == {device}
