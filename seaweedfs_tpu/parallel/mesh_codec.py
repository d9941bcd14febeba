"""MeshCodec — multi-chip EC as a first-class codec backend.

`-ec.backend mesh` runs every GF(2^8) coding matmul SPMD over a
`jax.sharding.Mesh` of all visible devices: the payload axis shards
over 'data' (stripes are independent byte positions — zero
communication), coefficients replicate, and XLA partitions the
GF(2) program (ops/rs_tpu.py documents the math and owns both program
bodies; this module only places them). On an
8-chip host a volume encode therefore streams through all chips from
the same `write_ec_files` call sites the single-chip TpuCodec uses;
on the CPU test mesh it exercises the identical program. Outputs are
bit-identical to every other backend (exact integer arithmetic).

Two program forms, chosen by mesh platform (same split as
ops/rs_tpu.fn_and_bitmat):

  * TPU — the bit-plane int8 matmul: unpack to GF(2) bit rows, one
    MXU dot, pack. The MXU eats the 8x lift for free.
  * everything else (the virtual CPU test mesh) — packed AND/popcount:
    the k*8 contraction bits packed into uint32 words, each output bit
    a parity of popcounts. ~64x less arithmetic and no 8x intermediate;
    this is what made the round-5 rebuild a usable hot path on the
    CPU mesh.

Dispatch discipline (the round-5 lesson): coefficients are lifted and
uploaded ONCE per coefficient matrix (bounded LRU, ops/codec._ConstCache),
chunk dispatches are issued before any output is drained (JAX dispatch is
async — blocking np.asarray per chunk serializes compute against d2h),
and the pipelined encode/rebuild path streams slabs through device_fn()
with bounded in-flight depth (ops/pipeline.PipelinedMatmul).

Width discipline (the round-16 lesson): the codec mesh puts EVERY
device on the 'data' axis (mesh.make_codec_mesh), slabs below the SW_EC_MESH_SHARD_MIN_BYTES
payload crossover keep the single-device kernel (sharding a
kilobyte-wide reconstruct pays partitioning overhead it can't
amortize), and every sharded put records its per-device byte landing
in ops/telemetry so a silent fall-back to width-1 dispatch is a
visible counter regression, not a wall-time surprise.

This is the serving-path face of SURVEY §2.6's device tier: the
volume server's encode/rebuild engine on a multi-chip host
(chip_smoke.py --chips 4 runs it on four real chips).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from ..ops import device_stats, gf256
from ..ops.rs_tpu import (DeviceCodec, bitplane_program, packed_program,
                          width_bucket)
from ..ops.telemetry import STATS
from ..util import config
from .mesh import make_codec_mesh


#: (mesh, rows_in, rows_out, n) -> jitted program, shared by
#: every MeshCodec of the process (like ops/rs_tpu's module-level jit
#: factories): several volume servers in one process — `weed server`
#: drills, chip_smoke.py — each own a codec, and a per-codec cache made
#: each of them compile the same program again (a latched recompile).
_FNS: Dict[Tuple, object] = {}
# the sharded program's name in a profiler trace (module and op scope)
PROGRAM_NAME = "sw_rs_mesh"


class MeshCodec(DeviceCodec):
    backend = "mesh"

    def __init__(self, data_shards: int, parity_shards: int,
                 matrix_kind: str = "vandermonde", mesh=None,
                 chunk_bytes: int = 32 << 20,
                 small_dispatch_bytes: int = None,
                 mesh_shard_min_bytes: int = None):
        super().__init__(data_shards, parity_shards, matrix_kind,
                         chunk_bytes, small_dispatch_bytes)
        self._mesh = mesh  # lazy: devices may not be initialized yet
        # payload bytes (k * width) below which a dispatch keeps the
        # single-device path: sharding a small slab pays partitioning
        # overhead on every device without enough columns to amortize it
        self.mesh_shard_min_bytes = (
            config.env_int("SW_EC_MESH_SHARD_MIN_BYTES")
            if mesh_shard_min_bytes is None else int(mesh_shard_min_bytes))

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_codec_mesh()
        return self._mesh

    def _on_tpu_mesh(self) -> bool:
        """True on a TPU mesh, False on an explicitly requested CPU
        mesh (JAX_PLATFORMS=cpu), an error on anything else."""
        from ..util.jax_platform import require_tpu
        return require_tpu(
            "mesh", self.mesh.devices.flat[0].platform) == "tpu"

    def _fn(self, rows_in: int, rows_out: int, n: int):
        """Jitted (const, data (rows_in, n) uint8) -> (rows_out, n)
        uint8, payload sharded over 'data', const replicated. The const
        is the int8 bit-matrix (TPU mesh) or the packed uint32 bit-
        matrix (elsewhere) — _device_const builds the matching form."""
        key = (self.mesh, rows_in, rows_out, n)
        fn = _FNS.get(key)
        if fn is not None:
            return fn
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        program = (bitplane_program if self._on_tpu_mesh()
                   else packed_program)(rows_in, rows_out, n)

        def sw_rs_mesh(const, data):    # the module is jit_sw_rs_mesh
            with jax.named_scope(PROGRAM_NAME):
                return program(const, data)

        mesh = self.mesh
        fn = device_stats.wrap(
            jax.jit(
                sw_rs_mesh,
                in_shardings=(NamedSharding(mesh, P(None, None)),
                              NamedSharding(mesh, P(None, "data"))),
                out_shardings=NamedSharding(mesh, P(None, "data"))),
            "mesh_codec._fn")
        return _FNS.setdefault(key, fn)

    def _device_const(self, coeffs: np.ndarray):
        """Device-resident replicated coefficient constant — uploaded
        once per coefficient matrix, reused across every slab of a
        rebuild/encode (round-5 fix: re-lifting + re-uploading per call
        was most of that rebuild's wall)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def make():
            if self._on_tpu_mesh():
                host = gf256.bit_matrix(coeffs).astype(np.int8)
            else:
                host = gf256.pack_bit_matrix(coeffs)
            return jax.device_put(
                host, NamedSharding(self.mesh, P(None, None)))

        return self._consts.get((coeffs.tobytes(), "mesh"), make)

    def _put(self, data: np.ndarray):
        """Sharded h2d: the width axis splits over 'data', and the
        per-device landing is recorded so a silent fall-back to a
        width-1 dispatch is visible in telemetry, not just wall time."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        arr = jax.device_put(
            data, NamedSharding(self.mesh, P(None, "data")))
        STATS.add("mesh_dispatches")
        for shard in arr.addressable_shards:
            STATS.add_mesh_device_bytes(str(shard.device),
                                        shard.data.nbytes)
        return arr

    def device_fn(self, coeffs: np.ndarray, width: int):
        """Streaming hook for PipelinedMatmul: (fn, resident const,
        put). `width` must come from pipeline_width_bucket (even shard
        split over 'data'). Below the SW_EC_MESH_SHARD_MIN_BYTES
        payload crossover (k * width) the single-device kernel
        (DeviceCodec.device_fn: fused Pallas on TPU, packed popcount
        elsewhere) is returned instead of the sharded program —
        dispatches too small to amortize mesh partitioning."""
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        r, k = coeffs.shape
        if k * width < self.mesh_shard_min_bytes or \
                self.mesh.shape["data"] <= 1:
            return super().device_fn(coeffs, width)
        return self._fn(k, r, width), self._device_const(coeffs), self._put

    def drain_pieces(self, out_dev, w: int):
        """Host pieces of a device output in width order: list of
        (col_offset, (r, piece_w) np.ndarray) covering [0, w). Sharded
        outputs drain one piece per device shard — consumers (the
        spread sink's lanes, rebuild shard writes) start
        on the first device's stripes without staging the full slab on
        the host; single-device outputs come back as one piece."""
        shards = getattr(out_dev, "addressable_shards", None) or []
        by_off = {}
        for shard in shards:
            lo = shard.index[1].start or 0
            if lo >= w or lo in by_off:  # clip tail pad; dedupe replicas
                continue
            piece = np.asarray(shard.data)
            if lo + piece.shape[1] > w:
                piece = piece[:, : w - lo]
            by_off[lo] = piece
        if not by_off:
            full = np.asarray(out_dev)
            return [(0, full[:, :w] if full.shape[1] > w else full)]
        return sorted(by_off.items())

    def pipeline_width_bucket(self, n: int, cap: Optional[int]) -> int:
        """Power-of-two bucket (compile reuse), then up to a multiple
        of the 'data' axis so the shard split is even."""
        bucket = width_bucket(n, cap)
        return bucket + (-bucket) % self.mesh.shape["data"]
