"""TPU Reed-Solomon backend — GF(2^8) coding as an MXU bit-plane matmul.

The north star (BASELINE.json): the reference's EC hot loop
(reference ec_encoder.go:118-134 -> klauspost AVX2 GF multiply) becomes a
single batched matmul per chunk on TPU.

Math: multiplication by a GF(2^8) constant is linear over GF(2)^8, so the
(r x k) byte coefficient matrix lifts to a (k*8 x r*8) binary matrix B
(ops/gf256.bit_matrix). With input bytes unpacked to bit-planes
X (k*8, n) in {0,1}, the coded output is

    Y = (B^T @ X) mod 2        -- int8 matmul on the MXU, ~896 MACs/byte
    out = pack_bits(Y)         -- VPU shifts/adds

This is exact integer arithmetic (row sums <= k*8 = 160 < 2^31), so the
result is bit-identical to the numpy/native backends. No gathers, no
data-dependent control flow; everything is static-shaped for XLA.

Chunking: the bit-plane expansion is 8x the payload, so a whole 30GB volume
cannot be lifted at once; the codec streams fixed-size chunks (default 32MB
per shard-row) through one compiled executable (one compilation per
(r, k, chunk) shape; tails are zero-padded to the chunk width, and GF
linearity makes zero-padding exact).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from .codec import ReedSolomonCodec, _ConstCache, small_dispatch_default
from . import device_stats
from . import gf256
from .telemetry import STATS
from ..util import config, tracing
from ..util.locks import make_lock

#: lru maxsize for the jit factories below — read once at import, a
#: registered knob so eviction pressure (a silent recompile source) is
#: tunable and visible in ec_xla_jit_cache_total.
_JIT_CACHE_SIZE = config.env_int("SW_EC_JIT_CACHE_SIZE")

#: trace-size crossover for _packed_fn: matrices with r*8*nw at or
#: below this unroll fully (constant indices, ms traces); above it the
#: rolled lax.scan form keeps the graph O(1) in the matrix dims (the
#: piggyback emat would otherwise unroll to ~10^5 ops and stall XLA
#: CPU compilation for minutes).
_PACKED_UNROLL_LIMIT = 4096


def _jax():
    import jax
    import jax.numpy as jnp
    from ..util.jax_platform import configure_compile_cache
    configure_compile_cache()
    return jax, jnp


def bitplane_program(k: int, r: int, n: int):
    """Un-jitted (bitmat (k*8, r*8) int8, data (k, n) uint8) -> (r, n)
    uint8: the bit-plane dot of the module docstring as an XLA program.
    MeshCodec shards it over the chips of a host (the only caller: one
    chip runs the same math as the fused Pallas kernel, ops/rs_pallas,
    which keeps the 8x bit-plane intermediate out of HBM)."""
    jax, jnp = _jax()

    def program(bitmat, data):
        shifts = jnp.arange(8, dtype=jnp.uint8)
        # unpack to bit-planes: row j*8+l is bit l of input shard j
        bits = ((data[:, None, :] >> shifts[None, :, None]) & 1)
        x = bits.reshape(k * 8, n).astype(jnp.int8)
        # MXU: (r*8, k*8) @ (k*8, n) with int32 accumulation
        y = jax.lax.dot_general(
            bitmat.T, x,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        ybits = (y & 1).astype(jnp.uint8).reshape(r, 8, n)
        weights = (jnp.uint8(1) << shifts)[None, :, None]
        return (ybits * weights).sum(axis=1, dtype=jnp.uint8)

    return program


def packed_program(k: int, r: int, n: int):
    """Un-jitted (packed bitmat (ceil(k*8/32), r*8) uint32, data (k, n)
    uint8) -> (r, n) uint8 — the AND/popcount form of the GF(2) matmul.

    The bit-plane dot lifts the payload 8x and feeds the CPU a
    (r*8, k*8) @ (k*8, n) int8 gemm with a tiny M — memory-bound (the
    round-5 mesh rebuild crawled on it). Packing the
    k*8 contraction bits into <=8 uint32 words turns each output bit
    into a handful of vectorized AND + popcount + parity ops: ~64x
    less arithmetic, no 8x intermediate, and much shorter
    compile times. Exact (popcount parity == mod-2 dot), so output is
    bit-identical to every other backend. TPU keeps the MXU dot /
    fused Pallas kernel (rs_pallas) where the matmul IS the fast path.
    """
    jax, jnp = _jax()
    nw = (k * 8 + 31) // 32

    if r * 8 * nw <= _PACKED_UNROLL_LIMIT:
        # flat-geometry matrices (parity rows, decode coeffs, repair
        # rows: r*8*nw in the hundreds): full unroll traces in
        # milliseconds and lets XLA see every constant index
        def program(bmp, data):
            d32 = data.astype(jnp.uint32)
            words = []
            for wi in range(nw):
                acc = jnp.zeros((n,), jnp.uint32)
                for b in range(4):
                    j = wi * 4 + b
                    if j < k:
                        acc = acc | (d32[j] << (8 * b))
                words.append(acc)
            outs = []
            for i in range(r):
                byte = jnp.zeros((n,), jnp.uint32)
                for bit in range(8):
                    col = i * 8 + bit
                    ones = jnp.zeros((n,), jnp.uint32)
                    for wi in range(nw):
                        ones = ones + jax.lax.population_count(
                            words[wi] & bmp[wi, col])
                    byte = byte | ((ones & 1) << bit)
                outs.append(byte.astype(jnp.uint8))
            return jnp.stack(outs)
    else:
        # sub-chunk matrices (the piggyback emat is (m*alpha, k*alpha):
        # r*8*nw ~ 10^5) would make the unrolled trace an XLA compile
        # bomb — tens of minutes on CPU. Same math, rolled: lax.scan
        # over output bytes keeps the graph O(1) in r and k, and the
        # per-step live set at nw*n words.
        def program(bmp, data):
            d32 = data.astype(jnp.uint32)
            pad = nw * 4 - k
            if pad:
                d32 = jnp.concatenate(
                    [d32, jnp.zeros((pad, n), jnp.uint32)])
            lanes = d32.reshape(nw, 4, n)
            words = (lanes[:, 0] | (lanes[:, 1] << 8)
                     | (lanes[:, 2] << 16) | (lanes[:, 3] << 24))

            def row(carry, cols):  # cols: (8, nw) one output byte
                byte = jnp.zeros((n,), jnp.uint32)
                for bit in range(8):
                    ones = jax.lax.population_count(
                        words & cols[bit][:, None]).sum(axis=0)
                    byte = byte | ((ones & 1) << bit)
                return carry, byte.astype(jnp.uint8)

            # bmp is (nw, r*8) with column i*8+bit; transpose/reshape
            # regroups it as (r, 8, nw) scan steps
            _, out = jax.lax.scan(
                row, None, bmp.T.reshape(r, 8, nw))
            return out

    return program


@functools.lru_cache(maxsize=_JIT_CACHE_SIZE)
def _packed_fn(k: int, r: int, n: int):
    """packed_program jitted for one device: what a `tpu` or `mesh`
    codec dispatches where JAX_PLATFORMS=cpu asked for the CPU."""
    jax, _ = _jax()
    return device_stats.wrap(jax.jit(packed_program(k, r, n)),
                             "rs_tpu._packed_fn")


@functools.lru_cache(maxsize=_JIT_CACHE_SIZE)
def _packed_bitmat(coeff_bytes: bytes, r: int, k: int):
    coeffs = np.frombuffer(coeff_bytes, dtype=np.uint8).reshape(r, k)
    return gf256.pack_bit_matrix(coeffs)


device_stats.register_jit_factory("rs_tpu._packed_fn", _packed_fn)
device_stats.register_jit_factory("rs_tpu._packed_bitmat", _packed_bitmat)


#: functools.lru_cache does not serialize concurrent misses: two reader
#: threads asking for the same new (k, r, n) would each build a jitted
#: fn and each compile it (a latched recompile). Factory calls from the
#: serving path go through fn_and_bitmat under this lock; a hit is a
#: dict lookup, a miss only wraps — the compile happens at first call.
_factory_lock = make_lock("rs_tpu._factory_lock")


def on_tpu() -> bool:
    """First JAX touch of the device codecs: True on the TPU, False on
    an explicitly requested CPU (JAX_PLATFORMS=cpu), an error anywhere
    else — `tpu`/`mesh` never compute on a platform nobody asked for."""
    from ..util.jax_platform import require_tpu
    return require_tpu("tpu|mesh") == "tpu"


def fn_and_bitmat(coeffs: np.ndarray, n: int,
                  device: Optional[int] = None):
    """Pick the device kernel for this platform: the fused Pallas kernel
    on the TPU (ops/rs_pallas — unpack/matmul/pack in VMEM, no HBM
    temporaries), the packed AND/popcount XLA program where the CPU was
    asked for (the test mesh, where the 8x bit-plane gemm is the
    bottleneck and Pallas would have to interpret). Returns (jitted fn,
    host constant — fused bitmat on TPU, packed uint32 bitmat off it)
    with matching layouts; both are bit-identical to the numpy oracle.
    ``device``: the index of the one local chip the caller computes on
    (OwnDeviceCodec); the fn is then that chip's own instance of the
    same program (device_stats.InstrumentedJit.on_device)."""
    coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
    r, k = coeffs.shape
    if on_tpu():
        from .rs_pallas import _fused_fn, fuse_bitmat, pick_tile
        with _factory_lock:
            fn = _fused_fn(k, r, n, pick_tile(k, r, n), False)
        const = fuse_bitmat(coeffs)
    else:
        with _factory_lock:
            fn = _packed_fn(k, r, n)
        const = _packed_bitmat(coeffs.tobytes(), r, k)
    return (fn if device is None else fn.on_device(device)), const


def width_bucket(n: int, cap: Optional[int]) -> int:
    """Pad widths up to power-of-two buckets so varied payload widths
    reuse compiled executables instead of jitting per exact n. A caller
    whose slabs all have one width passes it as `cap` and pays no
    padding on full slabs; one whose widths vary (degraded-read batches)
    passes None — a cap taken from each width would itself be an exact
    width, and compile a program per batch."""
    bucket = max(512, 1 << (n - 1).bit_length())
    return bucket if cap is None else min(bucket, cap)


class DeviceCodec(ReedSolomonCodec):
    """What the JAX backends share: chunked dispatch with every chunk
    issued before any is drained, device-resident constants, and the
    hooks ops/pipeline.PipelinedMatmul runs a stream through
    (device_fn, pipeline_width_bucket). As it stands it dispatches on
    one device; parallel/mesh_codec.MeshCodec lays the same dispatch
    over the chips of a host. Both compute on the CPU only where
    JAX_PLATFORMS=cpu asks for it (tests, rehearsals); any other
    platform is an error at the first device touch (on_tpu). Output is
    bit-identical everywhere."""

    pipelined = True

    def __init__(self, data_shards: int, parity_shards: int,
                 matrix_kind: str = "vandermonde",
                 chunk_bytes: int = 32 << 20,
                 small_dispatch_bytes: int = None):
        super().__init__(data_shards, parity_shards, matrix_kind)
        self.chunk_bytes = int(chunk_bytes)
        self.small_dispatch_bytes = (
            small_dispatch_default() if small_dispatch_bytes is None
            else int(small_dispatch_bytes))
        self._consts = _ConstCache()

    def device_fn(self, coeffs: np.ndarray, width: int):
        """(fn, device-resident constant, put) for `width`-wide slabs:
        ``fn(constant, put(slab))`` dispatches asynchronously. Here the
        platform's single-device kernel (fn_and_bitmat); the constant
        (fused/packed bitmat) uploads once per coefficient matrix and
        stays device-resident across the stream."""
        import jax.numpy as jnp
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        fn, const_host = fn_and_bitmat(coeffs, width)
        const_dev = self._consts.get(coeffs.tobytes(),
                                     lambda: jnp.asarray(const_host))
        return fn, const_dev, jnp.asarray

    def pipeline_width_bucket(self, n: int, cap: Optional[int]) -> int:
        """The compiled width an n-wide slab is padded to."""
        return width_bucket(n, cap)

    def _chunk_bucket(self, w: int, n: int) -> int:
        """The compiled width of a w-wide chunk of an n-wide _matmul."""
        return self.pipeline_width_bucket(w, self.chunk_bytes)

    def _matmul(self, coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        data = np.ascontiguousarray(data, dtype=np.uint8)
        r, k = coeffs.shape
        n = data.shape[1]
        if n == 0:
            return np.zeros((r, 0), dtype=np.uint8)
        out = np.empty((r, n), dtype=np.uint8)
        # dispatch every chunk before draining any: JAX dispatch is
        # async, so the device crunches chunk t+1 while chunk t copies
        # back — blocking np.asarray inside the dispatch loop would
        # serialize the two
        pending = []
        with tracing.span("dispatch", backend=self.backend,
                          bytes=int(n * k)):
            for off in range(0, n, self.chunk_bytes):
                end = min(off + self.chunk_bytes, n)
                w = end - off
                bucket = self._chunk_bucket(w, n)
                fn, bitmat, put = self.device_fn(coeffs, bucket)
                chunk = data[:, off:end]
                if w < bucket:  # zero-pad: GF-linear, so exact
                    padded = np.zeros((k, bucket), dtype=np.uint8)
                    padded[:, :w] = chunk
                    chunk = padded
                STATS.add_dispatch(self.geometry, w * k)
                pending.append((off, end, fn(bitmat, put(chunk))))
        with tracing.span("drain", backend=self.backend,
                          bytes=int(n * r)):
            for off, end, dev in pending:
                out[:, off:end] = np.asarray(dev)[:, : end - off]
        return out


class TpuCodec(DeviceCodec):
    """JAX backend on one TPU chip (`-ec.backend tpu`)."""

    backend = "tpu"

    def _chunk_bucket(self, w: int, n: int) -> int:
        """The tail of a call of several chunks pads to the full chunk:
        one shape for the whole call, where a bucket of the tail's own
        would be a second program."""
        if n > self.chunk_bytes:
            return self.chunk_bytes
        return super()._chunk_bucket(w, n)


def local_device(ordinal: int):
    """(index, device) of the local chip a store with this ordinal
    computes on: the process's local devices taken in turn, modulo their
    count. First JAX touch of a `tpu-own` server (the same platform rule
    as `tpu`: the TPU, or the CPU where JAX_PLATFORMS asks for it)."""
    from ..util.jax_platform import require_tpu
    jax, _ = _jax()
    require_tpu("tpu-own")
    devices = jax.local_devices()
    index = int(ordinal) % len(devices)
    return index, devices[index]


class OwnDeviceCodec(TpuCodec):
    """JAX backend on ONE chip of a host that has several
    (`-ec.backend tpu-own`): the single-chip programs and dispatch of
    `tpu`, with operands, constants and compiled executables placed on
    the local device the codec's store was given (``ordinal``: the
    store's place among the process's stores; the chip is that modulo
    the local device count). Four volume servers in one process on a
    four-chip host compute on four chips; a process a server with one
    visible chip gets index 0, which is where `tpu` computes too."""

    backend = "tpu-own"

    def __init__(self, data_shards: int, parity_shards: int,
                 matrix_kind: str = "vandermonde", ordinal: int = 0,
                 **kwargs):
        super().__init__(data_shards, parity_shards, matrix_kind, **kwargs)
        self.ordinal = int(ordinal)
        self._device = None

    @property
    def device(self):
        """(index, jax device), resolved at the first device touch."""
        if self._device is None:
            self._device = local_device(self.ordinal)
        return self._device

    def device_fn(self, coeffs: np.ndarray, width: int):
        import jax
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        index, device = self.device
        fn, const_host = fn_and_bitmat(coeffs, width, device=index)
        const_dev = self._consts.get(
            coeffs.tobytes(), lambda: jax.device_put(const_host, device))
        return fn, const_dev, lambda slab: jax.device_put(slab, device)
