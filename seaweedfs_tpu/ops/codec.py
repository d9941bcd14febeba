"""Reed-Solomon codec API + backend registry.

Semantics mirror the reference dependency's Encode/Reconstruct/ReconstructData
(klauspost/reedsolomon, used at reference ec_encoder.go:118-134, 231-285 and
store_ec.go:319-373): shards are equal-length byte rows, data rows are stored
verbatim (systematic code), missing shards are None and are regenerated
in place.

Backend selection (the reference's `-ec.backend` analog, SURVEY §5.6):
    get_codec(k, m, backend="numpy" | "native" | "tpu" | "auto")
"auto" picks tpu if a TPU is visible, else native if the C++ library is
built, else numpy. All backends produce bit-identical output.
"""

from __future__ import annotations

import itertools
import os
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import gf256
from ..util import config


def host_matmul(coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
    """The pure-numpy GF(2^8) matmul: one 256-entry LUT gather + XOR per
    (output row, input row) pair. The conformance oracle, and the
    small-payload path device codecs delegate kilobyte reads to (a
    device dispatch costs more than the whole LUT walk below
    small_dispatch_bytes)."""
    coeffs = np.asarray(coeffs, dtype=np.uint8)
    data = np.asarray(data, dtype=np.uint8)
    r = coeffs.shape[0]
    out = np.zeros((r, data.shape[1]), dtype=np.uint8)
    mt = gf256.MUL_TABLE
    for i in range(r):
        acc = out[i]
        for j in range(coeffs.shape[1]):
            c = coeffs[i, j]
            if c == 0:
                continue
            if c == 1:
                acc ^= data[j]
            else:
                acc ^= mt[c][data[j]]
    return out


# Live override of the hybrid threshold, set by the auto-tuner
# (stats.metrics.observe_span when SW_EC_SMALL_DISPATCH_AUTO=1) once it
# has fitted the host/device crossover from the first reconstruct
# calls. Consulted by small_dispatch_default() (new codecs) AND by
# reconstruct() (codecs already constructed), so a suggestion applies
# without a server restart.
_SMALL_DISPATCH_OVERRIDE: "int | None" = None


def small_dispatch_default() -> int:
    """Width (bytes) below which device codecs answer reconstruct() on
    the host: reconstruct-on-read serves kilobyte needle ranges
    (server/volume_server._reconstruct_shard_range) and a full device
    round-trip per read would dominate the latency. Env-tunable, and
    superseded by the auto-tuner's override once one is applied."""
    if _SMALL_DISPATCH_OVERRIDE is not None:
        return _SMALL_DISPATCH_OVERRIDE
    return config.env_int("SW_EC_SMALL_DISPATCH_BYTES")


def small_dispatch_override() -> "int | None":
    return _SMALL_DISPATCH_OVERRIDE


def set_small_dispatch_override(nbytes: "int | None"):
    """Install (or clear, with None/0) the live hybrid-threshold
    override."""
    global _SMALL_DISPATCH_OVERRIDE
    _SMALL_DISPATCH_OVERRIDE = int(nbytes) if nbytes else None


def maybe_auto_apply_small_dispatch(suggestion: int) -> bool:
    """Apply the tuner's suggested threshold when the operator opted in
    via SW_EC_SMALL_DISPATCH_AUTO=1. Returns whether it was applied."""
    if not config.env_bool("SW_EC_SMALL_DISPATCH_AUTO"):
        return False
    set_small_dispatch_override(suggestion)
    return True


def dispatch_threshold(codec) -> int:
    """Live host/device crossover width for a codec: the
    SW_EC_SMALL_DISPATCH_AUTO fitted override (installed by the tuner
    via set_small_dispatch_override) supersedes whatever the codec
    snapshotted at construction, so a tuner suggestion applies without
    reconstructing the codec; host-only codecs
    (small_dispatch_bytes == 0) never delegate to the device."""
    if not codec.small_dispatch_bytes:
        return 0
    ov = small_dispatch_override()
    return ov if ov is not None else codec.small_dispatch_bytes


class _ConstCache:
    """Bounded LRU of device-resident coefficient constants, keyed by
    the coefficient bytes. A 256 MB rebuild must upload its ~14 KB
    bit-matrix once, not once per slab — every make() call counts as a
    bitmat_upload in ops/telemetry, so the bench can assert exactly
    that."""

    def __init__(self, maxsize: int = 32):
        self._entries: OrderedDict = OrderedDict()
        self._maxsize = maxsize
        from .device_stats import DEVICE_STATS
        DEVICE_STATS.register_const_cache(self)

    def get(self, key, make):
        from .device_stats import DEVICE_STATS
        hit = self._entries.get(key)
        if hit is not None:
            self._entries.move_to_end(key)
            DEVICE_STATS.note_const_cache("hits")
            return hit
        val = make()
        from .telemetry import STATS
        STATS.add("bitmat_uploads")
        DEVICE_STATS.note_const_cache("misses")
        self._entries[key] = val
        if len(self._entries) > self._maxsize:
            self._entries.popitem(last=False)
            DEVICE_STATS.note_const_cache("evictions")
        return val

    def occupancy(self) -> dict:
        """Entries and device bytes currently pinned (best-effort:
        constants without .nbytes count zero bytes)."""
        nbytes = 0
        for val in list(self._entries.values()):
            nbytes += int(getattr(val, "nbytes", 0) or 0)
        return {"entries": len(self._entries), "bytes": nbytes}


class ReedSolomonCodec:
    """Base class: matrix construction + reconstruction planning.

    Subclasses implement _matmul(coeffs, data) — the GF(2^8) matrix-vector
    product over byte rows — which is the only compute-heavy primitive.
    A codec says itself whether encode and rebuild stream their slabs
    through ops/pipeline.PipelinedMatmul: the device codecs
    (ops/rs_tpu.DeviceCodec) set ``pipelined`` and carry the hooks that
    stream needs (device_fn, pipeline_width_bucket); a host codec
    computes each slab where it is read.
    """

    backend = "abstract"
    pipelined = False
    # 0 = never delegate; device codecs override with the env default
    small_dispatch_bytes = 0

    def __init__(self, data_shards: int, parity_shards: int,
                 matrix_kind: str = "vandermonde"):
        if data_shards <= 0 or parity_shards <= 0:
            raise ValueError("data_shards and parity_shards must be > 0")
        if data_shards + parity_shards > 256:
            raise ValueError("k + m must be <= 256 in GF(2^8)")
        self.k = data_shards
        self.m = parity_shards
        self.total = data_shards + parity_shards
        # the name dispatches, spans and replies count this geometry by
        self.geometry = f"{data_shards}+{parity_shards}"
        self.matrix_kind = matrix_kind
        self.matrix = gf256.build_matrix(self.k, self.total, matrix_kind)
        self._decode_cache: dict = {}
        self._plan_cache: dict = {}
        self._syndrome_rows: Optional[np.ndarray] = None

    # -- primitive ---------------------------------------------------------
    def _matmul(self, coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    # -- public API --------------------------------------------------------
    def encode(self, data: np.ndarray) -> np.ndarray:
        """data (k, n) uint8 -> parity (m, n) uint8."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        if data.shape[0] != self.k:
            raise ValueError(f"expected {self.k} data rows, got {data.shape[0]}")
        return self._matmul(self.matrix[self.k:], data)

    def encode_to_all(self, data: np.ndarray) -> np.ndarray:
        """data (k, n) -> all shards (total, n); data rows verbatim."""
        parity = self.encode(data)
        return np.concatenate([np.asarray(data, dtype=np.uint8), parity], axis=0)

    def _decode_coeffs(self, present: tuple) -> tuple:
        """For a presence tuple, return (src_rows, inv_matrix) where
        data = inv_matrix @ shards[src_rows]."""
        key = present
        hit = self._decode_cache.get(key)
        if hit is not None:
            return hit
        src = [i for i, p in enumerate(present) if p][: self.k]
        if len(src) < self.k:
            raise ValueError(
                f"too few shards: have {sum(present)}, need {self.k}")
        sub = self.matrix[src, :]
        inv = gf256.mat_inv(sub)
        self._decode_cache[key] = (src, inv)
        return src, inv

    def decode_plan(self, present: tuple, data_only: bool = False) -> tuple:
        """Fused decode plan for a presence pattern: (src_rows, missing,
        coeffs) with coeffs (len(missing), k) such that ALL missing rows
        — data and parity stacked — come from ONE matmul against the
        first k survivors. Cached per (present, data_only) alongside
        _decode_cache, so steady-state rebuild pays zero GF planning per
        slab and exactly one device dispatch."""
        key = (tuple(present), bool(data_only))
        hit = self._plan_cache.get(key)
        if hit is not None:
            return hit
        src, inv = self._decode_coeffs(key[0])
        limit = self.k if data_only else self.total
        missing = [i for i in range(limit) if not present[i]]
        coeffs = gf256.decode_coeff_rows(self.matrix, self.k, src,
                                         missing, inv=inv)
        plan = (src, missing, coeffs)
        self._plan_cache[key] = plan
        return plan

    def lost_row_coeffs(self, present: tuple, sid: int) -> tuple:
        """Single-shard slice of the fused decode plan: (src_rows,
        coeffs) with coeffs (1, k) such that shard[sid] = coeffs @
        shards[src_rows]. Degraded reads regenerate exactly one lost
        row — the full plan's other missing rows would be wasted
        compute per request — while still riding the _plan_cache, so
        repeated reads of the same loss pattern pay zero GF planning."""
        src, missing, coeffs = self.decode_plan(tuple(present))
        if sid not in missing:
            raise ValueError(f"shard {sid} is not missing in {present}")
        r = missing.index(sid)
        return src, np.ascontiguousarray(coeffs[r:r + 1])

    def reconstruct(self, shards: Sequence[Optional[np.ndarray]],
                    data_only: bool = False) -> List[np.ndarray]:
        """Fill in missing (None) shards. Mirrors reference Reconstruct /
        ReconstructData. Returns the full shard list (data-only mode leaves
        missing parity as None).

        All missing rows are regenerated by a single fused matmul
        (decode_plan), and device codecs answer sub-small_dispatch_bytes
        widths on the host — reconstruct-on-read of a kilobyte range
        must not pay a device round-trip."""
        shards = list(shards)
        if len(shards) != self.total:
            raise ValueError(f"expected {self.total} shards, got {len(shards)}")
        present = tuple(s is not None for s in shards)
        if all(present):
            return shards
        lens = {s.shape[-1] for s in shards if s is not None}
        if len(lens) != 1:
            raise ValueError("surviving shards have differing lengths")
        from ..util import tracing
        with tracing.span("plan", backend=self.backend):
            src, missing, coeffs = self.decode_plan(present, data_only)
        if not missing:
            return shards
        survivors = np.stack([np.asarray(shards[i], dtype=np.uint8)
                              for i in src], axis=0)
        thr = self.small_dispatch_bytes
        if thr and _SMALL_DISPATCH_OVERRIDE is not None:
            # the auto-tuner's live override supersedes the snapshot
            # taken at construction; host-only codecs (thr == 0) keep
            # their never-delegate behavior
            thr = _SMALL_DISPATCH_OVERRIDE
        small = thr and survivors.shape[1] < thr
        # the reconstruct span's (bytes, seconds, path) tags feed the
        # SW_EC_SMALL_DISPATCH_BYTES tuner (stats.metrics.observe_span)
        with tracing.span("reconstruct", backend=self.backend,
                          bytes=int(survivors.nbytes),
                          path="host" if small else "device"):
            if small:
                from .telemetry import STATS
                STATS.add("host_fallbacks")
                out = host_matmul(coeffs, survivors)
            else:
                out = self._matmul(coeffs, survivors)
        for r, i in enumerate(missing):
            shards[i] = out[r]
        return shards

    def reconstruct_data(self, shards: Sequence[Optional[np.ndarray]]
                         ) -> List[np.ndarray]:
        return self.reconstruct(shards, data_only=True)

    def verify(self, shards: Sequence[np.ndarray]) -> bool:
        """True iff parity rows match the data rows."""
        data = np.stack([np.asarray(s, dtype=np.uint8)
                         for s in shards[: self.k]], axis=0)
        parity = self.encode(data)
        for i in range(self.m):
            if not np.array_equal(parity[i],
                                  np.asarray(shards[self.k + i], dtype=np.uint8)):
                return False
        return True

    def syndrome_plan(self) -> np.ndarray:
        """Parity-check rows H = [P | I_m], shape (m, k+m), derived from
        the cached encode matrix ([I_k; P] for systematic codes).

        For a consistent codeword column x (all k+m shard bytes at one
        offset), H @ x = P @ data XOR parity = 0 — GF(2^8) addition IS
        subtraction, so the identity block needs no negation. Any
        nonzero syndrome byte pins corruption to that byte column, and
        the scrub verifies a whole slab as ONE (m, k+m) x (k+m, w)
        fused matmul — the same PipelinedMatmul hot path encode and
        rebuild ride, with coefficients swapped. Cached like the decode
        plans: steady-state scrub pays zero GF planning per slab."""
        if self._syndrome_rows is None:
            h = np.zeros((self.m, self.total), dtype=np.uint8)
            h[:, : self.k] = self.matrix[self.k:]
            h[:, self.k:] = np.eye(self.m, dtype=np.uint8)
            self._syndrome_rows = np.ascontiguousarray(h)
        return self._syndrome_rows


class NumpyCodec(ReedSolomonCodec):
    """Pure-numpy reference backend — the conformance oracle.

    Inner loop: one 256-entry LUT gather + XOR per (output row, input row)
    pair, equivalent to the reference dependency's galMulSlice without SIMD.
    """

    backend = "numpy"

    def _matmul(self, coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
        return host_matmul(coeffs, data)


_AUTO_CHOICE = None


def _auto_backend() -> str:
    """Resolve `auto` once per process: ask JAX which platform it
    computes on (a backend-init error propagates — a server that cannot
    reach its chip must not quietly serve EC from the CPU), take the TPU
    when it is there, else native C++, else numpy. The choice and its
    reason are logged once at warning level."""
    global _AUTO_CHOICE
    if _AUTO_CHOICE is None:
        from ..util import glog
        from ..util.jax_platform import default_platform
        from .rs_native import native_available
        platform = default_platform()
        if platform == "tpu":
            choice, why = "tpu", "JAX computes on a TPU"
        elif native_available():
            choice, why = "native", (f"JAX computes on {platform!r}, not "
                                     f"a TPU; native C++ codec built")
        else:
            choice, why = "numpy", (f"JAX computes on {platform!r}, not "
                                    f"a TPU; native C++ codec unavailable")
        glog.warningf("-ec.backend auto -> %s (%s)", choice, why)
        _AUTO_CHOICE = choice
    return _AUTO_CHOICE


#: `-ec.backend` values a server can be started with
BACKENDS = ("auto", "numpy", "native", "tpu", "tpu-own", "mesh")


def get_codec(data_shards: int, parity_shards: int,
              backend: str = "auto",
              matrix_kind: str = "vandermonde",
              device_ordinal: int = 0) -> ReedSolomonCodec:
    """``device_ordinal`` matters to `tpu-own` alone: which of the
    process's local chips the codec computes on (rs_tpu.local_device)."""
    if backend == "auto":
        backend = _auto_backend()
    if backend == "numpy":
        return NumpyCodec(data_shards, parity_shards, matrix_kind)
    if backend == "native":
        from .rs_native import NativeCodec
        return NativeCodec(data_shards, parity_shards, matrix_kind)
    if backend == "tpu":
        from .rs_tpu import TpuCodec
        return TpuCodec(data_shards, parity_shards, matrix_kind)
    if backend == "tpu-own":
        from .rs_tpu import OwnDeviceCodec
        return OwnDeviceCodec(data_shards, parity_shards, matrix_kind,
                              ordinal=device_ordinal)
    if backend == "mesh":
        # SPMD over every visible device (multi-chip hosts)
        from ..parallel.mesh_codec import MeshCodec
        return MeshCodec(data_shards, parity_shards, matrix_kind)
    raise ValueError(f"unknown backend {backend!r}")


# ---------------------------------------------------------------------------
# Trace repair of a single lost shard (arxiv 2205.11015).
#
# A dual codeword g satisfies sum_i g[i]*c_i = 0 over every stripe, so
#     Tr(g[lost]*c_lost) = sum_{i != lost} Tr(g[i]*c_i).
# Pick 8 dual codewords whose values at the lost position are
# GF(2)-independent and every bit of c_lost is a GF(2) combination of
# the trace bits Tr(g_j[i]*c_i).  Helper i only has to ship
# t_i = dim_2 span{g_j[i]} bits per byte — its projection onto a
# reduced basis of that span — instead of all 8, which is where the
# sub-k*slab repair bandwidth comes from.  The rebuilder's combine is a
# {0,1}-coefficient GF(2^8) matmul (XOR of bit-planes), so the existing
# pipelined device kernels run it unchanged: one dispatch per slab.
# ---------------------------------------------------------------------------

REPAIR_MAX_SUBSETS = 400   # cap on vanish-subset enumeration (RS(20,4))
# rows a repair stream pads its symbol blocks to (a multiple of):
# RS(10,4)'s plans have 50-56 bits by lost shard, and a compiled program
# per bit count would compile in the middle of a repair
REPAIR_ROW_BUCKET = 8
REPAIR_RESTARTS = 3        # greedy restarts with shuffled candidate order


@dataclass(frozen=True, eq=False)
class RepairPlan:
    """Single-lost-shard trace-repair scheme for one geometry.

    helpers lists the shard ids that must be contacted (t_i > 0 only);
    masks[sid] are the GF(2^8) projection masks that holder applies
    (one packed bit-plane per mask); combine is the (8, total_bits)
    {0,1} matrix that XORs the concatenated symbol planes back into
    the lost shard's 8 bit-planes, in helpers-then-mask order.
    """

    k: int
    m: int
    lost: int
    helpers: Tuple[int, ...]
    masks: Dict[int, Tuple[int, ...]] = field(hash=False)
    combine: np.ndarray = field(hash=False)
    matrix_kind: str = "vandermonde"

    @property
    def total_bits(self) -> int:
        return sum(len(v) for v in self.masks.values())

    @property
    def frac(self) -> float:
        """Repair symbol bits per stripe byte vs the k-byte baseline."""
        return self.total_bits / (8.0 * self.k)

    def bits_for(self, sid: int) -> int:
        return len(self.masks[sid])

    def wire_bytes(self, width: int) -> int:
        """Bytes on the wire for a width-byte slab range (all helpers,
        packed planes; excludes HTTP framing)."""
        return self.total_bits * ((width + 7) // 8)


# Both ends of the trace route re-lay bits on the host: the holder turns
# bytes into packed planes of trace bits, the rebuilder 8 packed planes
# into bytes. Either runs beside a dozen threads doing the same, on
# cores that share a memory bus and an interpreter lock, so what each
# costs is the bytes its passes stream and how often it comes back for
# the lock — whole-range numpy calls, few of them, and no temporary
# wider than the range (the chip's own readings: PERF.md section 6,
# PR 28).

def project_slab(data: np.ndarray, masks) -> np.ndarray:
    """Holder-side projection: trace bits Tr(mask * data) packed
    little-bit-first per mask. data (w,) uint8 -> (len(masks),
    ceil(w/8)) uint8, the tail bits of a ragged w zero.

    The masks (at most 8) fold into one table — bit j of lut[b] is
    Tr(masks[j] * b) — so all of a byte's trace bits come from one
    gather, made two bytes a lookup (a 65536-entry table of pairs:
    half the lookups and half of take()'s index copy). Plane j is then
    bit j of every gathered byte, packed. Cheap enough to run on the
    volume server's host CPU."""
    data = np.ascontiguousarray(data, dtype=np.uint8)
    m = np.asarray(list(masks), dtype=np.uint8)
    nm = len(m)
    if nm > 8:      # a byte's trace bits have to fit a byte of the table
        raise ValueError(f"at most 8 masks to a projection, got {nm}")
    lut = np.bitwise_or.reduce(
        gf256.TRACE_MUL[m] << np.arange(nm, dtype=np.uint8)[:, None],
        axis=0, dtype=np.uint8).astype("<u2")
    pair_lut = ((lut << 8)[:, None] | lut[None, :]).reshape(-1)
    w = data.shape[0]
    even = w - (w & 1)
    traces = np.empty(w, dtype=np.uint8)
    # uint16 indices cannot leave the table: "wrap" only spares take()
    # the bounce buffer its default mode keeps for out=
    np.take(pair_lut, data[:even].view("<u2"),
            out=traces[:even].view("<u2"), mode="wrap")
    if w & 1:
        traces[-1] = lut[data[-1]]
    out = np.empty((nm, (w + 7) // 8), dtype=np.uint8)
    plane = np.empty(w, dtype=np.uint8)
    for j in range(nm):
        np.bitwise_and(traces, 1 << j, out=plane)
        out[j] = np.packbits(plane, bitorder="little")   # non-zero is 1
    return out


# the 8x8 bit transpose's three block swaps: 1x1 blocks of each 2x2,
# 2x2 of each 4x4, 4x4 of the 8x8
_BIT_SWAPS = ((7, 0x00AA00AA00AA00AA), (14, 0x0000CCCC0000CCCC),
              (28, 0x00000000F0F0F0F0))


def _transpose_bits8(x: np.ndarray) -> None:
    """Transpose, in place, the 8x8 bit matrix in every little-endian
    64-bit word of ``x`` (byte i of a word is row i, bit j of it column
    j: bit 8i+j <-> bit 8j+i). A round is the classic
    ``t = (x ^ (x >> s)) & mask; x ^= t ^ (t << s)``; t's bits and their
    shifted copies never meet, so ``t ^ (t << s)`` is ``t * (2**s + 1)``
    and a round is five passes over x and one scratch array."""
    t = np.empty_like(x)
    for s, mask in _BIT_SWAPS:
        np.right_shift(x, s, out=t)
        np.bitwise_xor(t, x, out=t)
        np.bitwise_and(t, mask, out=t)
        np.multiply(t, (1 << s) + 1, out=t)
        np.bitwise_xor(x, t, out=x)


def combine_planes_to_bytes(planes: np.ndarray, width: int) -> np.ndarray:
    """Rebuilder-side interleave: 8 packed output bit-planes (8,
    ceil(width/8)) -> the lost shard's bytes (width,). Plane b holds
    bit b of every output byte, so byte q of the 8 planes, laid side by
    side as one 64-bit word, is the bit transpose of output bytes
    8q..8q+7: lay them so, transpose every word, and the words are the
    bytes."""
    planes = np.asarray(planes, dtype=np.uint8)
    stride = (width + 7) // 8
    out = np.empty(stride * 8, dtype=np.uint8)
    rows = out.reshape(stride, 8)
    for b in range(8):
        rows[:, b] = planes[b, :stride]
    _transpose_bits8(out.view("<u8"))
    return out[:width]


class _PlanLRU:
    """Bounded LRU for derived GF plans (repair / piggyback), shared
    hit/miss/evict accounting. Unlike _ConstCache this holds host-side
    plan objects, and identity is stable across repeated gets — callers
    (and tests) rely on ``plan_fn(...) is plan_fn(...)``. The bound is
    SW_EC_PLAN_CACHE_SIZE, read live so operators can resize without a
    restart; under geometry/survivor churn the old unbounded dict grew
    one entry per (k, m, lost, helpers, matrix) combination forever."""

    def __init__(self, name: str):
        self.name = name
        self._entries: OrderedDict = OrderedDict()

    def get(self, key, make):
        hit = self._entries.get(key)
        if hit is not None:
            self._entries.move_to_end(key)
            _PLAN_CACHE_EVENTS["hits"] += 1
            return hit
        _PLAN_CACHE_EVENTS["misses"] += 1
        val = make()
        self._entries[key] = val
        maxsize = max(config.env_int("SW_EC_PLAN_CACHE_SIZE"), 1)
        while len(self._entries) > maxsize:
            self._entries.popitem(last=False)
            _PLAN_CACHE_EVENTS["evictions"] += 1
        return val

    def __len__(self):
        return len(self._entries)


_PLAN_CACHE_EVENTS = {"hits": 0, "misses": 0, "evictions": 0}
_REPAIR_PLAN_CACHE = _PlanLRU("repair")


def plan_cache_stats() -> dict:
    """Snapshot for stats/metrics export (ec_plan_cache_* families):
    cumulative hit/miss/evict event counts plus current entry counts
    per plan cache."""
    return {
        "events": dict(_PLAN_CACHE_EVENTS),
        "entries": {c.name: len(c) for c in
                    (_REPAIR_PLAN_CACHE, _PIGGYBACK_PLAN_CACHE,
                     _PIGGYBACK_REPAIR_CACHE, _PIGGYBACK_DECODE_CACHE)},
    }


def repair_plan(k: int, m: int, lost_sid: int, survivors=None,
                matrix_kind: str = "vandermonde",
                matrix: "np.ndarray | None" = None,
                seed: int = 0) -> RepairPlan:
    """Build (and cache) the trace-repair scheme for one lost shard.

    survivors: iterable of reachable shard ids (default: all others).
    Unreachable positions are handled by forcing every dual codeword to
    vanish there, which needs n - 1 - len(survivors) <= m - 1; with
    fewer survivors than k the code cannot repair at all and this
    raises ValueError.

    The scheme search enumerates dual codewords supported off an
    (m-1)-subset of positions (nullspace of the transposed generator
    restricted to the complement), scales each by all 255 nonzero
    constants, and greedily picks 8 equations minimizing the total
    per-helper GF(2) span growth — deterministic for a given seed, so
    every process derives the identical plan.
    """
    n = k + m
    if not (0 <= lost_sid < n):
        raise ValueError(f"lost shard {lost_sid} outside 0..{n - 1}")
    if survivors is None:
        survivors = [i for i in range(n) if i != lost_sid]
    helpers = sorted(set(int(s) for s in survivors) - {lost_sid})
    unavailable = [i for i in range(n) if i != lost_sid and i not in helpers]
    if len(unavailable) > m - 1:
        raise ValueError(
            f"too few survivors: {len(helpers)} reachable, need >= {k}")
    key = (k, m, lost_sid, tuple(helpers), matrix_kind,
           None if matrix is None else matrix.tobytes(), seed)
    return _REPAIR_PLAN_CACHE.get(
        key, lambda: _build_repair_plan(k, m, lost_sid, helpers, unavailable,
                                        matrix_kind, matrix, seed))


def _build_repair_plan(k, m, lost_sid, helpers, unavailable, matrix_kind,
                       matrix, seed) -> RepairPlan:
    n = k + m
    if matrix is None:
        matrix = gf256.build_matrix(k, n, matrix_kind)

    # -- candidate dual codewords: vanish on unavailable + an
    #    (m-1-|unavailable|)-subset of helpers ---------------------------
    free = m - 1 - len(unavailable)
    subsets = list(itertools.combinations(helpers, free))
    rng = np.random.default_rng(seed)
    if len(subsets) > REPAIR_MAX_SUBSETS:
        idx = rng.choice(len(subsets), size=REPAIR_MAX_SUBSETS,
                         replace=False)
        subsets = [subsets[i] for i in sorted(idx)]
    base = []
    for sub in subsets:
        vanish = set(unavailable) | set(sub)
        support = [i for i in range(n) if i not in vanish]
        g_u = gf256.gf_nullspace(matrix[support, :].T)
        if g_u is None:
            continue
        g = np.zeros(n, dtype=np.uint8)
        g[support] = g_u
        if g[lost_sid] == 0:
            continue
        base.append(g)
    if not base:
        raise ValueError("no usable dual codewords for this geometry")
    base = np.stack(base, axis=0)
    betas = np.arange(1, 256, dtype=np.uint8)
    cand = gf256.MUL_TABLE[betas[None, :, None], base[:, None, :]]
    cand = cand.reshape(-1, n)

    # -- greedy scheme selection (restarts keep the best) ----------------
    best = None
    for r in range(REPAIR_RESTARTS):
        order = rng.permutation(cand.shape[0]) if r else \
            np.arange(cand.shape[0])
        cv = cand[order]
        chosen = []
        star_basis: list = []
        pos_basis = {i: [] for i in helpers}
        total = 0
        for _ in range(8):
            ok = gf256.gf2_reduce(cv[:, lost_sid], star_basis) != 0
            cost = np.zeros(cv.shape[0], dtype=np.int32)
            for i in helpers:
                cost += (gf256.gf2_reduce(cv[:, i], pos_basis[i]) != 0
                         ).astype(np.int32)
            c = int(np.argmin(np.where(ok, cost, np.int32(1 << 20))))
            chosen.append(cv[c].copy())
            gf256.gf2_insert(star_basis, int(cv[c, lost_sid]))
            for i in helpers:
                if gf256.gf2_insert(pos_basis[i], int(cv[c, i])):
                    total += 1
        if best is None or total < best[0]:
            best = (total, chosen, {i: list(pos_basis[i]) for i in helpers})

    _, chosen, bases = best
    active = [i for i in helpers if bases[i]]
    masks = {i: tuple(bases[i]) for i in active}

    # -- combine matrix: bits(c_lost) = inv(A) @ Lambda @ sigma ----------
    a = np.zeros((8, 8), dtype=np.uint8)
    for j, g in enumerate(chosen):
        for b in range(8):
            a[j, b] = gf256.TRACE_MUL[int(g[lost_sid]), 1 << b]
    lam = np.zeros((8, sum(len(masks[i]) for i in active)), dtype=np.uint8)
    for j, g in enumerate(chosen):
        col = 0
        for i in active:
            coords = gf256.gf2_decompose(int(g[i]), masks[i])
            lam[j, col:col + len(coords)] = coords
            col += len(coords)
    combine = (gf256.gf2_mat_inv(a).astype(np.int32) @
               lam.astype(np.int32)) % 2
    return RepairPlan(k=k, m=m, lost=lost_sid, helpers=tuple(active),
                      masks=masks, combine=combine.astype(np.uint8),
                      matrix_kind=matrix_kind)


def repair_gain(plan: RepairPlan) -> float:
    """Fraction of the k*slab baseline saved by trace repair
    (0 = no gain; ec.rebuild -repair auto requires > 0)."""
    return 1.0 - plan.frac


# ---------------------------------------------------------------------------
# Piggybacked sub-chunk layout (SW_EC_LAYOUT=piggyback).
#
# Each shard is split into alpha = 2^npairs sub-chunks per window
# (npairs = min(k//2, 5) data-shard pairs). Data shards stay verbatim;
# parity shard j's sub-chunk z couples each data shard i (pair p = i>>1,
# side b = i&1) with its partner sub-chunk across bit p:
#
#   P_j[z] = XOR_i  a[j,i]*s_i[z]  ^  [z_p == b] * c[j,i]*s_i[z ^ 2^p]
#
# with a = the flat RS parity rows and c[j,i] = theta_j * a[j,i]
# (theta distinct per parity). The gate [z_p == b] is what makes
# single-data-shard repair plane-local: to repair shard i*, the other
# k-1 data shards and any TWO parities ship only the half-plane
# {z : z_{p*} = b*}; per z the two parity equations form one constant
# 2x2 system in (s[z], s[z ^ 2^{p*}]), recovering both halves of the
# lost shard. Download = (k+1) * alpha/2 sub-chunks = (k+1)/(2k) of
# k*shard — 0.55 for RS(10,4), the d=k+1 cut-set point, below the
# 0.69 floor proven for linear repair of the flat code (2205.11015).
#
# Full decode of any <= m lost shards block-diagonalizes over cosets
# of span{2^{p(i)} : i lost}: at most (m * 2^m) x (m * 2^m) GF systems
# shared by every coset, so planning stays milliseconds and the slab
# hot path is still ONE fused matmul on the unchanged kernels. Node-MDS
# of the coupled code is not automatic — theta is chosen by a
# deterministic seed search that exhaustively sweeps every
# (lost-data, parity-subset) pattern at plan build, and the known-good
# seeds for common geometries are pinned below.
# ---------------------------------------------------------------------------

PIGGYBACK_MAX_PAIRS = 5            # alpha capped at 2^5 = 32 sub-chunks
PIGGYBACK_SEED_TRIES = 32          # theta seed search bound
# geometry -> verified theta seed (the MDS sweep still reruns once per
# process at plan build; these just skip the failed-seed prefix)
PIGGYBACK_KNOWN_SEEDS = {
    (10, 4): 5, (6, 3): 0, (20, 4): 1, (4, 2): 0, (8, 3): 0, (12, 4): 8,
}


def _pb_pairs_cap() -> int:
    """Effective pair cap: SW_EC_PIGGYBACK_PAIRS clamped to
    [1, PIGGYBACK_MAX_PAIRS]. Part of the plan cache key — lowering it
    trades repair savings on the tail shards for a smaller alpha."""
    cap = config.env_int("SW_EC_PIGGYBACK_PAIRS")
    return max(1, min(int(cap), PIGGYBACK_MAX_PAIRS))


def piggyback_supported(k: int, m: int) -> bool:
    """Geometries the piggyback layout accepts: >= 2 parities (the
    repair plane solves a 2x2 per z) and >= 1 data pair. Odd-k tails
    beyond the paired prefix stay uncoupled and repair via the flat
    fallback paths."""
    return m >= 2 and k >= 2 and k + m <= 256


@dataclass(frozen=True, eq=False)
class PiggybackPlan:
    """Verified coupled-layout geometry: encode matrix + coupling
    coefficients. emat is the (m*alpha, k*alpha) block matrix a single
    batched GF matmul applies per window-split slab."""

    k: int
    m: int
    npairs: int
    alpha: int
    theta_seed: int
    matrix_kind: str = "vandermonde"
    amat: np.ndarray = field(hash=False, default=None)
    cmat: np.ndarray = field(hash=False, default=None)
    emat: np.ndarray = field(hash=False, default=None)

    @property
    def coupled(self) -> int:
        """Number of data shards with a coupling partner (cheap repair)."""
        return 2 * self.npairs

    @property
    def repair_frac(self) -> float:
        """Single-coupled-data-shard repair download vs k*shard."""
        return (self.k + 1) / (2.0 * self.k)

    def syndrome_rows(self) -> np.ndarray:
        """[E | I] over flattened sub-chunk columns: zero syndrome iff
        the window's parity sub-chunks match the coupled encode."""
        ka, ma = self.k * self.alpha, self.m * self.alpha
        h = np.zeros((ma, ka + ma), dtype=np.uint8)
        h[:, :ka] = self.emat
        h[:, ka:] = np.eye(ma, dtype=np.uint8)
        return h


def _pb_build(k: int, m: int, matrix_kind: str, matrix, theta_seed: int,
              cap: int):
    """(a, c) coefficient rows for one theta seed."""
    n = k + m
    if matrix is None:
        matrix = gf256.build_matrix(k, n, matrix_kind)
    a = np.ascontiguousarray(matrix[k:])
    npairs = min(k // 2, cap)
    theta = [gf256.EXP_TABLE[(theta_seed * m + j) * 11 % 255]
             for j in range(m)]
    if len(set(theta)) != m:
        raise ValueError("theta collision — geometry too wide for seed")
    c = gf256.MUL_TABLE[np.asarray(theta, dtype=np.uint8)[:, None], a]
    c[:, 2 * npairs:] = 0
    return a, c, npairs, 1 << npairs


def _pb_encode_matrix(k, m, a, c, npairs, alpha) -> np.ndarray:
    emat = np.zeros((m * alpha, k * alpha), dtype=np.uint8)
    for j in range(m):
        for z in range(alpha):
            r = j * alpha + z
            for i in range(k):
                emat[r, i * alpha + z] ^= a[j, i]
                if i < 2 * npairs:
                    p, b = i >> 1, i & 1
                    if (z >> p) & 1 == b:
                        emat[r, i * alpha + (z ^ (1 << p))] ^= c[j, i]
    return emat


def _pb_decode_block(k, m, a, c, npairs, lostF, pJ):
    """Per-coset solve for lost data shards lostF from parities pJ:
    (Minv, V) with V the coupling span (coset offsets) and Minv the
    (f*|V|, f*|V|) inverse, or None when singular. Unknown order is
    (i in sorted F) x (v in V); equation order (j in pJ) x (v in V)."""
    F = sorted(lostF)
    f = len(F)
    V = [0]
    for p in sorted(set(i >> 1 for i in F if i < 2 * npairs)):
        V = V + [v | (1 << p) for v in V]
    t2 = len(V)
    vidx = {v: e for e, v in enumerate(V)}
    mat = np.zeros((f * t2, f * t2), dtype=np.uint8)
    for je, j in enumerate(pJ):
        for ve, v in enumerate(V):
            r = je * t2 + ve
            for ui, i in enumerate(F):
                mat[r, ui * t2 + ve] ^= a[j, i]
                if i < 2 * npairs:
                    p, b = i >> 1, i & 1
                    if (v >> p) & 1 == b:
                        mat[r, ui * t2 + vidx[v ^ (1 << p)]] ^= c[j, i]
    try:
        return gf256.mat_inv(mat), V
    except Exception:  # noqa: BLE001 - singular candidate
        return None


def _pb_mds_sweep(k, m, a, c, npairs) -> bool:
    """True iff every (lost-data, parity-subset) pattern is decodable.
    Coset block structure keeps this to small inversions; RS(10,4)
    sweeps its 1000 patterns in well under a second."""
    for f in range(1, m + 1):
        for F in itertools.combinations(range(k), f):
            for J in itertools.combinations(range(m), f):
                if _pb_decode_block(k, m, a, c, npairs, F, J) is None:
                    return False
    return True


_PIGGYBACK_PLAN_CACHE = _PlanLRU("piggyback")
_PIGGYBACK_REPAIR_CACHE = _PlanLRU("piggyback_repair")
_PIGGYBACK_DECODE_CACHE = _PlanLRU("piggyback_decode")


def piggyback_plan(k: int, m: int, matrix_kind: str = "vandermonde",
                   matrix: "np.ndarray | None" = None,
                   pairs: "int | None" = None) -> PiggybackPlan:
    """Build (and cache) the verified coupled-layout plan for a
    geometry. Deterministic: the theta seed search starts from the
    pinned known-good seed when the geometry has one, and every
    candidate must pass the exhaustive node-MDS sweep before the plan
    is returned — a layout that cannot decode some failure pattern
    must never reach a disk.

    `pairs` pins the pair cap for an already-encoded volume (from its
    sidecar); new encodes leave it None and take the
    SW_EC_PIGGYBACK_PAIRS knob."""
    if not piggyback_supported(k, m):
        raise ValueError(
            f"piggyback layout needs m >= 2 and k >= 2, got RS({k},{m})")
    cap = _pb_pairs_cap() if pairs is None else max(
        1, min(int(pairs), PIGGYBACK_MAX_PAIRS))
    key = (k, m, matrix_kind, cap,
           None if matrix is None else matrix.tobytes())
    return _PIGGYBACK_PLAN_CACHE.get(
        key, lambda: _build_piggyback_plan(k, m, matrix_kind, matrix, cap))


def _build_piggyback_plan(k, m, matrix_kind, matrix, cap) -> PiggybackPlan:
    known = PIGGYBACK_KNOWN_SEEDS.get((k, m))
    order = list(range(PIGGYBACK_SEED_TRIES))
    if known is not None:
        order.remove(known)
        order.insert(0, known)
    for seed in order:
        a, c, npairs, alpha = _pb_build(k, m, matrix_kind, matrix, seed,
                                        cap)
        if _pb_mds_sweep(k, m, a, c, npairs):
            emat = _pb_encode_matrix(k, m, a, c, npairs, alpha)
            return PiggybackPlan(k=k, m=m, npairs=npairs, alpha=alpha,
                                 theta_seed=seed, matrix_kind=matrix_kind,
                                 amat=a, cmat=c, emat=emat)
    raise ValueError(
        f"no MDS theta seed within {PIGGYBACK_SEED_TRIES} tries for "
        f"RS({k},{m}) {matrix_kind}")


@dataclass(frozen=True, eq=False)
class PiggybackRepairPlan:
    """Half-plane repair of one coupled data shard. Every helper
    (the k-1 other data shards + the two parity_sids) ships the
    sub-chunks {z : bit plane_bit of z == plane_side}; matrix is the
    (alpha, (k+1)*alpha/2) combine applied per window — one fused
    matmul rebuilds the lost shard bit-identically."""

    k: int
    m: int
    lost: int
    alpha: int
    plane_bit: int
    plane_side: int
    data_helpers: Tuple[int, ...]
    parity_sids: Tuple[int, ...]
    matrix: np.ndarray = field(hash=False, default=None)
    matrix_kind: str = "vandermonde"

    @property
    def helpers(self) -> Tuple[int, ...]:
        return self.data_helpers + self.parity_sids

    @property
    def frac(self) -> float:
        """Downloaded bytes vs the k*shard full-rebuild baseline."""
        return len(self.helpers) / (2.0 * self.k)

    def plane(self) -> Tuple[int, ...]:
        return tuple(z for z in range(self.alpha)
                     if (z >> self.plane_bit) & 1 == self.plane_side)

    def wire_bytes(self, shard_bytes: int) -> int:
        """Bytes on the wire for whole-shard repair (all helpers,
        half a shard each; excludes HTTP framing)."""
        return len(self.helpers) * (shard_bytes // 2)


def piggyback_repair_plan(k: int, m: int, lost_sid: int,
                          parity_sids=None,
                          matrix_kind: str = "vandermonde",
                          matrix: "np.ndarray | None" = None,
                          pairs: "int | None" = None
                          ) -> PiggybackRepairPlan:
    """Build (and cache) the half-plane repair scheme for one lost
    COUPLED data shard. parity_sids: the two reachable parity shard
    ids to use (absolute, >= k; default the first two). Uncoupled
    shards (odd-k tail, parity shards) have no plane scheme — callers
    route them to trace/full repair instead."""
    pplan = piggyback_plan(k, m, matrix_kind, matrix, pairs=pairs)
    if not (0 <= lost_sid < pplan.coupled):
        raise ValueError(
            f"shard {lost_sid} is not a coupled data shard "
            f"(coupled: 0..{pplan.coupled - 1})")
    if parity_sids is None:
        parity_sids = (k, k + 1)
    pj = tuple(sorted(int(s) for s in parity_sids))
    if len(pj) != 2 or not all(k <= s < k + m for s in pj):
        raise ValueError(f"need exactly two parity shard ids, got {pj}")
    key = (k, m, pplan.npairs, lost_sid, pj, matrix_kind,
           None if matrix is None else matrix.tobytes())
    return _PIGGYBACK_REPAIR_CACHE.get(
        key, lambda: _build_piggyback_repair(pplan, lost_sid, pj))


def _build_piggyback_repair(pplan: PiggybackPlan, lost: int,
                            pj: Tuple[int, int]) -> PiggybackRepairPlan:
    k, m = pplan.k, pplan.m
    a, c, alpha = pplan.amat, pplan.cmat, pplan.alpha
    npairs = pplan.npairs
    p_, b_ = lost >> 1, lost & 1
    half = alpha // 2
    plane = [z for z in range(alpha) if (z >> p_) & 1 == b_]
    zidx = {z: t for t, z in enumerate(plane)}
    dh = [i for i in range(k) if i != lost]
    j1, j2 = pj[0] - k, pj[1] - k
    minv = gf256.mat_inv(np.array(
        [[a[j1, lost], c[j1, lost]],
         [a[j2, lost], c[j2, lost]]], dtype=np.uint8))
    w = np.zeros((alpha, (len(dh) + 2) * half), dtype=np.uint8)
    colbase = {h: t * half for t, h in enumerate(dh)}
    pbase = {j1: len(dh) * half, j2: (len(dh) + 1) * half}
    mt = gf256.MUL_TABLE
    for z in plane:
        t = zidx[z]
        for col, jp in ((0, j1), (1, j2)):
            # K_jp[z] weights into the two unknowns (s[z], s[z^2^p*])
            for out_z, wc in ((z, minv[0, col]), (z ^ (1 << p_),
                                                  minv[1, col])):
                if wc == 0:
                    continue
                w[out_z, pbase[jp] + t] ^= wc
                for h in dh:
                    ah = mt[wc, a[jp, h]]
                    if ah:
                        w[out_z, colbase[h] + t] ^= ah
                    if h < 2 * npairs:
                        ph, bh = h >> 1, h & 1
                        if (z >> ph) & 1 == bh and c[jp, h]:
                            # gated partner term: stays on the plane
                            # because ph != p* for every helper whose
                            # gate can fire here
                            w[out_z, colbase[h] + zidx[z ^ (1 << ph)]] ^= \
                                mt[wc, c[jp, h]]
    return PiggybackRepairPlan(
        k=k, m=m, lost=lost, alpha=alpha, plane_bit=p_, plane_side=b_,
        data_helpers=tuple(dh), parity_sids=pj, matrix=w,
        matrix_kind=pplan.matrix_kind)


def piggyback_decode_plan(k: int, m: int, present,
                          matrix_kind: str = "vandermonde",
                          matrix: "np.ndarray | None" = None,
                          pairs: "int | None" = None):
    """Fused full decode for a presence pattern on the coupled layout:
    returns (src_sids, missing_sids, coeffs) with coeffs
    (len(missing)*alpha, len(src)*alpha) so every missing shard — data
    and parity — comes from ONE window-split matmul against the
    survivors. src is every surviving data shard plus as many parities
    as there are missing data shards (full decode still reads exactly
    k shards, same as the flat layout)."""
    pplan = piggyback_plan(k, m, matrix_kind, matrix, pairs=pairs)
    key = (k, m, tuple(bool(p) for p in present), matrix_kind,
           pplan.npairs,
           None if matrix is None else matrix.tobytes())
    return _PIGGYBACK_DECODE_CACHE.get(
        key, lambda: _build_piggyback_decode(pplan, key[2]))


def _build_piggyback_decode(pplan: PiggybackPlan, present):
    k, m, alpha = pplan.k, pplan.m, pplan.alpha
    n = k + m
    if len(present) != n:
        raise ValueError(f"presence tuple must have {n} entries")
    a, c, npairs = pplan.amat, pplan.cmat, pplan.npairs
    missing = [i for i in range(n) if not present[i]]
    lost_data = [i for i in missing if i < k]
    f = len(lost_data)
    live_data = [i for i in range(k) if present[i]]
    live_par = [j for j in range(m) if present[k + j]]
    if len(live_data) + len(live_par) < k:
        raise ValueError(
            f"too few shards: have {sum(present)}, need {k}")
    use_par = live_par[:f]
    src = live_data + [k + j for j in use_par]
    mt = gf256.MUL_TABLE
    src_col = {s: t * alpha for t, s in enumerate(src)}
    # L: full data flat (k*alpha) as a GF-linear map of the src stack
    ldat = np.zeros((k * alpha, len(src) * alpha), dtype=np.uint8)
    for i in live_data:
        for z in range(alpha):
            ldat[i * alpha + z, src_col[i] + z] = 1
    if f:
        blk = _pb_decode_block(k, m, a, c, npairs, lost_data, use_par)
        if blk is None:
            raise ValueError(
                "singular decode pattern — layout verification bug")
        minv, v_span = blk
        t2 = len(v_span)
        mask = 0
        for v in v_span:
            mask |= v
        vidx = {v: e for e, v in enumerate(v_span)}
        fs = sorted(lost_data)
        for z0 in range(alpha):
            if z0 & mask:
                continue
            # K rows for this coset, as rows over the src stack
            krows = np.zeros((f * t2, len(src) * alpha), dtype=np.uint8)
            for je, j in enumerate(use_par):
                for ve, v in enumerate(v_span):
                    z = z0 | v
                    r = je * t2 + ve
                    krows[r, src_col[k + j] + z] ^= 1
                    for h in live_data:
                        krows[r, src_col[h] + z] ^= a[j, h]
                        if h < 2 * npairs:
                            ph, bh = h >> 1, h & 1
                            if (z >> ph) & 1 == bh and c[j, h]:
                                krows[r, src_col[h] + (z ^ (1 << ph))] ^= \
                                    c[j, h]
            sol = gf256.mat_mul(minv, krows)
            for ui, i in enumerate(fs):
                for ve, v in enumerate(v_span):
                    ldat[i * alpha + (z0 | v)] = sol[ui * t2 + ve]
    rows = []
    for s in missing:
        if s < k:
            rows.append(ldat[s * alpha:(s + 1) * alpha])
        else:
            j = s - k
            erows = pplan.emat[j * alpha:(j + 1) * alpha]
            rows.append(gf256.mat_mul(erows, ldat))
    coeffs = np.concatenate(rows, axis=0) if rows else \
        np.zeros((0, len(src) * alpha), dtype=np.uint8)
    return src, missing, np.ascontiguousarray(coeffs)


# -- sub-chunk window transforms (pure reshapes, zero copy semantics
#    beyond the transpose) ---------------------------------------------------

def pb_window(small_block: int, alpha: int) -> int:
    """Sub-chunk window: every window bytes of a shard split into alpha
    interleaved sub-chunks. The window is the small stripe block, which
    divides every shard size the two-level striping can produce; it
    must itself be alpha-divisible."""
    if small_block % alpha:
        raise ValueError(
            f"small block {small_block} not divisible by alpha {alpha}")
    return small_block


def pb_split(rows: np.ndarray, alpha: int, window: int) -> np.ndarray:
    """(r, W) shard rows -> (r*alpha, W/alpha) sub-chunk rows, window
    by window; W must be window-aligned. Row order (shard-major,
    sub-chunk z) matches the encode/decode matrix column order."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    r, width = rows.shape
    if width % window:
        raise ValueError(f"width {width} not aligned to window {window}")
    wsub = window // alpha
    x = rows.reshape(r, width // window, alpha, wsub)
    return np.ascontiguousarray(
        x.transpose(0, 2, 1, 3).reshape(r * alpha, width // alpha))


def pb_merge(flat: np.ndarray, alpha: int, window: int) -> np.ndarray:
    """Inverse of pb_split: (r*alpha, W/alpha) -> (r, W)."""
    wsub = window // alpha
    ra, cols = flat.shape
    r = ra // alpha
    x = flat.reshape(r, alpha, cols // wsub, wsub)
    return np.ascontiguousarray(
        x.transpose(0, 2, 1, 3).reshape(r, cols * alpha))


def pb_plane_slice(shard: np.ndarray, alpha: int, window: int,
                   plane_bit: int, plane_side: int) -> np.ndarray:
    """Holder-side half-plane extraction: the repair protocol ships
    exactly these bytes. (W,) -> (W/2,) — the plane's sub-chunks in
    increasing z, window-major, so the rebuilder's pb_plane_rows can
    restack them without knowing the holder's file layout."""
    shard = np.ascontiguousarray(shard, dtype=np.uint8)
    wsub = window // alpha
    zs = [z for z in range(alpha) if (z >> plane_bit) & 1 == plane_side]
    x = shard.reshape(-1, alpha, wsub)
    return np.ascontiguousarray(x[:, zs, :].reshape(-1))


def pb_plane_rows(plane: np.ndarray, alpha: int, window: int,
                  out: np.ndarray) -> np.ndarray:
    """Rebuilder-side restack of one helper's plane bytes:
    (W/2,) -> (alpha/2, W/alpha) rows in plan column order, written
    into ``out`` (C-contiguous rows of that shape: the helper's range
    of a gather's block) in one pass."""
    half = alpha // 2
    wsub = window // alpha
    out.reshape(half, -1, wsub)[...] = \
        plane.reshape(-1, half, wsub).transpose(1, 0, 2)
    return out
