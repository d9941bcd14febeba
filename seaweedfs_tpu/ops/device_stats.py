"""Device-runtime observability plane: compile vs execute, split open.

Every jitted EC entry point (`rs_tpu._packed_fn`, `rs_pallas`'s
fused kernel, `MeshCodec._fn` — and `PipelinedMatmul` transitively
through all of them) routes its
compiled-executable lifecycle through this module via `wrap()`:

- **Explicit compile/execute separation.** The wrapper AOT-compiles
  with `fn.lower(*args).compile()` the first time it sees an abstract
  shape signature and times exactly that call, so compile wall is a
  counter (`compiles`, `compile_seconds` per entry point) instead of a
  mystery spike folded into the first dispatch. Subsequent calls hit
  the cached executable directly.

- **The recompile sentinel.** Width-bucketing exists so one executable
  serves a whole range of slab widths; when it breaks (a caller
  bypassing `width_bucket`, an lru eviction, a dtype drift) the
  symptom used to be wall time. The wrapper re-buckets every compiled
  signature's trailing width through `canonical_width()` — a properly
  bucketed width maps to itself, so each (entry, bucket) pair compiles
  at most once. A second compile for the same pair increments
  `recompiles` and latches the `sentinel` flag with a bounded offender
  list. An earlier round's collapsed mesh rebuild would have been a
  nonzero counter, not a PR-long bisect.

- **A dispatch reads no clock.** The hot path increments one counter
  under one lock and performs ZERO clock reads and zero
  synchronizations — tests/test_device_stats.py proves it by
  monkeypatching `device_stats._perf_counter`. Device time comes from
  a profiler trace (the kernels carry stable names, and the stream's
  stages are mirrored into it: util/tracing.Stage), never from a
  host-timed `block_until_ready`, which stalls what it measures.

- **Cache accounting.** `_ConstCache` (device-resident bit-matrix
  constants) reports hits/misses/evictions here and registers itself
  (weakly) so occupancy — entries and device bytes pinned — can be
  snapshotted. The `lru_cache` jit factories register via
  `register_jit_factory()`; evictions are derived as
  `misses - currsize`, because an evicted jitted fn is a silent
  recompile.

Everything lands in `snapshot()` → mirrored to `ec_xla_*` /
`ec_const_cache_*` metric families on `/metrics` (aggregated onto the
master's `/cluster/metrics`), `GET /admin/devices`, shell
`cluster.devices`, and the benchmark's `compiles_in_window` check.

jax is imported lazily (device inventory only), matching
telemetry.py: this module must import on hosts with no
jax at all.
"""

from __future__ import annotations

import weakref
from time import perf_counter as _perf_counter
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..util.jax_platform import backend_initialized
from ..util.locks import make_lock

#: Compiled signatures latched as sentinel offenders are capped here;
#: past that the counters still move but reprs stop accumulating.
MAX_OFFENDERS = 8


def canonical_width(n: int) -> int:
    """The width bucket `n` SHOULD have been dispatched under.

    Mirrors ops/rs_tpu.width_bucket's shape (512 floor, next pow2) so
    that a properly bucketed width is a fixed point: bucketed paths
    key one compile per bucket, while a caller jitting exact widths
    folds many widths into one bucket key and trips the sentinel on
    the second compile."""
    if n <= 0:
        return 0
    return max(512, 1 << (int(n) - 1).bit_length())


class DeviceStats:
    """Per-entry-point compile/execute accounting (thread-safe)."""

    def __init__(self):
        self._lock = make_lock("device_stats._lock")
        self.compiles: Dict[str, int] = {}
        self.compile_seconds: Dict[str, float] = {}
        self.recompiles: Dict[str, int] = {}
        self.dispatches: Dict[str, int] = {}
        # (entry, bucket-signature) -> compile count; >1 latches.
        self._bucket_compiles: Dict[Tuple[str, Any], int] = {}
        self.sentinel = False
        self.offenders: List[str] = []
        # const-cache event counters + live instances for occupancy.
        self.const_cache: Dict[str, int] = {
            "hits": 0, "misses": 0, "evictions": 0}
        self._const_caches: "weakref.WeakSet" = weakref.WeakSet()

    # -- hot path ------------------------------------------------------

    def tick(self, entry: str, chip: Optional[str] = None):
        """Count one dispatch: the ONLY per-dispatch cost — one lock,
        one dict increment, no clock reads. A program that belongs to
        one chip of several (`-ec.backend tpu-own`) counts it a second
        time under its ``chip`` (``dev<i>``), beside the entry points
        in the same table: `tpu` and `mesh` name no chip and count
        nothing there."""
        with self._lock:
            self.dispatches[entry] = self.dispatches.get(entry, 0) + 1
            if chip is not None:
                self.dispatches[chip] = self.dispatches.get(chip, 0) + 1

    # -- slow-path events ----------------------------------------------

    def note_compile(self, entry: str, bucket_key, seconds: float,
                     chip: Optional[str] = None):
        """``chip`` (``dev<i>``): the chip the executable belongs to,
        where the program is one chip's of several. The same (entry,
        bucket) on another chip is a compile of its own, not a
        recompile."""
        with self._lock:
            self.compiles[entry] = self.compiles.get(entry, 0) + 1
            self.compile_seconds[entry] = \
                self.compile_seconds.get(entry, 0.0) + seconds
            if chip is not None:
                bucket_key = (chip,) + tuple(bucket_key)
            key = (entry, bucket_key)
            seen = self._bucket_compiles.get(key, 0) + 1
            self._bucket_compiles[key] = seen
            if seen > 1:
                self.recompiles[entry] = self.recompiles.get(entry, 0) + 1
                self.sentinel = True
                if len(self.offenders) < MAX_OFFENDERS:
                    self.offenders.append(f"{entry}:{bucket_key!r}")

    def note_const_cache(self, event: str, n: int = 1):
        with self._lock:
            self.const_cache[event] = self.const_cache.get(event, 0) + n

    def register_const_cache(self, cache):
        self._const_caches.add(cache)

    # -- reads ---------------------------------------------------------

    def const_cache_occupancy(self) -> Dict[str, int]:
        entries = 0
        nbytes = 0
        for cache in list(self._const_caches):
            occ = cache.occupancy()
            entries += occ["entries"]
            nbytes += occ["bytes"]
        return {"entries": entries, "bytes": nbytes}

    def snapshot(self) -> dict:
        with self._lock:
            snap = {
                "compiles": dict(self.compiles),
                "compile_seconds": dict(self.compile_seconds),
                "recompiles": dict(self.recompiles),
                "dispatches": dict(self.dispatches),
                "sentinel": self.sentinel,
                "offenders": list(self.offenders),
                "const_cache": dict(self.const_cache),
            }
        snap["const_cache_occupancy"] = self.const_cache_occupancy()
        return snap


DEVICE_STATS = DeviceStats()


def delta(before: dict) -> dict:
    """Movement since a snapshot() — a phase's own compiles and
    dispatches (chip_smoke.py's device_proof line)."""
    now = DEVICE_STATS.snapshot()
    out = {}
    for field in ("compiles", "compile_seconds", "recompiles",
                  "dispatches"):
        prev = before.get(field, {})
        moved = {k: v - prev.get(k, 0) for k, v in now[field].items()
                 if v - prev.get(k, 0)}
        out[field] = moved
        out[field + "_total"] = sum(moved.values())
    out["sentinel"] = now["sentinel"]
    out["offenders"] = [o for o in now["offenders"]
                        if o not in before.get("offenders", [])]
    return out


# ---------------------------------------------------------------------------
# the instrumented jit wrapper
# ---------------------------------------------------------------------------

class InstrumentedJit:
    """Wraps a `jax.jit`-ed callable with AOT compile accounting.

    First call per abstract signature pays a timed
    `lower(*args).compile()`; later calls dispatch the cached
    executable. The sentinel key re-buckets the data argument's
    trailing width through canonical_width(), so per-bucket compiles
    are idempotent and exact-width churn latches."""

    __slots__ = ("_jit", "entry", "_stats", "_compiled", "_lock",
                 "device", "_chip", "_on")

    def __init__(self, jfn, entry: str, stats: Optional[DeviceStats] = None,
                 device: Optional[int] = None):
        self._jit = jfn
        self.entry = entry
        self._stats = stats if stats is not None else DEVICE_STATS
        self._compiled: Dict[Any, Callable] = {}
        self._lock = make_lock(f"device_stats.wrap[{entry}]")
        # the one chip this instance's executables are compiled for and
        # counted under; None: wherever JAX puts them (`tpu`, `mesh`)
        self.device = device
        self._chip = None if device is None else f"dev{device}"
        self._on: Dict[int, "InstrumentedJit"] = {}

    def on_device(self, index: int) -> "InstrumentedJit":
        """The same jitted program as one chip's own: a sibling with its
        own executables (an AOT executable is bound to the devices of
        the arguments it was lowered with), counted under the same entry
        point and under ``dev<index>``."""
        with self._lock:
            sibling = self._on.get(index)
            if sibling is None:
                sibling = self._on[index] = InstrumentedJit(
                    self._jit, self.entry, self._stats, device=index)
            return sibling

    @property
    def raw_jit(self):
        """The unwrapped `jax.jit` result, for consumers that need the
        genuine `stages.Wrapped` object (jax.export, serialization)."""
        return self._jit

    @staticmethod
    def _signature(args) -> tuple:
        return tuple((tuple(getattr(a, "shape", ())),
                      str(getattr(a, "dtype", type(a).__name__)))
                     for a in args)

    @staticmethod
    def _bucket_key(sig) -> tuple:
        """Signature with the LAST axis of the LAST array re-bucketed —
        the width axis every EC entry point varies."""
        if not sig:
            return sig
        head, (shape, dtype) = sig[:-1], sig[-1]
        if shape:
            shape = shape[:-1] + (canonical_width(shape[-1]),)
        return head + ((shape, dtype),)

    def _compile(self, sig, args):
        with self._lock:
            exe = self._compiled.get(sig)
            if exe is not None:  # lost the race; already compiled
                return exe
            t0 = _perf_counter()
            try:
                exe = self._jit.lower(*args).compile()
            except Exception:
                # Backends without AOT lowering (or non-array leaves)
                # still get counted; jit's own tracing then compiles
                # on first dispatch inside the timed window.
                exe = self._jit
            dt = _perf_counter() - t0
            self._compiled[sig] = exe
        self._stats.note_compile(self.entry, self._bucket_key(sig), dt,
                                 self._chip)
        return exe

    def __call__(self, *args):
        sig = self._signature(args)
        exe = self._compiled.get(sig)
        if exe is None:
            exe = self._compile(sig, args)
        self._stats.tick(self.entry, self._chip)
        return exe(*args)


def wrap(jfn, entry: str, stats: Optional[DeviceStats] = None):
    """Instrument a jitted callable under an entry-point name."""
    return InstrumentedJit(jfn, entry, stats)


# ---------------------------------------------------------------------------
# lru_cache jit-factory registry
# ---------------------------------------------------------------------------

_JIT_FACTORIES: Dict[str, Callable] = {}


def register_jit_factory(name: str, fn) -> None:
    """Register an `lru_cache`-decorated jit factory for cache_info()
    export; an evicted entry is a silent recompile, so evictions are
    first-class (misses - currsize)."""
    _JIT_FACTORIES[name] = fn


def jit_factory_snapshot() -> Dict[str, dict]:
    out = {}
    for name, fn in sorted(_JIT_FACTORIES.items()):
        info = fn.cache_info()
        out[name] = {
            "hits": info.hits,
            "misses": info.misses,
            "maxsize": info.maxsize,
            "currsize": info.currsize,
            "evictions": max(0, info.misses - info.currsize),
        }
    return out


# ---------------------------------------------------------------------------
# device inventory
# ---------------------------------------------------------------------------

def device_inventory() -> dict:
    """Platform, device kind×count, and memory_stats() gauges.

    A metrics scrape or a status question must never be the call that
    boots an XLA backend (a chip belongs to one process): until a codec
    has initialised one, this reports initialized=False and touches
    nothing. A backend whose init half-failed reports the error in the
    payload — only the codec path raises it."""
    empty = {"initialized": False, "platform": None,
             "device_kinds": {}, "devices": []}
    if not backend_initialized():
        return empty
    import jax
    try:
        devices = jax.devices()
        platform = jax.default_backend()
    except RuntimeError as exc:
        return {**empty, "error": str(exc)}
    kinds: Dict[str, int] = {}
    per_device = []
    for d in devices:
        kind = getattr(d, "device_kind", "unknown")
        kinds[kind] = kinds.get(kind, 0) + 1
        mem = None
        try:
            mem = d.memory_stats()
        except Exception:
            mem = None
        per_device.append({"id": d.id, "kind": kind,
                           "memory_stats": mem or {}})
    return {"initialized": True, "platform": platform,
            "device_kinds": kinds, "devices": per_device}


def admin_snapshot() -> dict:
    """The GET /admin/devices payload: full stats + factories +
    inventory (which, as on a metrics scrape, never boots a backend)."""
    return {
        "stats": DEVICE_STATS.snapshot(),
        "jit_factories": jit_factory_snapshot(),
        "inventory": device_inventory(),
    }
