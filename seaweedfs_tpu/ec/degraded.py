"""Batched degraded-read serving tier: reconstruct-on-read as a
first-class data-plane path.

When a shard holder dies, needle reads that land on the lost shard fall
through to reconstruction. The legacy loop
(``volume_server._reconstruct_shard_range``) paid three separate taxes
per read: it fanned out to all ``k+m-1`` siblings when k
survivors suffice, it decoded the full 14-row stripe to recover one row,
and it did all of it once per request even when a hundred readers were
asking for the same dead shard at once.

``DegradedReadEngine`` serves the same contract the other way around:

* **Coalescing** — concurrent reads of the same ``(vid, lost_sid)`` are
  funneled through a per-shard leader/follower batcher. The first
  request in becomes the leader, waits ``SW_EC_DEGRADED_BATCH_MS`` for
  followers, and executes ONE gather + ONE fused decode dispatch for
  the union of their slab-aligned ranges. Everyone else just waits on a
  future — the syndrome-decoding regime where a single matmul amortizes
  across requests.
* **Exactly-k gather** — the batch fetches the decode plan's first-k
  survivor column ranges (``ops/codec.decode_plan``) through the PR-4
  reader stack: ``LocalShardReader`` for shards on this server,
  ``RemoteShardReader`` (per-stripe round-robin, ``SW_EC_HEDGE_MS``
  hedging, failover) for the rest. Never ``k+m-1`` siblings.
* **One-row decode** — ``codec.lost_row_coeffs`` extracts the lost
  shard's single coefficient row from the cached decode plan, so the
  matmul output is (1, W), not (missing, W).
* **Host/device crossover** — batches below the ``SmallDispatchTuner``
  threshold run ``host_matmul`` (a device round-trip costs more than
  the LUT walk); wider batches stream through ``PipelinedMatmul`` as a
  single fused device dispatch.
* **Slab LRU** — reconstructed slabs park in a bounded LRU
  (``SW_EC_DEGRADED_CACHE_BYTES``) keyed ``(vid, sid, slab)``, so hot
  needles on a dead shard hit memory. The store's ``on_ec_mount`` hook
  invalidates ``(vid, *)`` when shards are (re-)registered after a
  rebuild — cached slabs are bit-identical to the real shard, so the
  invalidation is about memory, not correctness, but a mounted shard
  must win immediately.

Tracing: each batch runs under an ``ec.degraded`` span with the
canonical ``plan``/``gather``/``dispatch`` phases, so degraded reads
feed the same histograms and tuner as rebuilds.
"""

from __future__ import annotations

import os
import threading
from ..util.locks import make_lock
import time
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..util import config, tracing
from .ec_volume import EcShardNotFound
from .gather import ShardSizeCache
from .transport import (GatherStats, LocalShardReader, RemoteShardReader,
                        default_hedge_ms)

CACHE_BYTES_ENV = "SW_EC_DEGRADED_CACHE_BYTES"
SLAB_BYTES_ENV = "SW_EC_DEGRADED_SLAB_BYTES"
BATCH_MS_ENV = "SW_EC_DEGRADED_BATCH_MS"
READ_TIMEOUT_ENV = "SW_EC_DEGRADED_READ_TIMEOUT_S"
MODE_ENV = "SW_EC_DEGRADED_MODE"
READAHEAD_ENV = "SW_EC_DEGRADED_READAHEAD_SLABS"

def degraded_cache_bytes() -> int:
    return max(0, config.env_int(CACHE_BYTES_ENV))


def degraded_slab_bytes() -> int:
    return max(1 << 10, config.env_int(SLAB_BYTES_ENV))


def degraded_batch_ms() -> float:
    return max(0.0, config.env_float(BATCH_MS_ENV))


def degraded_read_timeout_s() -> float:
    """Per-holder budget for degraded-read shard fetches. The legacy
    30 s meant one dead holder could eat the whole request deadline
    before failover even started; default well under it."""
    return max(0.1, config.env_float(READ_TIMEOUT_ENV))


def degraded_readahead_slabs() -> int:
    """Neighbor slabs reconstructed per batch beyond the requested
    range: the batch is already paying a gather + dispatch, so widening
    it by a slab is nearly free and sequential readers of a dead shard
    hit the LRU instead of a fresh batch. 0 disables."""
    return max(0, config.env_int(READAHEAD_ENV))


def degraded_mode() -> str:
    """"batch" (the engine) or "naive" (per-read exactly-k fallback,
    kept for A/B benching and emergencies)."""
    return (config.env_str(MODE_ENV) or "batch").strip().lower() or "batch"


class SlabCache:
    """Bounded byte-budget LRU of reconstructed slabs keyed
    ``(vid, sid, slab_idx)``. ``max_bytes == 0`` disables caching."""

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._entries: "OrderedDict[tuple, bytes]" = OrderedDict()
        self._bytes = 0
        self._lock = make_lock("degraded.SlabCache._lock")
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: tuple) -> Optional[bytes]:
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return hit

    def peek(self, key: tuple) -> Optional[bytes]:
        """Presence probe that counts as neither hit nor miss and does
        not touch LRU order — readahead planning must not distort the
        cache stats or promote entries it only inspects."""
        with self._lock:
            return self._entries.get(key)

    def put(self, key: tuple, data: bytes):
        if self.max_bytes <= 0 or len(data) > self.max_bytes:
            return
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= len(old)
            self._entries[key] = data
            self._bytes += len(data)
            while self._bytes > self.max_bytes:
                _, evicted = self._entries.popitem(last=False)
                self._bytes -= len(evicted)
                self.evictions += 1

    def invalidate(self, vid: int, shard_ids=None):
        sids = None if shard_ids is None else {int(s) for s in shard_ids}
        with self._lock:
            doomed = [k for k in self._entries
                      if k[0] == vid and (sids is None or k[1] in sids)]
            for k in doomed:
                self._bytes -= len(self._entries.pop(k))
        return len(doomed)

    def stats(self) -> Tuple[int, int]:
        with self._lock:
            return len(self._entries), self._bytes


class _Batch:
    """Per-(vid, sid) coalescing state. The leader flag and the pending
    slab->future map share one lock so a follower can never register
    into a batch the leader has already taken."""

    def __init__(self):
        self.lock = make_lock("degraded.Batch.lock")
        self.pending: Dict[int, "_SlabFuture"] = {}
        self.leading = False
        self.requests = 0


class _SlabFuture:
    def __init__(self):
        self._done = threading.Event()
        self._value: Optional[bytes] = None
        self._exc: Optional[BaseException] = None

    def set(self, value: bytes):
        self._value = value
        self._done.set()

    def set_exception(self, exc: BaseException):
        self._exc = exc
        self._done.set()

    def result(self, timeout: Optional[float] = None) -> bytes:
        if not self._done.wait(timeout):
            raise TimeoutError("degraded slab reconstruction timed out")
        if self._exc is not None:
            raise self._exc
        return self._value


class DegradedReadEngine:
    """Serves ``_reconstruct_shard_range`` with batching, exactly-k
    survivor gather, fused one-row decode, and a reconstructed-slab LRU.

    ``store`` supplies ``find_ec_volume``; ``locations(vid)`` returns
    the cached ``{sid: [holders]}`` map; ``loc_cache`` (optional) is the
    ``EcShardLocationCache`` to invalidate when a survivor gather dies;
    ``self_url`` (str or callable) is this server's own address, which
    never counts as a remote holder; ``codec(ev)`` (callable) resolves
    the RS codec of one mounted volume's own geometry lazily, so the
    store's backend choice wins (storage/store.Store.ec_volume_codec).
    """

    def __init__(self, store, locations, codec,
                 loc_cache=None, self_url="",
                 cache_bytes: Optional[int] = None,
                 slab: Optional[int] = None,
                 batch_ms: Optional[float] = None,
                 hedge_ms: Optional[float] = None,
                 readahead: Optional[int] = None,
                 on_read=None, on_slabs=None):
        self.store = store
        self._locations = locations
        self._codec = codec
        self._loc_cache = loc_cache
        self._self_url = self_url
        self.slab = int(slab) if slab else degraded_slab_bytes()
        self.batch_s = (degraded_batch_ms() if batch_ms is None
                        else float(batch_ms)) / 1000.0
        self._hedge_ms = hedge_ms
        self.readahead = (degraded_readahead_slabs() if readahead is None
                          else max(0, int(readahead)))
        self.cache = SlabCache(degraded_cache_bytes()
                               if cache_bytes is None else cache_bytes)
        # readahead-produced cache keys, so hits on them are attributable
        self._ra_keys: set = set()
        self.size_cache = ShardSizeCache(timeout=degraded_read_timeout_s())
        self.on_read = on_read
        # on_slabs(vid, sid, {slab_idx: bytes}) fires after every fresh
        # reconstruction — the volume server publishes the slabs into
        # the native plane's cache so the NEXT read of these bytes never
        # leaves the plane. Invalidation is paired: everything that
        # invalidates self.cache also invalidates the plane's copy.
        self.on_slabs = on_slabs
        self._lock = make_lock("degraded.Engine._lock")
        self._batches: Dict[Tuple[int, int], _Batch] = {}
        self._latencies: deque = deque(maxlen=512)
        self._c: Dict[str, int] = {
            "reads": 0, "errors": 0, "batches": 0,
            "batched_requests": 0, "last_batch_requests": 0,
            "max_batch_requests": 0, "batch_slabs": 0,
            "survivor_rows": 0, "survivor_fetches": 0,
            "survivor_bytes": 0, "remote_bytes": 0,
            "hedges_fired": 0, "hedges_won": 0, "retries": 0,
            "host_dispatches": 0, "device_dispatches": 0,
            "readahead_slabs": 0, "readahead_hits": 0,
        }
        # the gather pool is shared across batches: a batch needs at
        # most k concurrent range reads and batches for different lost
        # shards overlap under multi-failure
        self._pool = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix="ec-degraded")

    # -- public API --------------------------------------------------------
    def read(self, vid: int, sid: int, offset: int, size: int) -> bytes:
        """Reconstructed bytes ``[offset, offset+size)`` of the lost
        shard, zero-padded past the shard tail like local reads."""
        t0 = time.perf_counter()
        try:
            out = self._read(int(vid), int(sid), int(offset), int(size))
        except Exception:
            with self._lock:
                self._c["errors"] += 1
            raise
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._c["reads"] += 1
                self._latencies.append(dt)
            if self.on_read is not None:
                try:
                    self.on_read(dt)
                except Exception:  # noqa: BLE001 - metrics must not fail reads
                    pass
        return out

    def invalidate(self, vid: int, shard_ids=None) -> int:
        """Drop cached slabs for a volume (optionally specific shards).
        Wired to ``store.on_ec_mount``: a shard re-registered after
        rebuild must be read from disk, not from the reconstruction
        cache."""
        return self.cache.invalidate(int(vid), shard_ids)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._c)
            lat = sorted(self._latencies)
        entries, nbytes = self.cache.stats()
        out["cache_hits"] = self.cache.hits
        out["cache_misses"] = self.cache.misses
        out["cache_evictions"] = self.cache.evictions
        out["cache_entries"] = entries
        out["cache_bytes"] = nbytes
        looked = out["cache_hits"] + out["cache_misses"]
        out["cache_hit_ratio"] = (out["cache_hits"] / looked) if looked \
            else 0.0
        out["readahead_hit_ratio"] = \
            (out["readahead_hits"] / out["readahead_slabs"]) \
            if out["readahead_slabs"] else 0.0
        if lat:
            out["p50_ms"] = lat[len(lat) // 2] * 1000.0
            out["p99_ms"] = lat[min(len(lat) - 1,
                                    int(len(lat) * 0.99))] * 1000.0
        else:
            out["p50_ms"] = out["p99_ms"] = 0.0
        return out

    # -- read path ---------------------------------------------------------
    def _read(self, vid: int, sid: int, offset: int, size: int) -> bytes:
        if size <= 0:
            return b""
        slab = self.slab
        first = offset // slab
        last = (offset + size - 1) // slab
        parts: Dict[int, bytes] = {}
        want: List[int] = []
        for idx in range(first, last + 1):
            key = (vid, sid, idx)
            hit = self.cache.get(key)
            if hit is None:
                want.append(idx)
            else:
                parts[idx] = hit
                with self._lock:
                    if key in self._ra_keys:
                        self._ra_keys.discard(key)
                        self._c["readahead_hits"] += 1
        if want:
            # the batch is already paying a gather + fused dispatch, so
            # widen it by the readahead window: neighbor slabs land in
            # the LRU and the next sequential read never reaches here
            ra = self.readahead if self.cache.max_bytes > 0 else 0
            extra = [idx for idx in range(last + 1, last + 1 + ra)
                     if self.cache.peek((vid, sid, idx)) is None]
            got = self._batched(vid, sid, want + extra)
            parts.update({i: got[i] for i in want})
            with self._lock:
                for idx in extra:
                    if got.get(idx):
                        self._ra_keys.add((vid, sid, idx))
                        self._c["readahead_slabs"] += 1
                if len(self._ra_keys) > 8192:  # evicted keys pile up
                    self._ra_keys.clear()
        out = bytearray()
        for idx in range(first, last + 1):
            seg = parts[idx]
            lo = max(offset, idx * slab) - idx * slab
            hi = min(offset + size, (idx + 1) * slab) - idx * slab
            piece = seg[lo:hi]
            if len(piece) < hi - lo:  # shard tail: zero-pad like local reads
                piece = piece + b"\x00" * (hi - lo - len(piece))
            out += piece
        return bytes(out)

    def _batched(self, vid: int, sid: int,
                 idxs: List[int]) -> Dict[int, bytes]:
        key = (vid, sid)
        with self._lock:
            st = self._batches.get(key)
            if st is None:
                st = self._batches[key] = _Batch()
        with st.lock:
            futs = {}
            for idx in idxs:
                f = st.pending.get(idx)
                if f is None:
                    f = st.pending[idx] = _SlabFuture()
                futs[idx] = f
            st.requests += 1
            lead = not st.leading
            if lead:
                st.leading = True
        if lead:
            if self.batch_s > 0:
                time.sleep(self.batch_s)
            with st.lock:
                take, st.pending = st.pending, {}
                nreq, st.requests = st.requests, 0
                st.leading = False
            try:
                got = self._reconstruct_batch(vid, sid,
                                              sorted(take), nreq)
                for idx, f in take.items():
                    f.set(got[idx])
            except BaseException as e:  # noqa: BLE001 - fail every waiter
                for f in take.values():
                    f.set_exception(e)
        deadline = degraded_read_timeout_s() * 3 + 30.0
        return {idx: f.result(timeout=deadline)
                for idx, f in futs.items()}

    # -- batch execution ---------------------------------------------------
    def _reconstruct_batch(self, vid: int, sid: int, idxs: List[int],
                           nreq: int) -> Dict[int, bytes]:
        with tracing.span("ec.degraded", volume=vid, shard=sid,
                          slabs=len(idxs), requests=nreq) as root:
            ev = self.store.find_ec_volume(vid)
            codec = self._codec(ev)
            self_url = self._self_url() if callable(self._self_url) \
                else self._self_url
            locations = self._locations(vid) or {}

            present = []
            for i in range(codec.total):
                if i == sid:
                    present.append(False)
                elif ev is not None and i in ev.shards:
                    present.append(True)
                else:
                    present.append(any(h != self_url
                                       for h in locations.get(i, [])))
            if sum(present) < codec.k:
                raise EcShardNotFound(
                    f"cannot reconstruct {vid}.{sid}: only "
                    f"{sum(present)} of {codec.k} survivors reachable")
            # the volume's layout picks the decode basis: flat volumes
            # use the single lost-row coefficients over raw bytes,
            # piggyback volumes need the coupled plan's alpha sub-chunk
            # rows over window-split survivor slabs
            li = self._layout(ev, codec)
            with tracing.span("plan", backend=codec.backend,
                              layout=li.layout):
                if li.piggyback:
                    from ..ops import codec as ops_codec
                    src, pmissing, coeffs = \
                        ops_codec.piggyback_decode_plan(
                            codec.k, codec.m, tuple(present),
                            matrix_kind=getattr(codec, "matrix_kind",
                                                "vandermonde"),
                            matrix=getattr(codec, "matrix", None),
                            pairs=li.pairs)
                    pos = pmissing.index(sid)
                    row = np.ascontiguousarray(
                        coeffs[pos * li.alpha:(pos + 1) * li.alpha])
                else:
                    src, row = codec.lost_row_coeffs(tuple(present), sid)

            stats = GatherStats()
            timeout = degraded_read_timeout_s()
            readers = []
            for s in src:
                if ev is not None and s in ev.shards:
                    readers.append(LocalShardReader(ev.shards[s].path,
                                                    stats))
                else:
                    holders = [h for h in locations.get(s, [])
                               if h != self_url]
                    r = RemoteShardReader(vid, s, holders, stats,
                                          timeout=timeout,
                                          hedge_ms=self._hedge_ms)
                    r.span = root
                    readers.append(r)

            shard_size = self._shard_size(vid, ev, src, locations,
                                          self_url)
            runs = self._runs(idxs, shard_size)
            if li.piggyback:
                # the coupled transform is window-local: widen each run
                # to window boundaries (shard sizes are window-aligned
                # by construction, so the widened runs stay in range)
                runs = self._window_runs(runs, li.window, shard_size)
            try:
                blocks = self._gather(readers, runs, root)
            except Exception as e:
                # survivors we believed in are gone — drop the stale
                # location set so the next batch re-plans from fresh
                # holders rather than repeating the same dead fetch
                if self._loc_cache is not None:
                    self._loc_cache.invalidate(vid)
                raise EcShardNotFound(
                    f"survivor gather for {vid}.{sid} failed: {e}") \
                    from e

            if li.piggyback:
                out = self._dispatch_piggyback(codec, row, blocks,
                                               li.alpha, li.window)
            else:
                out = self._dispatch(codec, row, blocks)
            slabs = self._split(runs, out, shard_size)
            for idx, data in slabs.items():
                self.cache.put((vid, sid, idx), data)
            if self.on_slabs is not None:
                try:
                    self.on_slabs(vid, sid, slabs)
                except Exception:
                    pass  # publish is best-effort; the read must serve

            width = sum(w for _, w, _m in runs)
            with self._lock:
                self._c["batches"] += 1
                self._c["batched_requests"] += nreq
                self._c["last_batch_requests"] = nreq
                if nreq > self._c["max_batch_requests"]:
                    self._c["max_batch_requests"] = nreq
                self._c["batch_slabs"] += len(idxs)
                self._c["survivor_rows"] += len(readers)
                self._c["survivor_fetches"] += stats.fetches
                self._c["survivor_bytes"] += stats.bytes
                self._c["remote_bytes"] += stats.remote_bytes
                self._c["hedges_fired"] += stats.hedges_fired
                self._c["hedges_won"] += stats.hedges_won
                self._c["retries"] += stats.retries
            root.tags["bytes"] = int(width * len(readers))
            return slabs

    def _shard_size(self, vid, ev, src, locations, self_url) -> int:
        """Shard length bounds the gather: ranges are clamped to it and
        the beyond-tail remainder is zeros (every shard is equal-length,
        so any survivor's size is the lost shard's size)."""
        if ev is not None:
            for s in src:
                if s in ev.shards:
                    return ev.shards[s].size
            if ev.shards:
                return next(iter(ev.shards.values())).size
        for s in src:
            holders = [h for h in locations.get(s, []) if h != self_url]
            if holders:
                return self.size_cache.get(vid, s, holders)
        raise EcShardNotFound(f"no survivor holders to size volume {vid}")

    def _runs(self, idxs: List[int], shard_size: int
              ) -> List[Tuple[int, int, List[int]]]:
        """Merge sorted slab indices into contiguous byte ranges
        ``(off, w, member_idxs)``, clamped to the shard; a zero-width
        run marks slabs entirely past the tail (all zeros)."""
        runs: List[Tuple[int, int, List[int]]] = []
        slab = self.slab
        i = 0
        while i < len(idxs):
            j = i
            while j + 1 < len(idxs) and idxs[j + 1] == idxs[j] + 1:
                j += 1
            off = idxs[i] * slab
            end = min((idxs[j] + 1) * slab, shard_size)
            runs.append((off, max(0, end - off), idxs[i:j + 1]))
            i = j + 1
        return runs

    def _layout(self, ev, codec):
        """Resolve the volume's on-disk layout from its local sidecars;
        a server with no mounted index (ev is None) cannot be serving
        the needle lookup that led here, so flat is the safe default."""
        from ..storage.types import entry_size
        from .layout import LayoutInfo, volume_layout
        base = getattr(ev, "base_name", None)
        if base is None:
            return LayoutInfo()
        width = getattr(ev, "offset_width", None) or 4
        return volume_layout(base, codec.k, record_size=entry_size(width))

    @staticmethod
    def _window_runs(runs, window: int, shard_size: int):
        """Widen byte runs to sub-chunk window boundaries so the
        piggyback transform sees whole windows; zero-width (past-tail)
        runs stay empty."""
        out = []
        for off, w, members in runs:
            if w <= 0:
                out.append((off, w, members))
                continue
            aoff = off - off % window
            end = off + w
            aend = min(-(-end // window) * window, shard_size)
            out.append((aoff, aend - aoff, members))
        return out

    def _dispatch_piggyback(self, codec, rows: np.ndarray,
                            blocks: List[np.ndarray], alpha: int,
                            window: int) -> np.ndarray:
        """ONE coupled decode dispatch for the whole batch: window-split
        the concatenated survivor slab, multiply by the lost shard's
        alpha sub-chunk coefficient rows, and interleave the result back
        into shard bytes. Same host/device crossover as the flat path,
        measured on the sub-chunk width."""
        from ..ops.codec import (dispatch_threshold, host_matmul, pb_merge,
                                 pb_split)
        data = blocks[0] if len(blocks) == 1 else \
            np.concatenate(blocks, axis=1)
        width = data.shape[1]
        if width == 0:
            return np.zeros(0, dtype=np.uint8)
        sub = pb_split(data, alpha, window)
        thr = dispatch_threshold(codec)
        host = (not thr) or sub.shape[1] < thr
        with tracing.span("dispatch", backend=codec.backend,
                          bytes=int(data.nbytes), layout="piggyback",
                          path="host" if host else "device"):
            if host:
                out = host_matmul(rows, sub)
                with self._lock:
                    self._c["host_dispatches"] += 1
            else:
                from ..ops.pipeline import PipelinedMatmul
                pm = PipelinedMatmul(rows, max_width=None, codec=codec)
                out = None
                for _meta, _d, o in pm.stream([(None, sub)]):
                    out = o
                with self._lock:
                    self._c["device_dispatches"] += 1
        merged = pb_merge(np.asarray(out, dtype=np.uint8), alpha, window)
        return np.ascontiguousarray(merged[0])

    def _gather(self, readers, runs, root) -> List[np.ndarray]:
        """Fetch every (survivor row x run) range concurrently; returns
        one (k, w) block per run. Exactly k rows — never more."""
        t0 = time.perf_counter()
        futs = {}
        for ri, (off, w, _m) in enumerate(runs):
            if w <= 0:
                continue
            stripe = off // self.slab
            for r, reader in enumerate(readers):
                futs[(ri, r)] = self._pool.submit(
                    reader.read, off, w, stripe)
        blocks = []
        err = None
        for ri, (off, w, _m) in enumerate(runs):
            if w <= 0:
                blocks.append(np.zeros((len(readers), 0), dtype=np.uint8))
                continue
            rows = []
            for r in range(len(readers)):
                f = futs[(ri, r)]
                if err is not None:
                    f.cancel()
                    continue
                try:
                    rows.append(np.frombuffer(f.result(), dtype=np.uint8))
                except Exception as e:  # noqa: BLE001 - drain then raise
                    err = e
            if err is None:
                blocks.append(np.stack(rows, axis=0))
        tracing.record_span("gather", time.perf_counter() - t0,
                            parent=root, op="ec.degraded",
                            bytes=sum(b.nbytes for b in blocks))
        if err is not None:
            raise err
        return blocks

    def _dispatch(self, codec, row: np.ndarray,
                  blocks: List[np.ndarray]) -> np.ndarray:
        """ONE decode dispatch for the whole batch: concatenate the
        per-run blocks into a (k, W) slab and multiply by the lost
        shard's single coefficient row. Below the small-dispatch
        crossover the host LUT walk wins; above it the batch streams
        through the device kernel."""
        from ..ops.codec import dispatch_threshold, host_matmul
        data = blocks[0] if len(blocks) == 1 else \
            np.concatenate(blocks, axis=1)
        width = data.shape[1]
        # dispatch_threshold folds the env default AND the
        # SW_EC_SMALL_DISPATCH_AUTO fitted crossover, so the tuner's
        # suggestion steers batches without reconstructing the codec
        thr = dispatch_threshold(codec)
        host = (not thr) or width < thr or width == 0
        with tracing.span("dispatch", backend=codec.backend,
                          bytes=int(data.nbytes),
                          path="host" if host else "device"):
            if host:
                out = host_matmul(row, data)
                with self._lock:
                    self._c["host_dispatches"] += 1
            else:
                from ..ops.pipeline import PipelinedMatmul
                # no cap: batches come in every width, and a cap taken
                # from the batch compiled a new device program for each
                pm = PipelinedMatmul(row, max_width=None, codec=codec)
                out = None
                for _meta, _d, o in pm.stream([(None, data)]):
                    out = o
                with self._lock:
                    self._c["device_dispatches"] += 1
        return np.ascontiguousarray(out[0])

    def _split(self, runs: List[Tuple[int, int, List[int]]],
               out: np.ndarray, shard_size: int) -> Dict[int, bytes]:
        """Carve the decoded (W,) row back into per-slab byte strings
        in the same run order the gather concatenated them. Slabs past
        the shard tail come back empty (assembly zero-pads)."""
        slabs: Dict[int, bytes] = {}
        slab = self.slab
        pos = 0
        for off, w, members in runs:
            run_out = out[pos:pos + w]
            pos += w
            for idx in members:
                rel = idx * slab - off
                n = min(slab, max(0, shard_size - idx * slab))
                slabs[idx] = run_out[rel:rel + n].tobytes() if n else b""
        return slabs
