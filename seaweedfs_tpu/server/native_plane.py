"""ctypes wrapper for the native volume-server read plane.

The C++ library (`server/native/http_plane.cc`) serves plain needle GETs
on a second advertised port without the Python GIL in the loop — the
native analog of the reference's Go data plane (reference
weed/server/volume_server_handlers_read.go). The Python server stays the
source of truth: the plane answers only the fast path and 307-redirects
everything else (EC volumes, gzip-stored payloads, chunk manifests,
Seaweed-* pairs, resize queries) back to the owning Python server.

The index the plane serves from is a mirror, pushed from Python:
  - `register_volume(volume)` bulk-loads the needle map after a volume
    is loaded/created (and re-syncs after compaction commit);
  - `put`/`delete` mirror every write/delete as it happens (the .dat is
    flushed before the index update, so the plane's independent fd sees
    the bytes through the page cache).
"""

from __future__ import annotations

import ctypes
import json
import os
import threading
import time
from ..ops import telemetry
from ..util import config
from ..util.locks import make_lock
from typing import Optional

_LIB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")
# SW_HTTP_PLANE_LIB overrides the library (e.g. an ASAN-instrumented
# build for the sanitizer test pass)
_LIB_PATH = config.env_str(
    "SW_HTTP_PLANE_LIB",
    os.path.join(_LIB_DIR, "libseaweed_http.so"))

_lib = None
_lib_lock = make_lock("native_plane._lib_lock")
# True once the one-time build (or load) failed and the server fell back
# to the Python path — mirrored into /metrics as the
# SeaweedFS_volumeServer_plane_build_failed gauge so a fleet silently
# running GIL-bound data planes is visible on a dashboard
BUILD_FAILED = False


def build_failed() -> bool:
    return BUILD_FAILED


def _compile():
    """One-shot g++ build of the library (build.sh also builds the
    loadgen tool, which server startup must not wait for). On failure
    the compiler's stderr is logged at warning level — a silent fall
    back to the Python path used to swallow it entirely."""
    import subprocess
    from ..util import glog
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-fPIC", "-shared",
             "-pthread", "-o", _LIB_PATH,
             os.path.join(_LIB_DIR, "http_plane.cc")],
            check=True, capture_output=True, timeout=60)
    except Exception as e:
        stderr = getattr(e, "stderr", b"") or b""
        glog.warningf(
            "native plane build failed (%s: %s) — falling back to the "
            "Python data plane; compiler stderr:\n%s",
            type(e).__name__, e,
            stderr.decode("utf-8", "replace").strip() or "(empty)")
        raise


def _load() -> Optional[ctypes.CDLL]:
    global _lib, BUILD_FAILED
    with _lib_lock:
        if _lib is not None:
            return _lib or None
        if config.env_is_set("SW_HTTP_PLANE_LIB") and \
                not os.path.exists(_LIB_PATH):
            # an explicit override must never silently degrade into a
            # freshly compiled plain build (it usually points at an
            # instrumented variant)
            raise FileNotFoundError(
                f"SW_HTTP_PLANE_LIB={_LIB_PATH} does not exist")
        try:
            src = os.path.join(_LIB_DIR, "http_plane.cc")
            if not os.path.exists(_LIB_PATH):
                _compile()
            elif not config.env_is_set("SW_HTTP_PLANE_LIB") and \
                    os.path.getmtime(_LIB_PATH) < os.path.getmtime(src):
                # stale build from before a source (possibly ABI)
                # change; rebuild before the first dlopen — replacing
                # the file after loading would keep serving the old
                # mapping for the process lifetime
                os.remove(_LIB_PATH)
                _compile()
            lib = ctypes.CDLL(_LIB_PATH)
        except Exception:
            BUILD_FAILED = True
            _lib = False
            return None
        lib.swhp_start.argtypes = [ctypes.c_char_p, ctypes.c_uint16,
                                   ctypes.c_char_p, ctypes.c_int]
        lib.swhp_start.restype = ctypes.c_void_p
        lib.swhp_port.argtypes = [ctypes.c_void_p]
        lib.swhp_port.restype = ctypes.c_uint16
        lib.swhp_add_volume.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                        ctypes.c_char_p, ctypes.c_int]
        lib.swhp_add_volume.restype = ctypes.c_int
        lib.swhp_remove_volume.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        lib.swhp_remove_volume.restype = ctypes.c_int
        lib.swhp_put.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                 ctypes.c_uint64, ctypes.c_uint64,
                                 ctypes.c_uint32]
        lib.swhp_put.restype = ctypes.c_int
        lib.swhp_put_bulk.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                      ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_void_p, ctypes.c_int64]
        lib.swhp_put_bulk.restype = ctypes.c_int
        lib.swhp_delete.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                    ctypes.c_uint64]
        lib.swhp_delete.restype = ctypes.c_int
        lib.swhp_served.argtypes = [ctypes.c_void_p]
        lib.swhp_served.restype = ctypes.c_uint64
        lib.swhp_redirected.argtypes = [ctypes.c_void_p]
        lib.swhp_redirected.restype = ctypes.c_uint64
        lib.swhp_written.argtypes = [ctypes.c_void_p]
        lib.swhp_written.restype = ctypes.c_uint64
        lib.swhp_enable_writer.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p,
            ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int]
        lib.swhp_enable_writer.restype = ctypes.c_int
        lib.swhp_disable_writer.argtypes = [ctypes.c_void_p,
                                            ctypes.c_uint32]
        lib.swhp_disable_writer.restype = ctypes.c_int64
        lib.swhp_set_accept_posts.argtypes = [ctypes.c_void_p,
                                              ctypes.c_uint32,
                                              ctypes.c_int]
        lib.swhp_set_accept_posts.restype = ctypes.c_int
        lib.swhp_append.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                    ctypes.c_char_p, ctypes.c_int64,
                                    ctypes.c_uint64, ctypes.c_uint32,
                                    ctypes.c_int, ctypes.c_uint32]
        lib.swhp_append.restype = ctypes.c_int64
        lib.swhp_lookup.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                    ctypes.c_uint64,
                                    ctypes.POINTER(ctypes.c_uint64),
                                    ctypes.POINTER(ctypes.c_uint32)]
        lib.swhp_lookup.restype = ctypes.c_int
        lib.swhp_writer_counters.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.swhp_writer_counters.restype = ctypes.c_int
        lib.swhp_stop.argtypes = [ctypes.c_void_p]
        lib.swhp_stop.restype = None
        # telemetry ABI — absent only in an explicitly overridden
        # pre-telemetry build (SW_HTTP_PLANE_LIB), where the wrapper
        # degrades to stats()=None instead of refusing to serve
        if hasattr(lib, "swhp_stats"):
            lib.swhp_stats_len.argtypes = []
            lib.swhp_stats_len.restype = ctypes.c_int
            lib.swhp_stats.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint64),
                                       ctypes.c_int]
            lib.swhp_stats.restype = ctypes.c_int
            lib.swhp_lat_bounds.argtypes = [
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
            lib.swhp_lat_bounds.restype = ctypes.c_int
            lib.swhp_slow_ring.argtypes = [ctypes.c_void_p,
                                           ctypes.c_char_p, ctypes.c_int]
            lib.swhp_slow_ring.restype = ctypes.c_int
            lib.swhp_set_stats_enabled.argtypes = [ctypes.c_void_p,
                                                   ctypes.c_int]
            lib.swhp_set_stats_enabled.restype = None
            lib.swhp_set_slow_us.argtypes = [ctypes.c_void_p,
                                             ctypes.c_uint64]
            lib.swhp_set_slow_us.restype = None
        # group-commit durability ABI — absent in an explicitly
        # overridden pre-durability build (SW_HTTP_PLANE_LIB), where
        # appends keep the page-cache ack contract as before
        if hasattr(lib, "swhp_set_sync_mode"):
            lib.swhp_set_sync_mode.argtypes = [ctypes.c_void_p,
                                               ctypes.c_int,
                                               ctypes.c_uint64,
                                               ctypes.c_uint64]
            lib.swhp_set_sync_mode.restype = ctypes.c_int
            lib.swhp_sync_stats_len.argtypes = []
            lib.swhp_sync_stats_len.restype = ctypes.c_int
            lib.swhp_sync_stats.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_int]
            lib.swhp_sync_stats.restype = ctypes.c_int
        # EC + reconstructed-slab cache ABI — absent in an explicitly
        # overridden pre-cache build (SW_HTTP_PLANE_LIB); the wrapper
        # then keeps every EC read on the redirect path as before
        if hasattr(lib, "swhp_cache_put"):
            lib.swhp_ec_register.argtypes = [
                ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64]
            lib.swhp_ec_register.restype = ctypes.c_int
            lib.swhp_ec_set_shard.argtypes = [
                ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int,
                ctypes.c_char_p]
            lib.swhp_ec_set_shard.restype = ctypes.c_int
            if hasattr(lib, "swhp_ec_set_data_shards"):
                lib.swhp_ec_set_data_shards.argtypes = [
                    ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int]
                lib.swhp_ec_set_data_shards.restype = ctypes.c_int
            lib.swhp_ec_put_bulk.argtypes = [
                ctypes.c_void_p, ctypes.c_uint32, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
            lib.swhp_ec_put_bulk.restype = ctypes.c_int
            lib.swhp_ec_delete.argtypes = [ctypes.c_void_p,
                                           ctypes.c_uint32,
                                           ctypes.c_uint64]
            lib.swhp_ec_delete.restype = ctypes.c_int
            lib.swhp_ec_unregister.argtypes = [ctypes.c_void_p,
                                               ctypes.c_uint32]
            lib.swhp_ec_unregister.restype = ctypes.c_int
            lib.swhp_cache_configure.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_uint64]
            lib.swhp_cache_configure.restype = None
            lib.swhp_cache_put.argtypes = [
                ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int,
                ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64]
            lib.swhp_cache_put.restype = ctypes.c_int
            lib.swhp_cache_invalidate.argtypes = [ctypes.c_void_p,
                                                  ctypes.c_uint32,
                                                  ctypes.c_int]
            lib.swhp_cache_invalidate.restype = ctypes.c_uint64
            lib.swhp_cache_stats_len.argtypes = []
            lib.swhp_cache_stats_len.restype = ctypes.c_int
            lib.swhp_cache_stats.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_int]
            lib.swhp_cache_stats.restype = ctypes.c_int
        _lib = lib
        return lib


def available() -> bool:
    return _load() is not None


def lat_bounds_us() -> tuple:
    """µs upper bounds of the plane's latency buckets (the +Inf bucket
    is implicit). Empty when the library is unavailable or predates the
    telemetry ABI."""
    lib = _load()
    if lib is None or not hasattr(lib, "swhp_lat_bounds"):
        return ()
    buf = (ctypes.c_uint64 * 32)()
    n = lib.swhp_lat_bounds(buf, 32)
    return tuple(int(buf[i]) for i in range(max(0, n)))


class NativeReadPlane:
    """One native fast-read server owned by a VolumeServer."""

    def __init__(self, host: str, port: int, fallback_hostport: str,
                 max_conns: int = 1024):
        lib = _load()
        if lib is None:
            raise RuntimeError("libseaweed_http.so unavailable")
        self._lib = lib
        self._h = lib.swhp_start(host.encode(), port,
                                 fallback_hostport.encode(), max_conns)
        if not self._h:
            raise RuntimeError(
                f"native read plane failed to listen on {host}:{port}")
        self.host = host
        self.port = lib.swhp_port(self._h)
        self._has_stats = hasattr(lib, "swhp_stats")
        if self._has_stats:
            # SW_PLANE_STATS=0 is the escape hatch that takes even the
            # relaxed-atomic bumps off the request path (the bench's
            # overhead assertion compares against this build)
            lib.swhp_set_stats_enabled(
                self._h, 1 if config.env_bool("SW_PLANE_STATS") else 0)
            lib.swhp_set_slow_us(
                self._h, max(0, config.env_int("SW_PLANE_SLOW_US")))
        self._has_cache = hasattr(lib, "swhp_cache_put")
        if self._has_cache:
            lib.swhp_cache_configure(
                self._h, max(0, config.env_int("SW_PLANE_CACHE_BYTES")))
        self._has_sync = hasattr(lib, "swhp_set_sync_mode")
        if self._has_sync:
            self.set_sync_mode(
                config.env_str("SW_PLANE_FSYNC_MODE"),
                config.env_int("SW_PLANE_FSYNC_BATCH_US"),
                config.env_int("SW_PLANE_FSYNC_MAX_PENDING"))

    # -- volume lifecycle --------------------------------------------------
    def register_volume(self, volume) -> bool:
        """Open the .dat and bulk-load the volume's live needle map.

        The plane answers index misses with a redirect to the Python
        server, so the add-then-fill window is safe (windowed misses
        are served by the fallback, never 404'd). The needle map is
        snapshotted under the volume lock — it mutates under writes."""
        h = self._h
        if not h:
            return False
        rc = self._lib.swhp_add_volume(
            h, volume.id, volume.dat_path.encode(), volume.version)
        if rc != 0:
            return False
        t0 = time.perf_counter()
        live_columns = getattr(volume.nm, "live_columns", None)
        looped = 0
        if live_columns is not None:
            with volume.lock:
                keys, offsets, sizes = live_columns()
            pushed = self._put_columns(self._lib.swhp_put_bulk, volume.id,
                                       keys, offsets, sizes)
        else:
            from ..storage.compact_map import snapshot_live_items
            with volume.lock:
                entries = snapshot_live_items(volume.nm)
            with entries:
                pushed = looped = self._bulk_load(volume, entries)
        telemetry.STATS.add_mirror(pushed, time.perf_counter() - t0, looped)
        return True

    def _put_columns(self, put_bulk, vid: int, keys, offsets, sizes) -> int:
        """Hand a mirror its entries as three native arrays (uint64
        keys, uint64 byte offsets, uint32 sizes), at most 2^20 a call."""
        import numpy as np
        for lo in range(0, len(keys), 1 << 20):
            chunk = slice(lo, lo + (1 << 20))
            ka = np.ascontiguousarray(keys[chunk], np.uint64)
            oa = np.ascontiguousarray(offsets[chunk], np.uint64)
            sa = np.ascontiguousarray(sizes[chunk], np.uint32)
            put_bulk(self._h, vid,
                     ka.ctypes.data_as(ctypes.c_void_p),
                     oa.ctypes.data_as(ctypes.c_void_p),
                     sa.ctypes.data_as(ctypes.c_void_p), len(ka))
        return len(keys)

    def _bulk_load(self, volume, entries) -> int:
        """A map that offers no columns (disk): its snapshot an entry
        an iteration, staged in bounded lists."""
        keys, offsets, sizes = [], [], []
        pushed = 0
        for key, nv in entries:
            keys.append(key)
            offsets.append(nv.offset)
            sizes.append(nv.size)
            if len(keys) >= (1 << 20):
                pushed += self._put_columns(
                    self._lib.swhp_put_bulk, volume.id, keys, offsets, sizes)
                keys, offsets, sizes = [], [], []
        return pushed + self._put_columns(
            self._lib.swhp_put_bulk, volume.id, keys, offsets, sizes)

    def unregister_volume(self, vid: int):
        h = self._h
        if h:
            self._lib.swhp_remove_volume(h, vid)

    # -- per-needle mirror -------------------------------------------------
    def put(self, vid: int, key: int, offset: int, size: int):
        h = self._h
        if h:
            self._lib.swhp_put(h, vid, key, offset, size)

    def delete(self, vid: int, key: int):
        h = self._h
        if h:
            self._lib.swhp_delete(h, vid, key)

    # -- write lease -------------------------------------------------------
    def enable_writer(self, volume, file_size_limit: int = 0,
                      accept_posts: bool = False):
        """Hand the volume's write lease to the plane (caller holds
        volume.lock). The mirror must already be registered and exact —
        register_volume under the same lock hold. Returns a
        NativeWriter (volume.fast_writer), or None on failure."""
        h = self._h
        if not h:
            return None
        from ..storage.types import max_volume_size
        tail = volume.size()
        rc = self._lib.swhp_enable_writer(
            h, volume.id, volume.idx_path.encode(), volume.offset_width,
            tail, max_volume_size(volume.offset_width),
            int(file_size_limit), 1 if accept_posts else 0)
        if rc != 0:
            return None
        return NativeWriter(self, volume.id)

    def disable_writer(self, vid: int) -> int:
        """Take the lease back (mutex barrier in C++). Returns the
        final tail offset, or -1 when no writer was active."""
        h = self._h
        if not h:
            return -1
        return int(self._lib.swhp_disable_writer(h, vid))

    # -- EC volumes + reconstructed-slab cache -----------------------------
    def register_ec_volume(self, ev, slab_bytes: int) -> bool:
        """Push an EC volume's geometry, local shard files and .ecx
        index mirror into the plane. slab_bytes must match the Python
        engine's slab size — cached slabs are addressed by index.

        Safe to call repeatedly (every mount/unmount re-syncs): a fresh
        record replaces the old one, so the shard set and index can
        never go stale. Index misses redirect to Python, so the
        register-then-fill window is served, never 404'd."""
        h = self._h
        if not h or not self._has_cache:
            return False
        from ..ec.constants import (DATA_SHARDS, LARGE_BLOCK_SIZE,
                                    SMALL_BLOCK_SIZE)
        try:
            dat_size = ev._dat_size_hint()
        except Exception:
            return False
        rc = self._lib.swhp_ec_register(
            h, ev.vid, ev.version, dat_size, LARGE_BLOCK_SIZE,
            SMALL_BLOCK_SIZE, int(slab_bytes))
        if rc != 0:
            return False
        if ev.k != DATA_SHARDS:
            # the volume's own k; an overridden build that stripes by
            # 10 only leaves the volume on the redirect path (Python
            # serves it)
            set_k = getattr(self._lib, "swhp_ec_set_data_shards", None)
            if set_k is None or set_k(h, ev.vid, ev.k) != 0:
                self._lib.swhp_ec_unregister(h, ev.vid)
                return False
        for sid in range(ev.total):
            shard = ev.shards.get(sid)
            self._lib.swhp_ec_set_shard(
                h, ev.vid, sid,
                shard.path.encode() if shard is not None else None)
        return self._bulk_load_ecx(ev)

    def _bulk_load_ecx(self, ev) -> bool:
        """Snapshot the .ecx under its lock and push every entry —
        tombstones included, so a deleted needle redirects (Python
        404s) instead of being resurrected by a re-sync. The snapshot
        is viewed as the record array it is (storage/idx_array): no
        entry passes through a Python object."""
        from ..storage import idx_array
        t0 = time.perf_counter()
        with ev.ecx_lock:
            ev.ecx_file.seek(0)
            raw = ev.ecx_file.read(ev.ecx_size)
        keys, offsets, sizes = idx_array.columns(
            idx_array.records_of(raw, ev.offset_width))
        pushed = self._put_columns(
            self._lib.swhp_ec_put_bulk, ev.vid, keys, offsets, sizes)
        telemetry.STATS.add_mirror(pushed, time.perf_counter() - t0)
        return True

    def unregister_ec_volume(self, vid: int):
        h = self._h
        if h and self._has_cache:
            self._lib.swhp_ec_unregister(h, vid)

    def ec_delete(self, vid: int, key: int):
        """Mirror an EC needle delete (tombstone, matching .ecx)."""
        h = self._h
        if h and self._has_cache:
            self._lib.swhp_ec_delete(h, vid, key)

    def cache_put(self, vid: int, sid: int, idx: int, data: bytes) -> bool:
        """Publish one reconstructed slab into the plane cache."""
        h = self._h
        if not h or not self._has_cache:
            return False
        return self._lib.swhp_cache_put(
            h, vid, sid, idx, data, len(data)) == 0

    def cache_invalidate(self, vid: int, sid: int = -1) -> int:
        """Drop cached slabs of (vid, sid), or all of vid when sid < 0.
        Returns the number of entries removed."""
        h = self._h
        if not h or not self._has_cache:
            return 0
        return int(self._lib.swhp_cache_invalidate(h, vid, sid))

    # field order of swhp_cache_stats's flat export
    _CACHE_STATS_FIELDS = (
        "puts", "put_bytes", "hits", "misses", "evictions", "invalidated",
        "entries", "bytes", "max_bytes", "degraded_served",
        "degraded_redirected", "ec_local_served")

    def cache_stats(self) -> Optional[dict]:
        """Slab-cache counters + EC serving outcomes, or None when the
        plane is stopped or the loaded library predates the cache ABI."""
        h = self._h
        if not h or not self._has_cache:
            return None
        n = int(self._lib.swhp_cache_stats_len())
        buf = (ctypes.c_uint64 * n)()
        if self._lib.swhp_cache_stats(h, buf, n) != n:
            return None
        return dict(zip(self._CACHE_STATS_FIELDS,
                        (int(x) for x in buf)))

    # -- stats / lifecycle -------------------------------------------------
    @property
    def served(self) -> int:
        # a scrape/status racing stop() must see 0, not hand the C side
        # a NULL handle
        h = self._h
        return int(self._lib.swhp_served(h)) if h else 0

    @property
    def redirected(self) -> int:
        h = self._h
        return int(self._lib.swhp_redirected(h)) if h else 0

    @property
    def written(self) -> int:
        h = self._h
        return int(self._lib.swhp_written(h)) if h else 0

    # field order of swhp_stats's flat export, ahead of the buckets
    _STATS_HEAD = ("requests", "status_1xx", "status_2xx", "status_3xx",
                   "status_4xx", "status_5xx", "bytes_sent", "redirects",
                   "index_misses", "lat_count", "lat_sum_us")

    def stats(self) -> Optional[dict]:
        """Telemetry snapshot: the flat counters plus the µs latency
        histogram as non-cumulative ``(bound_us, count)`` pairs, the
        trailing pair carrying ``None`` for the +Inf bucket. None when
        the plane is stopped or the loaded library predates the
        telemetry ABI."""
        h = self._h
        if not h or not self._has_stats:
            return None
        n = int(self._lib.swhp_stats_len())
        buf = (ctypes.c_uint64 * n)()
        if self._lib.swhp_stats(h, buf, n) != n:
            return None
        vals = [int(x) for x in buf]
        out = dict(zip(self._STATS_HEAD, vals))
        counts = vals[len(self._STATS_HEAD):]
        bounds = list(lat_bounds_us())[:len(counts) - 1]
        out["buckets"] = list(zip(bounds + [None], counts))
        return out

    def slow_requests(self) -> list:
        """Newest-first decoded slow-request ring (method, target,
        status, bytes, micros, unix_ms per entry)."""
        h = self._h
        if not h or not self._has_stats:
            return []
        buf = ctypes.create_string_buffer(1 << 16)
        n = self._lib.swhp_slow_ring(h, buf, len(buf))
        if n <= 0:
            return []
        try:
            return json.loads(buf.raw[:n].decode("utf-8", "replace"))
        except ValueError:
            return []

    # SW_PLANE_FSYNC_MODE values -> swhp_set_sync_mode codes
    _SYNC_MODES = {"off": 0, "group": 1, "always": 2}
    _SYNC_MODE_NAMES = {v: k for k, v in _SYNC_MODES.items()}

    def set_sync_mode(self, mode, batch_us: int, max_pending: int) -> bool:
        """Configure group-commit durability for subsequently-enabled
        write leases (live leases keep the mode they were enabled with —
        the volume server cycles leases to apply a change). mode is
        'off' | 'group' | 'always' (an unknown string falls back to
        'off' rather than refusing to serve)."""
        h = self._h
        if not h or not self._has_sync:
            return False
        code = self._SYNC_MODES.get(str(mode).strip().lower(), 0)
        return self._lib.swhp_set_sync_mode(
            h, code, max(0, int(batch_us)), max(1, int(max_pending))) == 0

    # field order of swhp_sync_stats's flat export, ahead of the buckets
    _SYNC_STATS_HEAD = ("mode", "batch_us", "max_pending", "batches",
                        "riders", "failures", "pending", "fsync_us_sum")

    def sync_stats(self) -> Optional[dict]:
        """Durability telemetry snapshot: config + batch/rider/failure
        counters, pending-queue depth, and the fsync µs histogram as
        ``(bound_us, count)`` pairs (trailing None = +Inf). The mode
        comes back as its knob string. None when the plane is stopped
        or the loaded library predates the durability ABI."""
        h = self._h
        if not h or not self._has_sync:
            return None
        n = int(self._lib.swhp_sync_stats_len())
        buf = (ctypes.c_uint64 * n)()
        if self._lib.swhp_sync_stats(h, buf, n) != n:
            return None
        vals = [int(x) for x in buf]
        out = dict(zip(self._SYNC_STATS_HEAD, vals))
        out["mode"] = self._SYNC_MODE_NAMES.get(out["mode"], "off")
        counts = vals[len(self._SYNC_STATS_HEAD):]
        bounds = list(lat_bounds_us())[:len(counts) - 1]
        out["buckets"] = list(zip(bounds + [None], counts))
        return out

    def set_stats_enabled(self, on: bool):
        h = self._h
        if h and self._has_stats:
            self._lib.swhp_set_stats_enabled(h, 1 if on else 0)

    def set_slow_us(self, us: int):
        """Runtime override of the SW_PLANE_SLOW_US ring threshold."""
        h = self._h
        if h and self._has_stats:
            self._lib.swhp_set_slow_us(h, max(0, int(us)))

    def stop(self):
        if self._h:
            self._lib.swhp_stop(self._h)
            self._h = None


class NativeWriter:
    """The write-lease handle a Volume holds while the native plane owns
    its .dat/.idx tails (volume.fast_writer). Implements the delegate
    surface storage/volume.py calls in writer mode: append (the one
    tail writer), lookup (the authoritative index), and the counter
    deltas the volume folds into its frozen needle-map counters."""

    __slots__ = ("_plane", "vid")

    def __init__(self, plane: "NativeReadPlane", vid: int):
        self._plane = plane
        self.vid = vid

    def append(self, blob: bytes, key: int, size_field: int,
               cookie: int = 0, check_cookie: bool = True) -> int:
        """Append one record; returns its .dat offset. size_field is
        the needle header Size (0xFFFFFFFF for tombstones). The
        overwrite/delete cookie is re-verified against the stored
        needle UNDER the append mutex — the Python-side pre-check
        races with concurrent fast-path POSTs."""
        from ..storage.volume import VolumeError
        h = self._plane._h
        if not h:
            raise OSError("native plane stopped")
        off = self._plane._lib.swhp_append(
            h, self.vid, blob, len(blob), key, size_field,
            1 if check_cookie else 0, cookie)
        if off == -2:
            raise VolumeError(
                f"volume {self.vid}: write exceeds the offset-width "
                f"addressing ceiling")
        if off == -4:
            raise VolumeError(
                f"needle {key}: mismatching cookie on overwrite")
        if off == -5:
            # durability lost (fsync poison / lease torn down
            # mid-batch): never acked, so the caller's retry through
            # the Python path is a harmless duplicate
            raise OSError(
                f"volume {self.vid}: group-commit batch poisoned — "
                f"durability of the append is unknown")
        if off < 0:
            raise OSError(
                f"native append failed on volume {self.vid} ({off})")
        return off

    def lookup(self, key: int):
        """(offset, size) from the plane's exact mirror, or None."""
        h = self._plane._h
        if not h:
            return None
        off = ctypes.c_uint64()
        size = ctypes.c_uint32()
        if self._plane._lib.swhp_lookup(h, self.vid, key,
                                        ctypes.byref(off),
                                        ctypes.byref(size)):
            return off.value, size.value
        return None

    def counters(self):
        """(puts, put_bytes, deletes, deleted_bytes, max_key, tail)."""
        h = self._plane._h
        if not h:
            return (0, 0, 0, 0, 0, 0)
        buf = (ctypes.c_uint64 * 6)()
        if self._plane._lib.swhp_writer_counters(h, self.vid, buf) != 0:
            return (0, 0, 0, 0, 0, 0)
        return tuple(int(x) for x in buf)

    def set_accept_posts(self, on: bool):
        h = self._plane._h
        if h:
            self._plane._lib.swhp_set_accept_posts(
                h, self.vid, 1 if on else 0)

    def release(self) -> int:
        """Hand the lease back (C++ mutex barrier; the group-commit
        committer drains its final batch first). Volume._demote_fast
        _writer calls this when an append came back ambiguous; the
        owning server's _writer_release does the same via the plane."""
        return self._plane.disable_writer(self.vid)
