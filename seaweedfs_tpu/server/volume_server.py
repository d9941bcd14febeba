"""VolumeServer — the data plane.

Reference weed/server/volume_server.go + handlers: public HTTP needle
read/write/delete with synchronous replica fan-out
(topology/store_replicate.go), heartbeat client loop
(volume_grpc_client_to_master.go), admin ops (allocate/delete/vacuum), and
the EC lifecycle + degraded read (store_ec.go): local shard -> remote
shard over HTTP -> reconstruct-on-read from >=10 sibling intervals.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..ec.constants import MAX_SHARDS, to_ext
from ..ops import telemetry
from ..util import malloc_policy, tracing
from ..util.locks import make_lock
from ..storage.needle import Needle
from ..storage.store import Store
from ..storage.types import parse_file_id
from ..storage.volume import NotFound, VolumeError, volume_file_prefix
from .http_util import (HttpError, HttpServer, Request, Response, Router,
                        get_json, http_call, post_json, profile_handler,
                        traces_export_handler, traces_handler)


def _tag_holder_read(bytes_read: int, bytes_sent: int):
    """On the server span of a holder-side survivor read (shard_read,
    shard_repair_read, shard_plane_read): the range read off the disk
    against what leaves the holder — the byte reduction of the trace
    and half-plane routes, per request — and ``bytes``, what it sent,
    under the one name every span that moves bytes carries: summed over
    the holders it is what a rebuilder's budget has to have let in."""
    span = tracing.current_span()
    if span is not None:
        span.tags["bytes_read"] = int(bytes_read)
        span.tags["bytes_sent"] = span.tags["bytes"] = int(bytes_sent)


# the holder's end of the streaming spread moves a run from the socket to
# its .part file through one buffer of this size (admin_ec_shard_write):
# a whole 8 MiB row of the default slab a piece (1 MiB pieces read 5 %
# less encode_mbps on the chip host, one run: PERF.md, PR 30)
SHARD_WRITE_PIECE = 8 << 20


def _count_shard_write(nbytes: int, pieces: int, wall_s: float,
                       recv_s: float, write_s: float, cpu_s: float):
    """One run of a holder's shard_write append, also when it ended
    short and was rolled back: onto the server span the bytes it moved
    from the socket to the stage file, in how many pieces of its buffer,
    the seconds inside the socket reads (``recv_s``) and inside
    ``os.write`` (``write_s``: the rest of the span is the interpreter
    between them) and the handler thread's CPU time (``cpu_s``); into
    ``ops/telemetry.STATS`` the same as the ``holder_*`` counters."""
    span = tracing.current_span()
    if span is not None:
        span.tags.update(bytes=int(nbytes), pieces=int(pieces),
                         recv_s=recv_s, write_s=write_s, cpu_s=cpu_s)
    telemetry.STATS.add_holder_run(nbytes, wall_s, recv_s, write_s, cpu_s)


class VolumeServer:
    def __init__(self, port: int = 8080, host: str = "127.0.0.1",
                 directories=None, master_url: str = "127.0.0.1:9333",
                 data_center: str = "", rack: str = "",
                 max_volume_counts=None, pulse_seconds: float = None,
                 public_url: str = "", read_redirect: bool = True,
                 ec_backend: str = "auto", jwt_signing_key: str = "",
                 whitelist=(), index_kind: str = "memory",
                 compaction_mbps: int = None, fast_port: int = 0,
                 file_size_limit_mb: int = 256):
        router = Router()
        router.add("*", "/status", self.status)
        router.add("POST", "/admin/assign_volume", self.admin_assign_volume)
        router.add("POST", "/admin/delete_volume", self.admin_delete_volume)
        router.add("POST", "/admin/volume/readonly", self.admin_readonly)
        router.add("POST", "/admin/volume/configure_replication",
                   self.admin_configure_replication)
        router.add("POST", "/admin/volume/mount", self.admin_volume_mount)
        router.add("POST", "/admin/volume/unmount",
                   self.admin_volume_unmount)
        router.add("POST", "/admin/vacuum/check", self.admin_vacuum_check)
        router.add("POST", "/admin/vacuum/compact", self.admin_vacuum_compact)
        router.add("POST", "/admin/vacuum/commit", self.admin_vacuum_commit)
        router.add("POST", "/admin/ec/generate", self.admin_ec_generate)
        router.add("POST", "/admin/ec/mount", self.admin_ec_mount)
        router.add("POST", "/admin/ec/unmount", self.admin_ec_unmount)
        router.add("POST", "/admin/ec/rebuild", self.admin_ec_rebuild)
        router.add("POST", "/admin/ec/copy", self.admin_ec_copy)
        router.add("POST", "/admin/ec/delete_shards",
                   self.admin_ec_delete_shards)
        router.add("POST", "/admin/ec/shard_write",
                   self.admin_ec_shard_write)
        router.add("POST", "/admin/volume/copy", self.admin_volume_copy)
        router.add("POST", "/admin/volume/verify", self.admin_volume_verify)
        router.add("POST", "/admin/ec/to_volume", self.admin_ec_to_volume)
        router.add("GET", "/admin/ec/shard_read", self.admin_ec_shard_read)
        router.add("POST", "/admin/ec/shard_repair_read",
                   self.admin_ec_shard_repair_read)
        router.add("POST", "/admin/ec/shard_plane_read",
                   self.admin_ec_shard_plane_read)
        router.add("POST", "/admin/ec/scrub", self.admin_ec_scrub)
        router.add("GET", "/admin/ec/scrub_status",
                   self.admin_ec_scrub_status)
        router.add("POST", "/admin/ec/scrub_repair",
                   self.admin_ec_scrub_repair)
        router.add("GET", "/admin/file", self.admin_file)
        router.add("POST", "/admin/volume/tier_upload",
                   self.admin_tier_upload)
        router.add("POST", "/admin/volume/tier_download",
                   self.admin_tier_download)
        router.add("GET", "/admin/volume/sync_status",
                   self.admin_volume_sync_status)
        router.add("GET", "/admin/volume/tail", self.admin_volume_tail)
        router.add("POST", "/admin/volume/tail_receive",
                   self.admin_volume_tail_receive)
        router.add("GET", "/metrics", self.metrics_handler)
        router.add("GET", "/admin/traces", traces_handler)
        router.add("GET", "/admin/traces/export", traces_export_handler)
        router.add("GET", "/admin/plane/slow", self.admin_plane_slow)
        router.add("GET", "/admin/plane/cache", self.admin_plane_cache)
        router.add("GET", "/admin/plane/durability",
                   self.admin_plane_durability)
        router.add("GET", "/admin/devices", self.admin_devices)
        router.add("POST", "/admin/profile", profile_handler)
        router.add("GET", "/stats/disk", self.stats_disk)
        router.add("GET", "/stats/memory", self.stats_memory)
        router.add("GET", "/ui", self.ui_handler)
        router.add("POST", "/query", self.query_handler)
        router.set_fallback(self.data_handler)
        router.before = self._guard_check
        from ..stats.metrics import (VOLUME_REQUEST_COUNTER,
                                     VOLUME_REQUEST_HISTOGRAM)

        def observe(label, seconds, ok):
            VOLUME_REQUEST_COUNTER.inc(label if ok else label + " error")
            # the router's server span is still current here, so the
            # bucket this lands in carries its trace id as an exemplar
            VOLUME_REQUEST_HISTOGRAM.observe(
                seconds, label, trace_id=tracing.current_trace_id())
        router.observe = observe

        self.server = HttpServer(port, router, host)
        self.port = self.server.port
        self.host = host
        router.node = f"{host}:{self.port}"
        # master_url may list several seed masters; heartbeats follow
        # the leader hint and rotate seeds on failure (reference
        # volume_grpc_client_to_master.go:25-55)
        self._seed_masters = [m.strip() for m in master_url.split(",")
                              if m.strip()]
        self.master_url = self._seed_masters[0]
        self._seed_i = 0
        from ..util import config as _config
        self.pulse_seconds = _config.env_float("SW_PULSE_S") \
            if pulse_seconds is None else pulse_seconds
        self.read_redirect = read_redirect
        # background copy throttle (reference -compactionMBps, "limit
        # background compaction or copying speed"): vacuum's copy loop
        # takes the rate, and everything this server pulls from another
        # in the background is charged to ONE budget of that rate —
        # every rebuild's survivor reads (the store's, whichever route
        # and however many streams at once), volume.copy and ec.copy
        # (upstream's doCopyFile, where its throttle sits). Reads a
        # client waits for (degraded GETs) and scrub, which paces
        # itself, are never charged. 0: no budget object at all
        self.compaction_bps = int(
            _config.env_int("SW_COMPACTION_MBPS")
            if compaction_mbps is None else compaction_mbps) << 20
        self.pull_budget = None
        if self.compaction_bps > 0:
            from ..util.throttler import ByteBudget
            self.pull_budget = ByteBudget(
                self.compaction_bps,
                on_charge=telemetry.STATS.add_throttle)
        self.store = Store(
            directories or ["./data"],
            max_volume_counts=max_volume_counts,
            ip=host, port=self.port,
            public_url=public_url or f"{host}:{self.port}",
            data_center=data_center, rack=rack,
            index_kind=index_kind, ec_backend=ec_backend,
            pull_budget=self.pull_budget)
        self.volume_size_limit = 30 * 1024 * 1024 * 1024
        # shard_write's piece buffers, kept between requests (a fresh
        # 8 MiB block is page faults on these hosts): a handler takes
        # one and puts it back, so there are as many as ever ran at once
        self._shard_write_bufs: List[memoryview] = []
        # upload size cap (reference -fileSizeLimitMB: "limit file size
        # to avoid out of memory"); 0 (or negative) disables
        self.file_size_limit = max(0, int(file_size_limit_mb)) << 20
        self.jwt_signing_key = jwt_signing_key
        from ..security.guard import Guard
        self.guard = Guard(whitelist)
        self._lookup_cache: Dict[int, tuple] = {}
        from ..client.vid_map import shared_vid_map
        self._vid_map = shared_vid_map(self.master_url)
        from ..ec.shard_cache import EcShardLocationCache
        self._ec_loc_cache = EcShardLocationCache(
            self._fetch_ec_shard_locations,
            geometry=self._mounted_ec_geometry)
        # batched degraded-read serving tier: reconstruct-on-read with
        # request coalescing, exactly-k survivor gather and a
        # reconstructed-slab LRU (ec/degraded.py)
        from ..ec.degraded import DegradedReadEngine
        from ..stats.metrics import DEGRADED_READ_HISTOGRAM
        self.degraded = DegradedReadEngine(
            store=self.store,
            locations=self._ec_shard_locations,
            codec=self.store.ec_volume_codec,
            loc_cache=self._ec_loc_cache,
            self_url=lambda: self.url,
            on_read=lambda s: DEGRADED_READ_HISTOGRAM.observe(
                s, trace_id=tracing.current_trace_id()),
            on_slabs=self._publish_slabs)
        # a shard (re-)registered after rebuild must win over cached
        # reconstructions immediately — in the engine's LRU AND in the
        # native plane's slab cache (_on_ec_mount re-syncs the plane's
        # shard set first, then invalidates both)
        self.store.on_ec_mount = self._on_ec_mount
        # background integrity scrub: paced H·x=0 syndrome verification
        # of every local EC volume, findings pushed to the master's
        # repair queue (ec/scrub.py)
        from ..ec.scrub import ScrubEngine
        self.scrub = ScrubEngine(
            store=self.store,
            locations=self._ec_shard_locations,
            codec=self.store.ec_volume_codec,
            self_url=lambda: self.url,
            on_finding=self._report_scrub_finding)
        self._stop = threading.Event()
        self._probing = False
        # immediate delta-push (reference store.go:40-64 change channels,
        # consumed by volume_grpc_client_to_master.go:57-185): volume
        # create/delete and EC shard mount/unmount wake the heartbeat
        # loop so the master learns within milliseconds, not a pulse.
        self._hb_wake = threading.Event()
        self.store.on_change = self._hb_wake.set
        # native read plane (reference: the Go data plane itself; here
        # a C++ thread-per-connection server on a second advertised
        # port, serving plain needle GETs without the GIL — anything
        # non-trivial 307s back to this Python server). Gated off when
        # read auth or TLS is configured: the plane speaks open HTTP.
        self.fast_plane = None
        from .http_util import tls_enabled
        if fast_port >= 0 and not whitelist and not tls_enabled():
            try:
                from .native_plane import NativeReadPlane
                self.fast_plane = NativeReadPlane(
                    host, fast_port,
                    public_url or f"{host}:{self.port}")
                for loc in self.store.locations:
                    for v in loc.volumes.values():
                        with v.lock:
                            self.fast_plane.register_volume(v)
                            self._writer_acquire(v)
                for loc in self.store.locations:
                    for vid in list(loc.ec_volumes):
                        self._fast_ec_sync(vid)
            except Exception as e:  # noqa: BLE001 - plane is optional
                from ..util import config as _config
                if _config.env_is_set("SW_HTTP_PLANE_LIB"):
                    raise   # explicit lib override must fail loudly
                from ..util import glog
                glog.V(0).infof("native read plane unavailable: %s", e)
                self.fast_plane = None
        # delta-heartbeat state: last volume set acked, and by whom
        self._hb_lock = make_lock("volume_server.heartbeat")
        # orders this server's heartbeats at the master; the clock's
        # nanoseconds at start, so a restarted server's come after
        self._hb_seq = time.time_ns()
        self._hb_acked_master = None
        self._hb_acked_volumes = None
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           daemon=True,
                                           name="volume-heartbeat")

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        # the EC streams this server runs live on recycled buffers
        malloc_policy.keep_freed_memory()
        # one probe of the interpreter lock a process, however many
        # servers it holds: the last stop() ends it
        tracing.start_lock_probe(telemetry.STATS.add_probe_sample)
        self._probing = True
        self.server.start()
        try:
            self.heartbeat_once()
        except HttpError as e:
            # no master reachable yet — serve anyway; the heartbeat
            # loop keeps retrying (reference volume servers outlive
            # master outages the same way)
            from ..util import glog
            glog.V(0).infof("initial heartbeat failed: %s", e)
        self._hb_thread.start()
        self.scrub.start()
        return self

    def stop(self):
        self._stop.set()
        self._hb_wake.set()
        self.scrub.stop()
        try:
            # clean shutdown: tell the master now so watch subscribers
            # reroute immediately instead of after heartbeat expiry
            post_json(f"http://{self.master_url}/cluster/goodbye",
                      {"url": self.url}, timeout=2)
        except Exception:  # noqa: BLE001 - master may already be gone
            pass
        if self.fast_plane is not None:
            self.fast_plane.stop()
        push = getattr(self, "_metrics_push", None)
        if push is not None:
            push.stop_event.set()
        self.server.stop()
        self.store.close()
        if self._probing:
            self._probing = False
            tracing.stop_lock_probe()

    @property
    def url(self) -> str:
        return f"{self.host}:{self.port}"

    @property
    def fast_url(self) -> str:
        return f"{self.host}:{self.fast_plane.port}" \
            if self.fast_plane else ""

    # -- native-plane index mirror + write lease ---------------------------
    def _writer_acquire(self, v):
        """Hand the volume's write lease to the native plane (caller
        holds v.lock; the mirror must have just been registered from
        the CURRENT needle map). Only volumes whose plain-POST shape
        the plane can serve exactly get a lease: unreplicated,
        un-TTL'd, v2/v3, no JWT — everything else keeps the round-3
        Python write path with best-effort mirror updates."""
        if self.fast_plane is None or v.fast_writer is not None:
            return
        if v.readonly or v.version < 2 or self.jwt_signing_key or \
                v.super_block.ttl.to_uint32() or \
                v.super_block.replica_placement.copy_count != 1:
            return
        v.fast_writer = self.fast_plane.enable_writer(
            v, self.file_size_limit, accept_posts=True)

    def _writer_release(self, v, reload: bool = True):
        """Take the write lease back. The C++ disable is a mutex
        barrier — after it returns no native append is in flight — so
        the needle map can be reloaded from the .idx the plane kept
        authoritative and Python-owned appends can resume."""
        if self.fast_plane is None:
            return
        with v.lock:
            if v.fast_writer is None:
                return
            v.fast_writer = None
            self.fast_plane.disable_writer(v.id)
            if reload:
                v.reload_nm()

    def _fast_put(self, vid: int, nid: int):
        if self.fast_plane is None:
            return
        v = self.store.find_volume(vid)
        if v is None or v.fast_writer is not None:
            # in writer mode the append already updated the mirror
            return
        nv = v.nm.get(nid)
        if nv is not None:
            self.fast_plane.put(vid, nid, nv.offset, nv.size)

    def _fast_delete(self, vid: int, nid: int):
        if self.fast_plane is None:
            return
        v = self.store.find_volume(vid)
        if v is not None and v.fast_writer is not None:
            return
        self.fast_plane.delete(vid, nid)

    def _fast_sync(self, vid: int):
        """Re-register a volume after a structural change (create,
        mount, compaction commit, copy, tail-receive, EC decode,
        readonly/replication toggle) or unregister it when it's gone.
        Re-establishes the write lease when the volume qualifies."""
        if self.fast_plane is None:
            return
        v = self.store.find_volume(vid)
        if v is None:
            self.fast_plane.unregister_volume(vid)
            return
        with v.lock:
            self._writer_release(v)  # reloads nm if a lease was out
            self.fast_plane.register_volume(v)
            self._writer_acquire(v)

    def _fast_unregister(self, vid: int):
        if self.fast_plane is None:
            return
        v = self.store.find_volume(vid)
        if v is not None:
            self._writer_release(v)
        self.fast_plane.unregister_volume(vid)

    # -- native-plane EC mirror + slab cache -------------------------------
    def _fast_ec_sync(self, vid: int):
        """Re-register an EC volume's geometry, local shard set and
        .ecx mirror in the plane (or unregister it when it's gone).
        Runs after every mount/unmount/rebuild: the plane must learn a
        rebuilt shard is local BEFORE the stale cached slabs for it are
        invalidated, or a read in the window would re-miss to Python."""
        if self.fast_plane is None:
            return
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            self.fast_plane.unregister_ec_volume(vid)
            return
        try:
            self.fast_plane.register_ec_volume(ev, self.degraded.slab)
        except Exception:  # noqa: BLE001 - mirror is optional
            self.fast_plane.unregister_ec_volume(vid)

    def _fast_ec_delete(self, vid: int, nid: int):
        if self.fast_plane is not None:
            self.fast_plane.ec_delete(vid, nid)

    def _publish_slabs(self, vid: int, sid: int, slabs: dict):
        """DegradedReadEngine on_slabs hook: push freshly reconstructed
        slabs into the plane cache so the next read of these bytes is
        served in-plane with zero redirects."""
        if self.fast_plane is None:
            return
        for idx, data in slabs.items():
            self.fast_plane.cache_put(vid, sid, int(idx), data)

    def _invalidate_reconstructions(self, vid: int, shard_ids):
        """Drop every cached reconstruction of these shards — the
        plane's slab cache AND the engine's LRU — after a mount or
        rebuild made them stale. Ordering matters: re-sync the plane's
        shard set FIRST, then drop the plane's slabs, then the
        engine's. A reader in the window sees either fresh local bytes
        or a miss (redirect to Python, which reconstructs from the
        fresh shards), never stale data."""
        self._fast_ec_sync(vid)
        if self.fast_plane is not None:
            for sid in shard_ids:
                self.fast_plane.cache_invalidate(vid, sid)
        self.degraded.invalidate(vid, shard_ids)

    def _on_ec_mount(self, vid: int, shard_ids):
        """store.on_ec_mount: a (re-)mounted shard must win over every
        cached reconstruction immediately."""
        self._invalidate_reconstructions(vid, shard_ids)

    def _heartbeat_loop(self):
        from ..util import glog
        while True:
            self._hb_wake.wait(self.pulse_seconds)
            self._hb_wake.clear()
            if self._stop.is_set():
                return
            try:
                self.heartbeat_once()
                glog.V(4).infof("heartbeat to %s ok", self.master_url)
            except HttpError as e:
                # heartbeat_once already rotated through every seed
                glog.V(0).infof("no master reachable: %s", e)

    def _heartbeat_payload(self, hb: dict, target: str) -> dict:
        """Full heartbeat, or a volume DELTA against the state the
        target master last acknowledged (reference incremental
        heartbeats, master_grpc_server.go:94-152): unchanged volumes
        stay home, only new/changed/deleted ride the wire."""
        if target != self._hb_acked_master or self._hb_acked_volumes is None:
            return hb
        current = {v["id"]: v for v in hb["volumes"]}
        previous = self._hb_acked_volumes
        delta = dict(hb)
        del delta["volumes"]
        delta["delta"] = True
        delta["new_volumes"] = [v for vid, v in current.items()
                                if previous.get(vid) != v]
        delta["deleted_volumes"] = [vid for vid in previous
                                    if vid not in current]
        return delta

    def _post_heartbeat(self, hb: dict, target: str) -> dict:
        resp = post_json(f"http://{target}/cluster/heartbeat",
                         self._heartbeat_payload(hb, target), timeout=10)
        if resp.get("resync"):
            # the master lost (or never had) our registration: replay
            # the full state immediately
            resp = post_json(f"http://{target}/cluster/heartbeat", hb,
                             timeout=10)
        if not resp.get("not_leader") and not resp.get("stale"):
            # (a state the master dropped as overtaken acknowledges
            # nothing: the newer one it holds set these)
            self._hb_acked_master = target
            self._hb_acked_volumes = {v["id"]: v for v in hb["volumes"]}
        return resp

    def heartbeat_once(self):
        """Heartbeat the current master, trying every seed before
        giving up — startup must not die because the first listed seed
        happens to be the down one. The pulse thread and the handlers
        that push a change (mount, unmount, delete_shards) all come
        through here, side by side: each collected state takes the next
        ``seq`` under ``_hb_lock`` (held for the collect only, never for
        the POST), and the master drops a state older than the one it
        last applied from this server. So a state collected before a
        change cannot overwrite the one collected behind it (the master
        would take dropped shards for held again until the next pulse,
        and an `ec.rebuild` typed then finds nothing lost)."""
        with self._hb_lock:
            hb = self.store.collect_heartbeat()
            self._hb_seq += 1
            hb["seq"] = self._hb_seq
        if self.fast_plane is not None:
            hb["fast_url"] = self.fast_url
        last = None
        for _ in range(len(self._seed_masters)):
            try:
                resp = self._post_heartbeat(hb, self.master_url)
                break
            except HttpError as e:
                last = e
                self._seed_i = (self._seed_i + 1) % \
                    len(self._seed_masters)
                self.master_url = self._seed_masters[self._seed_i]
        else:
            raise last
        if resp.get("volume_size_limit"):
            self.volume_size_limit = resp["volume_size_limit"]
        self._maybe_start_metrics_push(resp)
        # follow the leader hint: a follower master does not register
        # us, so re-send the heartbeat there right away
        leader = resp.get("leader")
        if leader and leader != self.master_url:
            self.master_url = leader
            if resp.get("not_leader"):
                resp = self._post_heartbeat(hb, self.master_url)
                if resp.get("volume_size_limit"):
                    self.volume_size_limit = resp["volume_size_limit"]

    def _maybe_start_metrics_push(self, resp: dict):
        """The master broadcasts the push-gateway address and interval
        in heartbeat responses (reference LoopPushingMetric,
        metrics.go:109-137 + master_grpc_server.go:75-77); start one
        push loop when it first appears."""
        addr = resp.get("metrics_address")
        if not addr or getattr(self, "_metrics_push", None) is not None:
            return
        from ..stats.metrics import VOLUME_SERVER_GATHER, start_push_loop
        if "://" not in addr:   # the master broadcasts a bare host:port
            addr = "http://" + addr
        self._metrics_push = start_push_loop(
            VOLUME_SERVER_GATHER, addr,
            job=f"volume_{self.host}_{self.port}",
            interval_s=max(1.0, float(
                resp.get("metrics_interval_seconds", 15) or 15)))

    # -- admin -------------------------------------------------------------
    def stats_disk(self, req: Request):
        """Per-directory disk usage (reference statsDiskHandler,
        volume_server.go:83)."""
        import shutil
        out = []
        for loc in self.store.locations:
            try:
                u = shutil.disk_usage(loc.directory)
                out.append({"dir": loc.directory, "all": u.total,
                            "used": u.used, "free": u.free})
            except OSError as e:
                out.append({"dir": loc.directory, "error": str(e)})
        return {"DiskStatuses": out}

    def stats_memory(self, req: Request):
        from .http_util import process_memory_stats
        return process_memory_stats()

    def status(self, req: Request):
        out = self.store.status()
        out["ec_degraded"] = self.degraded.snapshot()
        out["ec_scrub"] = self.scrub.snapshot()
        if self.fast_plane is not None:
            out["fast_plane"] = {
                "url": self.fast_url,
                "served": self.fast_plane.served,
                "redirected": self.fast_plane.redirected,
            }
        return out

    def query_handler(self, req: Request):
        """S3-Select-ish query over JSON needles (reference Query RPC,
        volume_grpc_query.go:12 + query/json/query_json.go:17). Body:
        {"fids": [...], "sql": "SELECT ... WHERE ..."}; rows stream
        back as JSON lines."""
        import json as _json
        from ..query import QueryError, query_json_lines
        body = _json.loads(req.body or b"{}")
        sql = body.get("sql", "")
        fids = body.get("fids", [])
        if not sql or not fids:
            raise HttpError(400, "need sql and fids")
        limit = int(body.get("limit", 0))
        rows: List[dict] = []
        for fid in fids:
            try:
                vid, key, cookie = parse_file_id(fid)
            except ValueError:
                raise HttpError(400, f"bad fid {fid!r}")
            got = self._read_needle_local(vid, key, cookie, fid)
            try:
                rows.extend(query_json_lines(
                    got.data, sql,
                    limit=(limit - len(rows)) if limit else 0))
            except QueryError as e:
                raise HttpError(400, str(e))
            if limit and len(rows) >= limit:
                break
        out = "\n".join(_json.dumps(r, separators=(",", ":"))
                        for r in rows)
        return Response((out + "\n").encode() if out else b"",
                        content_type="application/jsonl")

    def _read_needle_local(self, vid: int, key: int, cookie: int,
                           fid: str) -> Needle:
        """Needle from a local normal OR ec volume (the query path must
        keep working after ec.encode, like the public read path)."""
        v = self.store.find_volume(vid)
        if v is not None:
            try:
                return self.store.read_needle(
                    vid, Needle(cookie=cookie, id=key))
            except NotFound:
                raise HttpError(404, f"{fid} not found")
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            raise HttpError(404, f"volume {vid} not local")
        from ..ec.ec_volume import EcShardNotFound
        try:
            blob = ev.read_needle_blob(
                key,
                remote_fetch=self._read_shard_from_holders,
                reconstruct_fetch=self._reconstruct_shard_range)
        except KeyError:
            raise HttpError(404, f"{fid} not found") from None
        except EcShardNotFound as e:
            raise HttpError(503, f"ec volume {vid}: {e}") from None
        got = Needle.from_bytes(blob, ev.version)
        if got.id != key:
            # the blob parsed as a VALID needle but not the requested
            # one: the interval assembly went to the wrong place —
            # surface it, never serve another needle's bytes (cookies
            # alone don't disambiguate; they can collide)
            raise HttpError(
                500, f"ec read of {fid} assembled needle {got.id:x}")
        if got.cookie != cookie:
            raise HttpError(404, "cookie mismatch")
        return got

    def ui_handler(self, req: Request):
        """HTML status dashboard (reference volume_server_ui/)."""
        from .status_ui import volume_status_page
        return Response(volume_status_page(self),
                        content_type="text/html; charset=utf-8")

    def metrics_handler(self, req: Request):
        """Prometheus text exposition; volume/disk gauges refresh from
        the store on scrape (the reference sets them during heartbeat
        collection, store.go:232)."""
        from ..stats.metrics import (FAST_PLANE_COUNTER,
                                     VOLUME_COUNT_GAUGE,
                                     VOLUME_DISK_GAUGE,
                                     VOLUME_SERVER_GATHER)
        # aggregate across ALL locations before setting, and zero out
        # series for collections that disappeared so a scrape never
        # shows one directory's numbers or a stale collection
        by_coll: Dict[str, list] = {}
        ec_by_coll: Dict[str, int] = {}
        for loc in self.store.locations:
            for v in loc.volumes.values():
                agg = by_coll.setdefault(v.collection, [0, 0])
                agg[0] += 1
                agg[1] += v.size()
            for ev in loc.ec_volumes.values():
                ec_by_coll[ev.collection] = \
                    ec_by_coll.get(ev.collection, 0) + len(ev.shards)
        seen_count, seen_disk = set(), set()
        for coll, (count, size) in by_coll.items():
            VOLUME_COUNT_GAUGE.set(count, coll, "normal")
            VOLUME_DISK_GAUGE.set(size, coll, "normal")
            seen_count.add((coll, "normal"))
            seen_disk.add((coll, "normal"))
        for coll, count in ec_by_coll.items():
            VOLUME_COUNT_GAUGE.set(count, coll, "ec")
            seen_count.add((coll, "ec"))
        # zero each gauge's own vanished series — never mint a series
        # in a gauge that never carried it
        for stale in getattr(self, "_count_series", set()) - seen_count:
            VOLUME_COUNT_GAUGE.set(0, *stale)
        for stale in getattr(self, "_disk_series", set()) - seen_disk:
            VOLUME_DISK_GAUGE.set(0, *stale)
        self._count_series = seen_count
        self._disk_series = seen_disk
        if self.fast_plane is not None:
            FAST_PLANE_COUNTER.set_total(self.fast_plane.served, "served")
            FAST_PLANE_COUNTER.set_total(self.fast_plane.redirected,
                                         "redirected")
            FAST_PLANE_COUNTER.set_total(self.fast_plane.written,
                                         "written")
        # native-plane telemetry (in-plane counters + latency buckets,
        # mirrored so /cluster/metrics sums them fleet-wide)
        from . import native_plane as _np
        from ..stats.metrics import observe_plane
        if self.fast_plane is not None:
            observe_plane(self.fast_plane.stats(),
                          len(self.fast_plane.slow_requests()),
                          _np.build_failed())
        else:
            observe_plane(None, 0, _np.build_failed())
        # in-plane degraded serving + slab-cache counters (same mirror
        # pattern; None when the plane is off or predates the cache ABI)
        from ..stats.metrics import observe_plane_cache
        observe_plane_cache(self.fast_plane.cache_stats()
                            if self.fast_plane is not None else None)
        # group-commit durability counters (same mirror pattern; None
        # when the plane is off or predates the durability ABI)
        from ..stats.metrics import observe_plane_sync
        observe_plane_sync(self.fast_plane.sync_stats()
                           if self.fast_plane is not None else None)
        # device-codec telemetry (process-global monotonic counters)
        # mirrors onto the scrape so dispatches / bitmat uploads / host
        # fallbacks are visible without running a rebuild through bench
        from ..stats.metrics import (DEVICE_TELEMETRY_COUNTER,
                                     HTTP_POOL_CHURN_COUNTER)
        for kind, total in telemetry.STATS.snapshot().items():
            # the per-device mesh byte map exports via its own labeled
            # family (observe_mesh), not the flat kind counter
            if isinstance(total, (int, float)):
                DEVICE_TELEMETRY_COUNTER.set_total(total, kind)
            elif kind in ("repair_route", "geometry_dispatches",
                          "throttle"):
                # repair_route.full, geometry_dispatches.6+3,
                # throttle.bytes, ...
                for name, n in total.items():
                    DEVICE_TELEMETRY_COUNTER.set_total(
                        n, f"{kind}.{name}")
        # connection-pool churn (process-global, same mirror pattern)
        from .http_util import pool_stats_snapshot
        for event, total in pool_stats_snapshot().items():
            HTTP_POOL_CHURN_COUNTER.set_total(total, event)
        # device-runtime plane: compile/recompile accounting,
        # const-cache + jit-factory occupancy. The
        # inventory is only exported once a backend is initialized —
        # a scrape must never be the thing that boots a backend.
        from ..ops import device_stats as _ds
        from ..stats.metrics import observe_device_stats
        observe_device_stats(_ds.DEVICE_STATS.snapshot(),
                             _ds.jit_factory_snapshot(),
                             _ds.device_inventory())
        # EC plan caches (repair/piggyback schemes, process-global
        # LRUs in ops/codec) — same monotonic mirror pattern
        from ..stats.metrics import observe_plan_cache
        observe_plan_cache()
        # degraded-read engine counters (engine-global, same mirror
        # pattern; the per-read latency histogram streams in live via
        # the engine's on_read hook)
        from ..stats.metrics import observe_degraded, observe_scrub
        observe_degraded(self.degraded.snapshot())
        # integrity-scrub engine counters (same mirror pattern)
        observe_scrub(self.scrub.snapshot())
        # per-holder health scoreboard (process-global EWMAs fed by the
        # gather/repair/degraded readers) — fresh scores on every scrape
        # so the master's aggregator and /cluster/health see them
        from ..stats.health import export_board
        export_board()
        return Response(VOLUME_SERVER_GATHER.render().encode(),
                        content_type="text/plain; version=0.0.4")

    def admin_plane_slow(self, req: Request):
        """Newest-first contents of the native plane's slow-request ring
        (requests that took >= SW_PLANE_SLOW_US, bounded at 64 entries)
        plus the stats snapshot the ring indexes into."""
        if self.fast_plane is None:
            return {"plane": False, "slow": []}
        return {"plane": True,
                "slow": self.fast_plane.slow_requests(),
                "stats": self.fast_plane.stats()}

    def admin_devices(self, req: Request):
        """Device-runtime snapshot (ops/device_stats): per-entry-point
        compile/recompile/dispatch counters with the latched recompile
        sentinel, jit-factory cache_info,
        const-cache occupancy, and the device inventory incl.
        memory_stats(). Never boots a backend itself: a process whose
        codecs have not initialised one answers
        inventory.initialized=false (the chip belongs to one process; a
        status question must not grab it)."""
        from ..ops import device_stats as _ds
        out = _ds.admin_snapshot()
        # the store's own chip among the inventory's (`tpu-own`), None
        # where the backend names none
        out["own"] = self.store.device()
        return out

    def admin_plane_cache(self, req: Request):
        """Native-plane reconstructed-slab cache counters + EC serving
        outcomes (swhp_cache_stats), for the degraded fast-path debug
        loop: did the read hit the plane cache or redirect to Python?"""
        if self.fast_plane is None:
            return {"plane": False, "cache": None}
        return {"plane": True, "cache": self.fast_plane.cache_stats()}

    def admin_plane_durability(self, req: Request):
        """Group-commit durability config + telemetry (swhp_sync_stats):
        mode/window/rider-cap, batches vs riders (the amortization
        ratio), fsync µs histogram, pending-queue depth, and failures —
        a failure means a batch poisoned and its writer fail-stopped."""
        if self.fast_plane is None:
            return {"plane": False, "durability": None}
        return {"plane": True,
                "durability": self.fast_plane.sync_stats()}

    def admin_assign_volume(self, req: Request):
        vid = int(req.query["volume"])
        self.store.add_volume(vid, req.query.get("collection", ""),
                              req.query.get("replication", "000"),
                              req.query.get("ttl", ""))
        self._fast_sync(vid)
        self.heartbeat_once()
        return {"volume": vid}

    def admin_delete_volume(self, req: Request):
        vid = int(req.query["volume"])
        # plane offline BEFORE the unlink: a fast-path POST landing in
        # the gap would append to a deleted inode and ack a lost write
        self._fast_unregister(vid)
        if not self.store.delete_volume(vid):
            self._fast_sync(vid)   # nothing deleted; resume serving
            raise HttpError(404, f"volume {vid} not found")
        self._lookup_cache.pop(vid, None)
        self.heartbeat_once()
        return {"deleted": vid}

    def admin_readonly(self, req: Request):
        vid = int(req.query["volume"])
        readonly = req.query.get("readonly", "true") == "true"
        was = self.store.mark_volume_readonly(vid, readonly)
        if was is None:
            raise HttpError(404, f"volume {vid} not found")
        # was_readonly lets orchestrators (volume.copy/move/tier.upload
        # freeze) restore exactly the prior state instead of trusting
        # the master's heartbeat-delayed view
        if was != readonly:
            # the write lease follows writability: frozen volumes hand
            # it back (EC encode reads the .idx next), thawed ones may
            # re-qualify
            self._fast_sync(vid)
        return {"volume": vid, "readonly": readonly,
                "was_readonly": was}

    def admin_configure_replication(self, req: Request):
        """Rewrite a volume's replica placement in its superblock
        (reference volume_grpc_admin.go VolumeConfigure)."""
        from ..storage.types import ReplicaPlacement
        vid = int(req.query["volume"])
        try:
            rp = ReplicaPlacement.parse(req.query.get("replication", ""))
        except (ValueError, KeyError) as e:
            raise HttpError(400, f"bad replication: {e}") from None
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        from ..storage.backend import BackendError
        try:
            v.configure_replication(rp)
        except (VolumeError, BackendError) as e:
            raise HttpError(409, str(e)) from None
        # the lease's no-replica qualification may have flipped
        self._fast_sync(vid)
        return {"volume": vid, "replication": str(rp)}

    def admin_volume_mount(self, req: Request):
        """Load an on-disk volume into serving (reference
        volume_grpc_admin.go VolumeMount)."""
        vid = int(req.query["volume"])
        if self.store.find_volume(vid) is not None:
            return {"volume": vid, "mounted": False}  # already serving
        for loc in self.store.locations:
            if loc.load_volume(vid) is not None:
                self._fast_sync(vid)
                self.heartbeat_once()
                return {"volume": vid, "mounted": True}
        raise HttpError(404, f"volume {vid} files not found")

    def admin_volume_unmount(self, req: Request):
        """Stop serving a volume without deleting its files (reference
        VolumeUnmount)."""
        vid = int(req.query["volume"])
        if self.store.find_volume(vid) is not None:
            # plane offline BEFORE the unload: the fast path must not
            # keep acking writes to an officially unmounted volume
            self._fast_unregister(vid)
        for loc in self.store.locations:
            if loc.unload_volume(vid):
                self.heartbeat_once()
                return {"volume": vid, "unmounted": True}
        self._fast_sync(vid)   # nothing unloaded; resume serving
        raise HttpError(404, f"volume {vid} not mounted")

    def admin_vacuum_check(self, req: Request):
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        return {"volume": vid, "garbage": v.garbage_level()}

    def admin_vacuum_compact(self, req: Request):
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        # per-request override, else the server's configured rate
        bps = int(req.query.get("bytesPerSecond",
                                self.compaction_bps) or 0)
        # hand the write lease back first: compact() snapshots the
        # needle map, which is frozen while the native plane owns the
        # tail — the release reloads it from the authoritative .idx.
        # Writes during the copy go through the (slower) Python path
        # and are replayed by commit's makeup diff.
        self._writer_release(v)
        v.compact(bytes_per_second=bps)
        return {"volume": vid, "compacted": True}

    def admin_vacuum_commit(self, req: Request):
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        # the commit swaps .dat/.idx under the volume: take the plane
        # offline for this vid first so it can't serve old offsets
        # against the new file, then re-sync from the fresh needle map
        self._fast_unregister(vid)
        v.commit_compact()
        self._fast_sync(vid)
        return {"volume": vid, "committed": True}

    # -- EC admin (reference volume_grpc_erasure_coding.go) ----------------
    def admin_ec_generate(self, req: Request):
        """Encode a readonly volume into shard files. Query-only = the
        legacy local flow (all k+m shards land on this disk). When the
        POST body carries ``assignment`` ({shard: holder url}), the
        streaming encode+spread runs instead: each shard's slab ranges
        are pushed to its holder while later slabs encode, and shards
        bound for remote holders never touch this disk."""
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        # `ec.encode -geometry k,m`: the new volume's RS code; absent,
        # the default (10 + 4)
        geometry = req.query.get("geometry") or None
        try:
            body = req.json()
        except ValueError:
            raise HttpError(400, "bad JSON body") from None
        if isinstance(body, dict) and body.get("assignment"):
            from ..stats.metrics import observe_mesh, observe_spread
            from ..util import tracing
            stats: dict = {}
            base, final = self.store.generate_ec_shards_streaming(
                vid, collection,
                assignment={int(s): u
                            for s, u in body["assignment"].items()},
                spares=body.get("spares") or [],
                window=int(body.get("window") or 0) or None,
                stats=stats,
                rate_mbps=float(body.get("rate_mbps") or 0.0),
                geometry=geometry)
            observe_spread(stats)
            observe_mesh(stats)
            return {"volume": vid, "base": os.path.basename(base),
                    "assignment": {str(s): u for s, u in final.items()},
                    "stats": stats,
                    "trace_id": tracing.current_trace_id()}
        base = self.store.generate_ec_shards(vid, collection,
                                             geometry=geometry)
        return {"volume": vid, "base": os.path.basename(base)}

    def _ec_stage_base(self, vid: int, collection: str) -> str:
        """Base path for incoming shard stages: the location already
        holding this volume's EC files if any (staged ranges, finalized
        shards and the later sidecar copy must all land at ONE base or
        the mount won't see them), else a free location."""
        # a stage arrives before the volume's .vif: any shard id
        exts = [to_ext(s) for s in range(MAX_SHARDS)] + [".ecx"]
        for loc in self.store.locations:
            base = volume_file_prefix(loc.directory, collection, vid)
            if any(os.path.exists(base + e) or
                   os.path.exists(base + e + ".part") for e in exts):
                return base
        loc = self.store.find_free_location()
        if loc is None:
            raise HttpError(507, "no free disk location")
        return volume_file_prefix(loc.directory, collection, vid)

    def admin_ec_shard_write(self, req: Request):
        """Receive one shard's ranges from a streaming encode+spread
        (ec/spread.py): each POST appends one run at the expected offset
        into ``<shard>.part`` (409 carries the staged size on a
        mismatch, so a sender that lost an ack can tell delivered from
        diverged). The body streams: socket -> a reused buffer -> the
        file, a piece at a time (``Request.body_pieces``, either
        framing), so no run is ever held or joined and the write of one
        piece overlaps the arrival of the next. A run is appended whole
        or not at all — a body that ends short truncates the stage back
        to the request's offset before the error goes out — and is
        acknowledged only after its last byte is written to the file.
        ``action=finalize&size=`` verifies the stage and atomically
        renames it into place; ``action=abort`` drops the stages —
        failures never leave partial shard files."""
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        action = req.query.get("action", "append")
        if action == "abort":
            req.drain()
            removed = []
            for loc in self.store.locations:
                base = volume_file_prefix(loc.directory, collection, vid)
                for sid in range(MAX_SHARDS):
                    p = base + to_ext(sid) + ".part"
                    if os.path.exists(p):
                        os.remove(p)
                        removed.append(sid)
            return {"volume": vid, "aborted": removed}
        sid = int(req.query["shard"])
        base = self._ec_stage_base(vid, collection)
        part = base + to_ext(sid) + ".part"
        if action == "finalize":
            req.drain()
            size = int(req.query["size"])
            if not os.path.exists(part):
                raise HttpError(404, f"no staged shard {sid} for "
                                     f"volume {vid}")
            staged = os.path.getsize(part)
            if staged != size:
                raise HttpError(409, f"shard {sid} staged={staged} "
                                     f"expected={size}")
            os.replace(part, base + to_ext(sid))
            return {"volume": vid, "shard": sid, "size": size,
                    "finalized": True}
        off = int(req.query.get("offset", "0"))
        staged = os.path.getsize(part) if os.path.exists(part) else 0
        try:
            buf = self._shard_write_bufs.pop()
        except IndexError:
            buf = memoryview(bytearray(SHARD_WRITE_PIECE))
        try:
            if off != staged and off != 0:
                # consume the (window-bounded) body so the sender can
                # read this response off a cleanly framed connection —
                # a sender that lost an ack needs the staged size to
                # tell delivered from diverged
                for _ in req.body_pieces(buf):
                    pass
                raise HttpError(409, f"shard {sid} offset mismatch: "
                                     f"staged={staged} offset={off}")
            # offset 0 truncates: a replayed first range (failover to
            # this node, or a retry whose original died mid-body)
            # starts clean
            fd = os.open(part, os.O_WRONLY | os.O_CREAT | os.O_APPEND
                         | (os.O_TRUNC if off == 0 else 0), 0o644)
            nbytes = pieces = 0
            recv_s = write_s = 0.0
            body = req.body_pieces(buf)
            # where the run blocks: a clock pair around each fill of
            # the buffer from the socket and each write of it to the
            # stage; what they leave of the run is the interpreter
            clock = time.perf_counter
            cpu_s = time.thread_time()
            t_run = clock()
            try:
                while True:
                    t = clock()
                    piece = next(body, None)
                    recv_s += clock() - t
                    if piece is None:
                        break
                    nbytes += len(piece)
                    pieces += 1
                    t = clock()
                    while piece:
                        piece = piece[os.write(fd, piece):]
                    write_s += clock() - t
            except BaseException:
                os.ftruncate(fd, off)   # whole or not at all
                raise
            finally:
                wall_s = clock() - t_run
                os.close(fd)
                _count_shard_write(nbytes, pieces, wall_s, recv_s,
                                   write_s, time.thread_time() - cpu_s)
        finally:
            self._shard_write_bufs.append(buf)
        return {"volume": vid, "shard": sid, "staged": off + nbytes}

    def admin_ec_mount(self, req: Request):
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        shard_ids = [int(s) for s in req.query.get("shards", "").split(",")
                     if s != ""]
        mounted = self.store.mount_ec_shards(vid, collection, shard_ids)
        if not mounted and shard_ids:
            # distinguish "already mounted" from "files not found" so a
            # wrong/omitted collection fails loudly instead of no-opping
            ev = self.store.find_ec_volume(vid)
            if ev is None or not set(shard_ids) & set(ev.shards):
                raise HttpError(
                    404, f"no shard files for volume {vid} "
                         f"collection={collection!r} here")
        self.heartbeat_once()
        return {"volume": vid, "mounted": mounted}

    def admin_ec_unmount(self, req: Request):
        vid = int(req.query["volume"])
        shard_ids = [int(s) for s in req.query.get("shards", "").split(",")
                     if s != ""]
        out = self.store.unmount_ec_shards(vid, shard_ids)
        self._fast_ec_sync(vid)  # the plane must stop preading those fds
        self.heartbeat_once()
        return {"volume": vid, "unmounted": out}

    def admin_ec_rebuild(self, req: Request):
        """The streaming striped gather: the POST body's ``sources``
        ({shard: [holders]}) names the survivors this server does not
        hold; their ranges are pulled and decoded in overlapped slabs,
        never landing whole on disk. The query-only form (no
        ``sources``) is the same rebuild with every survivor a local
        file, and always the full decode (``repair`` full).
        ``target`` in the body names the server that keeps the rebuilt
        shards where that is another one: this server decodes on its
        chip and the rows go to the target's ``/admin/ec/shard_write``
        (the flat full gather only; the caller then has the target pull
        the sidecars and mount). The reply's stats name it
        (``delivered_to``), and the reply the chip that decoded
        (``device``: the store's own, "" where the backend names none)."""
        from ..stats.metrics import (observe_gather, observe_mesh,
                                     observe_repair)
        from ..util import tracing
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        try:
            body = req.json()
        except ValueError:
            raise HttpError(400, "bad JSON body") from None
        body = body if isinstance(body, dict) else {}
        sources = body.get("sources") or None
        hedge_ms = body.get("hedge_ms")
        stats: dict = {}
        rebuilt = self.store.rebuild_ec_shards_streaming(
            vid, collection, sources=sources, stats=stats,
            slab=int(body.get("slab") or 0) or None,
            window=int(body.get("window") or 0) or None,
            hedge_ms=float(hedge_ms) if hedge_ms is not None else None,
            repair=str(body.get("repair") or "auto") if sources
            else "full",
            deliver_to=body.get("target") or None)
        observe_gather(stats)
        observe_repair(stats)
        observe_mesh(stats)
        if rebuilt:
            # rebuilt shards serve from disk now; cached reconstructions
            # of them (engine LRU + plane slabs) are dead weight
            self._invalidate_reconstructions(vid, rebuilt)
        return {"volume": vid, "rebuilt": rebuilt, "stats": stats,
                "device": (self.store.device() or {}).get("chip", ""),
                "trace_id": tracing.current_trace_id()}

    def admin_ec_scrub(self, req: Request):
        """Trigger a synchronous scrub: one volume (?volume=) or a full
        pass over every local EC volume. Manual triggers bypass the
        lowest-shard ownership election — an operator asking this
        server to scrub means this server."""
        vid = req.query.get("volume")
        if vid is not None:
            return self.scrub.scrub_volume(int(vid), force=True)
        return self.scrub.run_pass(force=True)

    def admin_ec_scrub_status(self, req: Request):
        return self.scrub.snapshot()

    def admin_ec_scrub_repair(self, req: Request):
        """Quarantine + rebuild one corrupt shard: drop the poisoned
        file so it cannot serve reads or feed a decode, then stream a
        fresh copy from the surviving k. Driven by the master's repair
        queue when a scrub finding names this holder."""
        from ..stats.metrics import (observe_gather, observe_mesh,
                                     observe_repair)
        from ..util import tracing
        vid = int(req.query["volume"])
        sid = int(req.query["shard"])
        collection = req.query.get("collection", "")
        try:
            body = req.json()
        except ValueError:
            raise HttpError(400, "bad JSON body") from None
        body = body if isinstance(body, dict) else {}
        self.store.unmount_ec_shards(vid, [sid])
        # the plane must drop its fd on the poisoned shard file NOW —
        # an open fd would keep serving the quarantined bytes
        self._fast_ec_sync(vid)
        for loc in self.store.locations:
            base = volume_file_prefix(loc.directory, collection, vid)
            for p in (base + to_ext(sid), base + to_ext(sid) + ".part"):
                if os.path.exists(p):
                    os.remove(p)
        sources = body.get("sources") or self._ec_shard_locations(vid)
        sources = {int(s): [u for u in urls if u != self.url]
                   for s, urls in (sources or {}).items()
                   if int(s) != sid}
        stats: dict = {}
        rebuilt = self.store.rebuild_ec_shards_streaming(
            vid, collection, sources=sources, stats=stats,
            repair=str(body.get("repair") or "auto"))
        observe_gather(stats)
        observe_repair(stats)
        observe_mesh(stats)
        mounted = self.store.mount_ec_shards(vid, collection, rebuilt) \
            if rebuilt else []
        self._invalidate_reconstructions(vid, rebuilt or [sid])
        self.heartbeat_once()
        return {"volume": vid, "shard": sid, "rebuilt": rebuilt,
                "mounted": mounted, "stats": stats,
                "trace_id": tracing.current_trace_id()}

    def _report_scrub_finding(self, finding: dict) -> bool:
        """Push a scrub corruption finding to the master's repair
        queue; True only on an acknowledged report (the engine counts
        failures and the finding stays visible in its snapshot)."""
        try:
            post_json(f"http://{self.master_url}/cluster/scrub_report",
                      finding, timeout=5)
            return True
        except Exception:  # noqa: BLE001 - master may be down
            return False

    def admin_ec_copy(self, req: Request):
        """Pull shard files from a source server (reference
        VolumeEcShardsCopy: the target pulls via CopyFile stream)."""
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        source = req.query["source"]
        shard_ids = [int(s) for s in req.query.get("shards", "").split(",")
                     if s != ""]
        copy_ecx = req.query.get("copy_ecx", "true") == "true"
        # land next to any EC files this volume already has here (a
        # streamed spread may have staged shards on this server; the
        # sidecar pull must join them at the same base for the mount)
        base = self._ec_stage_base(vid, collection)
        name = os.path.basename(base)
        exts = [to_ext(s) for s in shard_ids]
        optional = []
        if copy_ecx:
            exts.append(".ecx")
            # .vif (volume version + offset width) is written by every
            # encode but can be legitimately gone (operator tooling,
            # pre-fix deployments where deleting the original volume
            # wiped it); .ecj exists only after EC deletes. A 404 on
            # either must not fail the copy — but ONLY a 404: any other
            # status (503 network blip) must propagate, or a silently
            # skipped .vif turns into a wrong offset-width guess on a
            # parity-only holder.
            optional = [".vif", ".ecj"]
        copied = []
        for ext in exts + optional:
            try:
                data = http_call(
                    "GET", f"http://{source}/admin/file?name={name}{ext}",
                    timeout=300)
            except HttpError as e:
                if ext in optional and e.status == 404:
                    continue
                raise
            with open(base + ext, "wb") as f:
                f.write(data)
            copied.append(ext)
            if self.pull_budget is not None:
                # upstream's doCopyFile: the throttle sits in the
                # puller's write loop
                self.pull_budget.charge(len(data))
        return {"volume": vid, "copied": copied}


    def admin_ec_delete_shards(self, req: Request):
        """Unmount + remove shard files (reference VolumeEcShardsDelete);
        drops .ecx/.ecj/.vif once no shard files remain."""
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        shard_ids = [int(s) for s in req.query.get("shards", "").split(",")
                     if s != ""]
        self.store.unmount_ec_shards(vid, shard_ids)
        self._fast_ec_sync(vid)
        removed = []
        for loc in self.store.locations:
            base = volume_file_prefix(loc.directory, collection, vid)
            for sid in shard_ids:
                # drop any spread stage alongside the shard — a failed
                # or failed-over stream must not leave .part orphans
                for p in (base + to_ext(sid),
                          base + to_ext(sid) + ".part"):
                    if os.path.exists(p):
                        os.remove(p)
                        if not p.endswith(".part"):
                            removed.append(sid)
            if not any(os.path.exists(base + to_ext(s))
                       for s in range(MAX_SHARDS)):
                for ext in (".ecx", ".ecj", ".vif", ".scrub"):
                    if os.path.exists(base + ext):
                        os.remove(base + ext)
        self.heartbeat_once()
        return {"volume": vid, "removed": removed}

    def admin_volume_copy(self, req: Request):
        """Pull a whole volume (.dat/.idx) from a source server and load it
        (reference VolumeCopy: target pulls via CopyFile)."""
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        source = req.query["source"]
        if self.store.find_volume(vid) is not None:
            raise HttpError(409, f"volume {vid} already here")
        loc = self.store.find_free_location()
        if loc is None:
            raise HttpError(507, "no free disk location")
        base = volume_file_prefix(loc.directory, collection, vid)
        name = os.path.basename(base)
        # .idx before .dat: the .dat is append-only, so an index snapshot
        # taken first can only reference bytes the later .dat snapshot
        # already contains (a torn copy the other way yields index entries
        # past the data end). Extra unindexed .dat tail is harmless.
        for ext in (".idx", ".dat"):
            self._pull_file(source, name + ext, base + ext)
        loc.load_existing_volumes()
        self._fast_sync(vid)
        self.heartbeat_once()
        return {"volume": vid, "copied": True}

    def _pull_file(self, source: str, name: str, dest: str,
                   chunk: int = 64 << 20):
        """Ranged streaming pull — never buffers whole volumes in RAM.
        Every range is charged to the server's budget for background
        pulls once it is written (upstream's doCopyFile: the throttle
        sits in the puller's write loop), and under a budget no range is
        larger than a second of it."""
        budget = self.pull_budget
        if budget is not None:
            chunk = min(chunk, max(1 << 20, budget.bps))
        stat = get_json(f"http://{source}/admin/file?name={name}&stat=true")
        total = stat["size"]
        with open(dest, "wb") as f:
            off = 0
            while off < total:
                n = min(chunk, total - off)
                data = http_call(
                    "GET", f"http://{source}/admin/file?name={name}"
                           f"&offset={off}&size={n}", timeout=600)
                f.write(data)
                off += len(data)
                if not data:
                    raise HttpError(502, f"short pull of {name} at {off}")
                if budget is not None:
                    budget.charge(len(data))

    def admin_volume_verify(self, req: Request):
        """Deep integrity check: walk the volume, CRC-verify every live
        needle against the index (volume.fsck's server side)."""
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        from ..storage.needle import CorruptNeedle
        checked = errors = 0
        from ..storage.compact_map import snapshot_live_items
        with v.lock:
            # offset order: the per-needle reads below then stream the
            # .dat sequentially instead of random-seeking a large volume
            snapshot = snapshot_live_items(v.nm, by_offset=True)
        with snapshot:
            for nid, nv in snapshot:
                checked += 1
                try:
                    # lock per needle, not for the whole scan — a
                    # multi-GB walk must not stall reads/writes on the
                    # volume
                    with v.lock:
                        blob = v._read_blob(nv.offset, nv.size)
                    Needle.from_bytes(blob, v.version,
                                      expected_size=nv.size)
                except (CorruptNeedle, OSError, VolumeError):
                    errors += 1
        return {"volume": vid, "checked": checked, "errors": errors}

    def admin_ec_to_volume(self, req: Request):
        """Decode mounted EC shards back into a normal volume (reference
        VolumeEcShardsToVolume)."""
        from ..ec import decoder as ec_decoder
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            raise HttpError(404, f"ec volume {vid} not mounted")
        if len([s for s in ev.shard_ids() if s < ev.k]) < ev.k:
            raise HttpError(409, "need all data shards local to decode")
        base = ev.base_name
        dat_size = ec_decoder.find_dat_file_size(base)
        ec_decoder.write_dat_file(base, dat_size, data_shards=ev.k)
        ec_decoder.write_idx_file_from_ec_index(base)
        self.store.unmount_ec_shards(vid, list(range(ev.total)))
        self._fast_ec_sync(vid)  # decoded back to a plain volume
        for loc in self.store.locations:
            if os.path.dirname(base) == loc.directory:
                loc.load_existing_volumes()
        self._fast_sync(vid)
        self.heartbeat_once()
        return {"volume": vid, "dat_size": dat_size}

    def admin_ec_shard_read(self, req: Request):
        """Ranged shard reads for the streaming gather. Two addressing
        forms: ``offset``/``size`` query params (legacy), or a standard
        ``Range: bytes=a-b`` / ``bytes=-N`` header — the header form
        answers 206 with ``Content-Range`` (whose ``/total`` lets the
        rebuilder size a shard via a 1-byte suffix probe)."""
        from .http_util import parse_range
        vid = int(req.query["volume"])
        sid = int(req.query["shard"])
        ev = self.store.find_ec_volume(vid)
        if ev is None or sid not in ev.shards:
            raise HttpError(404, f"shard {vid}.{sid} not here")
        shard = ev.shards[sid]
        total = shard.size
        rng = parse_range(req.headers.get("Range", ""), total)
        if rng is None:
            offset = int(req.query.get("offset", 0))
            size = int(req.query.get("size", 0))
            data = shard.read_at(offset, size)
            _tag_holder_read(len(data), len(data))
            return Response(data, headers={"Accept-Ranges": "bytes"})
        offset, length = rng
        if length == 0:
            return Response(b"", headers={"Accept-Ranges": "bytes"})
        data = shard.read_at(offset, length)
        _tag_holder_read(len(data), len(data))
        return Response(
            data, status=206,
            headers={
                "Accept-Ranges": "bytes",
                "Content-Range":
                    f"bytes {offset}-{offset + length - 1}/{total}",
            })

    def admin_ec_shard_repair_read(self, req: Request):
        """Projected shard read for single-shard trace repair: read the
        ``offset``/``size`` range of a local shard, apply the caller's
        GF(2^8) trace masks locally (ops/codec.project_slab: one gather
        through the masks' folded table, then a pack per plane), and
        return only the packed repair-symbol bit-planes — ``len(masks)``
        (at most 8) planes of ``ceil(size/8)`` bytes each,
        little-bit-first, in mask order, concatenated. This is where the
        sub-k*slab byte reduction happens: the full range is read off
        disk but never leaves the holder."""
        from ..ops import codec as ops_codec
        vid = int(req.query["volume"])
        sid = int(req.query["shard"])
        ev = self.store.find_ec_volume(vid)
        if ev is None or sid not in ev.shards:
            raise HttpError(404, f"shard {vid}.{sid} not here")
        shard = ev.shards[sid]
        try:
            offset = int(req.query.get("offset", 0))
            size = int(req.query["size"])
            masks = [int(x) for x in req.query["masks"].split(",")]
        except (KeyError, ValueError):
            raise HttpError(400, "need offset/size/masks query params")
        if offset < 0 or size <= 0:
            raise HttpError(400, f"bad range {offset}+{size}")
        if not 0 < len(masks) <= 8 or any(not (0 < x < 256) for x in masks):
            raise HttpError(
                400, f"need 1 to 8 masks, each 1..255, got {masks}")
        if offset + size > shard.size:
            raise HttpError(
                416, f"range {offset}+{size} beyond shard size {shard.size}")
        data = np.frombuffer(shard.read_at(offset, size), dtype=np.uint8)
        planes = ops_codec.project_slab(data, masks)
        _tag_holder_read(size, planes.nbytes)
        return Response(
            planes.reshape(-1).data,    # the socket reads the array itself
            headers={
                "X-Repair-Planes": str(planes.shape[0]),
                "X-Repair-Stride": str(planes.shape[1]),
            })

    def admin_ec_shard_plane_read(self, req: Request):
        """Half-plane shard read for piggyback repair: read the
        window-aligned ``offset``/``size`` range of a local shard and
        return only the sub-chunks of the caller's repair plane
        (ops/codec.pb_plane_slice) — ``size/2`` bytes. This is where
        the (k+1)/2k byte reduction happens: the full range is read off
        disk but only half of it leaves the holder."""
        from ..ops import codec as ops_codec
        vid = int(req.query["volume"])
        sid = int(req.query["shard"])
        ev = self.store.find_ec_volume(vid)
        if ev is None or sid not in ev.shards:
            raise HttpError(404, f"shard {vid}.{sid} not here")
        shard = ev.shards[sid]
        try:
            offset = int(req.query.get("offset", 0))
            size = int(req.query["size"])
            alpha = int(req.query["alpha"])
            window = int(req.query["window"])
            bit = int(req.query["bit"])
            side = int(req.query["side"])
        except (KeyError, ValueError):
            raise HttpError(
                400, "need offset/size/alpha/window/bit/side query params")
        if offset < 0 or size <= 0:
            raise HttpError(400, f"bad range {offset}+{size}")
        if alpha < 2 or alpha & (alpha - 1) or window % alpha:
            raise HttpError(
                400, f"bad sub-chunk geometry alpha={alpha} "
                     f"window={window}")
        if not (0 <= bit < alpha.bit_length() - 1) or side not in (0, 1):
            raise HttpError(400, f"bad plane bit={bit} side={side}")
        if offset % window or size % window:
            raise HttpError(
                400, f"range {offset}+{size} not aligned to "
                     f"window {window}")
        if offset + size > shard.size:
            raise HttpError(
                416, f"range {offset}+{size} beyond shard size {shard.size}")
        data = np.frombuffer(shard.read_at(offset, size), dtype=np.uint8)
        plane = ops_codec.pb_plane_slice(data, alpha, window, bit, side)
        _tag_holder_read(size, plane.nbytes)
        return Response(
            plane.tobytes(),
            headers={
                "X-Plane-Alpha": str(alpha),
                "X-Plane-Window": str(window),
            })

    def admin_tier_upload(self, req: Request):
        """Ship a readonly volume's .dat to a configured backend
        (reference VolumeTierMoveDatToRemote)."""
        from ..storage import volume_tier
        from ..storage.backend import BackendError
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        # plane offline first: once the local .dat is removed its pinned
        # fd would keep serving "local" reads AND hold the inode's disk
        # space — defeating the tiering. The Python server reads via the
        # remote backend from here on.
        self._fast_unregister(vid)
        try:
            info = volume_tier.upload_dat(
                v, req.query["dest"],
                keep_local=req.query.get("keep_local") == "true")
        except (VolumeError, BackendError) as e:
            self._fast_sync(vid)   # nothing moved; resume fast serving
            raise HttpError(400, str(e))
        if req.query.get("keep_local") == "true":
            self._fast_sync(vid)
        self.heartbeat_once()
        return info

    def admin_tier_download(self, req: Request):
        """Bring a remote .dat back to local disk (reference
        VolumeTierMoveDatFromRemote)."""
        from ..storage import volume_tier
        from ..storage.backend import BackendError
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        try:
            out = volume_tier.download_dat(
                v, delete_remote=req.query.get("delete_remote") == "true")
        except (VolumeError, BackendError) as e:
            raise HttpError(400, str(e))
        self._fast_sync(vid)   # fresh local .dat: (re)open + reload
        self.heartbeat_once()
        return out

    def admin_volume_sync_status(self, req: Request):
        """Sync metadata for incremental copy (reference
        volume_server.proto VolumeSyncStatus)."""
        from ..storage import volume_backup
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        try:
            last_ns = volume_backup.last_append_at_ns(v)
        except VolumeError as e:
            raise HttpError(400, str(e))
        return {
            "volume": vid,
            "collection": v.collection,
            "tail_offset": v.size(),
            "compact_revision": v.super_block.compaction_revision,
            "replication": str(v.super_block.replica_placement),
            "ttl": str(v.super_block.ttl),
            "version": v.version,
            "last_append_at_ns": last_ns,
        }

    def admin_volume_tail(self, req: Request):
        """Raw record bytes appended after since_ns (reference
        VolumeIncrementalCopy / VolumeTailSender)."""
        from ..storage import volume_backup
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        since_ns = int(req.query.get("since_ns", 0))
        # always page-capped: a whole-volume delta must not be buffered
        # into one Response body
        max_bytes = int(req.query.get("max_bytes", 0)) \
            or volume_backup.DEFAULT_TAIL_PAGE_BYTES
        try:
            return Response(volume_backup.read_incremental(v, since_ns,
                                                           max_bytes))
        except VolumeError as e:
            raise HttpError(400, str(e))

    def admin_volume_tail_receive(self, req: Request):
        """Apply raw record bytes shipped by a tail sender (reference
        VolumeTailReceiver): follower-side of volume.tail replication."""
        from ..storage import volume_backup
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            raise HttpError(404, f"volume {vid} not found")
        since = req.query.get("since_ns")
        # raw records land via the volume's own file handles: take the
        # write lease back so the native plane isn't appending the same
        # tail concurrently
        self._writer_release(v)
        try:
            applied, cursor = volume_backup.append_raw_records(
                v, req.body, int(since) if since is not None else None)
        except VolumeError as e:
            self._fast_sync(vid)
            raise HttpError(400, str(e))
        self._fast_sync(vid)
        return {"applied": applied, "cursor_ns": cursor}

    def admin_file(self, req: Request):
        """Serve a raw storage file (EC copy pull path). Restricted to the
        store's own directories and known extensions."""
        name = os.path.basename(req.query.get("name", ""))
        ok_ext = name.endswith((".ecx", ".ecj", ".vif", ".dat", ".idx")) or \
            ".ec" in name
        if not name or not ok_ext:
            raise HttpError(400, "bad file name")
        for loc in self.store.locations:
            path = os.path.join(loc.directory, name)
            if os.path.exists(path):
                if req.query.get("stat"):
                    return {"size": os.path.getsize(path)}
                offset = int(req.query.get("offset", 0))
                size = int(req.query.get("size", 0)) \
                    or os.path.getsize(path) - offset
                return Response(body_path=path, body_range=(offset, size))
        raise HttpError(404, f"{name} not found")

    def _guard_check(self, req: Request):
        """Whitelist applies to every route, admin included (reference
        wraps all handlers in guard.WhiteList). Under mutual TLS the
        admin plane (the reference's gRPC surface) additionally
        demands a CA-verified client certificate; public data routes
        stay server-TLS."""
        if req.path.startswith("/admin/"):
            from .http_util import require_client_cert
            require_client_cert(req)
        if self.guard.enabled and \
                not self.guard.allows(req.handler.client_address[0]):
            raise HttpError(403, "ip not in whitelist")

    # -- data path ---------------------------------------------------------
    def data_handler(self, req: Request):
        if req.path == "/":
            return self.status(req)
        try:
            vid, key, cookie = parse_file_id(req.path.lstrip("/"))
        except ValueError:
            raise HttpError(404, f"invalid fid path {req.path}") from None
        if req.method in ("GET", "HEAD"):
            return self.read_needle(req, vid, key, cookie)
        if req.method in ("POST", "PUT"):
            self._check_write_jwt(req)
            return self.write_needle(req, vid, key, cookie)
        if req.method == "DELETE":
            self._check_write_jwt(req)
            return self.delete_needle(req, vid, key, cookie)
        raise HttpError(405, req.method)

    def _check_write_jwt(self, req: Request):
        """Per-fid write token check (reference
        volume_server_handlers_write.go maybeCheckJwtAuthorization)."""
        if not self.jwt_signing_key:
            return
        from ..security.jwt import (VerifyError, jwt_from_request,
                                    verify_fid_jwt)
        token = jwt_from_request(req.headers, req.query)
        if not token:
            raise HttpError(401, "missing write jwt")
        fid = req.path.lstrip("/")
        try:
            verify_fid_jwt(self.jwt_signing_key, token, fid)
        except VerifyError as e:
            raise HttpError(401, f"jwt rejected: {e}") from None

    def write_needle(self, req: Request, vid, key, cookie):
        # reject oversized uploads BEFORE buffering the body (reference
        # -fileSizeLimitMB); the multipart envelope adds a little, so
        # this is a coarse pre-filter and the post-parse check is exact
        if self.file_size_limit:
            try:
                clen = int(req.headers.get("Content-Length") or 0)
            except ValueError:
                clen = 0
            if clen > self.file_size_limit + 65536:
                raise HttpError(413, "file over the size limit")
        filename, ctype, data = req.upload_payload()
        if self.file_size_limit and len(data) > self.file_size_limit:
            raise HttpError(413, "file over the size limit")
        n = Needle(cookie=cookie, id=key, data=data)
        if filename:
            n.set_name(filename.encode())
        if not ctype:
            # fall back to the filename's extension (reference
            # needle_parse_upload.go keeps only a meaningful mime); an
            # explicit octet-stream is respected — the filer uploads
            # chunk needles that way on purpose
            import mimetypes
            guessed, _ = mimetypes.guess_type(filename or "")
            ctype = guessed or ctype
        if ctype and ctype != "application/octet-stream":
            n.set_mime(ctype.encode())
        # explicit modified-time override (reference
        # needle_parse_upload.go:48 FormValue("ts")); the on-disk field
        # is 5 bytes, so only 0 < ts < 2^40 is honored — anything else
        # falls back to now, like the reference's ParseUint-error path
        ts_raw = req.query.get("ts", "")
        ts_val = int(ts_raw) if ts_raw.isdigit() else 0
        if not 0 < ts_val < 1 << 40:
            ts_val = 0
        n.set_last_modified(ts_val)
        if req.query.get("cm") == "true":
            # payload is a chunk-manifest JSON (reference
            # needle_parse_upload.go: FormValue("cm") sets the flag)
            n.set_is_chunk_manifest()
        # Seaweed-* headers ride with the needle as key/value pairs
        # (reference needle_parse_upload.go parsePairs; the uint16
        # PairsSize field caps them — oversize is an ERROR, silently
        # dropping metadata while returning 200 would lie to the client)
        pairs = {k: v for k, v in req.headers.items()
                 if k.lower().startswith("seaweed-")}
        if pairs:
            import json as _json
            blob = _json.dumps(pairs).encode()
            if len(blob) >= 65536:
                raise HttpError(400, "Seaweed-* pairs exceed 64KB")
            n.set_pairs(blob)
        from ..storage.types import TTL
        ttl = TTL.parse(req.query.get("ttl", ""))
        if ttl.to_uint32():
            n.set_ttl(ttl)
        try:
            self.store.write_needle(vid, n)
            size = len(data)  # reference reports DataSize, not needle Size
        except VolumeError as e:
            raise HttpError(500, str(e)) from None
        self._fast_put(vid, key)
        # synchronous replica fan-out, all-must-succeed (reference
        # store_replicate.go:20-83): attempt every replica, then fail the
        # request if any write is missing so the client knows the needle is
        # under-replicated
        if req.query.get("type") != "replicate":
            from ..security.jwt import jwt_from_request
            from ..util.fanout import fan_out
            from .http_util import post_multipart
            token = jwt_from_request(req.headers, req.query) \
                if self.jwt_signing_key else None
            jwt_q = f"&jwt={token}" if token else ""

            # payload-shaping params must survive the hop: cm marks the
            # manifest flag (a replica missing it would serve raw JSON
            # and never cascade deletes), ttl stamps per-needle expiry,
            # Seaweed-* headers carry the needle's metadata pairs
            extra_q = ""
            if req.query.get("cm") == "true":
                extra_q += "&cm=true"
            if req.query.get("ttl"):
                extra_q += f"&ttl={req.query['ttl']}"
            if ts_val:   # forward only the validated integer form
                extra_q += f"&ts={ts_val}"
            pair_headers = {k: v for k, v in req.headers.items()
                            if k.lower().startswith("seaweed-")} or None

            def replicate(node_url: str):
                post_multipart(
                    f"http://{node_url}{req.path}?type=replicate{jwt_q}"
                    f"{extra_q}",
                    filename, data, ctype or "application/octet-stream",
                    headers=pair_headers)

            failed = [
                f"{node_url}: {exc.message or exc.status}"
                if isinstance(exc, HttpError) else f"{node_url}: {exc}"
                for node_url, _, exc in fan_out(replicate,
                                                self._other_replicas(vid))
                if exc is not None]
            if failed:
                raise HttpError(
                    500, "replication failed on " + "; ".join(failed))
        return {"name": filename, "size": size, "eTag": n.etag}

    def _other_replicas(self, vid: int) -> List[str]:
        # push-updated vid map first (stale-by-at-most-one-pulse;
        # reference vidMap), TTL'd lookup as warm-up/outage fallback
        urls = None
        if self._vid_map is not None:
            urls = self._vid_map.lookup(vid)
        if urls is None:
            cached = self._lookup_cache.get(vid)
            if cached and time.time() - cached[0] < 10:
                urls = cached[1]
            else:
                try:
                    out = get_json(f"http://{self.master_url}/dir/lookup"
                                   f"?volumeId={vid}", timeout=10)
                    urls = [l["url"] for l in out.get("locations", [])]
                except HttpError:
                    urls = []
                self._lookup_cache[vid] = (time.time(), urls)
        return [u for u in urls if u != self.url]

    def read_needle(self, req: Request, vid, key, cookie):
        n = Needle(id=key, cookie=cookie)
        v = self.store.find_volume(vid)
        if v is None:
            ev = self.store.find_ec_volume(vid)
            if ev is not None:
                return self._read_ec_needle(req, ev, vid, key, cookie)
            # not local: redirect to a replica (reference
            # volume_server_handlers_read.go:57-80)
            if self.read_redirect:
                others = self._other_replicas(vid)
                if others:
                    return Response(
                        b"", 301,
                        headers={"Location":
                                 f"http://{others[0]}{req.path}"})
            raise HttpError(404, f"volume {vid} not found")
        try:
            got = self.store.read_needle(vid, n)
        except NotFound as e:
            raise HttpError(404, str(e)) from None
        return self._needle_response(got, req)

    def _needle_response(self, got: Needle,
                         req: Optional[Request] = None) -> Response:
        # chunk-manifest resolution (reference
        # volume_server_handlers_read.go: unless ?cm=false, a flagged
        # needle is resolved to the chunk needles it lists)
        if got.is_chunk_manifest() and (
                req is None or req.query.get("cm") != "false"):
            return self._chunk_manifest_response(got, req)
        ctype = got.mime.decode() if got.has_mime() \
            else "application/octet-stream"
        # Last-Modified + If-Modified-Since (reference
        # volume_server_handlers_read.go:99-109): checked before the
        # etag, like the reference
        lm_header = None
        if got.has_last_modified() and got.last_modified:
            from email.utils import formatdate, parsedate_to_datetime
            lm_header = formatdate(got.last_modified, usegmt=True)
            ims = req.headers.get("If-Modified-Since") \
                if req is not None else None
            if ims:
                try:
                    dt = parsedate_to_datetime(ims)
                    if dt.tzinfo is None:
                        # '-0000' parses naive; it means UTC (RFC5322),
                        # not server-local time
                        from datetime import timezone as _tz
                        dt = dt.replace(tzinfo=_tz.utc)
                    t = dt.timestamp()
                except (TypeError, ValueError):
                    t = None
                if t is not None and t >= got.last_modified:
                    return Response(b"", 304,
                                    headers={"Last-Modified": lm_header,
                                             "Etag": f'"{got.etag}"'})
        # conditional GET (reference volume_server_handlers_read.go
        # If-None-Match vs Etag -> 304): immutable needles make etags
        # exact, so a revalidating client pays zero body bytes.
        # RFC7232: the header is a comma list of (possibly weak)
        # validators, or "*" matching any representation.
        if req is not None:
            inm = (req.headers.get("If-None-Match") or "").strip()
            if inm:
                candidates = {c.strip().removeprefix("W/")
                              for c in inm.split(",")}
                if "*" in candidates or f'"{got.etag}"' in candidates:
                    return Response(b"", 304,
                                    headers={"Etag": f'"{got.etag}"'})
        headers = {"Etag": f'"{got.etag}"',
                   "Accept-Ranges": "bytes"}
        if lm_header:
            headers["Last-Modified"] = lm_header
        if got.has_pairs() and got.pairs:
            # stored Seaweed-* pairs come back as response headers
            # (reference volume_server_handlers_read.go SetEtag + pairs)
            import json as _json
            try:
                for pk, pv in _json.loads(got.pairs.decode()).items():
                    headers[pk] = pv
            except (ValueError, AttributeError):
                pass
        if got.has_name():
            # escape quotes/backslashes: the name is uploader-controlled
            # and lands inside a quoted-string header parameter
            name = got.name.decode("utf-8", "replace") \
                .replace("\\", "\\\\").replace('"', '\\"')
            headers["Content-Disposition"] = \
                f'inline; filename="{name}"'
        body = got.data
        # image ops on read (reference volume_server_handlers_read.go
        # resize-on-GET + images/orientation.go) — ONLY on explicit
        # whole-object resize requests. Range reads (the filer's chunk
        # fetch path) must return stored bytes verbatim: re-encoding
        # before slicing would change lengths and corrupt chunked
        # files' etags/content.
        if req is not None and ctype.startswith("image/") and \
                not req.headers.get("Range"):
            width = int(req.query.get("width", 0) or 0)
            height = int(req.query.get("height", 0) or 0)
            if width or height:
                from ..images import fix_orientation, resize_image
                if ctype == "image/jpeg":
                    body = fix_orientation(body, ctype)
                body, ctype = resize_image(
                    body, ctype, width, height,
                    req.query.get("mode", ""))
        # single-range requests (reference volume_server_handlers_read.go
        # processRangeRequest): the filer fetches chunk slices this way
        from .http_util import parse_range
        rng = req.headers.get("Range") if req is not None else None
        total = len(body)
        parsed = parse_range(rng or "", total)
        if parsed is not None:
            start, length = parsed
            headers["Content-Range"] = \
                f"bytes {start}-{start + length - 1}/{total}"
            return Response(body[start:start + length], 206, ctype,
                            headers)
        return Response(body, 200, ctype, headers)

    def _chunk_manifest_response(self, got: Needle,
                                 req: Optional[Request]) -> Response:
        """Assemble a chunked file window for the reader (reference
        chunked_file.go ChunkedFileReader): chunk slices are fetched in
        parallel with sub-range requests (a 16-byte Range read moves 16
        bytes, not whole chunks), routed through the push-updated vid
        map instead of per-chunk master lookups. A full GET of a file
        bigger than RAM should go through the filer's streaming path;
        like every raw-needle response here, this one is buffered."""
        from ..client.chunked import ChunkManifest
        from ..util.fanout import fan_out
        from .http_util import parse_range
        manifest = ChunkManifest.from_json(got.data)
        ctype = manifest.mime or "application/octet-stream"
        headers = {"Accept-Ranges": "bytes"}
        if manifest.name:
            headers["Content-Disposition"] = \
                f'inline; filename="{manifest.name}"'
        rng = req.headers.get("Range") if req is not None else None
        parsed = parse_range(rng or "", manifest.size)
        want_start, want_len = (parsed if parsed is not None
                                else (0, manifest.size))
        jobs = []
        for c in manifest.chunks:
            lo = max(c.offset, want_start)
            hi = min(c.offset + c.size, want_start + want_len)
            if lo < hi:
                jobs.append((c, lo, hi))

        def fetch(job):
            c, lo, hi = job
            return self._fetch_fid_range(c.fid, lo - c.offset,
                                         hi - lo)

        out = bytearray(want_len)
        for (c, lo, hi), seg, exc in fan_out(fetch, jobs, dedicated=True):
            if exc is not None:
                raise HttpError(
                    502, f"chunk {c.fid} unavailable: {exc}")
            out[lo - want_start:lo - want_start + len(seg)] = seg
        if parsed is not None:
            headers["Content-Range"] = (
                f"bytes {want_start}-{want_start + want_len - 1}"
                f"/{manifest.size}")
            return Response(bytes(out), 206, ctype, headers)
        return Response(bytes(out), 200, ctype, headers)

    def _fetch_fid_range(self, fid: str, offset: int, size: int) -> bytes:
        """Range-read one fid from whichever server holds it, using the
        push-updated vid map (fallback: lookup) for routing."""
        from ..storage.types import parse_file_id
        vid, _, _ = parse_file_id(fid)
        urls = self._vid_map.lookup(vid) if self._vid_map else None
        if not urls:
            from ..client.operation import lookup
            urls = lookup(self.master_url, vid)
        headers = {"Range": f"bytes={offset}-{offset + size - 1}"}
        last = None
        for u in urls:
            try:
                return http_call("GET", f"http://{u}/{fid}",
                                 headers=headers)
            except HttpError as e:
                last = e
        raise last or HttpError(404, f"no locations for {fid}")

    def _cascade_chunk_manifest_delete(self, vid: int, n: Needle):
        """Deleting a manifest deletes its chunk needles first
        (reference volume_server_handlers_write.go DeleteHandler +
        operation.DeleteChunks) — orphaned chunks are unreachable
        garbage otherwise. The flag is probed with two tiny preads so
        ordinary deletes never pay a full payload read."""
        from ..client.chunked import ChunkManifest
        from ..client.operation import delete_file
        from ..storage.needle import FLAG_IS_CHUNK_MANIFEST
        from ..util.fanout import fan_out
        try:
            flags = self.store.read_needle_flags(
                vid, Needle(id=n.id, cookie=n.cookie))
            if not flags & FLAG_IS_CHUNK_MANIFEST:
                return
            got = self.store.read_needle(vid, Needle(id=n.id,
                                                     cookie=n.cookie))
        except (NotFound, VolumeError):
            return
        try:
            manifest = ChunkManifest.from_json(got.data)
        except Exception:  # noqa: BLE001 - corrupt manifest: nothing to do
            return
        fan_out(lambda c: delete_file(self.master_url, c.fid),
                manifest.chunks, dedicated=True)

    # -- EC degraded read (reference store_ec.go:119-373) ------------------
    def _read_ec_needle(self, req: Request, ev, vid, key, cookie):
        got = self._read_needle_local(vid, key, cookie, f"{vid},{key:x}")
        return self._needle_response(got, req)

    def _fetch_ec_shard_locations(self, vid: int) -> Dict[int, List[str]]:
        try:
            out = get_json(f"http://{self.master_url}/cluster/ec_lookup"
                           f"?volumeId={vid}", timeout=10)
            return {int(k): v for k, v in out.get("shards", {}).items()}
        except HttpError:
            return {}

    def _mounted_ec_geometry(self, vid: int) -> tuple:
        """(k, k + m) of a volume mounted here, for the location
        cache's freshness tiers; the default for one that is not."""
        ev = self.store.find_ec_volume(vid)
        k, m = (ev.k, ev.m) if ev is not None \
            else self.store.default_geometry
        return k, k + m

    def _ec_geometry(self, vid: int) -> tuple:
        """(k, m) of an EC volume no shard of which is here: the
        master's, from its holders' heartbeats; the default where the
        master cannot say."""
        try:
            out = get_json(f"http://{self.master_url}/cluster/ec_lookup"
                           f"?volumeId={vid}", timeout=10)
            return (int(out["data_shards"]), int(out["parity_shards"]))
        except (HttpError, KeyError, TypeError, ValueError):
            return self.store.default_geometry

    def _ec_shard_locations(self, vid: int) -> Dict[int, List[str]]:
        """Cached with tiered freshness + invalidate-on-failure
        (reference store_ec.go:218-259); raw master hits only on expiry."""
        return self._ec_loc_cache.lookup(vid)

    def _read_shard_from_holders(self, vid: int, sid: int, offset: int,
                                 size: int) -> Optional[bytes]:
        """Try each cached holder of one shard; forget holders that fail
        (reference forgetShardId, store_ec.go:211). The per-holder
        budget is SW_EC_DEGRADED_READ_TIMEOUT_S — the old hardcoded 30 s
        let one dead holder eat the whole request deadline — and a
        socket timeout forgets the holder exactly like an HTTP error."""
        from ..ec.degraded import degraded_read_timeout_s
        from ..stats.health import BOARD
        timeout = degraded_read_timeout_s()
        for holder in self._ec_shard_locations(vid).get(sid, []):
            if holder == self.url:
                continue
            t0 = time.perf_counter()
            try:
                data = http_call(
                    "GET",
                    f"http://{holder}/admin/ec/shard_read?volume={vid}"
                    f"&shard={sid}&offset={offset}&size={size}",
                    timeout=timeout)
            except (HttpError, OSError):
                BOARD.record_error(holder, "degraded_read")
                self._ec_loc_cache.forget(vid, sid, holder)
                continue
            BOARD.record_latency(holder, "degraded_read",
                                 time.perf_counter() - t0)
            return data
        return None

    def _reconstruct_shard_range(self, vid, sid, offset, size) -> bytes:
        """Reconstruct-on-read of one lost shard's range (reference
        store_ec.go:329-362). Served by the batched DegradedReadEngine
        — coalesced fused-dispatch decode, exactly-k survivor gather,
        slab LRU — unless SW_EC_DEGRADED_MODE=naive selects the
        unbatched per-read path below (kept for A/B benching)."""
        from ..ec.degraded import degraded_mode
        if degraded_mode() == "naive":
            return self._reconstruct_shard_range_naive(
                vid, sid, offset, size)
        return self.degraded.read(vid, sid, offset, size)

    def _reconstruct_shard_range_naive(self, vid, sid, offset,
                                       size) -> bytes:
        """Per-read fallback. Still fixed relative to the original loop:
        fetches only the first-k survivors the decode plan needs (never
        all k+m-1 siblings) and decodes only the lost shard's
        row (codec.lost_row_coeffs) instead of regenerating the full
        stripe with codec.reconstruct."""
        from ..util.fanout import fan_out
        ev = self.store.find_ec_volume(vid)
        locations = self._ec_shard_locations(vid)
        # the volume's own geometry; a server that holds no shard of
        # it asks the master, which has it from the holders' heartbeats
        codec = self.store.ec_volume_codec(ev) if ev is not None \
            else self.store.codec_for(*self._ec_geometry(vid))

        present = []
        for other in range(codec.total):
            if other == sid:
                present.append(False)
            elif ev is not None and other in ev.shards:
                present.append(True)
            else:
                present.append(any(h != self.url
                                   for h in locations.get(other, [])))
        if sum(present) < codec.k:
            raise HttpError(
                503, f"cannot reconstruct {vid}.{sid}: "
                     f"{sum(present)} shards")
        src, row = codec.lost_row_coeffs(tuple(present), sid)

        def pad(data: bytes) -> np.ndarray:
            if len(data) < size:  # shard tail: zero-pad like local reads
                data = data + b"\x00" * (size - len(data))
            return np.frombuffer(data, dtype=np.uint8)

        rows: List[Optional[np.ndarray]] = [None] * len(src)
        remote = []
        for pos, other in enumerate(src):
            if ev is not None and other in ev.shards:
                rows[pos] = pad(ev.shards[other].read_at(offset, size))
            else:
                remote.append(pos)
        for pos, data, exc in fan_out(
                lambda p: self._read_shard_from_holders(
                    vid, src[p], offset, size), remote, dedicated=True):
            if exc is None and data is not None:
                rows[pos] = pad(data)
        if any(r is None for r in rows):
            have = sum(r is not None for r in rows)
            raise HttpError(
                503, f"cannot reconstruct {vid}.{sid}: {have} of "
                     f"{len(src)} survivors answered")
        from ..ops.codec import host_matmul
        out = host_matmul(row, np.stack(rows, axis=0))
        return out[0].tobytes()

    def _delete_ec_needle(self, req: Request, ev, vid, key):
        """EC delete: tombstone + journal locally, then broadcast to every
        other shard holder (reference store_ec_delete.go:15-110)."""
        found = ev.delete_needle(key)
        if found:
            # mirror the tombstone into the plane's .ecx mirror so the
            # fast path redirects (and Python 404s) instead of serving
            self._fast_ec_delete(vid, key)
        if req.query.get("type") != "replicate":
            from ..security.jwt import jwt_from_request
            from ..util.fanout import fan_out
            token = jwt_from_request(req.headers, req.query) \
                if self.jwt_signing_key else None
            jwt_q = f"&jwt={token}" if token else ""
            notified = {self.url}
            targets = []
            # fresh master lookup, NOT the tiered cache: a holder that
            # mounted shards after the cache filled (ec.balance/rebuild)
            # would otherwise miss the delete and resurrect the needle —
            # the exact failure this broadcast exists to prevent
            locations = self._fetch_ec_shard_locations(vid) or \
                self._ec_shard_locations(vid)
            for holders in locations.values():
                for holder in holders:
                    if holder not in notified:
                        notified.add(holder)
                        targets.append(holder)

            def broadcast(holder: str):
                http_call("DELETE",
                          f"http://{holder}{req.path}?type=replicate"
                          f"{jwt_q}")

            # a holder that misses the delete would silently resurrect the
            # needle on a read redirect — fail loudly like writes do; 404
            # (holder no longer has the volume) is benign
            failed = []
            for holder, _, exc in fan_out(broadcast, targets):
                if exc is None:
                    found = True
                elif not (isinstance(exc, HttpError) and exc.status == 404):
                    failed.append(f"{holder}: {exc}")
            if failed:
                raise HttpError(
                    500, "ec delete replication failed on "
                    + "; ".join(failed))
        if not found:
            raise HttpError(404, f"needle {key} not in ec volume {vid}")
        return {"size": 0}

    def delete_needle(self, req: Request, vid, key, cookie):
        n = Needle(id=key, cookie=cookie)
        v = self.store.find_volume(vid)
        if v is None:
            ev = self.store.find_ec_volume(vid)
            if ev is not None:
                return self._delete_ec_needle(req, ev, vid, key)
            raise HttpError(404, f"volume {vid} not found")
        if req.query.get("type") != "replicate" and \
                req.query.get("cm") != "false":
            self._cascade_chunk_manifest_delete(vid, n)
        try:
            freed = self.store.delete_needle(vid, n)
        except VolumeError as e:
            raise HttpError(500, str(e)) from None
        self._fast_delete(vid, key)
        if req.query.get("type") != "replicate":
            from ..security.jwt import jwt_from_request
            from ..util.fanout import fan_out
            token = jwt_from_request(req.headers, req.query) \
                if self.jwt_signing_key else None
            jwt_q = f"&jwt={token}" if token else ""

            def replicate(node_url: str):
                http_call("DELETE",
                          f"http://{node_url}{req.path}?type=replicate"
                          f"{jwt_q}")

            # deletes must fail-on-any-replica like writes (reference
            # ReplicatedDelete, store_replicate.go): a replica that keeps
            # the needle resurrects it via read redirects. 404 = already
            # gone there, which is the goal state.
            failed = []
            for node_url, _, exc in fan_out(replicate,
                                            self._other_replicas(vid)):
                if exc is not None and not (
                        isinstance(exc, HttpError) and exc.status == 404):
                    failed.append(f"{node_url}: {exc}")
            if failed:
                raise HttpError(
                    500, "delete replication failed on " + "; ".join(failed))
        return {"size": freed}
