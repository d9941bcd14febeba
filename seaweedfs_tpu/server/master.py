"""MasterServer — cluster coordinator.

Reference weed/server/master_server.go: HTTP API (/dir/assign, /dir/lookup,
/vol/grow, /vol/vacuum, /col/delete, /submit, status pages) + the heartbeat
channel (HTTP POST here instead of a gRPC stream; same payload). Volume
growth happens on demand under a lock when an Assign finds no writable
volume (reference master_grpc_server_volume.go:43-101).
"""

from __future__ import annotations

import os
import threading
from ..util.locks import make_lock
import time

from ..storage.types import TTL, ReplicaPlacement
from ..util import config, tracing
from ..topology.topology import RaftSequencer, Topology
from ..topology.volume_growth import NoFreeSlots, find_empty_slots
from .http_util import (HttpError, HttpServer, Request, Response,
                        Router, post_json, post_multipart, profile_handler,
                        traces_export_handler, traces_handler)


class MasterServer:
    def __init__(self, port: int = 9333, host: str = "127.0.0.1",
                 volume_size_limit_mb: int = 30 * 1024,
                 default_replication: str = "000",
                 pulse_seconds: float = None,
                 garbage_threshold: float = 0.3,
                 jwt_signing_key: str = "",
                 peers: str = "", raft_dir: str = "",
                 maintenance_scripts: str = "",
                 maintenance_interval: float = 17 * 60,
                 vacuum_interval: float = 15 * 60,
                 whitelist=(), metrics_address: str = "",
                 metrics_interval: int = 15, sequencer=None,
                 growth_counts: dict = None,
                 maintenance_filer_url: str = ""):
        if pulse_seconds is None:
            pulse_seconds = config.env_float("SW_PULSE_S")
        self.topology = Topology(
            volume_size_limit=volume_size_limit_mb * 1024 * 1024,
            pulse_seconds=pulse_seconds, sequencer=sequencer)
        self.default_replication = default_replication
        self.garbage_threshold = garbage_threshold
        self.jwt_signing_key = jwt_signing_key
        self.vg_lock = make_lock("master.vg_lock")
        self.host = host

        router = Router()
        router.add("*", "/dir/assign", self.dir_assign)
        router.add("*", "/dir/lookup", self.dir_lookup)
        router.add("*", "/dir/status", self.dir_status)
        router.add("*", "/vol/grow", self.vol_grow)
        router.add("*", "/vol/status", self.vol_status)
        router.add("*", "/vol/vacuum", self.vol_vacuum)
        router.add("GET", "/stats/health", self.stats_health)
        router.add("GET", "/stats/memory", self.stats_memory)
        router.add("*", "/col/delete", self.col_delete)
        router.add("POST", "/submit", self.submit)
        router.add("POST", "/cluster/heartbeat", self.cluster_heartbeat)
        router.add("POST", "/cluster/goodbye", self.cluster_goodbye)
        router.add("*", "/cluster/status", self.cluster_status)
        router.add("*", "/cluster/ec_lookup", self.ec_lookup)
        router.add("*", "/cluster/ec_status", self.ec_status)
        router.add("*", "/cluster/volumes", self.cluster_volumes)
        router.add("GET", "/cluster/watch", self.cluster_watch)
        router.add("GET", "/metrics", self.metrics_handler)
        router.add("GET", "/cluster/metrics", self.cluster_metrics)
        router.add("GET", "/cluster/health", self.cluster_health)
        router.add("GET", "/cluster/repairs", self.cluster_repairs)
        router.add("GET", "/cluster/tiering", self.cluster_tiering)
        router.add("POST", "/cluster/scrub_report",
                   self.cluster_scrub_report)
        router.add("GET", "/admin/traces", traces_handler)
        router.add("GET", "/admin/traces/export", traces_export_handler)
        router.add("POST", "/admin/profile", profile_handler)
        router.add("GET", "/", self.ui_handler)
        router.add("GET", "/ui", self.ui_handler)
        # GET /<fid> on the master redirects to a holder (reference
        # master_server.go:125 redirectHandler)
        router.set_fallback(self.redirect_handler)
        # ip whitelist on the user-facing surface (reference
        # guard.WhiteList wrapping of master_server.go:112-123); the
        # cluster-internal channels stay open — volume servers and raft
        # peers are not client traffic
        from ..security.guard import Guard
        self.guard = Guard(whitelist)
        router.before = self._guard_check
        # metrics push config broadcast to volume servers via heartbeat
        # responses (reference master_grpc_server.go:75-77)
        self.metrics_address = metrics_address
        self.metrics_interval = int(metrics_interval)
        # volume-location push channel (reference KeepConnected,
        # master_grpc_server.go:180-234): heartbeat deltas and node
        # deaths publish here; clients long-poll /cluster/watch
        from .watch_hub import WatchHub
        self.watch_hub = WatchHub(self._location_snapshot)
        self.topology.location_listener = self.watch_hub.publish
        from ..stats.metrics import (MASTER_REQUEST_COUNTER,
                                     MASTER_REQUEST_HISTOGRAM)

        def observe(label, seconds, ok):
            MASTER_REQUEST_COUNTER.inc(label if ok else label + " error")
            MASTER_REQUEST_HISTOGRAM.observe(
                seconds, label, trace_id=tracing.current_trace_id())
        router.observe = observe
        self.server = HttpServer(port, router, host)
        self.port = self.server.port
        router.node = f"{host}:{self.port}"
        # fleet health plane: scrape every heartbeating node's /metrics
        # on SW_CLUSTER_SCRAPE_S and serve the merged view at
        # /cluster/metrics (+ the per-holder fold at /cluster/health)
        from ..stats.aggregate import ClusterMetricsAggregator
        self.cluster_agg = ClusterMetricsAggregator(self._scrape_targets)
        # integrity plane: scrub findings + topology scans + health
        # signals feed a priority queue that drives repairs and accounts
        # time-to-re-protection (stats/repair_queue.py)
        from ..stats.repair_queue import RepairQueue
        self.repair_queue = RepairQueue()
        # vids whose stripe the scan has seen complete at least once —
        # only those can report lost shards (mid-encode holes are not
        # losses)
        self._repair_seen_complete: set = set()
        self.repair_interval = config.env_float("SW_REPAIR_INTERVAL_S")
        self.at_risk_score = config.env_float("SW_REPAIR_AT_RISK_SCORE")
        self._repair_thread = threading.Thread(
            target=self._repair_loop, daemon=True,
            name="master-repair-queue") \
            if self.repair_interval > 0 else None
        self._pruner = threading.Thread(target=self._prune_loop, daemon=True,
                                        name="master-pruner")
        self._stop = threading.Event()
        # cron'd embedded shell (reference startAdminScripts,
        # master_server.go:187-253): ';'-separated command lines run
        # against this master on an interval, leader-only
        from ..shell.command_env import split_script
        self.maintenance_scripts = split_script(maintenance_scripts)
        self.maintenance_interval = float(maintenance_interval)
        self.maintenance_filer_url = maintenance_filer_url
        # volumes grown per growth event by replica copy count
        # (reference master.toml [master.volume_growth])
        self.growth_counts = dict(growth_counts or {})
        self._maintenance_runs = 0
        self._maintenance_thread = None
        if self.maintenance_scripts:
            self._maintenance_thread = threading.Thread(
                target=self._maintenance_loop, daemon=True,
                name="master-maintenance")
        # automatic vacuum + TTL expiry (reference
        # Topo.StartRefreshWritableVolumes, master_server.go:128 →
        # topology_vacuum.go:139); 0 disables
        self.vacuum_interval = float(vacuum_interval)
        self._vacuum_thread = threading.Thread(
            target=self._vacuum_loop, daemon=True,
            name="master-vacuum") \
            if self.vacuum_interval > 0 else None
        # hot→warm tiering: leader-gated background demotion of sealed
        # volumes into EC over the shared stripe transport
        # (server/tiering.py); enabled via SW_TIER_ENABLE
        from .tiering import VolumeTierer
        self.tierer = VolumeTierer(self)

        # raft HA (reference weed/server/raft_server.go): multi-master
        # when -peers is set; single-master otherwise (no raft at all)
        self.raft = None
        if peers:
            from ..topology.raft import RaftNode
            peer_list = [p.strip() for p in peers.split(",")
                         if p.strip()]
            if not raft_dir:
                # persistence must never silently vanish: a node that
                # forgets voted_for can grant two votes in one term and
                # elect two leaders (reference defaults -mdir to the OS
                # temp dir the same way)
                import tempfile
                raft_dir = os.path.join(tempfile.gettempdir(),
                                        "weed-tpu-raft")
            # snapshots must capture only COMMITTED state:
            # topology.max_volume_id is bumped optimistically before
            # propose (and rolled back on failure), so it can briefly
            # exceed any committed entry — _raft_committed_max_vid
            # tracks the apply stream instead
            self._raft_committed_max_vid = 0
            # file keys become raft-backed grants so a failover leader
            # can never re-issue an id (the reference reaches for etcd
            # for this, sequence/etcd_sequencer.go; this build already
            # has a consensus log). Installed BEFORE RaftNode so a
            # disk-restored snapshot's sequence_ceiling lands in it;
            # the lambda resolves self.raft lazily for the same reason.
            # An explicitly injected sequencer (e.g. EtcdSequencer,
            # which coordinates across masters on its own) wins.
            if sequencer is None:
                self.topology.sequencer = RaftSequencer(
                    lambda cmd: self.raft.propose(cmd))

            def _snapshot_state():
                state = {"max_volume_id": self._raft_committed_max_vid}
                seq = self.topology.sequencer
                if isinstance(seq, RaftSequencer):
                    state["sequence_ceiling"] = seq.ceiling()
                return state

            def _restore_state(st):
                self._apply_raft(
                    {"type": "max_volume_id",
                     "value": int(st.get("max_volume_id", 0))})
                self._apply_raft(
                    {"type": "sequence_ceiling",
                     "value": int(st.get("sequence_ceiling", 0))})

            self.raft = RaftNode(
                self.url, peer_list, self._apply_raft,
                state_dir=raft_dir,
                snapshot_state_fn=_snapshot_state,
                restore_fn=_restore_state)
            router.add("POST", "/raft/request_vote",
                       self.raft_request_vote)
            router.add("POST", "/raft/append_entries",
                       self.raft_append_entries)
            router.add("POST", "/raft/install_snapshot",
                       self.raft_install_snapshot)
            router.add("GET", "/raft/status", self.raft_status)

    # -- raft glue ---------------------------------------------------------
    def _apply_raft(self, command: dict):
        """Apply a committed raft command (reference
        topology/cluster_commands.go MaxVolumeIdCommand)."""
        if command.get("type") == "max_volume_id":
            value = int(command["value"])
            self._raft_committed_max_vid = max(
                getattr(self, "_raft_committed_max_vid", 0), value)
            with self.topology.lock:
                self.topology.max_volume_id = max(
                    self.topology.max_volume_id, value)
        elif command.get("type") == "sequence_ceiling":
            seq = self.topology.sequencer
            if isinstance(seq, RaftSequencer):
                seq.apply_ceiling(int(command["value"]),
                                  command.get("nonce"))

    def raft_request_vote(self, req: Request):
        return self.raft.handle_request_vote(req.json())

    def raft_append_entries(self, req: Request):
        return self.raft.handle_append_entries(req.json())

    def raft_install_snapshot(self, req: Request):
        return self.raft.handle_install_snapshot(req.json())

    def raft_status(self, req: Request):
        return self.raft.status()

    def is_leader(self) -> bool:
        return self.raft is None or self.raft.is_leader

    def leader_url(self) -> str:
        if self.raft is None:
            return self.url
        return self.raft.leader() or ""

    def _leader_forward(self, req: Request):
        """Proxy a request to the raft leader when this master is a
        follower (reference master_server.go proxyToLeader:155-185) —
        followers hold no topology (volume servers heartbeat only to
        the leader), so every data-affecting call must run there.
        Returns None when this node should handle the request itself."""
        if self.is_leader():
            return None
        if req.headers.get("X-Raft-Forwarded"):
            raise HttpError(503, "raft leadership unsettled, retry")
        leader = self.leader_url()
        if not leader:
            raise HttpError(503, "no raft leader elected yet")
        import json as _json
        import urllib.parse
        from .http_util import http_call
        q = urllib.parse.urlencode(req.query)
        url = f"http://{leader}{req.path}" + (f"?{q}" if q else "")
        headers = {"X-Raft-Forwarded": "1"}
        # the payload-shaping headers must survive the hop or a
        # multipart /submit arrives at the leader as opaque bytes
        for h in ("Content-Type", "Authorization"):
            v = req.headers.get(h)
            if v:
                headers[h] = v
        out = http_call(req.method, url, req.body or None, headers)
        return _json.loads(out or b"{}")

    def metrics_handler(self, req: Request):
        from ..stats.metrics import MASTER_GATHER, observe_repair_queue
        from .http_util import Response
        observe_repair_queue(self.repair_queue.snapshot())
        return Response(MASTER_GATHER.render().encode(),
                        content_type="text/plain; version=0.0.4")

    def _scrape_targets(self):
        with self.topology.lock:
            return [n.url for n in self.topology.all_nodes()]

    def cluster_metrics(self, req: Request):
        """Merged cluster exposition: counters/histograms summed across
        nodes, gauges per-node under a node= label. ``?refresh=1``
        forces a synchronous scrape sweep first (tests, impatient
        operators); otherwise the background loop's snapshots serve."""
        if req.query.get("refresh"):
            self.cluster_agg.scrape_once()
        return Response(self.cluster_agg.render().encode(),
                        content_type="text/plain; version=0.0.4")

    def cluster_health(self, req: Request):
        """Per-holder health fold of every node's ec_holder_* families
        (worst observer score wins) + per-node scrape freshness + the
        repair queue's open-incident / time-to-re-protection summary."""
        if req.query.get("refresh"):
            self.cluster_agg.scrape_once()
        out = self.cluster_agg.holder_health()
        out["repairs"] = self.repair_queue.summary()
        return out

    def cluster_repairs(self, req: Request):
        """Integrity-plane view: open incidents by priority, recently
        resolved ones with their time-to-re-protection, and queue
        counters. ``?refresh=1`` runs a topology/health scan first so
        tests and operators see lost shards without waiting a repair
        interval."""
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        if req.query.get("refresh"):
            self._repair_scan()
        return self.repair_queue.snapshot()

    def cluster_tiering(self, req: Request):
        """Hot→warm lifecycle view: per-volume demotion state
        (candidate → demoting → warm / failed), knob values, and pass
        counters. ``?scan=1`` runs one scan+demote pass synchronously —
        how tests and the bench drive a demotion without waiting a
        tier interval (and without needing SW_TIER_ENABLE's loop)."""
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        if req.query.get("scan"):
            self.tierer.run_pass()
        return self.tierer.snapshot()

    def cluster_scrub_report(self, req: Request):
        """Scrub corruption findings from volume servers. One incident
        per (volume, corrupt shard); an unattributed finding (locator
        could not pin a shard) opens one incident keyed shard=-1 so the
        exposure is still tracked."""
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        finding = req.json()
        vid = int(finding.get("volume", 0))
        shards = [int(s) for s in (finding.get("shards") or [])] or [-1]
        detected = finding.get("detected_at")
        opened = []
        for sid in shards:
            inc = self.repair_queue.report(
                "corruption", volume=vid, shard=sid,
                source=str(finding.get("source", "")),
                detail={"slabs": finding.get("slabs"),
                        "columns": finding.get("columns"),
                        "collection": finding.get("collection", "")},
                detected_at=float(detected) if detected else None)
            opened.append(inc.id)
        return {"volume": vid, "incidents": opened}

    def ui_handler(self, req: Request):
        """HTML status dashboard (reference master_ui/templates.go)."""
        from .http_util import Response
        from .status_ui import master_status_page
        return Response(master_status_page(self),
                        content_type="text/html; charset=utf-8")

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self.server.start()
        self._pruner.start()
        self.cluster_agg.start()
        if self.raft is not None:
            self.raft.start()
        if self._maintenance_thread is not None:
            self._maintenance_thread.start()
        if self._vacuum_thread is not None:
            self._vacuum_thread.start()
        if self._repair_thread is not None:
            self._repair_thread.start()
        self.tierer.start()
        return self

    def stop(self):
        self._stop.set()
        self.cluster_agg.stop()
        if self.raft is not None:
            self.raft.stop()
        self.server.stop()

    @property
    def url(self) -> str:
        return f"{self.host}:{self.port}"

    def _prune_loop(self):
        while not self._stop.wait(self.topology.pulse_seconds):
            self.topology.prune_dead_nodes()

    def _ttl_expired_volumes(self):
        """(vid, [node urls]) for TTL volumes whose content outlived its
        TTL (reference volume.expired() + the vacuum loop's expiry
        sweep). Empty volumes never expire — they are writable targets."""
        out = {}
        now = time.time()
        with self.topology.lock:
            for node in self.topology.all_nodes():
                for vid, vi in node.volumes.items():
                    ttl = TTL.from_uint32(vi.ttl or 0)
                    if ttl.minutes == 0 or vi.size == 0:
                        continue
                    if not vi.modified_at:
                        continue
                    # 10% grace past the TTL like the reference, so a
                    # volume isn't reaped while still serving tail reads
                    if now - vi.modified_at > ttl.minutes * 60 * 1.1:
                        out.setdefault(vid, []).append(node.url)
        return sorted(out.items())

    def _run_vacuum_pass(self, threshold: float = None,
                         reap_ttl: bool = False) -> dict:
        """One vacuum sweep; ``reap_ttl`` additionally deletes
        TTL-expired volumes — only the background loop passes it (a
        manual /vol/vacuum must never have destructive side effects the
        operator didn't ask for)."""
        threshold = threshold if threshold is not None \
            else self.garbage_threshold
        results = []
        for vid, nodes in self.topology.vacuum_candidates(threshold):
            ok = True
            for n in nodes:
                try:
                    post_json(f"http://{n.url}/admin/vacuum/compact"
                              f"?volume={vid}")
                except HttpError:
                    ok = False
                    break
            if ok:
                for n in nodes:
                    try:
                        post_json(f"http://{n.url}/admin/vacuum/commit"
                                  f"?volume={vid}")
                    except HttpError:
                        ok = False
            results.append({"volume": vid, "ok": ok})
        expired = []
        if reap_ttl:
            for vid, urls in self._ttl_expired_volumes():
                # stop assigns FIRST (readonly in every layout) so no
                # fid can be handed out for a volume dying under it —
                # but keep the registration until each replica's delete
                # actually succeeds: a popped-but-undeleted volume would
                # be orphaned forever (delta heartbeats only resend
                # CHANGED volumes, so the master would never relearn it)
                with self.topology.lock:
                    for layout in self.topology.layouts.values():
                        layout.set_volume_readonly(vid, True)
                reaped = []
                for u in urls:
                    try:
                        post_json(f"http://{u}/admin/delete_volume"
                                  f"?volume={vid}")
                    except HttpError:
                        continue  # still registered: retried next pass
                    reaped.append(u)
                    with self.topology.lock:
                        node = self.topology.find_node(u)
                        if node is None:
                            continue
                        node.volumes.pop(vid, None)
                        for layout in self.topology.layouts.values():
                            layout.unregister_volume(vid, node)
                        if self.topology.location_listener is not None:
                            self.topology.location_listener(
                                "deleted", vid, node.url,
                                node.public_url, node.fast_url)
                if reaped:
                    expired.append(vid)
        return {"vacuumed": results, "ttl_expired": expired}

    def _vacuum_loop(self):
        from ..util import glog
        while not self._stop.wait(self.vacuum_interval):
            if not self.is_leader():
                continue
            try:
                out = self._run_vacuum_pass(reap_ttl=True)
                if out["vacuumed"] or out["ttl_expired"]:
                    glog.V(0).infof("auto vacuum: %s", out)
            except Exception as e:  # noqa: BLE001 - keep the loop alive
                glog.V(0).infof("auto vacuum failed: %s", e)

    # -- repair queue drive (integrity plane) ------------------------------
    def _repair_scan(self):
        """Open/close incidents from what the master already knows:
        missing shards in the heartbeat-built topology and holders the
        health fold scores at-risk. Scrub corruption arrives separately
        via /cluster/scrub_report. Idempotent — repeat sightings
        collapse onto the open incident and keep its original
        detection time."""
        with self.topology.lock:
            shard_map = {vid: [[n.url for n in holders]
                               for holders in per_shard]
                         for vid, per_shard in
                         self.topology.ec_shard_map.items()}
            totals = {vid: sum(self.topology.ec_geometry(vid))
                      for vid in shard_map}
        for vid, per_shard in shard_map.items():
            if not any(per_shard):
                continue  # fully unregistered volume, not a shard loss
            # whole at the volume's own k + m (9, 14, 24 ...)
            total = totals[vid]
            present = sum(1 for holders in per_shard[:total] if holders)
            if present == total:
                self._repair_seen_complete.add(vid)
            # a hole is only a LOSS if the stripe was once whole: a
            # streaming encode registers shards incrementally, and
            # opening incidents mid-spread fires doomed rebuilds at a
            # half-built volume
            if vid not in self._repair_seen_complete:
                continue
            for sid in range(total):
                holders = per_shard[sid] if sid < len(per_shard) else []
                if holders:
                    self.repair_queue.resolve("lost_shard", volume=vid,
                                              shard=sid, via="remounted")
                else:
                    self.repair_queue.report("lost_shard", volume=vid,
                                             shard=sid, source=self.url)
        # volumes gone from the map entirely: their incidents are moot
        self._repair_seen_complete &= set(shard_map)
        for inc in list(self.repair_queue.snapshot()["open"]):
            if inc["kind"] == "lost_shard" \
                    and inc["volume"] not in shard_map:
                self.repair_queue.resolve("lost_shard",
                                          volume=inc["volume"],
                                          shard=inc["shard"],
                                          via="volume_removed")
        health = self.cluster_agg.holder_health().get("holders", {})
        for holder, h in health.items():
            score = float(h.get("score", 1.0))
            if score < self.at_risk_score:
                self.repair_queue.report(
                    "at_risk_holder", holder=holder, source=self.url,
                    detail={"score": round(score, 3)})
            elif score > self.at_risk_score + 0.1:  # hysteresis
                self.repair_queue.resolve("at_risk_holder",
                                          holder=holder, via="recovered")

    def _repair_loop(self):
        from ..util import glog
        while not self._stop.wait(self.repair_interval):
            if not self.is_leader():
                continue
            try:
                self._repair_scan()
                for _ in range(4):  # bounded drain per tick
                    inc = self.repair_queue.next_incident()
                    if inc is None:
                        break
                    self._drain_one(inc)
            except Exception as e:  # noqa: BLE001 - keep the loop alive
                glog.V(0).infof("repair loop failed: %s", e)

    def _drain_one(self, inc):
        """Drive one incident through the existing repair machinery:
        corruption → the holder quarantines + rebuilds the poisoned
        shard (/admin/ec/scrub_repair); lost shard → a surviving holder
        streams the missing shard back (/admin/ec/rebuild + mount)."""
        from ..util import glog
        vid = inc.volume
        shards = self.topology.lookup_ec_shards(vid) or {}
        collection = self.topology.ec_collections.get(vid, "")
        try:
            if inc.kind == "corruption":
                if inc.shard < 0 or not shards.get(inc.shard):
                    raise RuntimeError(
                        f"no holder for corrupt shard {vid}.{inc.shard}")
                target = shards[inc.shard][0]
                sources = {str(s): [u for u in urls if u != target]
                           for s, urls in shards.items() if s != inc.shard}
                post_json(
                    f"http://{target}/admin/ec/scrub_repair"
                    f"?volume={vid}&shard={inc.shard}"
                    f"&collection={collection}",
                    {"sources": sources}, timeout=300)
                self.repair_queue.resolve("corruption", volume=vid,
                                          shard=inc.shard,
                                          via="scrub_repair")
            elif inc.kind == "lost_shard":
                if not shards:
                    raise RuntimeError(f"no survivors for volume {vid}")
                # rebuild on a node already holding shards of this
                # volume — its local rows never cross the wire
                target = shards[min(shards)][0]
                sources = {str(s): urls for s, urls in shards.items()
                           if target not in urls}
                out = post_json(
                    f"http://{target}/admin/ec/rebuild"
                    f"?volume={vid}&collection={collection}",
                    {"sources": sources}, timeout=300)
                rebuilt = out.get("rebuilt") or []
                if not rebuilt:
                    raise RuntimeError(f"rebuild of {vid} restored "
                                       f"nothing")
                post_json(
                    f"http://{target}/admin/ec/mount?volume={vid}"
                    f"&collection={collection}"
                    f"&shards={','.join(map(str, rebuilt))}", {},
                    timeout=60)
                for sid in rebuilt:
                    self.repair_queue.resolve("lost_shard", volume=vid,
                                              shard=int(sid),
                                              via="rebuild")
        except Exception as e:  # noqa: BLE001 - back off, retry later
            self.repair_queue.attempt_failed(inc, str(e))
            glog.V(0).infof("repair of %s %s.%s failed: %s",
                            inc.kind, vid, inc.shard, e)

    def _maintenance_loop(self):
        """Run the configured shell scripts every interval (leader-only,
        like the reference's masterClient-gated script runner)."""
        import seaweedfs_tpu.shell  # noqa: F401 (registers commands)
        from ..shell.command_env import CommandEnv, run_command
        from ..util import glog
        while not self._stop.wait(self.maintenance_interval):
            if not self.is_leader():
                continue
            env = CommandEnv(self.url,
                             filer_url=self.maintenance_filer_url)
            # unattended cron: one wedged volume server must not stall
            # the loop for the interactive shell's 3600s admin budget
            env.admin_timeout = 900.0
            for line in self.maintenance_scripts:
                try:
                    run_command(env, line)
                except Exception as e:  # noqa: BLE001 - keep the cron alive
                    glog.V(0).infof("maintenance %r failed: %s", line, e)
            self._maintenance_runs += 1

    # -- handlers ----------------------------------------------------------
    def cluster_heartbeat(self, req: Request):
        # volume servers must register with the LEADER only (reference
        # master_grpc_server.go: topology lives on the leader; followers
        # hand back the leader address and the client re-targets)
        if not self.is_leader():
            return {"volume_size_limit":
                    self.topology.volume_size_limit,
                    "leader": self.leader_url(),
                    "not_leader": True}
        hb = req.json()
        ec_shards = {int(k): v
                     for k, v in (hb.get("ec_shards") or {}).items()}
        ec_collections = {int(k): v
                          for k, v in
                          (hb.get("ec_collections") or {}).items()}
        # each EC volume's own [k, m]; a holder that names none (an
        # older volume server) leaves its volumes at the default
        ec_geometries = {int(k): v
                         for k, v in
                         (hb.get("ec_geometries") or {}).items()}
        url = f"{hb.get('ip', '127.0.0.1')}:{hb.get('port', 0)}"
        seq = int(hb.get("seq") or 0)
        # asked before the topology's lock is taken: raft's lock is
        # above it in the order
        leader = self.leader_url() or self.url
        # a server's heartbeats may pass each other on the way here (its
        # pulse thread and a handler that pushes a change post side by
        # side): one collected before the state last applied is dropped,
        # check and apply one step under the topology's lock
        with self.topology.lock:
            node = self.topology.find_node(url)
            if node is not None and seq and seq < node.hb_seq:
                return {"stale": True,
                        "volume_size_limit":
                        self.topology.volume_size_limit,
                        "leader": leader}
            if hb.get("delta"):
                # incremental heartbeat (reference master_grpc_server.go:
                # 94-152): only new/changed/deleted volumes ride the wire.
                # An unknown node means we lost its registration (restart,
                # failover) — ask for a full resync instead of guessing.
                applied = self.topology.apply_heartbeat_delta(
                    url=url,
                    new_volumes=hb.get("new_volumes", []),
                    deleted_volumes=[int(v) for v in
                                     hb.get("deleted_volumes", [])],
                    ec_shards=ec_shards, ec_collections=ec_collections,
                    max_file_key=int(hb.get("max_file_key", 0)),
                    ec_geometries=ec_geometries)
                if not applied:
                    return {"resync": True,
                            "volume_size_limit":
                            self.topology.volume_size_limit,
                            "leader": leader}
            else:
                self.topology.register_heartbeat(
                    dc_id=hb.get("data_center", ""),
                    rack_id=hb.get("rack", ""),
                    ip=hb.get("ip", "127.0.0.1"),
                    port=int(hb.get("port", 0)),
                    public_url=hb.get("public_url", ""),
                    fast_url=hb.get("fast_url", ""),
                    max_volume_count=int(hb.get("max_volume_count", 7)),
                    volumes=hb.get("volumes", []),
                    ec_shards=ec_shards,
                    ec_collections=ec_collections,
                    max_file_key=int(hb.get("max_file_key", 0)),
                    ec_geometries=ec_geometries,
                )
            node = self.topology.find_node(url)
            if node is not None:
                node.hb_seq = max(node.hb_seq, seq)
                if hb.get("device"):
                    # the chip of a `-ec.backend tpu-own` server (no
                    # other names one): /cluster/status passes it on
                    node.device = hb["device"]
        out = {"volume_size_limit": self.topology.volume_size_limit,
               "leader": leader}
        if self.metrics_address:
            # reference master_grpc_server.go:75-77: the master decides
            # where and how often servers push metrics
            out["metrics_address"] = self.metrics_address
            out["metrics_interval_seconds"] = self.metrics_interval
        return out

    def cluster_goodbye(self, req: Request):
        """Clean volume-server shutdown: unregister immediately and push
        the deletions, instead of waiting for heartbeat expiry (the
        reference gets this for free from gRPC stream breakage,
        master_grpc_server.go:24-50). Leader-forwarded like every other
        topology mutation — a goodbye swallowed by a follower would
        leave the dead node routed until expiry."""
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        url = req.json().get("url", "")
        node = self.topology.find_node(url)
        if node is not None:
            self.topology.unregister_node(node)
        return {"removed": node is not None}

    def dir_assign(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        from ..topology.raft import NotLeaderError
        try:
            return self._dir_assign_local(req)
        except NotLeaderError as e:
            # deposed between the forward check and the sequencer's
            # raft grant: answer like the forward path would — a
            # retriable 503 carrying the new leader
            raise HttpError(
                503, f"leadership changed during assign; leader is "
                     f"{e.leader or 'unknown'}") from None
        except TimeoutError:
            raise HttpError(
                503, "raft commit timed out during assign; retry"
            ) from None

    def _dir_assign_local(self, req: Request):
        count = int(req.query.get("count", 1))
        collection = req.query.get("collection", "")
        replication = req.query.get("replication") \
            or self.default_replication
        ttl = TTL.parse(req.query.get("ttl", ""))
        preferred_dc = req.query.get("dataCenter", "")

        picked = self.topology.pick_for_write(collection, replication, ttl,
                                              count)
        if picked is None:
            with self.vg_lock:
                picked = self.topology.pick_for_write(
                    collection, replication, ttl, count)
                if picked is None:
                    try:
                        self._grow_volumes(collection, replication, ttl,
                                           preferred_dc)
                    except NoFreeSlots as e:
                        raise HttpError(
                            406, f"no free volumes: {e}") from None
                    picked = self.topology.pick_for_write(
                        collection, replication, ttl, count)
        if picked is None:
            raise HttpError(406, "no writable volumes")
        fid, cnt, node, _ = picked
        out = {"fid": fid, "url": node.url,
               "publicUrl": node.public_url, "count": cnt}
        if node.fast_url:
            # the holder's native data plane: plain uploads land there
            # without the Python server in the loop (off-fast-path
            # shapes bounce back via 307, which clients follow)
            out["fastUrl"] = node.fast_url
        if self.jwt_signing_key:
            # hand out a write token bound to this fid (reference
            # master_server_handlers.go + security/jwt.go GenJwt)
            from ..security.jwt import GenJwt
            out["auth"] = GenJwt(self.jwt_signing_key, fid)
        return out

    def _next_volume_id(self) -> int:
        """New volume id — a raft command in HA mode (reference
        Topology.NextVolumeId raising a MaxVolumeIdCommand,
        topology.go:115-122) so a new leader never reissues an id."""
        if self.raft is None:
            return self.topology.next_volume_id()
        with self.topology.lock:
            # bump before proposing: two concurrent Assign/grow requests
            # must read distinct values, not both propose max+1 (the raft
            # apply is max(), so the optimistic local bump converges)
            value = self.topology.max_volume_id + 1
            self.topology.max_volume_id = value
        try:
            self.raft.propose({"type": "max_volume_id", "value": value})
        except Exception:
            # roll back the optimistic bump (only if no later bump landed
            # on top) so a failed propose — e.g. NotLeaderError during a
            # transition — doesn't leave the counter inflated and
            # un-backed by any raft entry
            with self.topology.lock:
                if self.topology.max_volume_id == value:
                    self.topology.max_volume_id = value - 1
            raise
        return value

    def _grow_volumes(self, collection: str, replication: str, ttl: TTL,
                      preferred_dc: str = "", count: int = None):
        rp = ReplicaPlacement.parse(replication)
        # reference growth counts by copy type (volume_growth.go:39-53),
        # overridable via master.toml [master.volume_growth]
        if count is None:
            defaults = {1: 7, 2: 6, 3: 3}
            if rp.copy_count in defaults:
                count = self.growth_counts.get(
                    rp.copy_count, defaults[rp.copy_count])
            else:
                count = self.growth_counts.get("other", 1)
        grown = 0
        for _ in range(count):
            try:
                nodes = find_empty_slots(self.topology, rp, preferred_dc)
            except NoFreeSlots:
                if grown:
                    break
                raise
            vid = self._next_volume_id()
            ok = True
            for n in nodes:
                try:
                    post_json(
                        f"http://{n.url}/admin/assign_volume"
                        f"?volume={vid}&collection={collection}"
                        f"&replication={replication}&ttl={ttl}")
                except HttpError:
                    ok = False
                    break
            if ok:
                grown += 1
        return grown

    def vol_grow(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        collection = req.query.get("collection", "")
        replication = req.query.get("replication") \
            or self.default_replication
        ttl = TTL.parse(req.query.get("ttl", ""))
        count = int(req.query.get("count", 1))
        with self.vg_lock:
            grown = self._grow_volumes(collection, replication, ttl,
                                       req.query.get("dataCenter", ""),
                                       count)
        return {"count": grown}

    def _location_snapshot(self):
        with self.topology.lock:
            out = {}
            for node in self.topology.all_nodes():
                for vid in node.volumes:
                    out.setdefault(str(vid), []).append(
                        {"url": node.url, "publicUrl": node.public_url,
                         **({"fastUrl": node.fast_url}
                            if node.fast_url else {})})
            return out

    def cluster_watch(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        since = int(req.query.get("since", 0))
        timeout = min(float(req.query.get("timeout", 20)), 25.0)
        return self.watch_hub.wait(since, timeout)

    def dir_lookup(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        vid_s = req.query.get("volumeId", "")
        if "," in vid_s:
            vid_s = vid_s.split(",")[0]
        if not vid_s:
            raise HttpError(400, "volumeId required")
        vid = int(vid_s)
        locs = self.topology.lookup(req.query.get("collection", ""), vid)
        if not locs:
            raise HttpError(404, f"volume {vid} not found")
        return {"volumeId": vid_s,
                "locations": [
                    {"url": n.url, "publicUrl": n.public_url,
                     **({"fastUrl": n.fast_url} if n.fast_url else {})}
                    for n in locs]}

    def ec_lookup(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        vid = int(req.query.get("volumeId", 0))
        shards = self.topology.lookup_ec_shards(vid)
        if shards is None:
            raise HttpError(404, f"ec volume {vid} not found")
        k, m = self.topology.ec_geometry(vid)
        return {"volumeId": vid, "shards": shards,
                "data_shards": k, "parity_shards": m}

    def ec_status(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        """Full EC shard map: vid -> shard -> holder urls."""
        with self.topology.lock:
            return {"volumes": {
                str(vid): {
                    "collection": self.topology.ec_collections.get(vid, ""),
                    **dict(zip(("data_shards", "parity_shards"),
                               self.topology.ec_geometry(vid))),
                    "shards": {str(sid): [n.url for n in holders]
                               for sid, holders in enumerate(per_shard)
                               if holders},
                } for vid, per_shard in self.topology.ec_shard_map.items()}}

    def cluster_volumes(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        """Every volume replica: vid -> [{url, ...volume info}]."""
        out = {}
        with self.topology.lock:
            for node in self.topology.all_nodes():
                for vid, vi in list(node.volumes.items()):
                    d = vi.to_dict()
                    d["url"] = node.url
                    out.setdefault(str(vid), []).append(d)
        return {"volumes": out}

    def dir_status(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        return {"topology": self.topology.to_dict(),
                "volumeSizeLimit": self.topology.volume_size_limit,
                "version": "seaweedfs_tpu 0.1"}

    def _guard_check(self, req: Request):
        # cluster-internal planes demand a CA-verified client cert
        # under mutual TLS (reference tls.go RequireAndVerifyClientCert
        # on every gRPC service; /dir/* and UI stay public like the
        # reference's public HTTP port)
        from .http_util import require_client_cert
        if req.path.startswith(("/cluster/", "/raft/", "/vol/")):
            require_client_cert(req)
        if not self.guard.enabled:
            return
        p = req.path
        # only genuinely server-to-server channels are exempt; watch/
        # volumes/status/ec_lookup serve the same data as the guarded
        # lookups, so cluster nodes (volume servers, filers, gateways)
        # must be included in -whiteList like any other HTTP client
        if p in ("/cluster/heartbeat", "/cluster/goodbye",
                 "/cluster/scrub_report", "/metrics") \
                or p.startswith("/raft/"):
            return
        if not self.guard.allows(req.handler.client_address[0]):
            raise HttpError(403, "ip not in whitelist")

    def vol_status(self, req: Request):
        """Cluster-wide volume map (reference volumeStatusHandler +
        Topology.ToVolumeMap, topology_map.go:30)."""
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        with self.topology.lock:
            dcs = {}
            total_max = 0
            for dc in self.topology.data_centers.values():
                racks = {}
                for rack in dc.racks.values():
                    racks[rack.id] = {
                        f"{n.ip}:{n.port}":
                            [vi.to_dict() for vi in n.volumes.values()]
                        for n in rack.nodes.values()}
                    total_max += sum(n.max_volume_count
                                     for n in rack.nodes.values())
                dcs[dc.id] = racks
            used = sum(len(n.volumes)
                       for n in self.topology.all_nodes())
        return {"Version": "seaweedfs_tpu 0.1",
                "Volumes": {"Max": total_max,
                            "Free": total_max - used,
                            "DataCenters": dcs}}

    def stats_health(self, req: Request):
        return {"ok": True, "leader": self.is_leader()}

    def stats_memory(self, req: Request):
        """Process memory stats (reference statsMemoryHandler)."""
        from .http_util import process_memory_stats
        return process_memory_stats()

    def redirect_handler(self, req: Request):
        """GET /<fid> → 301 to a random holder, query preserved
        (reference redirectHandler, master_server_handlers_admin.go:101).
        Only fid-shaped paths redirect; anything else is a 404."""
        import random as _random
        from ..storage.types import parse_file_id
        try:
            vid, _, _ = parse_file_id(req.path.lstrip("/"))
        except ValueError:
            raise HttpError(404, f"no such path {req.path}") from None
        q = ("?" + req.raw_query) if req.raw_query else ""
        # followers hold no topology: bounce the client to the leader
        # with the SAME path (a JSON-proxying _leader_forward would eat
        # the 301)
        if not self.is_leader():
            leader = self.leader_url()
            if not leader:
                raise HttpError(503, "no leader")
            return Response(b"", 301, headers={
                "Location": f"http://{leader}{req.path}{q}"})
        locs = self.topology.lookup(req.query.get("collection", ""), vid)
        if not locs:
            raise HttpError(404, f"volume {vid} not found")
        node = _random.choice(locs)
        return Response(b"", 301, headers={
            "Location": f"http://{node.public_url}{req.path}{q}"})

    def cluster_status(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        return {"isLeader": self.is_leader(),
                "leader": self.leader_url() or self.url,
                "peers": self.raft.peers if self.raft else [],
                "nodes": [n.to_dict() for n in self.topology.all_nodes()]}

    def vol_vacuum(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        threshold = float(req.query.get("garbageThreshold",
                                        self.garbage_threshold))
        return self._run_vacuum_pass(threshold)

    def col_delete(self, req: Request):
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        collection = req.query.get("collection", "")
        if not collection:
            raise HttpError(400, "collection required")
        deleted = []
        for node in self.topology.all_nodes():
            for vid, vi in list(node.volumes.items()):
                if vi.collection == collection:
                    try:
                        post_json(f"http://{node.url}/admin/delete_volume"
                                  f"?volume={vid}")
                        deleted.append(vid)
                    except HttpError:
                        pass
        # drop layouts for the collection
        with self.topology.lock:
            for key in [k for k in self.topology.layouts
                        if k[0] == collection]:
                del self.topology.layouts[key]
        return {"deleted": sorted(set(deleted))}

    def submit(self, req: Request):
        """Convenience upload: assign + forward (reference /submit)."""
        fwd = self._leader_forward(req)
        if fwd is not None:
            return fwd
        filename, ctype, data = req.upload_payload()
        assign = self.dir_assign(req)
        headers = {}
        if assign.get("auth"):
            headers["Authorization"] = f"Bearer {assign['auth']}"
        result = post_multipart(
            f"http://{assign['url']}/{assign['fid']}", filename, data,
            ctype or "application/octet-stream", headers=headers)
        return {"fid": assign["fid"], "fileUrl":
                f"{assign['publicUrl']}/{assign['fid']}",
                "size": result.get("size", len(data))}
