"""Hot→warm write-through tiering — the f4 lifecycle seam.

The reference architecture (f4: replicated-hot Haystack volumes age
into erasure-coded warm storage) has no drain window: a volume being
demoted KEEPS serving reads from its hot replicas the whole time. This
module is the master-side driver of that lifecycle:

  * a leader-gated scan (``SW_TIER_INTERVAL_S``) walks the heartbeat
    topology for sealed volumes — readonly, or past
    ``SW_TIER_FULL_FRAC`` of the size limit — that have gone
    unmodified for ``SW_TIER_AGE_S`` seconds;
  * each candidate is demoted through the shell's encode flow over the
    shared stripe transport (``ec/transport.py``): freeze replicas →
    streaming encode+spread paced at ``SW_TIER_RATE_MBPS`` → mount EC
    shards → delete the hot replicas. Until that final delete, every
    read hits the hot copy; after it, reads come off the EC stripe
    (degraded-read path included) — the flip is the replica delete,
    and there is never a moment with neither copy mounted;
  * per-volume demotion state is served at ``GET /cluster/tiering``.

New client writes are never blocked: the demoted volume was sealed, so
assigns already route to other writable volumes; a failed demotion
unwinds (shards deleted, replicas thawed) inside ``do_ec_encode``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from ..util import config, glog
from ..util.locks import make_lock

# lifecycle states surfaced at /cluster/tiering
CANDIDATE = "candidate"
DEMOTING = "demoting"
WARM = "warm"
FAILED = "failed"


class VolumeTierer:
    """Background demotion driver owned by a MasterServer. The loop
    only acts while its master is the raft leader (followers hold no
    topology); a failover restarts the scan from the new leader's
    heartbeat-built view, and the ``do_ec_encode`` unwind discipline
    makes a half-finished demotion safe to retry."""

    def __init__(self, master):
        self.master = master
        self.enabled = config.env_bool("SW_TIER_ENABLE")
        self.interval = config.env_float("SW_TIER_INTERVAL_S")
        self.age_s = config.env_float("SW_TIER_AGE_S")
        self.concurrency = max(1, config.env_int("SW_TIER_CONCURRENCY"))
        self.rate_mbps = config.env_float("SW_TIER_RATE_MBPS")
        self.full_frac = config.env_float("SW_TIER_FULL_FRAC")
        self._lock = make_lock("tiering.VolumeTierer._lock")
        # vid -> {"state", "collection", "hot_bytes", ...}; the whole
        # dict IS the /cluster/tiering payload
        self._volumes: Dict[int, dict] = {}
        self._inflight: set = set()
        self.scans = 0
        self.demotions_ok = 0
        self.demotions_failed = 0
        self._thread: Optional[threading.Thread] = None
        if self.enabled and self.interval > 0:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="master-tierer")

    # -- wiring ------------------------------------------------------------
    def start(self):
        if self._thread is not None:
            self._thread.start()

    def _loop(self):
        while not self.master._stop.wait(self.interval):
            if not self.master.is_leader():
                continue
            try:
                self.run_pass()
            except Exception as e:  # noqa: BLE001 - keep the loop alive
                glog.V(0).infof("tier scan failed: %s", e)

    # -- candidate scan ----------------------------------------------------
    def _sealed_volumes(self) -> Dict[int, dict]:
        """Non-EC volumes whose every replica is sealed (readonly or
        past the full fraction) and old enough: vid -> summary."""
        topo = self.master.topology
        now = time.time()
        out: Dict[int, dict] = {}
        with topo.lock:
            limit = topo.volume_size_limit
            ec_vids = set(topo.ec_shard_map)
            by_vid: Dict[int, list] = {}
            for node in topo.all_nodes():
                for vid, vi in node.volumes.items():
                    by_vid.setdefault(vid, []).append(vi)
        for vid, infos in by_vid.items():
            if vid in ec_vids:
                continue
            vi = infos[0]
            sealed = vi.read_only or (
                limit and vi.size >= self.full_frac * limit)
            if not sealed:
                continue
            if vi.modified_at and now - vi.modified_at < self.age_s:
                continue
            out[vid] = {"collection": vi.collection or "",
                        "hot_bytes": int(vi.size),
                        "replicas": len(infos)}
        return out

    def run_pass(self) -> Dict[int, str]:
        """One scan+demote pass; returns {vid: state} for what it
        touched. Called by the loop, and directly by tests/bench (the
        loop thread only exists when SW_TIER_ENABLE is on)."""
        self.scans += 1
        sealed = self._sealed_volumes()
        with self._lock:
            for vid, summary in sealed.items():
                st = self._volumes.get(vid)
                if st is None or st["state"] == FAILED:
                    # failed demotions re-enter as candidates: the
                    # unwind thawed the replicas, nothing is lost
                    self._volumes[vid] = dict(summary, state=CANDIDATE)
            todo = [vid for vid, st in sorted(self._volumes.items())
                    if st["state"] == CANDIDATE
                    and vid not in self._inflight]
            todo = todo[:max(0, self.concurrency - len(self._inflight))]
            for vid in todo:
                self._inflight.add(vid)
                self._volumes[vid]["state"] = DEMOTING
        if not todo:
            self._export_gauges()
            return {}
        threads = [threading.Thread(
            target=self._demote_one, args=(vid,), daemon=True,
            name=f"tier-demote-{vid}") for vid in todo]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self._export_gauges()
        with self._lock:
            return {vid: self._volumes[vid]["state"] for vid in todo}

    # -- one demotion ------------------------------------------------------
    def _demote_one(self, vid: int):
        """Hot→warm via the shell encode verb: freeze → streaming
        encode+spread (paced) → mount → delete hot replicas. Reads are
        served by the hot copy until that last step — the no-drain
        flip."""
        import sys

        from ..shell.command_ec import do_ec_encode
        from ..shell.command_env import CommandEnv
        from ..stats.metrics import (MASTER_TIER_BYTES,
                                     MASTER_TIER_DEMOTIONS,
                                     MASTER_TIER_MBPS_GAUGE,
                                     MASTER_TIER_SECONDS)
        with self._lock:
            st = self._volumes[vid]
            hot_bytes = st.get("hot_bytes", 0)
            st["started_at"] = time.time()
        env = CommandEnv(self.master.url, out=sys.stderr)
        env.admin_timeout = 900.0
        timings: Dict = {}
        t0 = time.perf_counter()
        try:
            do_ec_encode(env, vid, timings=timings,
                         rate_mbps=self.rate_mbps)
        except Exception as e:  # noqa: BLE001 - recorded, retried next scan
            glog.V(0).infof("tier demotion of volume %s failed: %s",
                            vid, e)
            with self._lock:
                st.update(state=FAILED, error=str(e)[:300],
                          finished_at=time.time())
                self._inflight.discard(vid)
                self.demotions_failed += 1
            MASTER_TIER_DEMOTIONS.inc("failed")
            return
        wall = time.perf_counter() - t0
        mbps = (hot_bytes / wall / 1e6) if wall > 0 else 0.0
        with self._lock:
            st.update(state=WARM, wall_s=round(wall, 3),
                      demote_mbps=round(mbps, 2),
                      overlap_frac=timings.get("overlap_frac", 0.0),
                      trace_id=timings.get("trace_id", ""),
                      finished_at=time.time())
            self._inflight.discard(vid)
            self.demotions_ok += 1
        MASTER_TIER_DEMOTIONS.inc("ok")
        MASTER_TIER_SECONDS.inc(amount=wall)
        if hot_bytes:
            MASTER_TIER_BYTES.inc(amount=hot_bytes)
        MASTER_TIER_MBPS_GAUGE.set(round(mbps, 2))
        glog.V(0).infof(
            "volume %s demoted hot→warm: %.1f MB in %.2fs (%.1f MB/s, "
            "rate cap %s)", vid, hot_bytes / 1e6, wall, mbps,
            self.rate_mbps or "off")

    # -- observability -----------------------------------------------------
    def _export_gauges(self):
        from ..stats.metrics import MASTER_TIER_VOLUMES_GAUGE
        counts = {CANDIDATE: 0, DEMOTING: 0, WARM: 0, FAILED: 0}
        with self._lock:
            for st in self._volumes.values():
                counts[st["state"]] = counts.get(st["state"], 0) + 1
        for state, n in counts.items():
            MASTER_TIER_VOLUMES_GAUGE.set(n, state)

    def snapshot(self) -> dict:
        """The /cluster/tiering payload."""
        with self._lock:
            volumes = {str(vid): dict(st)
                       for vid, st in self._volumes.items()}
        return {
            "enabled": self.enabled,
            "scans": self.scans,
            "demotions_ok": self.demotions_ok,
            "demotions_failed": self.demotions_failed,
            "knobs": {
                "interval_s": self.interval,
                "age_s": self.age_s,
                "concurrency": self.concurrency,
                "rate_mbps": self.rate_mbps,
                "full_frac": self.full_frac,
            },
            "volumes": volumes,
        }
