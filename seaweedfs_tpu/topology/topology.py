"""Topology — the master's root cluster state.

Reference weed/topology/topology.go + topology_ec.go +
master_grpc_server.go heartbeat handling: registers volume servers from
heartbeats, tracks per-layout writable volumes and the EC shard map, hands
out file ids (sequencer), and scans for vacuum candidates.
"""

from __future__ import annotations

import os
import random
import threading
from ..util.locks import make_lock, make_rlock
import time
from typing import Dict, List, Optional, Tuple

from ..storage.types import TTL, ReplicaPlacement
from .node import DataCenter, DataNode, VolumeInfo
from .volume_layout import VolumeLayout


class Sequencer:
    """In-memory monotonically increasing file-key generator
    (reference weed/sequence/memory_sequencer.go)."""

    def __init__(self, start: int = 1):
        self._counter = start
        self._lock = make_lock("topology.Sequencer._lock")

    def next_file_id(self, count: int = 1) -> int:
        with self._lock:
            start = self._counter
            self._counter += count
            return start

    def set_max(self, seen: int):
        with self._lock:
            if seen >= self._counter:
                self._counter = seen + 1


class RaftSequencer(Sequencer):
    """File-key generator whose allocations survive master failover —
    the HA role the reference fills with its etcd sequencer
    (weed/sequence/etcd_sequencer.go), built on this cluster's own raft
    log instead of an external store. Like the etcd variant it grants
    keys in blocks (one consensus round-trip amortized over ``block``
    ids), committing a rising "sequence ceiling" to the log; every
    master applies the ceiling, so a new leader always starts above
    every id any previous leader could have handed out.

    Concurrency contract: ``propose_fn`` blocks until commit and the
    raft apply runs ``apply_ceiling`` (possibly on another thread, or
    reentrantly on this one for a single-node cluster), so this class
    NEVER holds its lock across a propose call.

    A node only hands out ids from grants it proposed itself
    (``_grant_end``): applied ceilings from other leaders advance
    ``_ceiling`` but never open a local allocation window, which is
    what makes failover safe — ids at or below a remote ceiling may
    already be in use.
    """

    def __init__(self, propose_fn, block: int = 10000):
        super().__init__()
        self._propose = propose_fn
        self._block = int(block)
        self._ceiling = 0     # highest committed ceiling (any leader)
        self._grant_end = 0   # top of THIS node's own committed grant
        self._nonce = 0
        # process-unique prefix: nonces ride the replicated log, so two
        # masters' counters must never mint the same nonce (id() +
        # counter can coincide across identical processes — a foreign
        # entry matching a local pending nonce would be adopted as a
        # grant and collide file ids)
        import uuid
        self._nonce_prefix = uuid.uuid4().hex
        self._pending: set = set()  # nonces of my in-flight proposals

    def next_file_id(self, count: int = 1) -> int:
        while True:
            with self._lock:
                if self._counter + count - 1 <= self._grant_end:
                    start = self._counter
                    self._counter += count
                    return start
                need = max(self._block, count)
                target = max(self._ceiling, self._grant_end,
                             self._counter - 1) + need
                self._nonce += 1
                nonce = f"{self._nonce_prefix}-{self._nonce}"
                self._pending.add(nonce)
            # Outside the lock: propose blocks until commit and the
            # apply callback needs the lock. Raises NotLeaderError on a
            # follower — Assign is leader-only, callers redirect.
            # The grant's BASE is decided in apply_ceiling at commit
            # order, not here: a fresh leader may propose before
            # applying the previous leader's entries, and a
            # propose-time base would overlap that leader's grant.
            try:
                self._propose({"type": "sequence_ceiling",
                               "value": target, "nonce": nonce})
            finally:
                with self._lock:
                    self._pending.discard(nonce)
            # loop: if the apply granted us room, allocate; if a
            # foreign ceiling swallowed the whole range (empty grant),
            # re-propose above the now-visible ceiling

    def apply_ceiling(self, value: int, nonce: str = None):
        """Raft apply hook: a committed ceiling from any master. When
        ``nonce`` identifies one of THIS node's in-flight proposals,
        the range (ceiling-before-apply, value] becomes its exclusive
        allocation grant — commit order makes that base authoritative."""
        with self._lock:
            if nonce is not None and nonce in self._pending:
                base = self._ceiling
                if base < value:
                    if base > self._grant_end:
                        # cleared a foreign ceiling: jump the counter
                        # past ids other leaders may have issued
                        self._counter = max(self._counter, base + 1)
                    self._grant_end = max(self._grant_end, value)
            if value > self._ceiling:
                self._ceiling = value

    def ceiling(self) -> int:
        with self._lock:
            return self._ceiling


class EtcdSequencer(Sequencer):
    """File-key generator backed by an EXTERNAL etcd — the reference's
    exact etcd-sequencer slot (weed/sequence/etcd_sequencer.go): grab
    key blocks by compare-and-swapping a shared counter key upward (one
    etcd round trip amortized over `block` ids), so any number of
    masters sharing the etcd can never mint the same id; persist the
    granted ceiling to <meta_dir>/sequencer.dat like the reference, and
    seed etcd up to the file's value at boot (a wiped etcd cannot
    roll ids backwards under a surviving master).

    The raft-backed sequencer (RaftSequencer) fills this HA role
    without an external dependency; this variant exists for operators
    who already run etcd and want the reference's topology.
    """

    KEY = b"/seaweedfs/master/sequence"
    DEFAULT_BLOCK = 500  # reference DefaultEtcdSteps

    def __init__(self, addr: str, user: str = "", password: str = "",
                 meta_dir: str = "", block: int = DEFAULT_BLOCK,
                 api_prefix: str = "/v3"):
        super().__init__()
        # the etcd wire client lives with the etcd filer store; the
        # sequencer is a second consumer of the same gateway protocol
        from ..filer.etcd_store import EtcdClient
        self._client = EtcdClient.from_addr(addr, user=user,
                                            password=password,
                                            api_prefix=api_prefix)
        if user:
            self._client.authenticate()
        self._block = max(1, int(block))
        self._window_end = 0  # exclusive top of OUR granted window
        self._seq_file = os.path.join(meta_dir, "sequencer.dat") \
            if meta_dir else ""
        seed = 0
        if self._seq_file and os.path.exists(self._seq_file):
            try:
                with open(self._seq_file) as f:
                    seed = int(f.read().strip() or "0")
            except ValueError:
                seed = 0
        if seed:
            self._raise_etcd_to(seed)

    # -- etcd CAS ---------------------------------------------------------

    def _read_current(self):
        kvs = self._client.range(self.KEY)
        if not kvs:
            return None
        try:
            return int(kvs[0][1])
        except ValueError:
            raise RuntimeError(
                f"etcd sequence key {self.KEY!r} holds non-integer "
                f"{kvs[0][1]!r}")

    def _raise_etcd_to(self, floor: int):
        """CAS the shared counter up to at least `floor` (no grant)."""
        while True:
            cur = self._read_current()
            if cur is not None and cur >= floor:
                return
            expect = None if cur is None else str(cur).encode()
            if self._client.put_if(self.KEY, expect,
                                   str(floor).encode()):
                return

    def _grant(self, need: int) -> int:
        """CAS a block of `need` ids; returns the window base
        (exclusive — we own (base, base+need])."""
        while True:
            cur = self._read_current()
            base = cur or 0
            expect = None if cur is None else str(cur).encode()
            if self._client.put_if(self.KEY, expect,
                                   str(base + need).encode()):
                if self._seq_file:
                    tmp = self._seq_file + ".tmp"
                    with open(tmp, "w") as f:
                        f.write(str(base + need))
                    os.replace(tmp, self._seq_file)
                return base

    # -- Sequencer --------------------------------------------------------

    def next_file_id(self, count: int = 1) -> int:
        with self._lock:
            if self._counter + count - 1 < self._window_end:
                start = self._counter
                self._counter += count
                return start
            need = max(self._block, count)
            base = self._grant(need)
            start = max(base + 1, self._counter)
            if start + count - 1 > base + need:
                # local counter (via set_max) sits above even the fresh
                # grant: push etcd up and regrant from there
                self._raise_etcd_to(start - 1)
                base = self._grant(need)
                start = max(base + 1, self._counter)
            self._counter = start + count
            self._window_end = base + need + 1
            return start

    def set_max(self, seen: int):
        with self._lock:
            if seen < self._counter:
                return
            if seen < self._window_end - 1:
                self._counter = seen + 1
                return
            self._counter = seen + 1
            self._window_end = 0  # force a regrant above `seen`
        self._raise_etcd_to(seen)

    def close(self):
        self._client.close()


class Topology:
    def __init__(self, volume_size_limit: int = 30 * 1024 * 1024 * 1024,
                 pulse_seconds: int = 5, sequencer: Sequencer = None):
        self.data_centers: Dict[str, DataCenter] = {}
        self.volume_size_limit = volume_size_limit
        self.pulse_seconds = pulse_seconds
        self.sequencer = sequencer or Sequencer()
        self.layouts: Dict[Tuple[str, str, int], VolumeLayout] = {}
        # vid -> shard_id -> [DataNode] (reference topology_ec.go ecShardMap)
        self.ec_shard_map: Dict[int, List[List[DataNode]]] = {}
        self.ec_collections: Dict[int, str] = {}
        # vid -> (k, m), from the holders' heartbeats (ec_geometries)
        self.ec_geometries: Dict[int, Tuple[int, int]] = {}
        self.max_volume_id = 0
        # optional ("new"|"deleted", vid, url, public_url) callback — the
        # master wires its watch hub here to push location deltas
        self.location_listener = None
        self.lock = make_rlock("topology.lock")

    # -- tree --------------------------------------------------------------
    def get_or_create_dc(self, dc_id: str) -> DataCenter:
        with self.lock:
            dc = self.data_centers.get(dc_id)
            if dc is None:
                dc = DataCenter(dc_id)
                self.data_centers[dc_id] = dc
            return dc

    def all_nodes(self) -> List[DataNode]:
        return [n for dc in self.data_centers.values()
                for n in dc.all_nodes()]

    def find_node(self, url: str) -> Optional[DataNode]:
        for n in self.all_nodes():
            if n.url == url:
                return n
        return None

    # -- layouts -----------------------------------------------------------
    def get_layout(self, collection: str, replication: str,
                   ttl: int) -> VolumeLayout:
        key = (collection, replication, ttl)
        with self.lock:
            layout = self.layouts.get(key)
            if layout is None:
                layout = VolumeLayout(ReplicaPlacement.parse(replication),
                                      ttl, self.volume_size_limit)
                self.layouts[key] = layout
            return layout

    # -- heartbeat registration (reference master_grpc_server.go:20-176) ---
    def register_heartbeat(self, dc_id: str, rack_id: str, ip: str,
                           port: int, public_url: str,
                           max_volume_count: int,
                           volumes: List[dict],
                           ec_shards: Dict[int, int] = None,
                           ec_collections: Dict[int, str] = None,
                           max_file_key: int = 0,
                           fast_url: str = "",
                           ec_geometries: Dict[int, tuple] = None
                           ) -> DataNode:
        with self.lock:
            dc = self.get_or_create_dc(dc_id or "DefaultDataCenter")
            rack = dc.get_or_create_rack(rack_id or "DefaultRack")
            node = rack.get_or_create_node(ip, port, public_url,
                                           max_volume_count)
            node.last_seen = time.time()
            node.fast_url = fast_url
            self.sequencer.set_max(max_file_key)

            infos = [VolumeInfo.from_dict(v) for v in volumes]
            old_vids = set(node.volumes)
            new_vids = {vi.id for vi in infos}
            node.update_volumes(infos)
            for vi in infos:
                self.max_volume_id = max(self.max_volume_id, vi.id)
                layout = self.get_layout(vi.collection, vi.replica_placement,
                                         vi.ttl)
                layout.register_volume(vi, node)
            for vid in old_vids - new_vids:
                for layout in self.layouts.values():
                    layout.unregister_volume(vid, node)
            # push VolumeLocation deltas to watch subscribers (reference
            # master_grpc_server.go:94-152 heartbeat delta broadcast)
            if self.location_listener is not None:
                for vid in new_vids - old_vids:
                    self.location_listener("new", vid, node.url,
                                           node.public_url,
                                           node.fast_url)
                for vid in old_vids - new_vids:
                    self.location_listener("deleted", vid, node.url,
                                           node.public_url,
                                           node.fast_url)

            if ec_shards is not None:
                node.update_ec_shards(ec_shards, ec_collections or {},
                                      ec_geometries)
                self._sync_ec_shards(node)
            return node

    def apply_heartbeat_delta(self, url: str, new_volumes: List[dict],
                              deleted_volumes: List[int],
                              ec_shards: Dict[int, int] = None,
                              ec_collections: Dict[int, str] = None,
                              max_file_key: int = 0,
                              ec_geometries: Dict[int, tuple] = None
                              ) -> bool:
        """Incremental registration (reference master_grpc_server.go
        IncrementalHeartbeat path). Returns False when the node is
        unknown — the caller must then request a full resync."""
        with self.lock:
            node = self.find_node(url)
            if node is None:
                return False
            node.last_seen = time.time()
            self.sequencer.set_max(max_file_key)
            for v in new_volumes:
                vi = VolumeInfo.from_dict(v)
                was_known = vi.id in node.volumes
                node.volumes[vi.id] = vi
                self.max_volume_id = max(self.max_volume_id, vi.id)
                layout = self.get_layout(vi.collection,
                                         vi.replica_placement, vi.ttl)
                layout.register_volume(vi, node)
                if not was_known and self.location_listener is not None:
                    self.location_listener("new", vi.id, node.url,
                                           node.public_url,
                                           node.fast_url)
            for vid in deleted_volumes:
                was_present = node.volumes.pop(vid, None) is not None
                for layout in self.layouts.values():
                    layout.unregister_volume(vid, node)
                # a delta whose ack was lost gets resent: only a volume
                # we actually knew may broadcast a deletion, or watch
                # subscribers see duplicate events every pulse
                if was_present and self.location_listener is not None:
                    self.location_listener("deleted", vid, node.url,
                                           node.public_url,
                                           node.fast_url)
            if ec_shards is not None:
                node.update_ec_shards(ec_shards, ec_collections or {},
                                      ec_geometries)
                self._sync_ec_shards(node)
            return True

    def _sync_ec_shards(self, node: DataNode):
        # rebuild this node's contribution to the ec shard map
        for vid, per_shard in self.ec_shard_map.items():
            for holders in per_shard:
                if node in holders:
                    holders.remove(node)
        self._drop_empty_ec_volumes()
        for vid, bits in node.ec_shards.items():
            # a holder that names the volume's geometry wins over one
            # that names none (an older volume server)
            if vid in node.ec_shard_geometries or \
                    vid not in self.ec_geometries:
                self.ec_geometries[vid] = node.ec_geometry(vid)
            k, m = self.ec_geometries[vid]
            per_shard = self.ec_shard_map.setdefault(vid, [])
            # one holder list a shard of the volume's own k + m (and of
            # any id a holder reports beyond it)
            while len(per_shard) < max(k + m, bits.bit_length()):
                per_shard.append([])
            self.ec_collections[vid] = \
                node.ec_shard_collections.get(vid, "")
            self.max_volume_id = max(self.max_volume_id, vid)
            for sid in bits.shard_ids():
                if node not in per_shard[sid]:
                    per_shard[sid].append(node)

    def _drop_empty_ec_volumes(self):
        for vid in [v for v, per_shard in self.ec_shard_map.items()
                    if not any(per_shard)]:
            del self.ec_shard_map[vid]
            self.ec_collections.pop(vid, None)
            self.ec_geometries.pop(vid, None)

    def unregister_node(self, node: DataNode):
        """Heartbeat stream broke: drop the node and its volumes."""
        with self.lock:
            for layout in self.layouts.values():
                for vid in list(node.volumes):
                    layout.set_volume_unavailable(vid, node)
            # broadcast the dead node's locations as deleted (reference
            # master_grpc_server.go:24-50 onDisconnect)
            if self.location_listener is not None:
                for vid in list(node.volumes):
                    self.location_listener("deleted", vid, node.url,
                                           node.public_url,
                                           node.fast_url)
            for per_shard in self.ec_shard_map.values():
                for holders in per_shard:
                    if node in holders:
                        holders.remove(node)
            self._drop_empty_ec_volumes()
            if node.rack:
                node.rack.nodes.pop(node.url, None)

    def prune_dead_nodes(self, timeout: float = None) -> List[DataNode]:
        timeout = timeout or self.pulse_seconds * 5
        dead = [n for n in self.all_nodes()
                if time.time() - n.last_seen > timeout]
        for n in dead:
            self.unregister_node(n)
        return dead

    # -- assignment --------------------------------------------------------
    def next_volume_id(self) -> int:
        with self.lock:
            self.max_volume_id += 1
            return self.max_volume_id

    def pick_for_write(self, collection: str, replication: str,
                       ttl: TTL, count: int = 1) -> Optional[tuple]:
        """-> (fid, count, node, all_replica_nodes) or None."""
        layout = self.get_layout(collection, replication, ttl.to_uint32())
        picked = layout.pick_for_write()
        if picked is None:
            return None
        vid, locs = picked
        key = self.sequencer.next_file_id(count)
        cookie = random.getrandbits(32)
        from ..storage.types import format_file_id
        fid = format_file_id(vid, key, cookie)
        return fid, count, locs[0], locs

    def lookup(self, collection: str, vid: int) -> Optional[List[DataNode]]:
        with self.lock:
            for (coll, _, _), layout in self.layouts.items():
                if collection and coll != collection:
                    continue
                locs = layout.lookup(vid)
                if locs:
                    return locs
        # EC volumes resolve via the shard map
        per_shard = self.ec_shard_map.get(vid)
        if per_shard:
            nodes = []
            for holders in per_shard:
                for n in holders:
                    if n not in nodes:
                        nodes.append(n)
            return nodes or None
        return None

    def ec_geometry(self, vid: int) -> Tuple[int, int]:
        """(k, m) of an EC volume as its holders report it; the default
        10 + 4 for one no heartbeat has named a geometry for."""
        from ..ec.constants import DATA_SHARDS, PARITY_SHARDS
        return self.ec_geometries.get(vid, (DATA_SHARDS, PARITY_SHARDS))

    def lookup_ec_shards(self, vid: int) -> Optional[dict]:
        with self.lock:
            per_shard = self.ec_shard_map.get(vid)
            if not per_shard:
                return None
            return {sid: [n.url for n in holders]
                    for sid, holders in enumerate(per_shard) if holders}

    # -- vacuum scan (reference topology_vacuum.go) ------------------------
    def vacuum_candidates(self, garbage_threshold: float = 0.3
                          ) -> List[Tuple[int, List[DataNode]]]:
        out = []
        with self.lock:
            seen = set()
            for node in self.all_nodes():
                for vi in node.volumes.values():
                    if vi.id in seen or vi.read_only:
                        continue
                    if vi.size > 0 and \
                            vi.deleted_byte_count / max(vi.size, 1) \
                            > garbage_threshold:
                        layout = self.get_layout(
                            vi.collection, vi.replica_placement, vi.ttl)
                        locs = layout.lookup(vi.id) or [node]
                        out.append((vi.id, locs))
                        seen.add(vi.id)
        return out

    def to_dict(self) -> dict:
        with self.lock:
            return {
                "max_volume_id": self.max_volume_id,
                "data_centers": {
                    dc.id: {
                        rack.id: {n.url: n.to_dict()
                                  for n in rack.all_nodes()}
                        for rack in dc.racks.values()
                    } for dc in self.data_centers.values()
                },
                "layouts": [layout.to_dict()
                            for layout in self.layouts.values()],
                "ec_volumes": sorted(self.ec_shard_map),
            }
