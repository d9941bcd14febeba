"""Topology tree nodes (reference weed/topology/node.go, data_center.go,
rack.go, data_node.go).

Volume slots: a node's capacity is max_volume_count; EC shards consume
fractional slots (reference counts one EC shard as 1/10 of a volume —
store.go:99-112).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from ..ec.constants import DATA_SHARDS, PARITY_SHARDS
from ..ec.shard_bits import ShardBits


class VolumeInfo:
    """Master's view of one volume replica on one node."""

    __slots__ = ("id", "collection", "size", "file_count", "delete_count",
                 "deleted_byte_count", "read_only", "replica_placement",
                 "ttl", "version", "compact_revision", "modified_at")

    def __init__(self, id: int, collection: str = "", size: int = 0,
                 file_count: int = 0, delete_count: int = 0,
                 deleted_byte_count: int = 0, read_only: bool = False,
                 replica_placement: str = "000", ttl: int = 0,
                 version: int = 3, compact_revision: int = 0,
                 modified_at: float = 0):
        self.id = id
        self.collection = collection
        self.size = size
        self.file_count = file_count
        self.delete_count = delete_count
        self.deleted_byte_count = deleted_byte_count
        self.read_only = read_only
        self.replica_placement = replica_placement
        self.ttl = ttl
        self.version = version
        self.compact_revision = compact_revision
        self.modified_at = modified_at

    @classmethod
    def from_dict(cls, d: dict) -> "VolumeInfo":
        return cls(**{k: d[k] for k in
                      ("id", "collection", "size", "file_count",
                       "delete_count", "deleted_byte_count", "read_only",
                       "replica_placement", "ttl", "version",
                       "compact_revision", "modified_at") if k in d})

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


class DataNode:
    """One volume server."""

    def __init__(self, ip: str, port: int, public_url: str = "",
                 max_volume_count: int = 7):
        self.ip = ip
        self.port = port
        self.public_url = public_url or f"{ip}:{port}"
        # native read plane, when the server advertises one (empty
        # otherwise); read paths prefer it for plain needle GETs
        self.fast_url = ""
        # the chip the server's codecs compute on, as its heartbeat
        # names it (`-ec.backend tpu-own`); None: it names none
        self.device: Optional[dict] = None
        # `seq` of the heartbeat last applied (0: the server sends none)
        self.hb_seq = 0
        self.max_volume_count = max_volume_count
        self.volumes: Dict[int, VolumeInfo] = {}
        self.ec_shards: Dict[int, ShardBits] = {}  # vid -> bits
        self.ec_shard_collections: Dict[int, str] = {}
        # vid -> (k, m) as the holder's heartbeat names it; a volume
        # it names none for is the default geometry's
        self.ec_shard_geometries: Dict[int, tuple] = {}
        self.last_seen = time.time()
        self.rack: Optional["Rack"] = None

    @property
    def url(self) -> str:
        return f"{self.ip}:{self.port}"

    def id(self) -> str:
        return self.url

    def volume_count(self) -> int:
        return len(self.volumes)

    def ec_shard_count(self) -> int:
        return sum(b.shard_id_count() for b in self.ec_shards.values())

    def free_space(self) -> float:
        """Free volume slots, EC shards counted fractionally
        (reference store.go:99-112 FindFreeLocation)."""
        return self.max_volume_count - len(self.volumes) \
            - sum(bits.shard_id_count() / self.ec_geometry(vid)[0]
                  for vid, bits in self.ec_shards.items())

    def ec_geometry(self, vid: int) -> tuple:
        return self.ec_shard_geometries.get(
            vid, (DATA_SHARDS, PARITY_SHARDS))

    def update_volumes(self, infos: List[VolumeInfo]) -> None:
        self.volumes = {vi.id: vi for vi in infos}

    def add_or_update_volume(self, vi: VolumeInfo) -> bool:
        is_new = vi.id not in self.volumes
        self.volumes[vi.id] = vi
        return is_new

    def delete_volume(self, vid: int) -> None:
        self.volumes.pop(vid, None)

    def update_ec_shards(self, shards: Dict[int, int],
                         collections: Dict[int, str],
                         geometries: Dict[int, tuple] = None) -> None:
        self.ec_shards = {vid: ShardBits(bits)
                          for vid, bits in shards.items() if bits}
        self.ec_shard_collections = dict(collections)
        self.ec_shard_geometries = {
            vid: (int(g[0]), int(g[1]))
            for vid, g in (geometries or {}).items()}

    def to_dict(self) -> dict:
        rack = self.rack
        return {
            "url": self.url, "public_url": self.public_url,
            "volumes": len(self.volumes),
            "ec_shards": self.ec_shard_count(),
            "max": self.max_volume_count,
            "free": self.free_space(),
            "last_seen": self.last_seen,
            # placement context for rack-aware shell maintenance
            # (reference command_ec_balance.go works on racks)
            "rack": rack.id if rack else "",
            "dataCenter": rack.data_center.id
            if rack and rack.data_center else "",
            **({"device": self.device} if self.device else {}),
        }


class Rack:
    def __init__(self, rack_id: str):
        self.id = rack_id
        self.nodes: Dict[str, DataNode] = {}
        self.data_center: Optional["DataCenter"] = None

    def get_or_create_node(self, ip: str, port: int, public_url: str = "",
                           max_volume_count: int = 7) -> DataNode:
        key = f"{ip}:{port}"
        node = self.nodes.get(key)
        if node is None:
            node = DataNode(ip, port, public_url, max_volume_count)
            node.rack = self
            self.nodes[key] = node
        node.max_volume_count = max_volume_count
        if public_url:
            node.public_url = public_url
        return node

    def free_space(self) -> float:
        return sum(n.free_space() for n in self.nodes.values())

    def all_nodes(self) -> List[DataNode]:
        return list(self.nodes.values())


class DataCenter:
    def __init__(self, dc_id: str):
        self.id = dc_id
        self.racks: Dict[str, Rack] = {}

    def get_or_create_rack(self, rack_id: str) -> Rack:
        rack = self.racks.get(rack_id)
        if rack is None:
            rack = Rack(rack_id)
            rack.data_center = self
            self.racks[rack_id] = rack
        return rack

    def free_space(self) -> float:
        return sum(r.free_space() for r in self.racks.values())

    def all_nodes(self) -> List[DataNode]:
        return [n for r in self.racks.values() for n in r.all_nodes()]
