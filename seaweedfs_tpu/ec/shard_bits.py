"""ShardBits — bitmask of shard ids held by one (node, volume).

Reference ec_volume_info.go:61-113.
"""

from __future__ import annotations

from .constants import DATA_SHARDS, TOTAL_SHARDS


class ShardBits(int):
    def add_shard_id(self, sid: int) -> "ShardBits":
        return ShardBits(self | (1 << sid))

    def remove_shard_id(self, sid: int) -> "ShardBits":
        return ShardBits(self & ~(1 << sid))

    def has_shard_id(self, sid: int) -> bool:
        return bool(self & (1 << sid))

    def shard_ids(self):
        """Every shard id set, whatever the volume's geometry (any
        k + m up to the bitmap's 32 bits: RS(20,4) has 24)."""
        return [i for i in range(self.bit_length())
                if self.has_shard_id(i)]

    def shard_id_count(self) -> int:
        return bin(self).count("1")

    def plus(self, other: "ShardBits") -> "ShardBits":
        return ShardBits(self | other)

    def minus(self, other: "ShardBits") -> "ShardBits":
        return ShardBits(self & ~other)

    def minus_parity_shards(self, data_shards: int = DATA_SHARDS,
                            total_shards: int = TOTAL_SHARDS
                            ) -> "ShardBits":
        out = self
        for sid in range(data_shards, total_shards):
            out = out.remove_shard_id(sid)
        return out
