"""EC geometry constants (reference ec_encoder.go:17-23).

The three shard counts are the DEFAULT geometry only: a volume's own
(k, m) is stamped into its ``.vif`` at encode time (ec/layout.py,
``ec_data_shards`` / ``ec_parity_shards``) and reported in the
heartbeat's ``ec_geometries``; a volume that names none is 10 + 4.
"""

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = 14
# the most shards any geometry may have: ShardBits' 32 bits (RS(20,4)
# needs 24). Where a volume's geometry is not known yet (a stage that
# arrives before the .vif), shard files are looked for up to here.
MAX_SHARDS = 32

LARGE_BLOCK_SIZE = 1024 * 1024 * 1024  # 1GB
SMALL_BLOCK_SIZE = 1024 * 1024         # 1MB

# the reference reads 256KB per shard per batch (ec_encoder.go:58); the TPU
# pipeline batches far larger slabs per device call — this constant remains
# only as the wire-compatible streaming granularity for shard reads
BUFFER_SIZE = 256 * 1024


def to_ext(shard_id: int) -> str:
    return f".ec{shard_id:02d}"
