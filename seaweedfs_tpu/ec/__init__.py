"""ec — the erasure-coding pipeline (north star).

RS(10,4) striping of volumes into 14 shard files with a two-level block
layout (1GB large rows, 1MB small rows — reference
weed/storage/erasure_coding/ec_encoder.go:17-23), with the GF(2^8) compute
routed through ops.get_codec (numpy / native C++ / TPU MXU backends).

One flat rebuild: `rebuild_ec_files_streaming` decodes from a striped
gather of local files and remote holders (what a volume server runs);
`rebuild_ec_files` is its entry for a directory that holds every
survivor (reference RebuildEcFiles).
"""

from .constants import (  # noqa: F401
    DATA_SHARDS, PARITY_SHARDS, TOTAL_SHARDS,
    LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, to_ext,
)
from .encoder import write_ec_files, write_ec_files_spread, \
    write_sorted_file_from_idx, rebuild_ec_files, \
    rebuild_ec_files_streaming  # noqa: F401
from .transport import (  # noqa: F401
    GatherStats, LocalShardReader, LocalShardWriter, RemoteShardReader,
    RemoteShardWriter, SpreadError, SpreadStats, TransportStats,
)
from .gather import (  # noqa: F401
    StripedGatherSource, fetch_index_files, probe_shard_size,
)
from .spread import StripedSpreadSink, spread_window  # noqa: F401
from .locate import Interval, locate_data  # noqa: F401
