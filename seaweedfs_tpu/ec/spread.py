"""Streaming striped shard spread for EC encode — the *push* role of
``ec/transport.py``.

The copy-then-spread flow materializes all k+m shard files on the
source disk and only then lets every target pull its shards whole over
``/admin/ec/copy`` — encode wall is encode + spread, the source pays a
1.4x shard write pass plus the copy re-read, and nothing overlaps.
The streaming spread instead takes the stripe stream coming out of the
encode (each stripe is one slab-aligned ``[off, off+w)`` range of every
shard) and pushes each shard's ranges straight to its assigned holder
via the ``/admin/ec/shard_write`` endpoint while later slabs are still
encoding: a run of a shard's contiguous ranges is one POST whose body
is views of the stripes' rows, on the connection that shard's lane
keeps open (a target's shards ride two lanes, so a holder takes two
runs at once), and the holder streams it from the socket into the
``.part`` stage. Shards bound for remote holders never touch the
source disk, and no shard byte is copied in Python on its way.

All of the transport — the lanes, the bounded ``SW_EC_SPREAD_WINDOW``
window of each with peak-buffer and blocked-time accounting, contiguous-run
merging, retry/failover onto spares, first-run ``SW_EC_HEDGE_MS``
hedging, the ``.part``-stage/atomic-finalize discipline — lives in
``ec/transport.py``, shared byte-for-byte with the gather pull side.
This module keeps only what is specific to pushing an encode: mapping
a shard assignment onto transport writers.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from .transport import (  # noqa: F401  - the shared transport, push role
    DEFAULT_WINDOW, _SENTINEL, _STAGED_RE, LocalShardWriter,
    RemoteShardWriter, SpreadError, SpreadStats, StripedPush,
    TransportStats, merge_runs, push_window,
)
from .transport import TargetWorker as _TargetWorker  # noqa: F401

SPREAD_WINDOW_ENV = "SW_EC_SPREAD_WINDOW"


def spread_window() -> int:
    return push_window()


class StripedSpreadSink(StripedPush):
    """The placement stream: ``write_stripe`` routes each shard row of
    the arriving stripe to the bounded send queue of its shard's lane;
    the lanes push the ranges while the encode produces the next stripes.
    ``assignment`` maps shard id -> holder url; shards mapped to
    ``local_url`` (or unmapped) take the local-writer fast path and are
    staged next to ``base_name``. ``slab`` is the stripe width of the
    stream the sink is handed: a lane's window is ``window`` stripes of
    it, in bytes. Everything after writer construction — windows, runs,
    failover, hedging, pacing, finalize/abort — is ``StripedPush``."""

    def __init__(self, vid: int, base_name: str,
                 assignment: Dict[int, str], total: int,
                 collection: str = "",
                 local_url: str = "",
                 spares: Optional[Sequence[str]] = None,
                 window: Optional[int] = None,
                 stats: Optional[TransportStats] = None,
                 parent_span=None,
                 rate_mbps: float = 0.0,
                 slab: int = 8 << 20):
        from .constants import to_ext
        self.vid = vid
        self.base_name = base_name
        writers: List = []
        by_target: Dict[Optional[str], List[int]] = {}
        for sid in range(int(total)):
            url = assignment.get(sid) or ""
            if url == local_url:
                url = ""
            if url:
                w = RemoteShardWriter(vid, sid, collection)
            else:
                w = LocalShardWriter(base_name + to_ext(sid))
            writers.append(w)
            by_target.setdefault(url or None, []).append(sid)
        super().__init__(writers, by_target, spares=spares,
                         window=window, stats=stats,
                         parent_span=parent_span, rate_mbps=rate_mbps,
                         slab=slab)


class RebuiltShardSink(StripedPush):
    """The sink of a rebuild whose decode runs on another node than the
    one placement names for the rebuilt shards (shell/command_ec: one
    volume in flight a chip; f4's rebuilder nodes, apart from its
    storage nodes). Row r of every decoded stripe is the next range of
    shard ``missing[r]``, pushed to ``target``'s
    ``/admin/ec/shard_write`` exactly as the encode's spread pushes a
    remote shard: the same lanes, windows, runs, ``.part`` stage and
    atomic finalize, and ``abort`` leaves nothing of them on the
    target. No spare: the shards of a rebuild belong on the node the
    shell chose, and a failed delivery is the shell's to retry there."""

    def __init__(self, vid: int, missing: Sequence[int], target: str,
                 collection: str = "", window: Optional[int] = None,
                 stats: Optional[TransportStats] = None,
                 parent_span=None, slab: int = 8 << 20):
        self.vid = vid
        self.missing = list(missing)
        self.target = target
        writers = [RemoteShardWriter(vid, sid, collection)
                   for sid in self.missing]
        super().__init__(writers, {target: list(range(len(writers)))},
                         window=window, stats=stats,
                         parent_span=parent_span, slab=slab)

    def write_rows(self, rows):
        """One decoded stripe: ``rows[r]`` continues shard
        ``missing[r]``. The rows are queued as views (StripedPush.
        write_stripe): the array is the sink's until they are sent."""
        self.write_stripe(rows, rows[:0])
