"""Streaming striped survivor gather for EC rebuild — the *pull* role
of ``ec/transport.py``.

The copy-then-rebuild flow pulls every surviving shard whole onto the
rebuilder before the first GF byte is computed — rebuild wall is
gather + compute and the rebuilder briefly stores a full extra copy of
the volume. The streaming gather instead fetches slab-aligned byte
ranges of each survivor straight from its holders (the ranged
``/admin/ec/shard_read`` endpoint over ``http_util``'s keep-alive
pool) and hands each arriving stripe to the pipelined decode while the
next stripes are still in flight.

All of the transport — the bounded ``SW_EC_GATHER_WINDOW`` in-flight
window with peak-buffer accounting, per-holder rotation, failover,
``SW_EC_HEDGE_MS`` hedging with loser-drain health attribution, local
fast paths — lives in ``ec/transport.py``, shared byte-for-byte with
the spread push side. This module keeps only what is specific to
pulling shards: shard-size probing, index-sidecar fetching, and the
trace-repair projection readers/stream shape.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..util import tracing
from ..util.locks import make_lock
from .transport import (  # noqa: F401  - the shared transport, pull role
    DEFAULT_WINDOW, FETCH_SPAN, HEDGE_MS_ENV, GatherStats, LocalShardReader,
    RemoteShardReader, StripedPull, TransportStats, default_hedge_ms,
    hedge_pool, pull_window,
)

GATHER_WINDOW_ENV = "SW_EC_GATHER_WINDOW"

# old private name — tests and older callers reach for it
_hedge_pool = hedge_pool

_CONTENT_RANGE_RE = re.compile(r"bytes\s+(\d+)-(\d+)/(\d+)")


def auto_slab(shard_size: int, default: int = 8 << 20,
              min_slab: int = 1 << 20, target_stripes: int = 4) -> int:
    """Slab size for a rebuild when the caller didn't pick one. The
    default 8 MB slab is right for volume-scale shards, but a shard
    smaller than ~one slab degenerates to a single stripe — nothing for
    the gather to overlap with the decode. Shrink the slab (never below
    ``min_slab``) so the stream has at least ``target_stripes`` stripes;
    truly tiny shards keep the default (one stripe — pipelining dust
    costs more than it saves)."""
    if shard_size <= 2 * min_slab:
        return default
    per = -(-shard_size // target_stripes)
    return max(min_slab, min(default, per))


def gather_window() -> int:
    return pull_window()


def probe_shard_size(vid: int, sid: int, holders: Sequence[str],
                     timeout: float = 30.0) -> int:
    """Total shard size via a one-byte suffix-range read: the 206's
    ``Content-Range: bytes a-b/total`` carries the full size without
    transferring the shard (and exercises the ``bytes=-N`` path).

    A holder that rejects the suffix form with 416 (strict servers do
    for some edge encodings) falls back to sizing the shard with
    1-byte ``offset=`` reads — double the offset until EOF, then
    binary-search the boundary: ~2*log2(size) tiny requests instead of
    transferring (or asking the holder to buffer) the whole shard."""
    from ..server.http_util import HttpError, http_call, \
        http_get_with_headers

    def _size_by_tiny_reads(url: str) -> int:
        def has_byte(off: int) -> bool:
            data = http_call("GET", url + f"&offset={off}&size=1",
                             timeout=timeout)
            return len(data) > 0

        if not has_byte(0):
            return 0
        lo, hi = 0, 1
        while has_byte(hi):
            lo, hi = hi, hi * 2
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if has_byte(mid):
                lo = mid
            else:
                hi = mid
        return lo + 1

    last = None
    for holder in holders:
        url = (f"http://{holder}/admin/ec/shard_read?volume={vid}"
               f"&shard={sid}")
        try:
            _, hdrs = http_get_with_headers(
                url, timeout=timeout, headers={"Range": "bytes=-1"})
        except HttpError as e:
            if e.status == 416:
                try:
                    return _size_by_tiny_reads(url)
                except HttpError as e2:
                    last = e2
                    continue
            last = e
            continue
        cr = next((v for k, v in hdrs.items()
                   if k.lower() == "content-range"), "")
        m = _CONTENT_RANGE_RE.match(cr or "")
        if m:
            return int(m.group(3))
        last = HttpError(
            502, f"no Content-Range from {holder} for {vid}.{sid}")
    if last is not None:
        raise last
    raise ValueError(f"shard {vid}.{sid}: no holders to probe")


class ShardSizeCache:
    """Per-rebuild memo of ``probe_shard_size`` keyed by (vid, sid).

    Trace repair sizes the lost shard off whichever survivor answers
    first, and a multi-volume rebuild touches the same survivors
    repeatedly — one suffix probe per shard per rebuild is enough.
    ``probes`` counts actual wire probes so tests can assert the memo
    held."""

    def __init__(self, timeout: float = 30.0):
        self.timeout = timeout
        self.probes = 0
        self._sizes: Dict[Tuple[int, int], int] = {}
        self._lock = make_lock("gather.ShardSizeCache._lock")

    def get(self, vid: int, sid: int, holders: Sequence[str]) -> int:
        key = (int(vid), int(sid))
        with self._lock:
            if key in self._sizes:
                return self._sizes[key]
        size = probe_shard_size(vid, sid, holders, timeout=self.timeout)
        with self._lock:
            self.probes += 1
            self._sizes[key] = size
        return size


class RemoteRepairReader(RemoteShardReader):
    """Projected reads for trace repair: asks the holder to apply this
    survivor's GF(2^8) trace masks server-side and ship only the packed
    symbol planes — ``len(masks) * ceil(n/8)`` bytes for an n-byte
    range. Rotation, failover and hedging come from the shared
    transport reader."""

    _method = "POST"
    _health_kind = "repair_read"
    fetch_span = FETCH_SPAN + ".trace.remote"

    def __init__(self, vid: int, sid: int, holders: Sequence[str],
                 masks: Sequence[int],
                 stats: Optional[TransportStats] = None,
                 timeout: float = 300.0,
                 hedge_ms: Optional[float] = None):
        super().__init__(vid, sid, holders, stats=stats, timeout=timeout,
                         hedge_ms=hedge_ms)
        if not masks:
            raise ValueError(f"shard {vid}.{sid}: no repair masks")
        self.masks = [int(x) for x in masks]

    def _url(self, holder: str, off: int, n: int) -> str:
        m = ",".join(str(x) for x in self.masks)
        return (f"http://{holder}/admin/ec/shard_repair_read"
                f"?volume={self.vid}&shard={self.sid}"
                f"&offset={off}&size={n}&masks={m}")

    def _expect_len(self, n: int) -> int:
        return len(self.masks) * ((n + 7) // 8)


class LocalRepairReader:
    """Trace projection of a survivor shard already on the rebuilder's
    disk: read the range locally, project, and account only the symbol
    bytes (the range itself never crossed the network)."""

    remote = False
    fetch_span = FETCH_SPAN + ".trace.local"
    span = None          # set by StripedPull: trace parent

    def __init__(self, path: str, masks: Sequence[int],
                 stats: Optional[TransportStats] = None):
        if not masks:
            raise ValueError(f"{path}: no repair masks")
        self.path = path
        self.masks = [int(x) for x in masks]
        self.stats = stats or GatherStats()

    def read(self, off: int, n: int, stripe_idx: int = 0) -> memoryview:
        from ..ops.codec import project_slab
        with tracing.Stage(self.fetch_span, self.span) as st:
            with open(self.path, "rb") as f:
                f.seek(off)
                data = f.read(n)
            if len(data) != n:
                raise IOError(f"short read of {self.path} at {off}: "
                              f"{len(data)} < {n}")
            planes = project_slab(np.frombuffer(data, dtype=np.uint8),
                                  self.masks)
            st.nbytes = planes.nbytes
        self.stats.add_fetch(planes.nbytes, st.t0, st.t1)
        return planes.reshape(-1).data


class RemotePlaneReader(RemoteShardReader):
    """Half-plane reads for piggyback repair: asks the holder to apply
    the sub-chunk plane selection server-side (ops/codec.pb_plane_slice)
    and ship only the lost shard's repair plane — ``n/2`` bytes for an
    n-byte window-aligned range. Rotation, failover and hedging come
    from the shared transport reader."""

    _method = "POST"
    _health_kind = "plane_read"
    fetch_span = FETCH_SPAN + ".plane.remote"

    def __init__(self, vid: int, sid: int, holders: Sequence[str],
                 alpha: int, window: int, plane_bit: int, plane_side: int,
                 stats: Optional[TransportStats] = None,
                 timeout: float = 300.0,
                 hedge_ms: Optional[float] = None):
        super().__init__(vid, sid, holders, stats=stats, timeout=timeout,
                         hedge_ms=hedge_ms)
        self.alpha = int(alpha)
        self.window = int(window)
        self.plane_bit = int(plane_bit)
        self.plane_side = int(plane_side)

    def _url(self, holder: str, off: int, n: int) -> str:
        return (f"http://{holder}/admin/ec/shard_plane_read"
                f"?volume={self.vid}&shard={self.sid}"
                f"&offset={off}&size={n}&alpha={self.alpha}"
                f"&window={self.window}&bit={self.plane_bit}"
                f"&side={self.plane_side}")

    def _expect_len(self, n: int) -> int:
        return n // 2


class LocalPlaneReader:
    """Plane slice of a helper shard already on the rebuilder's disk:
    read the window-aligned range locally, slice the repair plane, and
    account only the plane bytes (the range never crossed the
    network)."""

    remote = False
    fetch_span = FETCH_SPAN + ".plane.local"
    span = None          # set by StripedPull: trace parent

    def __init__(self, path: str, alpha: int, window: int,
                 plane_bit: int, plane_side: int,
                 stats: Optional[TransportStats] = None):
        self.path = path
        self.alpha = int(alpha)
        self.window = int(window)
        self.plane_bit = int(plane_bit)
        self.plane_side = int(plane_side)
        self.stats = stats or GatherStats()

    def read(self, off: int, n: int, stripe_idx: int = 0) -> memoryview:
        from ..ops.codec import pb_plane_slice
        with tracing.Stage(self.fetch_span, self.span) as st:
            with open(self.path, "rb") as f:
                f.seek(off)
                data = f.read(n)
            if len(data) != n:
                raise IOError(f"short read of {self.path} at {off}: "
                              f"{len(data)} < {n}")
            plane = pb_plane_slice(np.frombuffer(data, dtype=np.uint8),
                                   self.alpha, self.window,
                                   self.plane_bit, self.plane_side)
            st.nbytes = plane.nbytes
        self.stats.add_fetch(plane.nbytes, st.t0, st.t1)
        return plane.data


def fetch_index_files(base_name: str, holders: Sequence[str],
                      timeout: float = 300.0, budget=None) -> List[str]:
    """Pull the small index sidecars onto the rebuilder: .ecx required
    (the rebuilt .ecx tombstone replay and the mount need it), .vif and
    .ecj best-effort. These are KB-sized — the only whole files the
    streaming rebuild copies; each is charged to ``budget`` (the
    server's for background pulls) where one is given."""
    from ..server.http_util import HttpError, http_call
    name = os.path.basename(base_name)
    fetched: List[str] = []
    for ext, required in ((".ecx", True), (".vif", False), (".ecj", False)):
        if os.path.exists(base_name + ext):
            continue
        last = None
        data = None
        for holder in holders:
            try:
                data = http_call(
                    "GET",
                    f"http://{holder}/admin/file?name={name}{ext}",
                    timeout=timeout)
                break
            except HttpError as e:
                last = e
                data = None
        if data is None:
            if required:
                raise last if last is not None else HttpError(
                    404, f"{name}{ext}: no holder serves it")
            continue
        with open(base_name + ext, "wb") as f:
            f.write(data)
        fetched.append(ext)
        if budget is not None:
            budget.charge(len(data))
    return fetched


class StripedGatherSource(StripedPull):
    """The survivor stream: ``slabs()`` yields ``(meta, (k, w) uint8)``
    stripes in order, fetching up to ``window`` stripes ahead.
    ``readers`` are the first-k survivors in decode plan order — local
    files and remote holders mixed freely. Pure transport: the window,
    pool, ordering, rotation, failover and hedging all come from
    ``StripedPull``, and so does the hand-over — every reader fills its
    own row of the stripe's pooled block, which the consumer hands back
    (``transport._give_slab``) when the decode has drained it."""


class RepairGatherSource(StripedPull):
    """Trace-repair symbol stream: the readers are one projection
    reader per plan helper (``ops/codec.RepairPlan`` order), each
    returning its packed symbol planes for the stripe range. ``slabs()``
    yields ``(meta, (total_bits, ceil(w/8)) uint8)`` blocks — the
    concatenated planes in helper-then-mask order, ready for the fused
    combine matmul. The bounded window, round-robin rotation, failover
    and hedging all come from the shared transport; only the stripe
    shape and memory accounting differ.

    A plan's bit count differs by lost shard (50 to 56 of 80 for
    RS(10,4)), and a device program is compiled per operand shape, so
    every block comes ``rows`` tall — total_bits rounded up to
    ``ops/codec.REPAIR_ROW_BUCKET``, the tail rows zero — and the
    repairs of one geometry share one compiled combine.

    A helper's planes are a contiguous row range of the pooled block;
    they are laid there from the buffers the readers' ``read`` returns
    (one pass into recycled memory where a concatenate filled a new
    array) and the tail rows zeroed, once a block."""

    lands_in_place = False

    def __init__(self, readers: Sequence, shard_size: int, plan,
                 slab: int = 8 << 20, window: Optional[int] = None,
                 stats: Optional[TransportStats] = None,
                 parent_span=None):
        if len(readers) != len(plan.helpers):
            raise ValueError(
                f"need one reader per helper: {len(readers)} != "
                f"{len(plan.helpers)}")
        super().__init__(readers, shard_size, slab=slab, window=window,
                         stats=stats, parent_span=parent_span)
        self.plan = plan
        from ..ops.codec import REPAIR_ROW_BUCKET as bucket
        self.rows = -(-plan.total_bits // bucket) * bucket

    def _stripe_nbytes(self, w: int) -> int:
        return self.plan.total_bits * ((w + 7) // 8)

    def _block_shape(self, w: int) -> Tuple[int, int]:
        return self.rows, (w + 7) // 8

    def _assemble(self, block: np.ndarray, bufs: List, w: int
                  ) -> np.ndarray:
        stride = block.shape[1]
        row = 0
        for b in bufs:
            planes = np.frombuffer(b, dtype=np.uint8).reshape(-1, stride)
            block[row:row + len(planes)] = planes
            row += len(planes)
        if row != self.plan.total_bits:
            raise ValueError(f"helpers sent {row} planes, the plan has "
                             f"{self.plan.total_bits}")
        block[row:] = 0
        return block


class PlaneGatherSource(StripedPull):
    """Piggyback-repair plane stream: the readers are one plane reader
    per plan helper (``ops/codec.PiggybackRepairPlan.helpers`` order —
    k-1 data shards then the 2 parities), each returning its half-plane
    bytes for the stripe range. ``slabs()`` yields
    ``(meta, ((k+1)*alpha/2, w/alpha) uint8)`` blocks — the restacked
    plane rows in plan column order, ready for the fused repair matmul.
    Stripes are clamped to sub-chunk windows so every holder-side slice
    and rebuilder-side restack is window-local. The restack is a true
    transpose: ``_assemble`` writes it straight into the pooled block,
    one pass a helper."""

    lands_in_place = False

    def __init__(self, readers: Sequence, shard_size: int, plan,
                 window: int, slab: int = 8 << 20,
                 gather_window: Optional[int] = None,
                 stats: Optional[TransportStats] = None,
                 parent_span=None):
        if len(readers) != len(plan.helpers):
            raise ValueError(
                f"need one reader per helper: {len(readers)} != "
                f"{len(plan.helpers)}")
        if shard_size % window:
            raise ValueError(
                f"piggyback shard size {shard_size} not aligned to "
                f"window {window}")
        slab = max(window, slab - slab % window)
        super().__init__(readers, shard_size, slab=slab,
                         window=gather_window, stats=stats,
                         parent_span=parent_span)
        self.plan = plan
        self.pb_window = int(window)

    def _stripe_nbytes(self, w: int) -> int:
        return len(self.readers) * (w // 2)

    def _block_shape(self, w: int) -> Tuple[int, int]:
        alpha = self.plan.alpha
        return len(self.readers) * (alpha // 2), w // alpha

    def _assemble(self, block: np.ndarray, bufs: List, w: int
                  ) -> np.ndarray:
        from ..ops.codec import pb_plane_rows
        half = self.plan.alpha // 2
        for i, b in enumerate(bufs):
            pb_plane_rows(np.frombuffer(b, dtype=np.uint8),
                          self.plan.alpha, self.pb_window,
                          out=block[i * half:(i + 1) * half])
        return block
