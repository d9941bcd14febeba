"""Volume -> EC shard files (.dat -> .ec00..ec13), sorted index, rebuild.

Behavior-compatible with reference ec_encoder.go:
  * write_sorted_file_from_idx: .idx append log -> .ecx (same 16B entries,
    sorted by needle id) [ec_encoder.go:27-54]
  * write_ec_files: two-level striping — while MORE than one large row
    (k x 1GB) remains, emit a large row; tail as small rows (k x 1MB),
    zero-padded [ec_encoder.go:192-229]
  * rebuild_ec_files: regenerate missing .ecNN from >=k survivors
    [ec_encoder.go:61-116, 231-285] — the local entry of the two full
    decodes, which are the served rebuild's too:
    rebuild_ec_files_streaming (flat) and rebuild_ec_files_piggyback
    (coupled), each over a gather of local files and remote holders.
    With the two single-shard repairs of ec/decoder.py they share the
    matmul stream (matmul_stream), the all-or-nothing outputs
    (rebuilt_outputs) and the node's reply (close_rebuild)

Geometry is taken from the codec (generic RS(k,m), default 10+4 — the
reference hardcodes 10+4 at ec_encoder.go:17-20).

TPU-first difference: the reference streams k x 256KB buffers per GF call;
here each device call covers a whole slab (default k x 8MB) so a volume
encode is a few hundred kernel launches instead of ~120k, and the GF math
runs as one MXU matmul per slab (ops/rs_tpu.py). With a TPU-backed codec
the slabs additionally flow through ops/pipeline.PipelinedMatmul, which
overlaps disk reads (reader thread), h2d, MXU compute, d2h and shard-file
writes. Every backend gets its slabs from one reader (_dat_slabs): block i
of a row lives at start + i*block_size, the same column layout the
reference uses, so shard bytes are identical across all backends; each
device call's (k, W) slab is filled in place by vectored positional reads
(os.preadv: one per small row, whose k blocks are one file range) — one
pass over every byte of the .dat, no seek, no intermediate copy.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator, List, Optional, Tuple

import numpy as np

from ..ops.codec import ReedSolomonCodec, get_codec
from ..storage.needle_map import MemDb
from ..util import tracing
from ..util.locks import make_lock
from ..util.profiling import StageTimer
# the slab pool is the transport's: one for the encode's reader and the
# rebuild's gather
from .transport import _SLAB_POOL, _give_slab, _take_slab  # noqa: F401
from .constants import (DATA_SHARDS, LARGE_BLOCK_SIZE, PARITY_SHARDS,
                        SMALL_BLOCK_SIZE, to_ext)

DEFAULT_SLAB = 8 << 20  # bytes per shard per device call


def volume_codec(base_name: str) -> ReedSolomonCodec:
    """The codec of an encoded volume for a caller that brought none:
    the geometry its ``.vif`` names (10 + 4 where it names none), on
    the backend ``auto`` picks."""
    from .layout import volume_geometry
    return get_codec(*volume_geometry(base_name))


def write_sorted_file_from_idx(base_name: str, ext: str = ".ecx",
                               timer: Optional[StageTimer] = None):
    """Build the sorted EC index next to the volume files. Record width
    follows the volume's offset width (superblock flag; 5-byte-offset
    volumes have 17B .idx/.ecx records).

    The log is read as one record array and sorted once
    (needle_map.MemDb.load_from_idx), a ``delete`` a key its log leaves
    dead. On a volume of small needles it is still a stage of its own,
    so a stream's ``timer`` takes it as ``index`` (span
    ``ec.encode.index`` under the timer's root, tagged with what it read
    and wrote) and the process counts it (ops/telemetry
    ``index_entries``, ``index_us``) whoever called."""
    from ..ops import telemetry
    stage = (timer or StageTimer()).stage("index", span="ec.encode.index")
    with stage as st:
        db, st.nbytes = _sorted_file_from_idx(base_name, ext)
        st.tags["entries"], st.tags["tombstones"] = len(db), db.tombstones
    telemetry.STATS.add_index(len(db), st.t1 - st.t0)


def _sorted_file_from_idx(base_name: str, ext: str):
    width = 4
    try:
        from ..storage.super_block import SUPER_BLOCK_SIZE, SuperBlock
        with open(base_name + ".dat", "rb") as f:
            width = SuperBlock.from_bytes(
                f.read(SUPER_BLOCK_SIZE)).offset_width
    except Exception:  # noqa: BLE001 - no/short .dat: default width
        pass
    db = MemDb.load_from_idx(base_name + ".idx", width)
    return db, db.save_to_idx(base_name + ext)


def _dispatch_plan(dat_size: int, k: int, large_block: int, small_block: int,
                   slab: int, target_width: int
                   ) -> Iterator[List[Tuple[int, int, int, int]]]:
    """Each device call of a .dat, in shard-file order, as the pieces
    (row_start, block, off, width) it is made of.

    A row of k blocks (large rows while MORE than one remains, then
    small ones) is cut into pieces at most ``slab`` wide; whole pieces
    pack into one call up to ``target_width`` columns. GF coding is
    columnwise-independent and consecutive pieces append contiguously
    to each shard file, so a call's rows are exactly the next byte
    range of every shard — the 'streaming stripe batches' of BASELINE
    config 3. Without the packing a volume of 1MB small rows would
    reach the device 10MB per call."""
    rows = []
    remaining, start = dat_size, 0
    for block, at_least in ((large_block, large_block * k), (small_block, 0)):
        while remaining > at_least:
            rows.append((start, block))
            remaining -= block * k
            start += block * k
    batch: List[Tuple[int, int, int, int]] = []
    total = 0
    for start, block in rows:
        step = min(slab, block)
        for off in range(0, block, step):
            width = min(step, block - off)  # a row's last piece may be partial
            if batch and total + width > target_width:
                yield batch
                batch, total = [], 0
            batch.append((start, block, off, width))
            total += width
    if batch:
        yield batch


def _read_ranges(fd: int, rows: List[np.ndarray], offset: int,
                 dat_size: int):
    """One vectored positional read of consecutive file ranges into
    ``rows``. Only the file's tail may come short: what it did not fill
    is zeroed (a slab is never handed out clean); short anywhere else
    is an error."""
    want = sum(r.size for r in rows)
    n = os.preadv(fd, rows, offset)
    if n < min(want, dat_size - offset):
        raise IOError(f"short .dat read at {offset}: {n} of {want} bytes, "
                      f"{dat_size - offset} left in the file")
    for r in rows:
        if n < r.size:
            r[max(n, 0):] = 0
        n -= r.size


class _SlabLease:
    """Who still reads a slab: the encode's loop, and every stripe of
    it a spread has not had acknowledged (one a slab, one a piece on
    the mesh). The last to let go hands the slab back
    (transport._give_slab, the pool the rebuild's gather takes its
    blocks from too); a lease nobody lets go of — a failed spread's —
    just drops its slab, and so does a lease on ``None`` (a stripe
    piggyback's window re-cut made of copies: the reader's slab went
    back when the copies were made, _window_batches)."""

    def __init__(self, data: Optional[np.ndarray]):
        self.data = data
        self.users = 1
        self._lock = make_lock("encoder._SlabLease._lock")

    def share(self):
        """One more reader; returns its ``release``."""
        with self._lock:
            self.users += 1
        return self.release

    def release(self):
        with self._lock:
            self.users -= 1
            last = self.users == 0
        if last and self.data is not None:
            _give_slab(self.data)


def _dat_slabs(dat_path: str, dat_size: int, k: int, large_block: int,
               small_block: int, slab: int, target_width: int,
               timer: StageTimer) -> Iterator[Tuple[None, np.ndarray]]:
    """The .dat as one (k, W) host slab per device call, every byte
    landed in its final place by the read itself: no intermediate
    bytes, no row slab, no concatenate.

    Block i of a row lives at row_start + i*block — the column layout
    the reference uses — so where a piece spans whole blocks (small
    rows) the row's k blocks are one contiguous file range that
    scatters to the slab's k rows in ONE preadv; where a block is wider
    than the slab (large rows) the k ranges lie a block apart and take
    a read each.

    Producing one call's slab is one stage (``disk_read`` in the timer,
    span ``ec.encode.read`` under its root) on the thread that iterates
    this — the pipeline's producer where there is one — counted in
    ops/telemetry beside it. The consumer may _give_slab each one back."""
    from ..ops.telemetry import STATS
    fd = os.open(dat_path, os.O_RDONLY)
    try:
        for pieces in _dispatch_plan(dat_size, k, large_block, small_block,
                                     slab, target_width):
            with timer.stage("disk_read", span="ec.encode.read") as st:
                out = _take_slab(k, sum(p[3] for p in pieces),
                                 room=k * target_width)
                col = 0
                for start, block, off, width in pieces:
                    cols = slice(col, col + width)
                    if width == block:
                        _read_ranges(fd, [out[i, cols] for i in range(k)],
                                     start, dat_size)
                    else:
                        for i in range(k):
                            _read_ranges(fd, [out[i, cols]],
                                         start + i * block + off, dat_size)
                    col += width
                st.nbytes = out.nbytes
            STATS.add_read(out.nbytes, st.t1 - st.t0, st.cpu_s)
            yield None, out
            # handed on: the next slab is read without this one kept alive
            out = None
    finally:
        os.close(fd)


def _window_batches(slabs: Iterator[Tuple[None, np.ndarray]],
                    window: int, timer: StageTimer
                    ) -> Iterator[Tuple[Optional[np.ndarray], np.ndarray]]:
    """Re-chunk a slab stream onto sub-chunk window boundaries.

    The piggyback parity transform is window-local (ops/codec.pb_split
    interleaves alpha sub-chunks per window), so every batch fed to the
    encode matmul must be a whole number of windows. Slab widths from
    the block reader are arbitrary, but shards append contiguously, so
    buffering the non-aligned remainder into the next batch preserves
    shard bytes exactly. The stream total is window-aligned by
    construction (both stripe blocks divide by the window), so the
    buffer always drains.

    One stage a slab (``pb_recut`` in the timer, span
    ``ec.encode.pb_recut``) on the thread that iterates this, after the
    slab's ``ec.encode.read`` has closed: a slab that is window-aligned
    already (every one, at the default slab and window) passes through
    as it came, and the stage holds nothing but the test.

    Yields ``(slab, batch)``: the reader's slab where the batch is that
    slab itself, for the consumer to hand back when the stripe is
    written; ``None`` where the batch is a copy — the slab it was cut
    from has gone back to the pool here."""
    held: Optional[np.ndarray] = None
    for _, slab in slabs:
        data = slab
        with timer.stage("pb_recut", span="ec.encode.pb_recut") as st:
            if held is not None:
                data = np.concatenate([held, data], axis=1)
                held = None
            cut = (data.shape[1] // window) * window
            if cut < data.shape[1]:
                held = data[:, cut:].copy()
                data = data[:, :cut]
            data = np.ascontiguousarray(data)
            st.nbytes = data.nbytes
        if not np.may_share_memory(data, slab):
            _give_slab(slab)
            slab = None
        if data.shape[1]:
            yield slab, data
    if held is not None and held.shape[1]:
        raise ValueError(
            f"stream tail of {held.shape[1]} bytes is not window-aligned "
            f"(window {window}); block sizes must divide by the window")


def piggyback_geometry(codec: ReedSolomonCodec, layout,
                       large_block: int, small_block: int):
    """Resolve (plan, window) for a piggyback encode/rebuild and check
    the stripe geometry supports sub-chunking: the window must divide
    both stripe blocks so every shard size is window-aligned."""
    from ..ops import codec as ops_codec
    pplan = ops_codec.piggyback_plan(
        codec.k, codec.m, matrix_kind=getattr(codec, "matrix_kind",
                                              "vandermonde"),
        matrix=getattr(codec, "matrix", None))
    window = ops_codec.pb_window(small_block, pplan.alpha)
    if large_block % window:
        raise ValueError(
            f"piggyback layout: large block {large_block} not divisible "
            f"by the sub-chunk window {window}")
    return pplan, window


def write_ec_files(base_name: str, codec: Optional[ReedSolomonCodec] = None,
                   large_block: int = LARGE_BLOCK_SIZE,
                   small_block: int = SMALL_BLOCK_SIZE,
                   slab: int = DEFAULT_SLAB,
                   pipelined: Optional[bool] = None,
                   timer: Optional[StageTimer] = None,
                   sink=None,
                   layout: str = "flat"):
    """Encode base_name.dat into base_name.ec00 .. .ec{k+m-1}.

    pipelined: None = auto (pipeline when the codec is device-backed);
    True/False forces. The synchronous path and the pipelined path produce
    byte-identical shard files. ``timer`` collects a per-stage breakdown
    (disk_read / h2d / d2h+mxu / shard_write / waits) for bench/profiling.

    ``sink``: when given (an ec.spread.StripedSpreadSink), the stripe
    stream is teed into ``sink.write_stripe(data, parity)`` instead of
    local shard files — each stripe is the next slab-aligned byte range
    of every shard, pushed to its holder while later slabs encode. The
    caller owns the sink lifecycle (finish/abort).

    ``layout``: "flat" (default; plain RS parity) or "piggyback"
    (coupled sub-chunk parity, ops/codec.piggyback_plan). Data shard
    bytes are identical under both layouts — only the parity rows
    differ, computed per window by one (m*alpha, k*alpha) matmul on
    the same kernels. Callers record the layout in the volume's
    sidecars (ec/layout.py); this function only shapes the bytes.

    Returns the (rows, cols) of the coefficient matrix every call ran:
    (m, k) flat, (m*alpha, k*alpha) piggyback.
    """
    from ..ops import codec as ops_codec
    codec = codec or get_codec(DATA_SHARDS, PARITY_SHARDS)
    k, m = codec.k, codec.m
    if pipelined is None:
        pipelined = codec.pipelined
    piggyback = layout == "piggyback"
    pplan = window = None
    if piggyback:
        pplan, window = piggyback_geometry(codec, layout, large_block,
                                           small_block)
    dat_path = base_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    # always collect stages: the per-phase spans below need them even
    # when no caller asked for a bench breakdown
    timer = timer if timer is not None else \
        StageTimer(root=tracing.current_span())
    # one reader for every backend and layout: a whole-window width for
    # piggyback (re-cut on window boundaries below), the slab otherwise
    slabs = _dat_slabs(dat_path, dat_size, k, large_block, small_block, slab,
                       _pb_slab(slab, window) if piggyback else slab, timer)
    outs = [] if sink is not None else \
        [open(base_name + to_ext(i), "wb") for i in range(k + m)]
    # device-parallel compute feeding holder-parallel network: with a
    # piecewise-draining codec (mesh) and a sink, each device shard's
    # parity piece is routed to the lanes' send queues the moment
    # its d2h lands — the host never stages the full (m, slab) output.
    # The piggyback transform is window-interleaved, so its parity must
    # merge whole slabs: no pieces.
    pieces = pipelined and sink is not None and \
        hasattr(codec, "drain_pieces") and not piggyback
    try:
        if piggyback:
            alpha = pplan.alpha
            operand = pplan.emat.shape

            # the two host re-layouts around the coupled matmul, one
            # stage a dispatch each: the split (an 80 MiB transpose into
            # a new array) on the thread that iterates the batches (the
            # pipeline's producer where there is one), the merge of the
            # drained parity on the consumer
            def split():
                for whole, data in _window_batches(slabs, window, timer):
                    with timer.stage("pb_split",
                                     span="ec.encode.pb_split") as st:
                        sub = ops_codec.pb_split(data, alpha, window)
                        st.nbytes = sub.nbytes
                    yield (whole, data), sub

            def merge(psub):
                with timer.stage("pb_merge",
                                 span="ec.encode.pb_merge") as st:
                    parity = ops_codec.pb_merge(
                        np.asarray(psub, dtype=np.uint8), alpha, window)
                    st.nbytes = parity.nbytes
                return parity

            def pb_stream():
                if pipelined:
                    from ..ops.pipeline import PipelinedMatmul
                    pm = PipelinedMatmul(
                        pplan.emat,
                        max_width=max(slab // alpha, window // alpha),
                        timer=timer, codec=codec)
                    for orig, _sub, psub in pm.stream(split()):
                        yield (*orig, merge(psub))
                else:
                    for orig, sub in split():
                        yield (*orig,
                               merge(codec._matmul(pplan.emat, sub)))

            # (the reader's slab where the stripe is it, stripe, parity)
            stream = pb_stream()
        elif pipelined:
            from ..ops.pipeline import PipelinedMatmul
            operand = codec.matrix[k:].shape
            pm = PipelinedMatmul(codec.matrix[k:], max_width=slab,
                                 timer=timer, codec=codec, pieces=pieces)
            stream = pm.stream(slabs)
        else:
            operand = codec.matrix[k:].shape
            stream = ((meta, data, codec.encode(data))
                      for meta, data in slabs)
        for whole, data, parity in stream:
            lease = _SlabLease(whole if piggyback else data)
            with timer.stage("shard_write", span="ec.encode.write") as st:
                if pieces:
                    for lo, piece in parity:
                        pw = piece.shape[1]
                        sink.write_stripe(data[:, lo:lo + pw], piece,
                                          done=lease.share())
                        st.nbytes += k * pw + piece.nbytes
                elif sink is not None:
                    sink.write_stripe(data, parity, done=lease.share())
                    st.nbytes = data.nbytes + parity.nbytes
                else:
                    for i in range(k):
                        outs[i].write(data[i].tobytes())
                    for j in range(m):
                        outs[k + j].write(parity[j].tobytes())
                    st.nbytes = data.nbytes + parity.nbytes
            lease.release()
            # the stripe is handed on: hold neither while the next is
            # awaited
            data = parity = lease = None
    finally:
        for o in outs:
            o.close()
    _record_phase_spans(timer, pipelined, op="ec.encode")
    return tuple(int(n) for n in operand)


def write_ec_files_spread(base_name: str, sink,
                          codec: Optional[ReedSolomonCodec] = None,
                          large_block: int = LARGE_BLOCK_SIZE,
                          small_block: int = SMALL_BLOCK_SIZE,
                          slab: int = DEFAULT_SLAB,
                          pipelined: Optional[bool] = None,
                          stats: Optional[dict] = None,
                          layout: str = "flat",
                          index: bool = False):
    """Streaming encode+spread: tee write_ec_files' stripe stream into
    ``sink`` (an ec.spread.StripedSpreadSink) so each shard's slab
    ranges reach its holder while later slabs are still encoding —
    the write-path mirror of rebuild_ec_files_streaming. Wall
    approaches max(encode, spread); shards bound for remote holders
    never touch the source disk.

    On ANY failure the sink is aborted (``.part`` cleanup on every
    holder) before the exception propagates — callers either get a
    complete finalized shard set or nothing.

    ``index`` builds the volume's ``.ecx`` from its ``.idx`` first, as
    the stage ``index`` of this stream's account (``phases.index``,
    ``stage_max_s.index``, span ``ec.encode.index``); the stream's own
    wall (``stream_s``, ``encode_busy_s``) starts after it.

    ``stats``, when given, is filled with the spread counters plus
    ``encode_busy_s`` / ``spread_busy_s`` / ``overlap_frac`` — the
    encode-side analogue of the streaming rebuild's gather stats."""
    codec = codec or get_codec(DATA_SHARDS, PARITY_SHARDS)
    if pipelined is None:
        pipelined = codec.pipelined
    from ..ops import telemetry
    before = telemetry.STATS.snapshot()
    # the stream's root span (ec.encode.stream, current here): the
    # reader, drain and write stages hang under it as real spans
    timer = StageTimer(root=tracing.current_span())
    try:
        if index:
            write_sorted_file_from_idx(base_name, timer=timer)
        t_stream = time.perf_counter()
        operand = write_ec_files(
            base_name, codec=codec, large_block=large_block,
            small_block=small_block, slab=slab, pipelined=pipelined,
            timer=timer, sink=sink, layout=layout)
        sink.finish()
    except BaseException:
        sink.abort()
        raise
    stream_s = time.perf_counter() - t_stream
    if stats is not None:
        ss = sink.stats
        stats.update(telemetry.delta(before))
        stats.update(ss.snapshot())
        stats["shard_size"] = sink.offset
        stats["stream_s"] = round(stream_s, 3)
        stats["backend"] = codec.backend
        stats["operand"] = list(operand)
        stats["k"], stats["m"] = codec.k, codec.m
        stats["shards"] = codec.total
        stats["phases"] = {n: round(s, 6) for n, s in
                           _phases_from_timer(timer, pipelined).items()}
        if index:
            # before the stream, on the thread that then consumes it
            stats["phases"]["index"] = round(timer.totals["index"], 6)
        stats["stage_max_s"] = {**timer.max_s(), **ss.timer.max_s()}
        # encode busy = stream wall minus the time the consumer spent
        # blocked on full send windows; spread busy = the union of send
        # intervals across every target's lanes. The overlap fraction is
        # the same clamped serialized-vs-wall estimate the streaming
        # rebuild reports for gather/compute.
        spread_busy = ss.busy_s()
        encode_busy = max(stream_s - sink.blocked_s, 0.0)
        serialized = encode_busy + spread_busy
        overlap = 0.0
        if serialized > 0:
            overlap = max(0.0, min(1.0,
                                   (serialized - stream_s) / serialized))
        stats["encode_busy_s"] = round(encode_busy, 3)
        stats["spread_busy_s"] = round(spread_busy, 3)
        stats["overlap_frac"] = round(overlap, 4)
        stats["spread_mbps"] = round(ss.mbps(), 1)
        stats["spread_remote_shards"] = ss.remote_shards


def _phases_from_timer(timer: StageTimer, pipelined: bool) -> dict:
    """Map StageTimer stages onto the canonical EC phase names, from
    the consumer thread's perspective: in the pipelined path the waits
    (read_wait / h2d / drain_wait) plus the write stage tile the stream
    wall, so the phases sum to ~the operation time instead of
    double-counting overlapped worker-thread work."""
    t = timer.totals
    return {
        "gather": t.get("read_wait" if pipelined else "disk_read", 0.0),
        "dispatch": t.get("h2d", 0.0),
        "drain": t.get("drain_wait", 0.0),
        # the consumer re-lays each drained block into shard bytes before
        # it writes them: the coupled layout's merge, and a single-shard
        # repair's (ec/decoder._write_relaid)
        "write": (t.get("shard_write", 0.0) + t.get("pb_merge", 0.0) +
                  t.get("relayout", 0.0)),
    }


def _record_phase_spans(timer: StageTimer, pipelined: bool, op: str):
    for name, secs in _phases_from_timer(timer, pipelined).items():
        if secs > 0:
            tracing.record_span(name, secs, op=op)


def matmul_stream(codec: ReedSolomonCodec, coeffs: np.ndarray, stripes,
                  timer: StageTimer, pipelined: bool, max_width: int,
                  pieces: bool = False):
    """``coeffs @ stripe`` for each ``(meta, stripe)`` of a rebuild's
    gather, as ``(meta, stripe, product)``: through PipelinedMatmul
    where the codec pipelines; where it computes on the host, the gather
    and then the matmul on this thread, timed under the pipeline's names
    (``read_wait``, ``h2d``) so that one account of the phases serves
    both. ``pieces``: the product as the ``[(column, block)]`` list the
    pipeline drains a device's share into."""
    if pipelined:
        from ..ops.pipeline import PipelinedMatmul
        yield from PipelinedMatmul(coeffs, max_width=max_width, codec=codec,
                                   timer=timer, pieces=pieces
                                   ).stream(stripes)
        return
    stripes = iter(stripes)
    while True:
        t0 = time.perf_counter()
        item = next(stripes, None)
        t1 = time.perf_counter()
        timer.add("read_wait", t1 - t0)
        if item is None:
            return
        out = np.ascontiguousarray(codec._matmul(coeffs, item[1]),
                                   dtype=np.uint8)
        timer.add("h2d", time.perf_counter() - t1)
        yield item[0], item[1], [(0, out)] if pieces else out


@contextlib.contextmanager
def rebuilt_outputs(base_name: str, shard_ids: List[int], sink=None):
    """The shard files a rebuild writes, open, by shard id (none where a
    ``sink`` takes the rows). A rebuild is all-or-nothing: on any failure
    they are closed and removed and the sink is aborted, so that neither
    this node nor the sink's target keeps part of a shard — the next
    rebuild would count it a survivor."""
    outs = {} if sink is not None else \
        {i: open(base_name + to_ext(i), "wb") for i in shard_ids}
    try:
        yield outs
    except BaseException:
        if sink is not None:
            sink.abort()
        for i, h in outs.items():
            h.close()
            try:
                os.remove(base_name + to_ext(i))
            except OSError:
                pass
        raise
    finally:
        for h in outs.values():
            h.close()


def close_rebuild(stats: Optional[dict], timer: StageTimer, stream_s: float,
                  before: dict, source, codec: ReedSolomonCodec,
                  coeffs: np.ndarray, lost: List[int], **route):
    """The end of every rebuild body (the flat and the coupled full
    decode here, the two single-shard repairs of ec/decoder.py), and the
    one place that knows the node's reply.

    The phases are the consumer thread's account of the stream: the
    waits for stripes still in flight (``gather``: the gather's
    UNOVERLAPPED remainder, not its busy time), the puts (``dispatch``),
    the waits for results (``drain``) and the host re-layout and appends
    (``write``, or ``deliver`` where a sink took the rows); the workers'
    overlapped time is not added on top, and what the consumer did
    between its stages (pads, dispatch issuance) goes to ``dispatch``, so
    that they tile ``stream_s``. ``plan`` came before the stream. Each
    leaves as a span. ``stats`` takes the telemetry since ``before``
    (``telemetry.STATS.snapshot()``), the gather's own account
    (``source.stats``), the decode's shape, and the byte account every
    route gives: what the gather's readers received, of the k whole
    shards a full gather pulls. ``route`` holds the keys that are one
    route's own."""
    from ..ops import telemetry
    t = timer.totals
    phases = {"plan": t.get("plan", 0.0), **_phases_from_timer(timer, True)}
    if "deliver" in t:
        phases["deliver"] = t["deliver"]
    residual = stream_s - (sum(phases.values()) - phases["plan"])
    if residual > 0:
        phases["dispatch"] += residual
    for name, secs in phases.items():
        if secs > 0:
            tracing.record_span(name, secs, op="ec.rebuild",
                                backend=codec.backend)
    if stats is None:
        return
    gs = source.stats
    stats.update(telemetry.delta(before))
    stats.update(gs.snapshot())
    stats.update(route)
    stats["rebuilt_bytes"] = timer.bytes.get("shard_write", 0) + \
        timer.bytes.get("deliver", 0)
    stats["stream_s"] = round(stream_s, 3)
    stats["backend"] = codec.backend
    stats["operand"] = list(coeffs.shape)
    stats["k"], stats["m"] = codec.k, codec.m
    stats["lost"] = list(lost)
    stats["phases"] = {n: round(s, 6) for n, s in phases.items()}
    stats["stage_max_s"] = {**timer.max_s(), **gs.timer.max_s()}
    stats.update(gs.overlap(stream_s, phases["gather"]))
    stats["repair_bytes"] = gs.bytes
    stats["repair_remote_bytes"] = gs.remote_bytes
    stats["repair_baseline_bytes"] = codec.k * source.shard_size


def rebuild_ec_files(base_name: str,
                     codec: Optional[ReedSolomonCodec] = None,
                     slab: int = DEFAULT_SLAB,
                     pipelined: Optional[bool] = None,
                     stats: Optional[dict] = None,
                     layout=None) -> List[int]:
    """Regenerate missing shard files from survivors that are all local
    files (reference RebuildEcFiles). Returns the list of rebuilt shard
    ids. Raises if fewer than k survive.

    The bodies are the served rebuild's — rebuild_ec_files_streaming,
    and rebuild_ec_files_piggyback for a volume whose ``layout`` (an
    ec.layout.LayoutInfo, None for flat) is piggyback — over a gather
    whose every reader is a local file; ``stats``, when given, is the
    reply they fill (close_rebuild)."""
    from .gather import GatherStats, LocalShardReader, StripedGatherSource
    codec = codec or volume_codec(base_name)
    present = [os.path.exists(base_name + to_ext(i))
               for i in range(codec.total)]
    missing = [i for i, p in enumerate(present) if not p]
    if not missing:
        return []
    if sum(present) < codec.k:
        raise ValueError(
            f"cannot rebuild: only {sum(present)} of {codec.total} shards")
    sizes = {os.path.getsize(base_name + to_ext(i))
             for i, p in enumerate(present) if p}
    if len(sizes) > 1:
        raise ValueError("surviving shards differ in size")
    shard_size = sizes.pop()
    gstats = GatherStats()

    def local_source(src, slab):
        return StripedGatherSource(
            [LocalShardReader(base_name + to_ext(i), gstats) for i in src],
            shard_size, slab=slab, stats=gstats,
            parent_span=tracing.current_span())

    if layout is not None and getattr(layout, "piggyback", False):
        return rebuild_ec_files_piggyback(
            base_name, present, missing, layout,
            lambda src: local_source(src, _pb_slab(slab, layout.window)),
            codec=codec, pipelined=pipelined, stats=stats)
    # only the first k survivors feed the decode plan; reading more would
    # be dead I/O (their coefficient columns are zero by construction)
    src = [i for i, p in enumerate(present) if p][:codec.k]
    return rebuild_ec_files_streaming(
        base_name, present, missing, local_source(src, slab), codec=codec,
        slab=slab, pipelined=pipelined, stats=stats)


def _pb_slab(slab: int, window: int) -> int:
    """Clamp a slab size to whole windows (never below one window) so
    every stripe of a piggyback stream stays window-aligned."""
    return max(window, slab - slab % window)


def rebuild_ec_files_piggyback(base_name: str, present: List[bool],
                               missing: List[int], layout, make_source,
                               codec: Optional[ReedSolomonCodec] = None,
                               pipelined: Optional[bool] = None,
                               stats: Optional[dict] = None) -> List[int]:
    """The full coupled decode of a piggyback volume: every shard of
    ``missing`` — data and parity, any loss RS(k, m) survives — from k
    whole survivor shards, local files and remote holders alike.

    ``make_source(src)`` is handed the decode plan's source shards (every
    surviving data shard, then as many parities as data shards are lost:
    not the first k) and returns the gather that reads them in that row
    order (an ec.gather.StripedGatherSource whose slab is a whole number
    of windows). Each stripe is window-split on the thread that gathers
    it (the pipeline's producer; the split is a copy, so the gather's
    block goes back to the slab pool there), runs one fused matmul
    against the (alpha * lost, alpha * k) plan — through PipelinedMatmul
    where the codec pipelines, ``codec._matmul`` where it computes on
    the host, as the coupled encode does — and is merged back into shard
    bytes and appended, as views, on the consumer. One span a stripe and stage
    under the stream's root (``ec.rebuild.plan``, ``.pb_split``,
    ``ec.h2d``, ``ec.d2h``, ``.pb_merge``, ``.write``, beside the
    gather's ``.fetch.*`` and ``.assemble``). Failure removes the
    partial outputs: a caller gets whole shards or nothing."""
    from ..ops import codec as ops_codec
    from ..ops import telemetry
    codec = codec or volume_codec(base_name)
    if pipelined is None:
        pipelined = codec.pipelined
    if not missing:
        return []
    alpha, window = layout.alpha, layout.window
    before = telemetry.STATS.snapshot()
    timer = StageTimer(root=tracing.current_span())
    # dense, and a cache hit after the first loss of a pattern
    with timer.stage("plan", span="ec.rebuild.plan"):
        src, plan_missing, coeffs = ops_codec.piggyback_decode_plan(
            codec.k, codec.m, tuple(bool(p) for p in present),
            matrix_kind=getattr(codec, "matrix_kind", "vandermonde"),
            matrix=getattr(codec, "matrix", None),
            pairs=layout.pairs)
    if plan_missing != list(missing):
        raise ValueError(f"shards {list(missing)} asked for, the pattern "
                         f"has {plan_missing} missing")
    source = make_source(src)
    if source.shard_size % window or source.slab % window:
        raise ValueError(
            f"piggyback shard size {source.shard_size} or stripe "
            f"{source.slab} not window-aligned ({window}); sidecar "
            f"geometry is wrong for these shards")

    def split():
        for meta, block in source.slabs():
            with timer.stage("pb_split", span="ec.rebuild.pb_split") as st:
                sub = ops_codec.pb_split(block, alpha, window)
                st.nbytes = sub.nbytes
            # the split made its copy (of a stripe one window wide it
            # is a view): the gather's block can be filled again
            if not np.may_share_memory(sub, block):
                _give_slab(block)
            yield meta, sub

    t_stream = time.perf_counter()
    with rebuilt_outputs(base_name, missing) as outs:
        for _, _, out in matmul_stream(codec, coeffs, split(), timer,
                                       pipelined, source.slab // alpha):
            with timer.stage("pb_merge", span="ec.rebuild.pb_merge") as st:
                merged = ops_codec.pb_merge(
                    np.asarray(out, dtype=np.uint8), alpha, window)
                st.nbytes = merged.nbytes
            with timer.stage("shard_write", merged.nbytes,
                             span="ec.rebuild.write"):
                for row, i in zip(merged, missing):
                    outs[i].write(row)
    stream_s = time.perf_counter() - t_stream
    telemetry.STATS.add("coupled_decodes")
    close_rebuild(stats, timer, stream_s, before, source, codec, coeffs,
                  missing, layout="piggyback",
                  survivor_bytes=codec.k * source.shard_size)
    return list(missing)


def rebuild_ec_files_streaming(base_name: str,
                               present: List[bool],
                               missing: List[int],
                               source,
                               codec: Optional[ReedSolomonCodec] = None,
                               slab: int = DEFAULT_SLAB,
                               pipelined: Optional[bool] = None,
                               stats: Optional[dict] = None,
                               sink=None) -> List[int]:
    """The flat rebuild, served and local (rebuild_ec_files): the
    survivor bytes arrive from ``source`` (an
    ec.gather.StripedGatherSource over the first k survivors — local
    files and remote holders mixed), one fused decode a stripe
    regenerates every missing shard (data + parity rows stacked), and
    each rebuilt slab is appended to the missing shard files as the
    decode drains. Rebuild wall approaches max(gather, compute) and the
    rebuilder never materializes a survivor copy.

    ``present``/``missing`` describe the cluster-wide shard state (the
    decode plan), not local files. On ANY failure the partially written
    missing-shard files are removed — callers either get complete
    rebuilt shards or nothing.

    ``sink`` (an ec.spread.RebuiltShardSink) takes the rebuilt rows in
    place of local files: the decode runs here and the shards land on
    another node's disk, pushed as the encode's spread pushes a remote
    shard. The stage is ``deliver`` (span ``ec.rebuild.deliver``: the
    time the consumer spent handing rows to the sink — blocked on its
    windows — and waiting for its finish) where a local rebuild has
    ``shard_write``; the reply carries it as ``phases.deliver``. A
    failure aborts the sink: no partial shard stays on the target."""
    codec = codec or volume_codec(base_name)
    k, total = codec.k, codec.total
    if pipelined is None:
        pipelined = codec.pipelined
    if not missing:
        return []
    if sum(present) < k:
        raise ValueError(
            f"cannot rebuild: only {sum(present)} of {total} shards")
    from ..ops import telemetry
    before = telemetry.STATS.snapshot()
    # the stream's root span (ec.rebuild.stream, current here)
    timer = StageTimer(root=tracing.current_span())
    with timer.stage("plan"):
        coeffs = _rebuild_coeffs(codec, present, missing)
    # where the rebuilt rows go: the local shard files, or the sink
    out_stage, out_span = ("shard_write", "ec.rebuild.write") \
        if sink is None else ("deliver", "ec.rebuild.deliver")
    route = {"survivor_bytes": k * source.shard_size}
    t_stream = time.perf_counter()
    with rebuilt_outputs(base_name, missing, sink) as outs:
        # pieces: the sharded decode's per-device outputs append as they
        # land, no full-slab host staging
        for _, data, parts in matmul_stream(codec, coeffs, source.slabs(),
                                            timer, pipelined, slab,
                                            pieces=True):
            # its output is drained: the gather may fill it again
            _give_slab(data)
            with timer.stage(out_stage, span=out_span) as st:
                for _, piece in parts:
                    st.nbytes += piece.nbytes
                    if sink is not None:
                        sink.write_rows(piece)    # views: no copy
                        continue
                    for r, i in enumerate(missing):
                        outs[i].write(piece[r])   # a view: no copy
        if sink is not None:
            # the lanes drained, every shard finalized on the target
            with timer.stage(out_stage, span=out_span):
                sink.finish()
            route["delivered_to"] = sink.target
            route["deliver_blocked_s"] = round(sink.blocked_s, 6)
    stream_s = time.perf_counter() - t_stream
    telemetry.STATS.add("rebuild_local_bytes" if sink is None
                        else "rebuild_delivered_bytes",
                        timer.bytes.get(out_stage, 0))
    close_rebuild(stats, timer, stream_s, before, source, codec, coeffs,
                  missing, **route)
    return list(missing)


def _rebuild_coeffs(codec: ReedSolomonCodec, present: List[bool],
                    missing: List[int]) -> np.ndarray:
    """(len(missing), k) GF coefficients so that
    missing_rows = coeffs @ stack(first k surviving shards).

    ``missing`` may be a subset of the shards absent from ``present``:
    health-aware survivor selection masks surplus slow-holder shards
    out of the presence vector without wanting them rebuilt, so only
    the requested rows are sliced from the fused plan.

    Delegates to the codec's fused decode-plan cache (the same plan
    reconstruct() uses per-slab), so the derivation exists once —
    ops/gf256.decode_coeff_rows."""
    _, plan_missing, coeffs = codec.decode_plan(tuple(bool(p)
                                                      for p in present))
    if plan_missing == list(missing):
        return coeffs
    rows = [plan_missing.index(i) for i in missing]
    return np.ascontiguousarray(coeffs[rows])


def ec_shard_base_size(dat_size: int, large_block: int = LARGE_BLOCK_SIZE,
                       small_block: int = SMALL_BLOCK_SIZE,
                       data_shards: int = DATA_SHARDS) -> int:
    """Size every shard file will have for a given .dat size."""
    large_row = large_block * data_shards
    n_large = 0
    remaining = dat_size
    while remaining > large_row:
        n_large += 1
        remaining -= large_row
    small_row = small_block * data_shards
    n_small = (remaining + small_row - 1) // small_row
    return n_large * large_block + n_small * small_block
