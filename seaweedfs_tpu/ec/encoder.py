"""Volume -> EC shard files (.dat -> .ec00..ec13), sorted index, rebuild.

Behavior-compatible with reference ec_encoder.go:
  * write_sorted_file_from_idx: .idx append log -> .ecx (same 16B entries,
    sorted by needle id) [ec_encoder.go:27-54]
  * write_ec_files: two-level striping — while MORE than one large row
    (k x 1GB) remains, emit a large row; tail as small rows (k x 1MB),
    zero-padded [ec_encoder.go:192-229]
  * rebuild_ec_files: regenerate missing .ecNN from >=k survivors
    [ec_encoder.go:61-116, 231-285]

Geometry is taken from the codec (generic RS(k,m), default 10+4 — the
reference hardcodes 10+4 at ec_encoder.go:17-20).

TPU-first difference: the reference streams k x 256KB buffers per GF call;
here each device call covers a whole slab (default k x 8MB) so a volume
encode is a few hundred kernel launches instead of ~120k, and the GF math
runs as one MXU matmul per slab (ops/rs_tpu.py). With a TPU-backed codec
the slabs additionally flow through ops/pipeline.PipelinedMatmul, which
overlaps disk reads (reader thread), h2d, MXU compute, d2h and shard-file
writes. Slab reads are strided (block i of a row lives at start +
i*block_size), the same column layout the reference uses, so shard bytes
are identical across all backends.
"""

from __future__ import annotations

import os
import time
from typing import Iterator, List, Optional, Tuple

import numpy as np

from ..ops.codec import ReedSolomonCodec, get_codec
from ..storage.needle_map import MemDb
from ..util import tracing
from ..util.profiling import StageTimer
from .constants import (DATA_SHARDS, LARGE_BLOCK_SIZE, PARITY_SHARDS,
                        SMALL_BLOCK_SIZE, to_ext)

DEFAULT_SLAB = 8 << 20  # bytes per shard per device call


def write_sorted_file_from_idx(base_name: str, ext: str = ".ecx"):
    """Build the sorted EC index next to the volume files. Record width
    follows the volume's offset width (superblock flag; 5-byte-offset
    volumes have 17B .idx/.ecx records)."""
    width = 4
    try:
        from ..storage.super_block import SUPER_BLOCK_SIZE, SuperBlock
        with open(base_name + ".dat", "rb") as f:
            width = SuperBlock.from_bytes(
                f.read(SUPER_BLOCK_SIZE)).offset_width
    except Exception:  # noqa: BLE001 - no/short .dat: default width
        pass
    db = MemDb.load_from_idx(base_name + ".idx", width)
    db.save_to_idx(base_name + ext)


def _row_slabs(f, k: int, start: int, block_size: int, slab: int,
               timer: Optional[StageTimer] = None
               ) -> Iterator[Tuple[None, np.ndarray]]:
    """Yield the slabs of one row of k blocks at [start, start+k*block)."""
    step = min(slab, block_size)
    for off in range(0, block_size, step):
        width = min(step, block_size - off)  # final chunk may be partial
        t0 = time.perf_counter()
        data = np.zeros((k, width), dtype=np.uint8)
        for i in range(k):
            f.seek(start + i * block_size + off)
            chunk = f.read(width)
            if chunk:
                data[i, :len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)
        if timer is not None:
            end = time.perf_counter()
            timer.add("disk_read", end - t0, k * width, interval=(t0, end))
        yield None, data


def _dat_slabs(dat_path: str, dat_size: int, k: int, large_block: int,
               small_block: int, slab: int,
               timer: Optional[StageTimer] = None
               ) -> Iterator[Tuple[None, np.ndarray]]:
    """All slabs of a .dat in shard-file order (large rows, then small)."""
    with open(dat_path, "rb") as f:
        remaining = dat_size
        processed = 0
        large_row = large_block * k
        while remaining > large_row:
            yield from _row_slabs(f, k, processed, large_block, slab, timer)
            remaining -= large_row
            processed += large_row
        small_row = small_block * k
        while remaining > 0:
            yield from _row_slabs(f, k, processed, small_block, slab, timer)
            remaining -= small_row
            processed += small_row


def _window_batches(slabs: Iterator[Tuple[None, np.ndarray]],
                    window: int) -> Iterator[Tuple[None, np.ndarray]]:
    """Re-chunk a slab stream onto sub-chunk window boundaries.

    The piggyback parity transform is window-local (ops/codec.pb_split
    interleaves alpha sub-chunks per window), so every batch fed to the
    encode matmul must be a whole number of windows. Slab widths from
    the block reader are arbitrary, but shards append contiguously, so
    buffering the non-aligned remainder into the next batch preserves
    shard bytes exactly. The stream total is window-aligned by
    construction (both stripe blocks divide by the window), so the
    buffer always drains."""
    held: Optional[np.ndarray] = None
    for _, data in slabs:
        if held is not None:
            data = np.concatenate([held, data], axis=1)
            held = None
        cut = (data.shape[1] // window) * window
        if cut < data.shape[1]:
            held = np.ascontiguousarray(data[:, cut:])
            data = data[:, :cut]
        if data.shape[1]:
            yield None, np.ascontiguousarray(data)
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


def _coalesce_slabs(slabs: Iterator[Tuple[None, np.ndarray]],
                    target_width: int, timer: StageTimer
                    ) -> Iterator[Tuple[None, np.ndarray]]:
    """Hstack consecutive row-slabs up to target_width per device call.

    GF coding is columnwise-independent, so concat-then-encode equals
    encode-then-concat; and consecutive slabs append contiguously to each
    shard file, so the batched rows are exactly the shard byte ranges —
    the 'streaming stripe batches' of BASELINE config 3. Without this, a
    volume of 1MB small rows would reach the device 10MB per call.

    Producing one device call's batch — the row-slab reads it pulls
    from ``slabs`` and the concatenate — is one stage (span
    ``ec.encode.read`` under the timer's root) on the thread that
    iterates this (the pipeline's producer), counted in ops/telemetry
    beside it.
    """
    from ..ops.telemetry import STATS
    it = iter(slabs)
    held: Optional[np.ndarray] = None   # read, but past this batch's width
    more = True
    while more:
        with tracing.Stage("ec.encode.read", timer.root) as st:
            batch = [] if held is None else [held]
            total = sum(b.shape[1] for b in batch)
            held = None
            for _, data in it:
                w = data.shape[1]
                if batch and total + w > target_width:
                    held = data
                    break
                batch.append(data)
                total += w
            else:
                more = False
            if not batch:
                return
            out = batch[0] if len(batch) == 1 \
                else np.concatenate(batch, axis=1)
            st.nbytes = out.nbytes
        STATS.add_read(out.nbytes, st.t1 - st.t0, st.cpu_s)
        yield None, out
        # handed on: the next batch is read without this one kept alive
        out = batch = None


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
    """
    from ..ops import codec as ops_codec
    codec = codec or get_codec(DATA_SHARDS, PARITY_SHARDS)
    k, m = codec.k, codec.m
    if pipelined is None:
        pipelined = codec.backend in ("tpu", "mesh")
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
    slabs = _dat_slabs(dat_path, dat_size, k, large_block, small_block, slab,
                       timer)
    outs = [] if sink is not None else \
        [open(base_name + to_ext(i), "wb") for i in range(k + m)]
    # device-parallel compute feeding holder-parallel network: with a
    # piecewise-draining codec (mesh) and a sink, each device shard's
    # parity piece is routed to the per-target send queues the moment
    # its d2h lands — the host never stages the full (m, slab) output.
    # The piggyback transform is window-interleaved, so its parity must
    # merge whole slabs: no pieces.
    pieces = pipelined and sink is not None and \
        hasattr(codec, "drain_pieces") and not piggyback
    try:
        if piggyback:
            batches = _window_batches(
                _coalesce_slabs(slabs, max(slab - slab % window, window),
                                timer),
                window)
            alpha = pplan.alpha

            def pb_stream():
                if pipelined:
                    from ..ops.pipeline import PipelinedMatmul
                    pm = PipelinedMatmul(
                        pplan.emat,
                        max_width=max(slab // alpha, window // alpha),
                        timer=timer, codec=codec)
                    split = ((data, ops_codec.pb_split(data, alpha, window))
                             for _, data in batches)
                    for orig, _sub, psub in pm.stream(split):
                        yield orig, ops_codec.pb_merge(
                            np.asarray(psub, dtype=np.uint8), alpha, window)
                else:
                    for _, data in batches:
                        sub = ops_codec.pb_split(data, alpha, window)
                        psub = np.asarray(
                            codec._matmul(pplan.emat, sub), dtype=np.uint8)
                        yield data, ops_codec.pb_merge(psub, alpha, window)

            stream = ((None, data, parity) for data, parity in pb_stream())
        elif pipelined:
            from ..ops.pipeline import PipelinedMatmul
            pm = PipelinedMatmul(codec.matrix[k:], max_width=slab,
                                 timer=timer, codec=codec, pieces=pieces)
            stream = pm.stream(_coalesce_slabs(slabs, slab, timer))
        else:
            stream = ((meta, data, codec.encode(data))
                      for meta, data in slabs)
        for _, data, parity in stream:
            with timer.stage("shard_write", span="ec.encode.write") as st:
                if pieces:
                    for lo, piece in parity:
                        pw = piece.shape[1]
                        sink.write_stripe(data[:, lo:lo + pw], piece)
                        st.nbytes += k * pw + piece.nbytes
                elif sink is not None:
                    sink.write_stripe(data, parity)
                    st.nbytes = data.nbytes + parity.nbytes
                else:
                    for i in range(k):
                        outs[i].write(data[i].tobytes())
                    for j in range(m):
                        outs[k + j].write(parity[j].tobytes())
                    st.nbytes = data.nbytes + parity.nbytes
    finally:
        for o in outs:
            o.close()
    _record_phase_spans(timer, pipelined, op="ec.encode")


def write_ec_files_spread(base_name: str, sink,
                          codec: Optional[ReedSolomonCodec] = None,
                          large_block: int = LARGE_BLOCK_SIZE,
                          small_block: int = SMALL_BLOCK_SIZE,
                          slab: int = DEFAULT_SLAB,
                          pipelined: Optional[bool] = None,
                          stats: Optional[dict] = None,
                          layout: str = "flat"):
    """Streaming encode+spread: tee write_ec_files' stripe stream into
    ``sink`` (an ec.spread.StripedSpreadSink) so each shard's slab
    ranges reach its holder while later slabs are still encoding —
    the write-path mirror of rebuild_ec_files_streaming. Wall
    approaches max(encode, spread); shards bound for remote holders
    never touch the source disk.

    On ANY failure the sink is aborted (``.part`` cleanup on every
    holder) before the exception propagates — callers either get a
    complete finalized shard set or nothing.

    ``stats``, when given, is filled with the spread counters plus
    ``encode_busy_s`` / ``spread_busy_s`` / ``overlap_frac`` — the
    encode-side analogue of the streaming rebuild's gather stats."""
    codec = codec or get_codec(DATA_SHARDS, PARITY_SHARDS)
    if pipelined is None:
        pipelined = codec.backend in ("tpu", "mesh")
    from ..ops import telemetry
    before = telemetry.STATS.snapshot()
    # the stream's root span (ec.encode.stream, current here): the
    # reader, drain and write stages hang under it as real spans
    timer = StageTimer(root=tracing.current_span())
    t_stream = time.perf_counter()
    try:
        write_ec_files(base_name, codec=codec, large_block=large_block,
                       small_block=small_block, slab=slab,
                       pipelined=pipelined, timer=timer, sink=sink,
                       layout=layout)
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
        stats["phases"] = {n: round(s, 6) for n, s in
                           _phases_from_timer(timer, pipelined).items()}
        # encode busy = stream wall minus the time the consumer spent
        # blocked on full send windows; spread busy = the union of send
        # intervals across all target workers. The overlap fraction is
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
        "write": t.get("shard_write", 0.0),
    }


def _record_phase_spans(timer: StageTimer, pipelined: bool, op: str):
    for name, secs in _phases_from_timer(timer, pipelined).items():
        if secs > 0:
            tracing.record_span(name, secs, op=op)


def rebuild_ec_files(base_name: str,
                     codec: Optional[ReedSolomonCodec] = None,
                     slab: int = DEFAULT_SLAB,
                     pipelined: Optional[bool] = None,
                     stats: Optional[dict] = None,
                     layout=None) -> List[int]:
    """Regenerate missing shard files from survivors. Returns the list of
    rebuilt shard ids. Raises if fewer than k survive.

    Device-backed codecs (tpu AND mesh) stream survivor slabs through
    PipelinedMatmul with the fused decode plan: one device dispatch per
    slab regenerates every missing shard (data + parity rows stacked),
    with bounded in-flight depth instead of a synchronous per-slab
    round-trip. ``stats``, when given, is filled with the dispatch
    telemetry of this rebuild (dispatches / bitmat_uploads /
    device_bytes / host_fallbacks deltas, survivor_bytes, stream_s) —
    the bench's regression counters.

    ``layout``: an ec.layout.LayoutInfo (or None for flat). Piggyback
    volumes decode through ops/codec.piggyback_decode_plan — the same
    one-fused-dispatch-per-slab stream, with each survivor slab split
    into sub-chunk rows per window before the matmul and each rebuilt
    slab merged back before the write."""
    from ..ops import codec as ops_codec
    codec = codec or get_codec(DATA_SHARDS, PARITY_SHARDS)
    k, total = codec.k, codec.total
    if pipelined is None:
        pipelined = codec.backend in ("tpu", "mesh")
    piggyback = layout is not None and getattr(layout, "piggyback", False)
    present = [os.path.exists(base_name + to_ext(i)) for i in range(total)]
    missing = [i for i, p in enumerate(present) if not p]
    if not missing:
        return []
    if sum(present) < k:
        raise ValueError(
            f"cannot rebuild: only {sum(present)} of {total} shards")
    shard_size = None
    for i, p in enumerate(present):
        if p:
            sz = os.path.getsize(base_name + to_ext(i))
            if shard_size is None:
                shard_size = sz
            elif shard_size != sz:
                raise ValueError("surviving shards differ in size")
    if piggyback:
        return _rebuild_ec_files_piggyback(
            base_name, codec, layout, present, missing, shard_size,
            slab, stats)
    ins = [open(base_name + to_ext(i), "rb") if present[i] else None
           for i in range(total)]
    outs = {i: open(base_name + to_ext(i), "wb") for i in missing}
    # only the first k survivors feed the decode plan; reading more would
    # be dead I/O (their coefficient columns are zero by construction)
    src = [i for i, p in enumerate(present) if p][:k]

    def survivor_slabs():
        for off in range(0, shard_size, slab):
            n = min(slab, shard_size - off)
            rows = []
            for i in src:
                ins[i].seek(off)
                rows.append(np.frombuffer(ins[i].read(n), dtype=np.uint8))
            yield None, np.stack(rows, axis=0)

    from ..ops import telemetry
    before = telemetry.STATS.snapshot()
    phases = {"gather": 0.0, "plan": 0.0, "dispatch": 0.0,
              "drain": 0.0, "write": 0.0}
    t_stream = time.perf_counter()
    try:
        if pipelined:
            from ..ops.pipeline import PipelinedMatmul
            t0 = time.perf_counter()
            coeffs = _rebuild_coeffs(codec, present, missing)
            phases["plan"] = time.perf_counter() - t0
            ptimer = StageTimer(root=tracing.current_span())
            # pieces: device-shard outputs drain and append to the
            # missing-shard files per device, no full-slab host staging
            pm = PipelinedMatmul(coeffs, max_width=slab, codec=codec,
                                 timer=ptimer, pieces=True)
            for _, _, parts in pm.stream(survivor_slabs()):
                with ptimer.stage("shard_write",
                                  span="ec.rebuild.write") as st:
                    for _, piece in parts:
                        for r, i in enumerate(missing):
                            outs[i].write(piece[r].tobytes())
                            st.nbytes += piece[r].nbytes
            phases["write"] = ptimer.totals.get("shard_write", 0.0)
            # consumer-side accounting: the stream loop's time splits
            # into waiting for survivor reads (gather), h2d puts
            # (dispatch), waiting for device results (drain), and the
            # writes above — overlapped worker-thread work (reader,
            # drain pool) is deliberately NOT added on top, so the
            # phases tile the wall instead of exceeding it
            phases["gather"] = ptimer.totals.get("read_wait", 0.0)
            phases["dispatch"] = ptimer.totals.get("h2d", 0.0)
            phases["drain"] = ptimer.totals.get("drain_wait", 0.0)
        else:
            for off in range(0, shard_size, slab):
                n = min(slab, shard_size - off)
                t0 = time.perf_counter()
                shards: List[Optional[np.ndarray]] = []
                for i in range(total):
                    if ins[i] is None:
                        shards.append(None)
                    else:
                        ins[i].seek(off)
                        shards.append(np.frombuffer(ins[i].read(n),
                                                    dtype=np.uint8))
                t1 = time.perf_counter()
                rebuilt = codec.reconstruct(shards)
                t2 = time.perf_counter()
                for i in missing:
                    outs[i].write(rebuilt[i].tobytes())
                t3 = time.perf_counter()
                phases["gather"] += t1 - t0
                phases["dispatch"] += t2 - t1
                phases["write"] += t3 - t2
    finally:
        for h in ins:
            if h is not None:
                h.close()
        for h in outs.values():
            h.close()
    stream_s = time.perf_counter() - t_stream
    # pad/bucket copies and dispatch issuance are the only consumer-side
    # work not bracketed above; attribute the remainder to dispatch so
    # the phase breakdown sums to the operation wall
    residual = stream_s - sum(phases.values())
    if residual > 0:
        phases["dispatch"] += residual
    for name, secs in phases.items():
        if secs > 0:
            tracing.record_span(name, secs, op="ec.rebuild",
                                backend=codec.backend)
    if stats is not None:
        stats.update(telemetry.delta(before))
        stats["survivor_bytes"] = shard_size * k
        stats["rebuilt_bytes"] = shard_size * len(missing)
        stats["stream_s"] = round(stream_s, 3)
        stats["backend"] = codec.backend
        stats["phases"] = {n: round(s, 6) for n, s in phases.items()}
    return missing


def _pb_slab(slab: int, window: int) -> int:
    """Clamp a slab size to whole windows (never below one window) so
    every stripe of a piggyback stream stays window-aligned."""
    return max(window, slab - slab % window)


def _rebuild_ec_files_piggyback(base_name, codec, layout, present,
                                missing, shard_size, slab, stats
                                ) -> List[int]:
    """Local piggyback rebuild: decode every missing shard (data AND
    parity) from the coupled decode plan's source set in one fused
    matmul per slab. Shard sizes are window-aligned by construction
    (both stripe blocks divide by the window), so slabs clamp to whole
    windows with no tail special-case."""
    import time as _time
    from ..ops import codec as ops_codec
    from ..ops import telemetry
    k = codec.k
    alpha, window = layout.alpha, layout.window
    if shard_size % window:
        raise ValueError(
            f"piggyback shard size {shard_size} not window-aligned "
            f"({window}); sidecar geometry is wrong for these shards")
    src, plan_missing, coeffs = ops_codec.piggyback_decode_plan(
        codec.k, codec.m, tuple(bool(p) for p in present),
        matrix_kind=getattr(codec, "matrix_kind", "vandermonde"),
        matrix=getattr(codec, "matrix", None),
        pairs=layout.pairs)
    rows = [plan_missing.index(i) for i in missing]
    eff_slab = _pb_slab(slab, window)
    before = telemetry.STATS.snapshot()
    phases = {"gather": 0.0, "plan": 0.0, "dispatch": 0.0,
              "drain": 0.0, "write": 0.0}
    ins = {i: open(base_name + to_ext(i), "rb") for i in src}
    outs = {i: open(base_name + to_ext(i), "wb") for i in missing}
    t_stream = _time.perf_counter()
    try:
        for off in range(0, shard_size, eff_slab):
            n = min(eff_slab, shard_size - off)
            t0 = _time.perf_counter()
            stack = []
            for i in src:
                ins[i].seek(off)
                stack.append(np.frombuffer(ins[i].read(n), dtype=np.uint8))
            block = np.stack(stack, axis=0)
            t1 = _time.perf_counter()
            sub = ops_codec.pb_split(block, alpha, window)
            out = np.asarray(codec._matmul(coeffs, sub), dtype=np.uint8)
            merged = ops_codec.pb_merge(out, alpha, window)
            t2 = _time.perf_counter()
            for r, i in zip(rows, missing):
                outs[i].write(merged[r].tobytes())
            t3 = _time.perf_counter()
            phases["gather"] += t1 - t0
            phases["dispatch"] += t2 - t1
            phases["write"] += t3 - t2
    finally:
        for h in ins.values():
            h.close()
        for h in outs.values():
            h.close()
    stream_s = _time.perf_counter() - t_stream
    for name, secs in phases.items():
        if secs > 0:
            tracing.record_span(name, secs, op="ec.rebuild",
                                backend=codec.backend, layout="piggyback")
    if stats is not None:
        stats.update(telemetry.delta(before))
        stats["survivor_bytes"] = shard_size * len(src)
        stats["rebuilt_bytes"] = shard_size * len(missing)
        stats["stream_s"] = round(stream_s, 3)
        stats["backend"] = codec.backend
        stats["layout"] = "piggyback"
        stats["phases"] = {n: round(s, 6) for n, s in phases.items()}
    return list(missing)


def rebuild_ec_files_streaming_piggyback(base_name: str,
                                         present: List[bool],
                                         missing: List[int],
                                         source,
                                         layout,
                                         codec: Optional[
                                             ReedSolomonCodec] = None,
                                         slab: int = DEFAULT_SLAB,
                                         stats: Optional[dict] = None
                                         ) -> List[int]:
    """Streaming full decode for a piggyback volume: ``source`` yields
    survivor stripes whose ROWS ARE THE DECODE PLAN'S src ORDER (every
    surviving data shard, then the plan's parity picks — the caller
    builds readers from piggyback_decode_plan's src list, not first-k).
    Each stripe is window-split, pushed through the fused coupled
    decode, merged, and appended to the missing shard files. Failure
    removes partial outputs, same contract as the flat streaming
    rebuild."""
    import time as _time
    from ..ops import codec as ops_codec
    from ..ops import telemetry
    codec = codec or get_codec(DATA_SHARDS, PARITY_SHARDS)
    if not missing:
        return []
    alpha, window = layout.alpha, layout.window
    before = telemetry.STATS.snapshot()
    phases = {"gather": 0.0, "plan": 0.0, "dispatch": 0.0,
              "drain": 0.0, "write": 0.0}
    t0 = _time.perf_counter()
    src, plan_missing, coeffs = ops_codec.piggyback_decode_plan(
        codec.k, codec.m, tuple(bool(p) for p in present),
        matrix_kind=getattr(codec, "matrix_kind", "vandermonde"),
        matrix=getattr(codec, "matrix", None),
        pairs=layout.pairs)
    rows = [plan_missing.index(i) for i in missing]
    phases["plan"] = _time.perf_counter() - t0
    outs = {i: open(base_name + to_ext(i), "wb") for i in missing}
    rebuilt_bytes = 0
    t_stream = _time.perf_counter()
    try:
        it = source.slabs()
        while True:
            t0 = _time.perf_counter()
            try:
                _, block = next(it)
            except StopIteration:
                break
            t1 = _time.perf_counter()
            sub = ops_codec.pb_split(block, alpha, window)
            out = np.asarray(codec._matmul(coeffs, sub), dtype=np.uint8)
            merged = ops_codec.pb_merge(out, alpha, window)
            t2 = _time.perf_counter()
            for r, i in zip(rows, missing):
                outs[i].write(merged[r].tobytes())
                rebuilt_bytes += merged.shape[1]
            t3 = _time.perf_counter()
            phases["gather"] += t1 - t0
            phases["dispatch"] += t2 - t1
            phases["write"] += t3 - t2
    except BaseException:
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
    stream_s = _time.perf_counter() - t_stream
    for name, secs in phases.items():
        if secs > 0:
            tracing.record_span(name, secs, op="ec.rebuild",
                                backend=codec.backend, streaming=True,
                                layout="piggyback")
    if stats is not None:
        gs = source.stats
        stats.update(telemetry.delta(before))
        stats.update(gs.snapshot())
        stats["survivor_bytes"] = source.shard_size * len(src)
        stats["rebuilt_bytes"] = rebuilt_bytes
        stats["stream_s"] = round(stream_s, 3)
        stats["backend"] = codec.backend
        stats["layout"] = "piggyback"
        stats["phases"] = {n: round(s, 6) for n, s in phases.items()}
        stats["gather_mbps"] = round(gs.mbps(), 1)
        stats["gather_remote_shards"] = gs.remote_shards
    return list(missing)


def rebuild_ec_files_streaming(base_name: str,
                               present: List[bool],
                               missing: List[int],
                               source,
                               codec: Optional[ReedSolomonCodec] = None,
                               slab: int = DEFAULT_SLAB,
                               pipelined: Optional[bool] = None,
                               stats: Optional[dict] = None) -> List[int]:
    """Streaming variant of rebuild_ec_files: the survivor bytes arrive
    from ``source`` (an ec.gather.StripedGatherSource — local files and
    remote holders mixed) instead of whole shard files on local disk,
    and each rebuilt slab is appended to the missing shard files as the
    decode drains. Rebuild wall approaches max(gather, compute) and the
    rebuilder never materializes a survivor copy.

    ``present``/``missing`` describe the cluster-wide shard state (the
    decode plan), not local files. On ANY failure the partially written
    missing-shard files are removed — callers either get complete
    rebuilt shards or nothing."""
    codec = codec or get_codec(DATA_SHARDS, PARITY_SHARDS)
    k, total = codec.k, codec.total
    if pipelined is None:
        pipelined = codec.backend in ("tpu", "mesh")
    if not missing:
        return []
    if sum(present) < k:
        raise ValueError(
            f"cannot rebuild: only {sum(present)} of {total} shards")
    from ..ops import telemetry
    before = telemetry.STATS.snapshot()
    phases = {"gather": 0.0, "plan": 0.0, "dispatch": 0.0,
              "drain": 0.0, "write": 0.0}
    t0 = time.perf_counter()
    coeffs = _rebuild_coeffs(codec, present, missing)
    phases["plan"] = time.perf_counter() - t0
    outs = {i: open(base_name + to_ext(i), "wb") for i in missing}
    rebuilt_bytes = 0
    t_stream = time.perf_counter()
    try:
        if pipelined:
            from ..ops.pipeline import PipelinedMatmul
            # the stream's root span (ec.rebuild.stream, current here)
            ptimer = StageTimer(root=tracing.current_span())
            # pieces, same as rebuild_ec_files: the sharded decode's
            # per-device outputs append as they land
            pm = PipelinedMatmul(coeffs, max_width=slab, codec=codec,
                                 timer=ptimer, pieces=True)
            for _, _, parts in pm.stream(source.slabs()):
                with ptimer.stage("shard_write",
                                  span="ec.rebuild.write") as st:
                    for _, piece in parts:
                        for r, i in enumerate(missing):
                            outs[i].write(piece[r].tobytes())
                            st.nbytes += piece[r].nbytes
            phases["write"] = ptimer.totals.get("shard_write", 0.0)
            rebuilt_bytes = ptimer.bytes.get("shard_write", 0)
            # consumer-side accounting, same discipline as
            # rebuild_ec_files: read_wait is the time this thread spent
            # blocked on stripes still in flight — the UNOVERLAPPED
            # remainder of the gather, not its busy time
            phases["gather"] = ptimer.totals.get("read_wait", 0.0)
            phases["dispatch"] = ptimer.totals.get("h2d", 0.0)
            phases["drain"] = ptimer.totals.get("drain_wait", 0.0)
        else:
            it = source.slabs()
            while True:
                t0 = time.perf_counter()
                try:
                    _, data = next(it)
                except StopIteration:
                    break
                t1 = time.perf_counter()
                out = codec._matmul(coeffs, data)
                t2 = time.perf_counter()
                for r, i in enumerate(missing):
                    outs[i].write(np.asarray(out[r],
                                             dtype=np.uint8).tobytes())
                    rebuilt_bytes += data.shape[1]
                t3 = time.perf_counter()
                phases["gather"] += t1 - t0
                phases["dispatch"] += t2 - t1
                phases["write"] += t3 - t2
    except BaseException:
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
    stream_s = time.perf_counter() - t_stream
    residual = stream_s - (sum(phases.values()) - phases["plan"])
    if residual > 0:
        phases["dispatch"] += residual
    for name, secs in phases.items():
        if secs > 0:
            tracing.record_span(name, secs, op="ec.rebuild",
                                backend=codec.backend, streaming=True)
    if stats is not None:
        gs = source.stats
        stats.update(telemetry.delta(before))
        stats.update(gs.snapshot())
        stats["survivor_bytes"] = source.shard_size * k
        stats["rebuilt_bytes"] = rebuilt_bytes
        stats["stream_s"] = round(stream_s, 3)
        stats["backend"] = codec.backend
        stats["phases"] = {n: round(s, 6) for n, s in phases.items()}
        gather_busy = gs.busy_s()
        compute_busy = max(stream_s - phases["gather"], 0.0)
        serialized = gather_busy + compute_busy
        overlap = 0.0
        if serialized > 0:
            overlap = max(0.0, min(1.0,
                                   (serialized - stream_s) / serialized))
        stats["gather_busy_s"] = round(gather_busy, 3)
        stats["compute_busy_s"] = round(compute_busy, 3)
        stats["overlap_frac"] = round(overlap, 4)
        stats["gather_mbps"] = round(gs.mbps(), 1)
        stats["gather_remote_shards"] = gs.remote_shards
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
