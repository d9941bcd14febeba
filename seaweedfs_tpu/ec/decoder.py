"""EC shard files -> volume (.ec00-09 -> .dat, .ecx+.ecj -> .idx),
plus the trace-repair combine for single-lost-shard rebuild.

Reference ec_decoder.go: decoding back to a volume is a pure interleave
copy (no GF math — data shards hold the original bytes); the .idx is the
.ecx stream plus tombstone entries replayed from the .ecj journal; the
.dat size is inferred from the maximum ecx entry end.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import List, Optional

import numpy as np

from ..storage.needle import get_actual_size
from ..storage.needle_map import bytes_to_entry, entry_to_bytes
from ..util import tracing
from ..util.profiling import StageTimer
from ..storage.super_block import SUPER_BLOCK_SIZE, SuperBlock
from ..storage.types import NEEDLE_ENTRY_SIZE, NEEDLE_ID_SIZE, \
    TOMBSTONE_FILE_SIZE, bytes_to_needle_id
from .constants import DATA_SHARDS, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, to_ext
from .transport import _give_slab


def iterate_ecx_file(base_name: str, offset_width: int = 4):
    from ..storage.types import entry_size
    rec_size = entry_size(offset_width)
    with open(base_name + ".ecx", "rb") as f:
        while True:
            rec = f.read(rec_size)
            if len(rec) < rec_size:
                break
            yield bytes_to_entry(rec)


def iterate_ecj_file(base_name: str):
    path = base_name + ".ecj"
    if not os.path.exists(path):
        return
    with open(path, "rb") as f:
        while True:
            rec = f.read(NEEDLE_ID_SIZE)
            if len(rec) < NEEDLE_ID_SIZE:
                break
            yield bytes_to_needle_id(rec)


def write_idx_file_from_ec_index(base_name: str):
    """.ecx + .ecj -> .idx (reference WriteIdxFileFromEcIndex).

    Only the record-aligned prefix of the .ecx is copied: a piggyback
    volume's index carries a trailing layout version byte (ec/layout),
    and copying it would misalign every tombstone record appended
    below. The .idx format has no layout tag — the tag describes shard
    parity, and the .idx outlives the shards."""
    from ..storage.types import entry_size
    from .layout import ecx_record_bytes
    width = read_ec_volume_superblock(base_name).offset_width
    aligned = ecx_record_bytes(base_name + ".ecx", entry_size(width))
    with open(base_name + ".ecx", "rb") as src, \
            open(base_name + ".idx", "wb") as idx:
        left = aligned
        while left > 0:
            chunk = src.read(min(8 << 20, left))
            if not chunk:
                break
            idx.write(chunk)
            left -= len(chunk)
        for nid in iterate_ecj_file(base_name):
            idx.write(entry_to_bytes(nid, 0, TOMBSTONE_FILE_SIZE, width))


def read_ec_volume_superblock(base_name: str) -> SuperBlock:
    """The volume superblock rides at the start of .ec00 (data shards carry
    the original bytes verbatim) — version AND flags (offset width)."""
    with open(base_name + to_ext(0), "rb") as f:
        return SuperBlock.from_bytes(f.read(SUPER_BLOCK_SIZE))


def read_ec_volume_version(base_name: str) -> int:
    return read_ec_volume_superblock(base_name).version


def find_dat_file_size(base_name: str) -> int:
    sb = read_ec_volume_superblock(base_name)
    version = sb.version
    dat_size = 0
    for nid, offset, size in iterate_ecx_file(base_name, sb.offset_width):
        if size == TOMBSTONE_FILE_SIZE:
            continue
        end = offset + get_actual_size(size, version)
        dat_size = max(dat_size, end)
    return dat_size


def write_dat_file(base_name: str, dat_size: int,
                   large_block: int = LARGE_BLOCK_SIZE,
                   small_block: int = SMALL_BLOCK_SIZE,
                   buf_size: int = 8 << 20,
                   data_shards: Optional[int] = None):
    """Interleave-copy the k data shards (.ec00-09 of a 10 + 4 volume)
    back into a .dat of dat_size bytes; k is the volume's own (.vif)
    unless the caller names it."""
    if data_shards is None:
        from .layout import volume_geometry
        data_shards = volume_geometry(base_name)[0]
    with tracing.span("write", op="ec.to_volume", bytes=int(dat_size)):
        _write_dat_file(base_name, dat_size, large_block, small_block,
                        buf_size, data_shards)


def _write_dat_file(base_name, dat_size, large_block, small_block,
                    buf_size, data_shards=DATA_SHARDS):
    ins = [open(base_name + to_ext(i), "rb") for i in range(data_shards)]
    try:
        with open(base_name + ".dat", "wb") as dat:
            remaining = dat_size
            large_row = large_block * data_shards
            block_row = 0
            while remaining > large_row:
                for i in range(data_shards):
                    _copy_block(ins[i], block_row * large_block, large_block,
                                dat, buf_size)
                remaining -= large_row
                block_row += 1
            large_rows = block_row
            small_row_idx = 0
            small_row = small_block * data_shards
            while remaining > 0:
                for i in range(data_shards):
                    want = min(remaining, small_block)
                    if want <= 0:
                        break
                    _copy_block(
                        ins[i],
                        large_rows * large_block + small_row_idx * small_block,
                        want, dat, buf_size)
                    remaining -= want
                small_row_idx += 1
    finally:
        for f in ins:
            f.close()


def _copy_block(src, offset: int, length: int, dst, buf_size: int):
    src.seek(offset)
    left = length
    while left > 0:
        chunk = src.read(min(buf_size, left))
        if not chunk:
            dst.write(b"\x00" * left)
            return
        dst.write(chunk)
        left -= len(chunk)


# ---------------------------------------------------------------------------
# Trace-repair combine: the rebuilder side of bandwidth-optimal
# single-shard repair (ops/codec.repair_plan has the scheme math).
# ---------------------------------------------------------------------------

def _write_relaid(timer: StageTimer, out, span: str, w: int, relayout):
    """One drained block of a single-shard repair: its host re-layout
    into ``w`` shard bytes, then the append. Under the timer's root (the
    stream's ``ec.rebuild.stream``) both leave as spans, ``span`` and
    ``ec.rebuild.write``; the repair's ``write`` phase is their sum."""
    with timer.stage("relayout", span=span) as st:
        block = relayout()
        st.nbytes = block.nbytes
    with timer.stage("shard_write", w, span="ec.rebuild.write"):
        out.write(block.data)


def rebuild_ec_file_repair(base_name: str, lost_sid: int, source, plan,
                           codec=None, slab: int = 8 << 20,
                           pipelined: Optional[bool] = None,
                           stats: Optional[dict] = None) -> List[int]:
    """Rebuild ONE lost shard from the trace-repair symbol stream.

    ``source`` is an ec.gather.RepairGatherSource: each stripe arrives
    as the concatenated packed symbol planes of every helper —
    ``(plan.total_bits, ceil(w/8))`` uint8. The combine matrix
    ``plan.combine`` has {0,1} coefficients, and in GF(2^8) multiplying
    by 1 is the identity while addition is XOR — so the combine IS a
    GF(2^8) matmul and the existing device kernels (PipelinedMatmul
    over the codec's device_fn) run it unchanged: one fused dispatch
    per slab, same as the full-RS decode. The 8 output planes are
    interleaved back into shard bytes on the host (an 8x8 bit transpose
    per 8 bytes, ops/codec.combine_planes_to_bytes) and appended to the
    lost shard file.

    All-or-nothing like rebuild_ec_files_streaming: any failure removes
    the partial shard file before propagating, so the caller can fall
    back to the full streaming gather with a clean slate."""
    from ..ops import telemetry
    from ..ops.codec import combine_planes_to_bytes
    from . import encoder
    codec = codec or encoder.volume_codec(base_name)
    if pipelined is None:
        pipelined = codec.pipelined
    if lost_sid != plan.lost:
        raise ValueError(f"plan repairs shard {plan.lost}, not {lost_sid}")
    before = telemetry.STATS.snapshot()
    # the source hands out blocks as tall as its row bucket (zero rows
    # below the symbol planes); the combine gets as many zero columns,
    # so the product is the plan's own
    combine = plan.combine
    rows = source.rows
    if rows > plan.total_bits:
        combine = np.zeros((combine.shape[0], rows), dtype=np.uint8)
        combine[:, :plan.total_bits] = plan.combine
    # plane widths are byte strides: an 8 MB slab arrives as
    # total_bits x 1 MB planes, so the pipeline buckets on the stride
    stride_cap = (max(1, int(slab)) + 7) // 8
    timer = StageTimer(root=tracing.current_span())
    t_stream = time.perf_counter()
    with encoder.rebuilt_outputs(base_name, [lost_sid]) as outs:
        for meta, block, planes in encoder.matmul_stream(
                codec, combine, source.slabs(), timer, pipelined,
                stride_cap):
            _give_slab(block)   # drained: the gather's again
            _write_relaid(
                timer, outs[lost_sid], "ec.rebuild.trace_unpack", meta[2],
                lambda: combine_planes_to_bytes(planes, meta[2]))
    stream_s = time.perf_counter() - t_stream
    # the repair story: symbol bytes moved vs the k*shard baseline the
    # full-RS gather would have pulled for the same rebuild
    encoder.close_rebuild(
        stats, timer, stream_s, before, source, codec, combine, [lost_sid],
        repair_mode="trace", repair_total_bits=plan.total_bits,
        repair_bits={int(s): plan.bits_for(s) for s in plan.helpers},
        **_single_shard_account(plan, source))
    return [lost_sid]


def _single_shard_account(plan, source) -> dict:
    """What the reply of a single-shard repair says beside the common
    keys: how many helpers sent, and what they sent of the k whole
    shards a full gather pulls, as a fraction and a rate."""
    gs = source.stats
    baseline = plan.k * source.shard_size
    return {"repair_helpers": len(plan.helpers),
            "repair_bytes_frac": round(gs.bytes / baseline, 4)
            if baseline else 0.0,
            "repair_mbps": round(gs.mbps(), 1)}


def rebuild_ec_file_piggyback(base_name: str, lost_sid: int, source,
                              rplan, window: int, codec=None,
                              slab: int = 8 << 20,
                              pipelined: Optional[bool] = None,
                              stats: Optional[dict] = None) -> List[int]:
    """Rebuild ONE coupled data shard from half-plane helper streams.

    ``source`` is an ec.gather.PlaneGatherSource: each stripe arrives
    as the restacked plane rows of every helper — k-1 data shards plus
    2 parities, ((k+1)*alpha/2, w/alpha) uint8 for a w-byte shard
    range. ``rplan.matrix`` (ops/codec.piggyback_repair_plan) turns
    that stack into the lost shard's alpha sub-chunk rows in one
    GF(2^8) matmul — the same fused kernels as the full decode — and
    pb_merge interleaves the rows back into shard bytes. Download is
    (k+1)/(2k) of the k*shard full-gather baseline: 0.55 for RS(10,4).

    All-or-nothing: any failure removes the partial shard file before
    propagating, so the caller can fall back to the full decode with a
    clean slate."""
    from ..ops import telemetry
    from ..ops.codec import pb_merge
    from . import encoder
    codec = codec or encoder.volume_codec(base_name)
    if pipelined is None:
        pipelined = codec.pipelined
    if lost_sid != rplan.lost:
        raise ValueError(f"plan repairs shard {rplan.lost}, not {lost_sid}")
    alpha = rplan.alpha
    before = telemetry.STATS.snapshot()
    # stripe columns are w/alpha wide for a w-byte shard range
    stride_cap = max(1, int(slab)) // alpha + 1
    timer = StageTimer(root=tracing.current_span())
    t_stream = time.perf_counter()
    with encoder.rebuilt_outputs(base_name, [lost_sid]) as outs:
        for meta, block, sub in encoder.matmul_stream(
                codec, rplan.matrix, source.slabs(), timer, pipelined,
                stride_cap):
            _give_slab(block)   # drained: the gather's again
            _write_relaid(
                timer, outs[lost_sid], "ec.rebuild.pb_merge", meta[2],
                lambda: pb_merge(np.asarray(sub, dtype=np.uint8),
                                 alpha, window)[0])
    stream_s = time.perf_counter() - t_stream
    # the repair story: half-plane bytes moved vs the k*shard baseline
    # the full-RS gather would have pulled
    encoder.close_rebuild(
        stats, timer, stream_s, before, source, codec, rplan.matrix,
        [lost_sid], repair_mode="piggyback", layout="piggyback",
        **_single_shard_account(rplan, source))
    return [lost_sid]
