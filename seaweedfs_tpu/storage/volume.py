"""Volume — one append-only .dat file + .idx index (Haystack store).

Mirrors reference behavior (weed/storage/volume.go, volume_read_write.go,
volume_loading.go, volume_checking.go) over the same disk formats:
  * append-only writes at 8-byte-aligned offsets, write-through .idx
  * deletes append a zero-size tombstone needle and a tombstone idx entry
  * reads validate cookie + CRC, honor TTL expiry
  * boot: load superblock, replay .idx, truncate torn tails
"""

from __future__ import annotations

import os
import threading
from ..util.locks import make_rlock
import time
from typing import Optional

from .needle import Needle, get_actual_size
from .compact_map import load_needle_map
from .needle_map import walk_index_file
from .super_block import SUPER_BLOCK_SIZE, SuperBlock
from .types import (NEEDLE_PADDING_SIZE, TOMBSTONE_FILE_SIZE, TTL,
                    ReplicaPlacement)


class VolumeError(Exception):
    pass


class NotFound(VolumeError):
    pass


def volume_file_prefix(dirname: str, collection: str, vid: int) -> str:
    name = f"{collection}_{vid}" if collection else str(vid)
    return os.path.join(dirname, name)


class Volume:
    def __init__(self, dirname: str, collection: str, vid: int,
                 replica_placement: Optional[ReplicaPlacement] = None,
                 ttl: Optional[TTL] = None, create: bool = False,
                 version: int = None, index_kind: str = "memory",
                 offset_width: int = 4):
        self.dir = dirname
        self.collection = collection or ""
        self.id = vid
        # needle-map variant (reference volume -index flag): memory |
        # compact (16B/needle sorted arrays) | sortedfile (mmap'd .sdx)
        self.index_kind = index_kind
        self._readonly = False
        self.lock = make_rlock("volume.lock")
        self.last_modified = 0
        # write-lease delegate (server/native_plane.NativeWriter).
        # While set, the native plane owns the .dat/.idx tails: appends
        # go through it, its mirror index is authoritative, and the
        # needle map here is FROZEN (reloaded from .idx when the lease
        # comes back — reload_nm). Set/cleared under self.lock by the
        # owning VolumeServer.
        self.fast_writer = None

        prefix = volume_file_prefix(dirname, self.collection, vid)
        self.dat_path = prefix + ".dat"
        self.idx_path = prefix + ".idx"
        self._finish_interrupted_commit(prefix)

        # a .vif sidecar marks a tiered volume: the .dat lives on a
        # remote backend and reads are range requests — but only when
        # the local .dat is actually gone (a keep-local tier upload
        # leaves both, and the local copy must win or every read pays a
        # pointless network round trip)
        remote_info = None
        if not os.path.exists(self.dat_path):
            from .volume_tier import load_volume_info
            info = load_volume_info(prefix + ".vif")
            if info and "remote" in info:
                remote_info = info["remote"]

        if remote_info is not None:
            from .backend import BackendError, RemoteFile, get_backend
            backend = get_backend(remote_info["backend"])
            # a stale .vif pointing at a truncated/replaced object would
            # serve short reads forever; refuse the mount instead
            expect = int(remote_info["file_size"])
            try:
                actual = backend.size(remote_info["key"])
            except NotImplementedError:
                actual = expect
            except BackendError as e:
                raise VolumeError(
                    f"volume {vid}: remote .dat "
                    f"{remote_info['key']} unreachable: {e}") from None
            if actual != expect:
                raise VolumeError(
                    f"volume {vid}: remote .dat {remote_info['key']} is "
                    f"{actual} bytes but .vif records {expect}; refusing "
                    f"to serve a mismatched remote volume")
            self.dat = RemoteFile(backend, remote_info["key"], expect)
            self.super_block = SuperBlock.from_bytes(
                self.dat.read(SUPER_BLOCK_SIZE))
            self.readonly = True
            self.nm = load_needle_map(self.idx_path, self.index_kind,
                                  self.offset_width)
            self.last_modified = remote_info.get("modified_at", 0)
            return

        if create and not os.path.exists(self.dat_path):
            os.makedirs(dirname, exist_ok=True)
            from .super_block import FLAG_5_BYTE_OFFSETS
            sb = SuperBlock(
                replica_placement=replica_placement or ReplicaPlacement(),
                ttl=ttl or TTL(),
                flags=FLAG_5_BYTE_OFFSETS if offset_width == 5 else 0)
            if version:
                sb.version = version
            with open(self.dat_path, "wb") as f:
                f.write(sb.to_bytes())
            self.super_block = sb
            open(self.idx_path, "ab").close()
        else:
            with open(self.dat_path, "rb") as f:
                self.super_block = SuperBlock.from_bytes(
                    f.read(SUPER_BLOCK_SIZE))

        self.dat = open(self.dat_path, "r+b")
        self.check_integrity()
        self.nm = load_needle_map(self.idx_path, self.index_kind,
                                  self.offset_width)
        self.last_modified = int(os.path.getmtime(self.dat_path))
        # a keep-local tier upload leaves .dat + .vif side by side; the
        # volume serves locally but must stay frozen or the parked
        # remote copy silently diverges
        if not create and os.path.exists(prefix + ".vif"):
            from .volume_tier import load_volume_info
            info = load_volume_info(prefix + ".vif")
            if info and "remote" in info:
                self.readonly = True

    # -- properties --------------------------------------------------------
    @property
    def version(self) -> int:
        return self.super_block.version

    @property
    def offset_width(self) -> int:
        """4 (32GB max, reference-compatible) or 5 (8TB volumes);
        carried by the superblock flag byte."""
        return self.super_block.offset_width

    def file_name(self) -> str:
        return volume_file_prefix(self.dir, self.collection, self.id)

    @property
    def readonly(self) -> bool:
        return self._readonly

    @readonly.setter
    def readonly(self, value: bool):
        """Freezing a volume must IMMEDIATELY stop the native plane's
        fast-path writes, whatever code path flipped the flag (the
        admin route, EC-encode orchestration, tier parking, or a test
        poking the attribute) — the plane's accept gate cannot see a
        Python attribute on its own. Thawing does NOT re-open the
        gate here: re-qualification is the owning server's policy
        (_fast_sync re-acquires the lease).

        Thawing puts the configured needle map back where a frozen
        reload swapped the array map in (_reload_kind), under the lock
        and so before the volume takes a write or a lease goes out."""
        if value:
            self._readonly = True
            if self.fast_writer is not None:
                self.fast_writer.set_accept_posts(False)
            return
        with self.lock:
            self._readonly = False
            if self.index_kind == "memory" and self.nm.kind != "memory":
                self.reload_nm()

    def _writer_deltas(self):
        """(puts, put_bytes, deletes, deleted_bytes, max_key) appended
        by the native writer since the needle map was last fresh."""
        w = self.fast_writer
        return w.counters()[:5] if w is not None else (0, 0, 0, 0, 0)

    def content_size(self) -> int:
        return self.nm.content_size + self._writer_deltas()[1]

    def deleted_size(self) -> int:
        return self.nm.deleted_size + self._writer_deltas()[3]

    def file_count(self) -> int:
        return self.nm.file_counter + self._writer_deltas()[0]

    def deleted_count(self) -> int:
        return self.nm.deletion_counter + self._writer_deltas()[2]

    def max_file_key(self) -> int:
        return max(self.nm.maximum_file_key, self._writer_deltas()[4])

    def _nv_get(self, nid: int):
        """Live (offset, size) for a needle id: the native writer's
        exact mirror while the lease is out, else the needle map."""
        w = self.fast_writer
        if w is not None:
            hit = w.lookup(nid)
            if hit is None:
                return None
            from .needle_map import NeedleValue
            return NeedleValue(hit[0], hit[1])
        return self.nm.get(nid)

    def _reload_kind(self) -> str:
        """The needle-map kind a reload takes: the map follows the
        volume's writability. Nothing will ever `put` into a frozen
        volume's map, so the dict of an in-memory index (29,000
        NeedleValues a volume of 4 KB needles, built to be dropped by
        the encode that froze it) stays the record array the .idx was
        replayed into — the compact map. 5-byte offsets keep the dict
        (load_needle_map's rule); compact, sortedfile and disk are what
        the operator asked for."""
        if self.readonly and self.index_kind == "memory" and \
                self.offset_width == 4:
            return "compact"
        return self.index_kind

    def reload_nm(self):
        """Refresh the needle map from the .idx (call under self.lock,
        after the native writer's lease has been taken back — the .idx
        it kept is authoritative). A frozen volume with an in-memory
        index reloads into the array map (_reload_kind); the thaw
        (readonly = False) reloads the configured kind."""
        kind = self._reload_kind()
        self.nm.close()
        self.nm = load_needle_map(self.idx_path, kind, self.offset_width)
        if kind != self.index_kind:
            from ..ops import telemetry
            telemetry.STATS.add("frozen_array_maps")

    def _demote_fast_writer(self, err):
        """The native writer failed with ambiguity (I/O error, poisoned
        group-commit batch, fail-stopped lease): take the lease back,
        reload the needle map from the .idx the plane kept
        authoritative, and resume Python-owned appends — the plane's
        standing poison-demote philosophy. Caller holds self.lock."""
        from ..util import glog
        glog.V(0).infof(
            "volume %d: native writer demoted to the Python append "
            "path (%s)", self.id, err)
        w = self.fast_writer
        self.fast_writer = None
        try:
            w.release()
        finally:
            self.reload_nm()

    def _durable_sync(self):
        """fdatasync the .dat and .idx when SW_PLANE_FSYNC_MODE is on:
        an append demoted to the Python path must honor the same
        durability contract the native plane's group commit acks under
        — per-append fsync is acceptable on the slow path."""
        from ..util import config
        mode = (config.env_str("SW_PLANE_FSYNC_MODE") or "off")
        if mode.strip().lower() == "off":
            return
        os.fdatasync(self.dat.fileno())
        sync = getattr(self.nm, "sync", None)
        if sync is not None:
            sync()

    def size(self) -> int:
        with self.lock:
            self.dat.seek(0, os.SEEK_END)
            return self.dat.tell()

    def configure_replication(self, rp) -> None:
        """Rewrite this volume's replica placement in the superblock
        (reference command_volume_configure_replication.go →
        VolumeConfigure: byte 1 of the .dat). The master sees the new
        placement on the next heartbeat."""
        with self.lock:
            if self.readonly:
                # same guard as every write path: a tiered/parked
                # volume's local superblock must not silently diverge
                # from the remote copy — thaw (or tier.download) first
                raise VolumeError(
                    f"volume {self.id} is read only; cannot reconfigure "
                    f"replication")
            self.super_block.replica_placement = rp
            self.dat.seek(1)
            self.dat.write(bytes([rp.to_byte()]))
            self.dat.flush()

    def garbage_level(self) -> float:
        sz = self.size()
        if sz <= SUPER_BLOCK_SIZE:
            return 0.0
        return self.deleted_size() / sz

    def expired(self, volume_size_limit: int) -> bool:
        """Reference semantics (volume.go expired()): a 0 size limit means
        never expire; empty volumes don't expire either."""
        if volume_size_limit == 0 or self.content_size() == 0:
            return False
        ttl = self.super_block.ttl
        if ttl.minutes == 0:
            return False
        return time.time() - self.last_modified > ttl.minutes * 60

    # -- integrity (reference volume_checking.go:14) ----------------------
    def check_integrity(self):
        """Truncate a torn tail: the .dat must end on an 8-byte boundary and
        cover every .idx entry; trailing garbage after a crash is dropped."""
        self.dat.seek(0, os.SEEK_END)
        size = self.dat.tell()
        if size < SUPER_BLOCK_SIZE:
            raise VolumeError(f"volume {self.id}: missing superblock")
        aligned = SUPER_BLOCK_SIZE + (
            (size - SUPER_BLOCK_SIZE) // NEEDLE_PADDING_SIZE
        ) * NEEDLE_PADDING_SIZE
        if aligned != size:
            self.dat.truncate(aligned)
        # truncate trailing idx entries that point past the .dat end (crash
        # lost .dat pages but kept .idx pages); partial trailing entry too
        if os.path.exists(self.idx_path):
            from .needle_map import bytes_to_entry
            from .needle import get_actual_size
            from .types import entry_size
            rec = entry_size(self.super_block.offset_width)
            idx_size = os.path.getsize(self.idx_path)
            idx_size -= idx_size % rec
            dat_end = self.dat.seek(0, os.SEEK_END)
            version = self.super_block.version
            with open(self.idx_path, "r+b") as f:
                while idx_size >= rec:
                    f.seek(idx_size - rec)
                    nid, offset, size = bytes_to_entry(f.read(rec))
                    if size == TOMBSTONE_FILE_SIZE or offset == 0 or \
                            offset + get_actual_size(size, version) <= dat_end:
                        break
                    idx_size -= rec
                f.truncate(idx_size)

    # -- write -------------------------------------------------------------
    def write_needle(self, n: Needle) -> int:
        with self.lock:
            if self.readonly:
                raise VolumeError(f"volume {self.id} is read only")
            self._reject_empty(n)
            # reject overwrites that don't present the original cookie
            # (cookies exist to stop id-guessing; reference
            # volume_read_write.go checks the stored header's cookie)
            existing = self._nv_get(n.id)
            if existing is not None and existing.offset != 0 and \
                    existing.size != TOMBSTONE_FILE_SIZE:
                self.dat.seek(existing.offset)
                stored = Needle.parse_header(self.dat.read(16))
                if stored.cookie != n.cookie:
                    raise VolumeError(
                        f"needle {n.id}: mismatching cookie on overwrite")
            # needles inherit the volume's TTL when they carry none
            # (reference stamps n.Ttl = v.Ttl so per-needle expiry fires)
            vol_ttl = self.super_block.ttl
            if not n.has_ttl() and vol_ttl.to_uint32():
                n.set_ttl(vol_ttl)
                if not n.has_last_modified():
                    n.set_last_modified()
            if not n.append_at_ns:
                n.append_at_ns = time.time_ns()
            if self.fast_writer is not None:
                # the native plane owns the tail: one append updates
                # .dat, .idx, and the serving mirror atomically (the
                # ceiling check and the authoritative cookie re-check
                # live there too). OSError means ambiguity — an I/O
                # failure or a poisoned group-commit batch — so the
                # lease comes back and THIS append retries below on the
                # Python path (a durability-unknown duplicate on disk
                # is harmless: the index points at the latest record).
                # VolumeError (ceiling, cookie mismatch) propagates.
                blob = n.to_bytes(self.version)
                try:
                    self.fast_writer.append(blob, n.id, n.size,
                                            cookie=n.cookie)
                    self.last_modified = int(time.time())
                    return n.size
                except OSError as e:
                    self._demote_fast_writer(e)
            self.dat.seek(0, os.SEEK_END)
            offset = self.dat.tell()
            if offset % NEEDLE_PADDING_SIZE:
                offset += NEEDLE_PADDING_SIZE - offset % NEEDLE_PADDING_SIZE
                self.dat.truncate(offset)
            blob = n.to_bytes(self.version)
            # hard addressing ceiling for this volume's offset width
            # (32GB / 8TB); checked BEFORE the append so a too-far write
            # can't land in the .dat and then fail to index
            from .types import max_volume_size
            if offset + len(blob) > max_volume_size(self.offset_width):
                raise VolumeError(
                    f"volume {self.id}: write at {offset} exceeds the "
                    f"{self.offset_width}-byte-offset ceiling")
            try:
                self.dat.seek(offset)
                self.dat.write(blob)
                self.dat.flush()
            except OSError:
                self.dat.truncate(offset)
                raise
            if n.size > 0 or self.version == 1:
                self.nm.put(n.id, offset, n.size)
            self._durable_sync()
            self.last_modified = int(time.time())
            return n.size

    def _reject_empty(self, n: Needle):
        """Zero-size records ARE the tombstone format on disk (v2/v3):
        the write path never indexes them and every .dat replayer
        (rebuild_index, tail replication, vacuum) treats size==0 as a
        delete — matching the reference (fix.go, volume_read_write.go).
        Reject the write loudly instead of silently storing a needle
        that could never be read back."""
        if len(n.data) == 0 and self.version != 1:
            raise VolumeError(
                f"needle {n.id}: empty data — zero-size records are "
                "tombstones; store empty objects at the filer layer "
                "(an entry with no chunks)")

    def delete_needle(self, n: Needle) -> int:
        """Append a tombstone; returns freed size (0 if absent)."""
        with self.lock:
            if self.readonly:
                raise VolumeError(f"volume {self.id} is read only")
            nv = self._nv_get(n.id)
            if nv is None or nv.size == TOMBSTONE_FILE_SIZE:
                return 0
            # deletes must present the original cookie too (same id-guessing
            # protection as the overwrite path; reference DeleteHandler
            # reads the needle and compares cookies)
            self.dat.seek(nv.offset)
            stored = Needle.parse_header(self.dat.read(16))
            if stored.cookie != n.cookie:
                raise VolumeError(
                    f"needle {n.id}: mismatching cookie on delete")
            freed = nv.size
            tomb = Needle(cookie=n.cookie, id=n.id, data=b"",
                          append_at_ns=time.time_ns())
            if self.fast_writer is not None:
                # same demotion contract as write_needle: OSError =
                # ambiguity, retry this tombstone on the Python path
                try:
                    self.fast_writer.append(tomb.to_bytes(self.version),
                                            n.id, TOMBSTONE_FILE_SIZE,
                                            cookie=n.cookie)
                    self.last_modified = int(time.time())
                    return freed
                except OSError as e:
                    self._demote_fast_writer(e)
            self.nm.delete(n.id)
            self.dat.seek(0, os.SEEK_END)
            offset = self.dat.tell()
            self.dat.seek(offset)
            self.dat.write(tomb.to_bytes(self.version))
            self.dat.flush()
            self._durable_sync()
            self.last_modified = int(time.time())
            return freed

    # -- read --------------------------------------------------------------
    def read_needle(self, n: Needle) -> Needle:
        """Read by id; validates cookie and TTL. n carries id+cookie."""
        with self.lock:
            nv = self._nv_get(n.id)
            if nv is None or nv.offset == 0 or nv.size == TOMBSTONE_FILE_SIZE:
                raise NotFound(f"needle {n.id} not found in volume {self.id}")
            blob = self._read_blob(nv.offset, nv.size)
        got = Needle.from_bytes(blob, self.version, expected_size=nv.size)
        if got.cookie != n.cookie:
            raise NotFound(
                f"cookie mismatch for needle {n.id} in volume {self.id}")
        if got.has_ttl() and got.ttl.minutes and got.has_last_modified():
            if time.time() - got.last_modified > got.ttl.minutes * 60:
                raise NotFound(f"needle {n.id} expired")
        return got

    def read_needle_flags(self, n: Needle) -> int:
        """Flags byte of a stored needle via two tiny preads — no payload
        read (the delete path probes FLAG_IS_CHUNK_MANIFEST this way; a
        full read_needle would drag the whole blob off disk first).
        v1 needles carry no flags byte -> 0. NotFound if absent."""
        import struct
        with self.lock:
            nv = self._nv_get(n.id)
            if nv is None or nv.offset == 0 or \
                    nv.size == TOMBSTONE_FILE_SIZE:
                raise NotFound(
                    f"needle {n.id} not found in volume {self.id}")
            if self.version == 1 or nv.size == 0:
                return 0
            self.dat.seek(nv.offset + 16)
            raw = self.dat.read(4)
            if len(raw) < 4:
                return 0
            data_size = struct.unpack(">I", raw)[0]
            self.dat.seek(nv.offset + 16 + 4 + data_size)
            b = self.dat.read(1)
            return b[0] if b else 0

    def _read_blob(self, offset: int, size: int) -> bytes:
        want = get_actual_size(size, self.version)
        self.dat.seek(offset)
        blob = self.dat.read(want)
        if len(blob) < want:
            from .needle import CorruptNeedle
            raise CorruptNeedle(
                f"volume {self.id}: short read at {offset} "
                f"({len(blob)} < {want})")
        return blob

    # -- scan (used by export/fix/compact; reference volume_read_all.go) ---
    def scan(self):
        """Yield (needle, offset) for every record in the .dat, in order."""
        with self.lock:
            end = self.size()
            offset = SUPER_BLOCK_SIZE
            while offset + 16 <= end:
                self.dat.seek(offset)
                header = self.dat.read(16)
                n = Needle.parse_header(header)
                actual = get_actual_size(n.size, self.version)
                self.dat.seek(offset)
                blob = self.dat.read(actual)
                if len(blob) < actual:
                    break
                yield Needle.from_bytes(blob, self.version), offset
                offset += actual

    # -- vacuum (reference volume_vacuum.go) -------------------------------
    def _ttl_clock(self):
        """(ttl_seconds, now) for one vacuum pass — captured once so
        both algorithms expire against the same instant."""
        return self.super_block.ttl.minutes * 60, time.time()

    def _blob_expired(self, blob: bytes, ttl_seconds: int,
                      now: float) -> bool:
        """Volume-TTL expiry of one raw needle record (both vacuum
        algorithms; reference volume_vacuum.go:333-335 and :426-428).
        Skips the payload CRC — it is irrelevant to the timestamp and
        would double vacuum CPU. Unparseable records report
        not-expired: vacuum keeps the bytes verbatim instead of
        aborting (reclamation would starve forever) or dropping them."""
        if not ttl_seconds or self.version == 1:
            return False              # v1 records carry no timestamp
        try:
            n = Needle.from_bytes(blob, self.version, verify_crc=False)
        except Exception:  # noqa: BLE001 - corrupt record: keep it
            return False
        # needles written before the volume acquired its TTL (or via a
        # path that never stamped the flag) carry no TTL bit — expiring
        # them off last_modified alone would vacuum live data
        return n.has_ttl() and bool(n.last_modified) and \
            now >= n.last_modified + ttl_seconds

    def _begin_compaction(self):
        """Shared preamble of both vacuum algorithms (caller holds the
        lock): claim the single-compaction guard, name the .cpd/.cpx
        outputs, bump the superblock revision, and capture the makeup
        watermark. Returns (new_sb, cpd, cpx, deleted_size)."""
        # exactly one compaction at a time: two copiers would
        # interleave writes into the same .cpd and commit garbage
        if getattr(self, "_compacting", False):
            raise VolumeError(
                f"volume {self.id}: compaction already in progress")
        self._compacting = True
        prefix = self.file_name()
        new_sb = SuperBlock(
            version=self.version,
            replica_placement=self.super_block.replica_placement,
            ttl=self.super_block.ttl,
            compaction_revision=(
                self.super_block.compaction_revision + 1) & 0xFFFF,
            flags=self.super_block.flags)
        self._compact_idx_watermark = os.path.getsize(self.idx_path)
        return (new_sb, prefix + ".cpd", prefix + ".cpx",
                self.nm.deleted_size)

    def compact(self, bytes_per_second: int = 0) -> int:
        """Copy live needles to .cpd/.cpx. Returns reclaimed byte estimate.

        Iterates the needle map (not a raw .dat scan) so garbage records in
        the .dat — e.g. a torn-but-aligned write followed by later appends —
        can never cause live needles to be silently dropped; this matches
        the reference's Compact2, which copies from the index.

        bytes_per_second > 0 throttles the copy (reference
        compactionBytePerSecond + util.WriteThrottler) so compaction
        doesn't starve live reads on the same disk."""
        from ..util.throttler import WriteThrottler
        throttler = WriteThrottler(bytes_per_second)
        # snapshot under the lock, then copy WITHOUT it: the lock is
        # only re-taken per blob read, so live reads/writes interleave
        # with the (possibly throttled, minutes-long) copy. Anything
        # that lands after the snapshot is replayed by commit_compact's
        # makeup_diff — that replay is the whole reason the watermark
        # exists (holding the lock throughout would make it dead code
        # and stall the volume for the copy's duration).
        from .compact_map import snapshot_live_items
        with self.lock:
            new_sb, cpd, cpx, deleted_size = self._begin_compaction()
            try:
                width = self.offset_width
                live = snapshot_live_items(self.nm, by_offset=True)
            except BaseException:
                # anything failing after the guard was claimed (e.g.
                # sqlite disk-I/O error in flush) must release it, or
                # every future vacuum on this volume is wedged
                self._compacting = False
                raise
        from .needle_map import entry_to_bytes
        # volume-TTL'd needles past last_modified+ttl are reclaimed here
        # too (reference Compact2 does the same check as the scan path,
        # volume_vacuum.go:426-428)
        ttl_seconds, now = self._ttl_clock()
        try:
            with live, open(cpd, "wb") as dat_out, \
                    open(cpx, "wb") as idx_out:
                dat_out.write(new_sb.to_bytes())
                for nid, nv in live:
                    if nv.size == TOMBSTONE_FILE_SIZE or nv.offset == 0:
                        continue
                    with self.lock:
                        blob = self._read_blob(nv.offset, nv.size)
                    if self._blob_expired(blob, ttl_seconds, now):
                        continue
                    new_off = dat_out.tell()
                    dat_out.write(blob)
                    idx_out.write(entry_to_bytes(nid, new_off, nv.size,
                                                 width))
                    throttler.maybe_slowdown(len(blob))
        finally:
            self._compacting = False
        return deleted_size

    def compact_scan(self, bytes_per_second: int = 0) -> int:
        """Scan-based compaction — the reference's OTHER vacuum
        algorithm (`Compact`, volume_vacuum.go:37 +
        VolumeFileScanner4Vacuum, :310-352; `weed compact -method 0`,
        command/compact.go:20-30): walk the .dat sequentially and keep
        a record only when the needle map shows it live at exactly this
        offset and its TTL (volume-level, against the needle's
        last_modified) hasn't expired. compact() is the index-driven
        Compact2/method 1. Same .cpd/.cpx outputs, same
        commit_compact()."""
        from ..util.throttler import WriteThrottler
        throttler = WriteThrottler(bytes_per_second)
        from .compact_map import snapshot_live_items
        with self.lock:
            new_sb, cpd, cpx, deleted_size = self._begin_compaction()
            try:
                width = self.offset_width
                end = self.size()
                # one offset-ordered live snapshot taken here, then
                # merge-walked against the .dat scan — no per-record
                # lock/map-lookup round trips (mutations after this
                # point are covered by commit's makeup diff, exactly
                # like compact())
                live = snapshot_live_items(self.nm, by_offset=True)
                live_iter = iter(live)
            except BaseException:
                self._compacting = False   # same guard as compact()
                raise
        from .needle_map import entry_to_bytes
        from .volume_backup import walk_records
        ttl_seconds, now = self._ttl_clock()
        live_nid, live_nv = next(live_iter, (None, None))
        try:
            with open(self.dat_path, "rb") as src, \
                    open(cpd, "wb") as dat_out, \
                    open(cpx, "wb") as idx_out:

                def pread(off, size):
                    src.seek(off)
                    return src.read(size)

                dat_out.write(new_sb.to_bytes())
                for n, offset, actual in walk_records(
                        pread, self.version, SUPER_BLOCK_SIZE, end):
                    if n.size == TOMBSTONE_FILE_SIZE or n.size <= 0:
                        continue
                    while live_nv is not None and \
                            live_nv.offset < offset:
                        live_nid, live_nv = next(live_iter,
                                                 (None, None))
                    if live_nv is None or live_nv.offset != offset or \
                            live_nid != n.id or live_nv.size <= 0 or \
                            live_nv.size == TOMBSTONE_FILE_SIZE:
                        continue
                    blob = pread(offset, actual)
                    if self._blob_expired(blob, ttl_seconds, now):
                        continue
                    new_off = dat_out.tell()
                    dat_out.write(blob)
                    idx_out.write(entry_to_bytes(n.id, new_off, n.size,
                                                 width))
                    throttler.maybe_slowdown(len(blob))
        finally:
            # the merge-walk usually ends before the snapshot is
            # exhausted (.dat tail past the last live record) — close
            # explicitly so the WAL snapshot doesn't outlive the pass
            live.close()
            self._compacting = False
        return deleted_size

    def _finish_interrupted_commit(self, prefix: str):
        """Redo a compaction commit that crashed mid-rename. The
        `.commit` marker exists only between _makeup_diff completing
        and both renames landing, so whatever of .cpd/.cpx is still
        present is strictly newer than its .dat/.idx counterpart and
        the renames are safe to replay in any crash state."""
        marker = prefix + ".commit"
        if not os.path.exists(marker):
            return
        for src, dst in ((prefix + ".cpd", self.dat_path),
                         (prefix + ".cpx", self.idx_path)):
            if os.path.exists(src):
                os.replace(src, dst)
        # mirror commit_compact's in-window sidecar cleanup: a stale
        # .sdx whose watermark happens to match the new .idx size would
        # serve pre-compaction offsets into the compacted .dat
        for ext in (".sdx", ".sdx.meta"):
            if os.path.exists(prefix + ext):
                os.remove(prefix + ext)
        os.remove(marker)

    def commit_compact(self):
        with self.lock:
            prefix = self.file_name()
            cpd, cpx = prefix + ".cpd", prefix + ".cpx"
            if not (os.path.exists(cpd) and os.path.exists(cpx)):
                raise VolumeError("no compaction files to commit")
            self._makeup_diff(cpd, cpx)
            self.dat.close()
            self.nm.close()
            # intent marker makes the two renames redo-able: a crash
            # between them otherwise leaves new .dat + old .idx, whose
            # stale offsets the boot integrity check could silently
            # truncate into a wrong-but-plausible volume. (The
            # reference has this window, volume_vacuum.go CommitCompact;
            # the marker closes it — finish_interrupted_commit below.)
            marker = prefix + ".commit"
            with open(marker, "w") as f:
                f.write("compact-commit")
                f.flush()
                os.fsync(f.fileno())
            # the marker's DIRECTORY ENTRY must be durable before the
            # renames: a journaled rename surviving a crash that lost
            # the marker dirent would reopen the exact window the
            # marker closes
            dfd = os.open(os.path.dirname(marker) or ".", os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
            os.replace(cpd, self.dat_path)
            os.replace(cpx, self.idx_path)
            # sidecar cleanup stays INSIDE the marker window: the
            # compacted .idx can coincidentally match a stale .sdx
            # watermark size, and a crash after marker removal would
            # leave nothing to redo the cleanup
            for ext in (".sdx", ".sdx.meta"):
                if os.path.exists(prefix + ext):
                    os.remove(prefix + ext)
            os.remove(marker)
            with open(self.dat_path, "rb") as f:
                self.super_block = SuperBlock.from_bytes(
                    f.read(SUPER_BLOCK_SIZE))
            self.dat = open(self.dat_path, "r+b")
            # for -index disk this reload detects the rewritten .idx
            # (watermark/CRC mismatch) and rebuilds the sqlite map from
            # the post-vacuum index, under the lock. The index is at its
            # smallest right now (live needles only), and the .ndb being
            # self-validating derived data keeps every crash window safe;
            # building it alongside .cpx would shave the stall but add a
            # third commit artifact to the crash protocol.
            self.nm = load_needle_map(self.idx_path, self.index_kind,
                                  self.offset_width)

    def _makeup_diff(self, cpd: str, cpx: str):
        """Replay .idx entries appended after compact()'s snapshot onto the
        compacted files (reference makeupDiff, volume_vacuum.go:181)."""
        watermark = getattr(self, "_compact_idx_watermark", None)
        if watermark is None:
            return
        idx_size = os.path.getsize(self.idx_path)
        if idx_size <= watermark:
            return
        from .needle_map import bytes_to_entry, entry_to_bytes
        from .types import entry_size
        width = self.offset_width
        rec = entry_size(width)
        with open(self.idx_path, "rb") as f:
            f.seek(watermark)
            delta = f.read(idx_size - watermark)
        new_off = os.path.getsize(cpd)
        with open(cpd, "ab") as dat_out, open(cpx, "ab") as idx_out:
            for i in range(0, len(delta) - rec + 1, rec):
                nid, offset, size = bytes_to_entry(delta[i:i + rec])
                if size == TOMBSTONE_FILE_SIZE or offset == 0:
                    idx_out.write(
                        entry_to_bytes(nid, 0, TOMBSTONE_FILE_SIZE, width))
                    continue
                blob = self._read_blob(offset, size)
                dat_out.write(blob)
                idx_out.write(entry_to_bytes(nid, new_off, size, width))
                new_off += len(blob)
        self._compact_idx_watermark = None

    def cleanup_compact(self):
        for ext in (".cpd", ".cpx"):
            p = self.file_name() + ext
            if os.path.exists(p):
                os.remove(p)

    # -- lifecycle ---------------------------------------------------------
    def close(self):
        with self.lock:
            # a still-held write lease is the owner's to revoke; clear
            # the delegate so no append lands after the files close
            self.fast_writer = None
            self.nm.close()
            self.dat.close()

    def destroy(self):
        self.close()
        # .ndb* are the -index disk sqlite checkpoint (+ WAL/shm); .sdx*
        # the sortedfile sidecar — all derived from the .idx being removed
        exts = [".dat", ".idx", ".cpd", ".cpx",
                ".ndb", ".ndb-wal", ".ndb-shm", ".sdx", ".sdx.meta"]
        # the .vif sidecar is shared with the EC lifecycle: after
        # ec.encode deletes the original volume, parity-only holders
        # still need its offset_width — keep it while shard files exist
        if not os.path.exists(self.file_name() + ".ecx"):
            exts.append(".vif")
        for ext in exts:
            p = self.file_name() + ext
            if os.path.exists(p):
                os.remove(p)
