"""Store — a volume server's aggregate of disk locations.

Reference weed/storage/store.go: owns volumes + EC volumes across
directories, assembles heartbeats for the master, routes reads/writes to
volumes, and hosts the EC lifecycle operations (generate/mount/rebuild).
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
from ..util.locks import make_rlock
from typing import Dict, List, Optional

from ..ec import encoder as ec_encoder
from ..ec.constants import (DATA_SHARDS, MAX_SHARDS, PARITY_SHARDS,
                            to_ext)
from ..ec.ec_volume import EcVolume, ec_offset_width, rebuild_ecx_file
from ..ops.codec import ReedSolomonCodec
from .disk_location import DiskLocation
from .needle import Needle
from .types import TTL, ReplicaPlacement
from .volume import Volume, VolumeError, volume_file_prefix


def _remove_quietly(path: str):
    try:
        os.remove(path)
    except OSError:
        pass


#: `-ec.backend tpu-own`: the process's stores take its local chips in
#: turn, in the order they are built (the chip is the ordinal modulo the
#: local device count, ops/rs_tpu.local_device)
_OWN_DEVICE_ORDINALS = itertools.count()


class Store:
    def __init__(self, directories: List[str], max_volume_counts=None,
                 ip: str = "127.0.0.1", port: int = 8080,
                 public_url: str = "", data_center: str = "",
                 rack: str = "", codec: Optional[ReedSolomonCodec] = None,
                 index_kind: str = "memory", ec_backend: str = "auto",
                 pull_budget=None):
        if isinstance(directories, str):
            directories = [directories]
        max_volume_counts = max_volume_counts or [7] * len(directories)
        self.locations = [DiskLocation(d, m, index_kind=index_kind)
                          for d, m in zip(directories, max_volume_counts)]
        self.ip = ip
        self.port = port
        self.public_url = public_url or f"{ip}:{port}"
        self.data_center = data_center
        self.rack = rack
        # one codec a geometry, built on first use on the configured
        # backend: a volume's (k, m) is its own (.vif), and volumes of
        # several geometries live here at once. A codec handed in is
        # its geometry's, and names the geometry of a volume whose
        # sidecars name none (10 + 4 unless a caller brought another)
        self.ec_backend = ec_backend
        # the server's one budget for what it pulls in the background
        # (util/throttler.ByteBudget, -compactionMBps): every rebuild
        # here charges its remote survivor reads and sidecar fetches to
        # it, whichever route it takes. None: unthrottled
        self.pull_budget = pull_budget
        # a chip of its own among the process's (`tpu-own`); None: the
        # codecs compute wherever their backend does
        self.device_ordinal = next(_OWN_DEVICE_ORDINALS) \
            if ec_backend == "tpu-own" else None
        self._device: Optional[dict] = None
        self.default_geometry = (codec.k, codec.m) if codec is not None \
            else (DATA_SHARDS, PARITY_SHARDS)
        self._codecs: Dict[tuple, ReedSolomonCodec] = {}
        self._codec_like = codec
        if codec is not None:
            self._codecs[self.default_geometry] = codec
        # fired after any volume create/delete or EC shard mount/unmount
        # (reference store.go:40-64 NewVolumesChan/DeletedVolumesChan/
        # NewEcShardsChan/DeletedEcShardsChan): lets the volume server
        # push a heartbeat delta immediately instead of waiting a pulse.
        self.on_change = None
        # fired with (vid, mounted_shard_ids) after mount_ec_shards
        # registers shards: the degraded-read engine drops its cached
        # reconstructions of them — a shard back on disk (e.g. after
        # rebuild) must be served from disk, not from the slab LRU.
        self.on_ec_mount = None
        self.lock = make_rlock("store.lock")
        for loc in self.locations:
            loc.load_existing_volumes()
            loc.load_all_ec_shards()

    # -- codecs ------------------------------------------------------------
    def codec_for(self, k: int, m: int) -> ReedSolomonCodec:
        """The codec of one geometry, built the first time a volume of
        it is touched: the configured backend, or the class and matrix
        kind of the codec this store was handed."""
        key = (int(k), int(m))
        with self.lock:
            codec = self._codecs.get(key)
            if codec is None:
                like = self._codec_like
                if like is not None:
                    codec = type(like)(*key, like.matrix_kind)
                else:
                    from ..ops.codec import get_codec
                    codec = get_codec(*key, backend=self.ec_backend,
                                      device_ordinal=self.device_ordinal
                                      or 0)
                self._codecs[key] = codec
            return codec

    def device(self) -> Optional[dict]:
        """The chip this store's codecs compute on, where it has one of
        its own (`tpu-own`): platform, index among the process's local
        devices, kind, and ``chip``, a name no other chip of the cluster
        has (host, process and index: two servers share a chip exactly
        where they name the same one). None on every other backend: the
        server then says nothing of a device, and whoever asks takes
        such servers to share one. Resolving it is the store's first
        JAX touch (a `tpu-own` server asked for a chip; it is taken when
        the server starts, not at a status question of a process that
        has none yet)."""
        if self.device_ordinal is None:
            return None
        if self._device is None:
            import socket
            from ..ops.rs_tpu import local_device
            index, dev = local_device(self.device_ordinal)
            self._device = {
                "platform": dev.platform, "index": index,
                "kind": getattr(dev, "device_kind", "unknown"),
                "chip": f"{socket.gethostname()}/{os.getpid()}/"
                        f"{dev.platform}{index}"}
        return self._device

    @property
    def codec(self) -> ReedSolomonCodec:
        """The default geometry's codec (volumes whose .vif names no
        geometry, and callers that ask for no volume in particular)."""
        return self.codec_for(*self.default_geometry)

    def volume_geometry(self, base: str) -> tuple:
        """(k, m) of the EC volume at `base`, from its .vif."""
        from ..ec import layout as ec_layout
        return ec_layout.volume_geometry(base, self.default_geometry)

    def volume_codec(self, base: str) -> ReedSolomonCodec:
        return self.codec_for(*self.volume_geometry(base))

    def ec_volume_codec(self, ev: Optional[EcVolume]
                        ) -> ReedSolomonCodec:
        """What the degraded-read and scrub engines ask with: the codec
        of a mounted volume's own geometry (no volume: the default)."""
        if ev is None:
            return self.codec
        return self.codec_for(ev.k, ev.m)

    # -- lookup ------------------------------------------------------------
    def find_volume(self, vid: int) -> Optional[Volume]:
        for loc in self.locations:
            v = loc.get_volume(vid)
            if v is not None:
                return v
        return None

    def find_ec_volume(self, vid: int) -> Optional[EcVolume]:
        for loc in self.locations:
            ev = loc.ec_volumes.get(vid)
            if ev is not None:
                return ev
        return None

    def find_free_location(self) -> Optional[DiskLocation]:
        """Location with a free slot; an EC shard counts as 1/k of a
        volume, k the volume's own (1/10 by default; reference
        store.go:99-112)."""
        best, best_free = None, 0.0
        for loc in self.locations:
            ec_slots = sum(len(ev.shards) / ev.k
                           for ev in loc.ec_volumes.values())
            free = loc.max_volume_count - len(loc.volumes) - ec_slots
            if free >= 1 and free > best_free:
                best, best_free = loc, free
        return best

    # -- volume lifecycle --------------------------------------------------
    def add_volume(self, vid: int, collection: str = "",
                   replication: str = "000", ttl: str = "") -> Volume:
        if self.find_volume(vid) is not None:
            return self.find_volume(vid)
        loc = self.find_free_location()
        if loc is None:
            raise VolumeError("no free volume slots")
        v = loc.add_volume(
            collection, vid,
            replica_placement=ReplicaPlacement.parse(replication),
            ttl=TTL.parse(ttl))
        self._changed()
        return v

    def delete_volume(self, vid: int) -> bool:
        for loc in self.locations:
            if loc.delete_volume(vid):
                self._changed()
                return True
        return False

    def _changed(self):
        cb = self.on_change
        if cb is not None:
            cb()

    def mark_volume_readonly(self, vid: int,
                             readonly: bool = True) -> Optional[bool]:
        """Set the flag; returns the PREVIOUS readonly state, or None
        when the volume is absent — orchestrators restore exactly the
        prior state on failure."""
        v = self.find_volume(vid)
        if v is None:
            return None
        was, v.readonly = v.readonly, readonly
        return was

    # -- data path ---------------------------------------------------------
    def write_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is None:
            raise VolumeError(f"volume {vid} not found")
        return v.write_needle(n)

    def read_needle(self, vid: int, n: Needle) -> Needle:
        v = self.find_volume(vid)
        if v is None:
            raise VolumeError(f"volume {vid} not found")
        return v.read_needle(n)

    def read_needle_flags(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is None:
            raise VolumeError(f"volume {vid} not found")
        return v.read_needle_flags(n)

    def delete_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is None:
            raise VolumeError(f"volume {vid} not found")
        return v.delete_needle(n)

    # -- EC lifecycle (reference volume_grpc_erasure_coding.go) ------------
    def _encode_layout(self, codec: ReedSolomonCodec):
        """(layout name, plan, window) for a NEW ec volume of `codec`'s
        geometry, from SW_EC_LAYOUT. A geometry the piggyback
        construction does not cover (m < 2) raises, by name and before
        anything is written, rather than silently downgrading an
        operator's explicit piggyback choice."""
        from ..ec import layout as ec_layout
        from ..ec.constants import LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE
        from ..ops import codec as ops_codec
        from ..util import config as _config
        name = (_config.env_str("SW_EC_LAYOUT") or
                ec_layout.LAYOUT_FLAT).lower()
        if name == ec_layout.LAYOUT_FLAT:
            return ec_layout.LAYOUT_FLAT, None, None
        if name != ec_layout.LAYOUT_PIGGYBACK:
            raise VolumeError(f"unknown SW_EC_LAYOUT {name!r}")
        if not ops_codec.piggyback_supported(codec.k, codec.m):
            raise VolumeError(
                f"SW_EC_LAYOUT=piggyback unsupported for "
                f"RS({codec.k},{codec.m})")
        pplan, window = ec_encoder.piggyback_geometry(
            codec, None, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE)
        return ec_layout.LAYOUT_PIGGYBACK, pplan, window

    def _encode_codec(self, geometry) -> ReedSolomonCodec:
        """The codec a new EC volume is coded with: the geometry
        `ec.encode -geometry k,m` named, else the default."""
        if not geometry:
            return self.codec
        from ..ec import layout as ec_layout
        try:
            return self.codec_for(*ec_layout.parse_geometry(geometry))
        except ValueError as e:
            raise VolumeError(str(e)) from None

    def _volume_layout(self, base):
        """Resolve an existing volume's on-disk layout from its
        sidecars (ec/layout.volume_layout): the routing predicate for
        every layout-sensitive path below."""
        from ..ec import layout as ec_layout
        from .types import entry_size
        k = self.volume_geometry(base)[0]
        try:
            width = ec_offset_width(base)
        except Exception:  # noqa: BLE001 - no sidecars at all: flat
            width = 4
        return ec_layout.volume_layout(base, k,
                                       record_size=entry_size(width))

    def _write_layout_sidecars(self, base, v, layout, pplan, window,
                               codec):
        """Record the volume metadata, its RS geometry AND layout in
        one .vif/.ecx-tag write (ec/layout). offset_width must ride
        along: a shard receiver holding only parity shards has no .ec00
        superblock to infer the .ecx record width from; the geometry
        travels with the .vif wherever the index files are copied."""
        from ..ec import layout as ec_layout
        from .types import entry_size
        ec_layout.write_layout_sidecars(
            base, layout,
            window=window,
            pairs=(pplan.npairs if pplan is not None else None),
            record_size=entry_size(v.offset_width),
            version=v.version, offset_width=v.offset_width,
            ec_data_shards=codec.k, ec_parity_shards=codec.m)

    def generate_ec_shards(self, vid: int, collection: str = "",
                           geometry=None) -> str:
        """Volume .dat/.idx -> .ec00-13 + .ecx + .vif on the same disk
        (k + m shard files of the `geometry` asked for, 10 + 4 by
        default).
        SW_EC_LAYOUT picks the parity layout for the new shards; the
        choice is stamped into the sidecars so every later reader
        (scrub, degraded reads, rebuild) routes by the volume, not the
        environment."""
        v = self.find_volume(vid)
        if v is None:
            raise VolumeError(f"volume {vid} not found")
        if not v.readonly:
            raise VolumeError(f"volume {vid} must be readonly for ec encode")
        base = v.file_name()
        codec = self._encode_codec(geometry)
        layout, pplan, window = self._encode_layout(codec)
        from ..util import tracing
        with tracing.span("ec.encode.local", volume=vid, layout=layout,
                          k=codec.k, m=codec.m):
            ec_encoder.write_sorted_file_from_idx(base)
            ec_encoder.write_ec_files(base, codec=codec, layout=layout)
        self._write_layout_sidecars(base, v, layout, pplan, window, codec)
        return base

    def generate_ec_shards_streaming(self, vid: int, collection: str = "",
                                     assignment: Dict[int, str] = None,
                                     spares: List[str] = None,
                                     window: Optional[int] = None,
                                     stats: dict = None,
                                     rate_mbps: float = 0.0,
                                     geometry=None):
        """Streaming encode+spread: encode the readonly volume and push
        each shard's slab ranges to its assigned holder while later
        slabs are still encoding (ec/spread.py). ``assignment`` maps
        shard id -> holder url; shards assigned to this server (or
        unassigned) are written locally. Returns ``(base, final)``
        where ``final`` is the post-failover placement ({sid: url, ''
        for local}). On ANY failure every holder's ``.part`` stage is
        aborted and local outputs removed — no partial shards survive.

        Only the shards this server keeps (plus .ecx/.vif) touch its
        disk; remote-bound shards stream straight from the encode.
        ``rate_mbps`` > 0 paces the producer so a background demotion
        cannot saturate the network foreground reads share."""
        from ..ec import spread
        from ..stats.metrics import observe_transport
        from ..util import tracing
        v = self.find_volume(vid)
        if v is None:
            raise VolumeError(f"volume {vid} not found")
        if not v.readonly:
            raise VolumeError(f"volume {vid} must be readonly for ec encode")
        base = v.file_name()
        assignment = {int(s): u for s, u in (assignment or {}).items()}
        sstats = spread.SpreadStats()
        codec = self._encode_codec(geometry)
        total = codec.total
        # same slab policy as the streaming gather: shrink the stripe
        # so even a near-slab-sized shard gives the spread several
        # stripes to overlap with the encode (slab only batches device
        # columns — shard bytes are invariant under it)
        from ..ec.gather import auto_slab
        slab = auto_slab(ec_encoder.ec_shard_base_size(
            os.path.getsize(base + ".dat"), data_shards=codec.k))
        layout, pplan, pb_window = self._encode_layout(codec)
        with tracing.span("ec.encode.stream", volume=vid,
                          layout=layout, k=codec.k, m=codec.m) as root:
            sink = spread.StripedSpreadSink(
                vid, base, assignment, total, collection=collection,
                local_url=self.public_url, spares=spares,
                window=window, stats=sstats, parent_span=root,
                rate_mbps=rate_mbps, slab=slab)
            try:
                ec_encoder.write_ec_files_spread(
                    base, sink, codec=codec, slab=slab, stats=stats,
                    layout=layout, index=True)
            except BaseException:
                # the sink already aborted every holder's stage; drop
                # anything the local fast path finalized plus the index
                for i in range(total):
                    for p in (base + to_ext(i), base + to_ext(i) + ".part"):
                        try:
                            os.remove(p)
                        except OSError:
                            pass
                try:
                    os.remove(base + ".ecx")
                except OSError:
                    pass
                raise
            self._write_layout_sidecars(base, v, layout, pplan, pb_window,
                                        codec)
        observe_transport("push", sstats, window=sink.window)
        return base, sink.assignment()

    def mount_ec_shards(self, vid: int, collection: str,
                        shard_ids: List[int]) -> List[int]:
        mounted = []
        for loc in self.locations:
            base = volume_file_prefix(loc.directory, collection, vid)
            if not os.path.exists(base + ".ecx"):
                continue
            ev = loc.ec_volumes.get(vid)
            created = ev is None
            if created:
                ev = EcVolume(loc.directory, collection, vid)
            for sid in shard_ids:
                if os.path.exists(base + to_ext(sid)) and ev.add_shard(sid):
                    mounted.append(sid)
            if created:
                # never leave a shard-less EcVolume registered — it would
                # shadow the replica-redirect path for reads
                if ev.shards:
                    loc.ec_volumes[vid] = ev
                else:
                    ev.close()
            break
        if mounted:
            cb = self.on_ec_mount
            if cb is not None:
                cb(vid, mounted)
            self._changed()
        return mounted

    def unmount_ec_shards(self, vid: int, shard_ids: List[int]) -> List[int]:
        ev = self.find_ec_volume(vid)
        if ev is None:
            return []
        out = []
        for sid in shard_ids:
            shard = ev.delete_shard(sid)
            if shard is not None:
                shard.close()
                out.append(sid)
        if not ev.shards:
            for loc in self.locations:
                if loc.ec_volumes.get(vid) is ev:
                    loc.ec_volumes.pop(vid)
            ev.close()
        if out:
            self._changed()
        return out

    def rebuild_ec_shards_streaming(self, vid: int, collection: str = "",
                                    sources: Dict[int, List[str]] = None,
                                    stats: dict = None,
                                    slab: Optional[int] = None,
                                    window: Optional[int] = None,
                                    hedge_ms: Optional[float] = None,
                                    repair: str = "auto",
                                    deliver_to: Optional[str] = None
                                    ) -> List[int]:
        """Rebuild missing shards by streaming slab ranges of remote
        survivors straight into the decode — no whole-shard copies on
        this server's disks, before, during, or after. ``sources`` maps
        shard id -> holder urls for survivors NOT local to this store;
        shards already here are read from disk (with no ``sources`` every
        survivor is: the query-only ``POST /admin/ec/rebuild``). Only the
        KB-scale index sidecars (.ecx/.vif/.ecj) are copied whole.

        ``repair`` picks the single-shard repair strategy: ``trace``
        gathers per-survivor projected symbols over
        ``/admin/ec/shard_repair_read`` (sub-k*slab network bytes, see
        ops/codec.repair_plan), ``piggyback`` gathers half-plane
        sub-chunk streams over ``/admin/ec/shard_plane_read``
        ((k+1)/2k of the baseline, piggyback-layout volumes only),
        ``full`` is the full streaming decode, ``auto`` (default)
        routes by the volume's layout — piggyback repair on coupled
        layouts, trace on flat. A loss those routes were never meant
        for (more than one shard; a parity or uncoupled shard of a
        piggyback volume) takes the layout's full decode as its own
        route: the reply names ``repair_mode`` full and nothing else.
        A single-shard route that was tried and abandoned (no-gain
        geometry, too few parities, a holder that predates the repair
        routes) falls back to it bit-identically, counted in
        ``telemetry.repair_fallbacks`` and named under
        ``repair_fallback``. Forcing ``trace`` on a piggyback volume
        (or ``piggyback`` on flat) is an error: the modes read parity
        bytes the other layout does not have.

        ``deliver_to`` names another server as the home of the rebuilt
        shards: this one gathers and decodes (on its chip), and the rows
        leave through the encode's sink to that server's disk
        (ec/spread.RebuiltShardSink; nothing of them is written here,
        and sidecars this server pulled for the decode alone are dropped
        again). The flat full gather only: a piggyback volume is refused
        (VolumeError), and a single-shard loss is decoded in full.

        Whatever the route, what this server receives from another
        holder for the rebuild — survivor ranges, half-planes, trace
        bits, the sidecars — is charged to ``pull_budget`` where the
        server has one (-compactionMBps; the gather's stats carry it to
        the remote readers); local shards are read unpaced."""
        import time as _time
        from ..ec import gather
        from ..util import tracing
        sources = {int(s): list(urls) for s, urls in
                   (sources or {}).items() if urls}
        holders: List[str] = []
        for urls in sources.values():
            for u in urls:
                if u not in holders:
                    holders.append(u)
        # prefer a location that already has volume files; else the
        # freest one — the rebuilt shards and index live there
        loc = None
        for cand in self.locations:
            base = volume_file_prefix(cand.directory, collection, vid)
            if os.path.exists(base + ".ecx") or any(
                    os.path.exists(base + to_ext(i))
                    for i in range(MAX_SHARDS)):
                loc = cand
                break
        if loc is None:
            loc = self.find_free_location() or self.locations[0]
        base = volume_file_prefix(loc.directory, collection, vid)
        if deliver_to == self.public_url:
            deliver_to = None
        with tracing.span("ec.rebuild.stream", volume=vid) as root, \
                contextlib.ExitStack() as borrowed:
            if holders:
                # the sidecars this rebuilder lacks (.ecx by the entry,
                # so by the needle: 0.5 MB for a volume of 4 KB needles)
                with tracing.Stage("ec.rebuild.index", root) as pulled:
                    pulled.tags["files"] = gather.fetch_index_files(
                        base, holders, budget=self.pull_budget)
                    pulled.nbytes = sum(os.path.getsize(base + ext)
                                        for ext in pulled.tags["files"])
                if deliver_to and not any(
                        os.path.exists(base + to_ext(i))
                        for i in range(MAX_SHARDS)):
                    # nothing of the volume lives here and nothing will:
                    # what was pulled is for this decode alone
                    for ext in pulled.tags["files"]:
                        borrowed.callback(_remove_quietly, base + ext)
            # the .vif is local now (fetched above when remote): the
            # volume's own geometry, and the codec of that geometry
            codec = self.volume_codec(base)
            k, total = codec.k, codec.total
            root.tags["k"], root.tags["m"] = codec.k, codec.m
            local = [os.path.exists(base + to_ext(i))
                     for i in range(total)]
            present = [local[i] or i in sources for i in range(total)]
            missing = [i for i, p in enumerate(present) if not p]
            if not missing:
                return []
            if sum(present) < k:
                raise VolumeError(
                    f"cannot rebuild {vid}: only {sum(present)} of "
                    f"{total} shards reachable")
            mode = (repair or "auto").lower()
            if mode not in ("auto", "trace", "piggyback", "full"):
                raise VolumeError(f"unknown repair mode {mode!r}")
            # sidecars are local now (fetched above when remote): the
            # volume's layout routes every path below
            li = self._volume_layout(base)
            if deliver_to:
                if li.piggyback:
                    raise VolumeError(
                        "a rebuild delivered to another node is the flat "
                        "full gather's; a piggyback volume is rebuilt on "
                        "the node that keeps the shards")
                mode = "full"
                root.tags["deliver_to"] = deliver_to
            if mode == "trace" and li.piggyback:
                raise VolumeError(
                    "-repair trace: volume has the piggyback layout "
                    "(trace masks read flat parity bytes); use "
                    "piggyback, auto or full")
            if mode == "piggyback" and not li.piggyback:
                raise VolumeError(
                    "-repair piggyback: volume has the flat layout "
                    "(no coupled parity planes); use trace, auto or "
                    "full")
            # one wire probe per (vid, sid) for this whole rebuild, no
            # matter how many paths need a size below
            size_cache = gather.ShardSizeCache()

            def sized(candidates) -> int:
                sz = None
                for i in candidates:
                    if local[i]:
                        s = os.path.getsize(base + to_ext(i))
                        if sz is None:
                            sz = s
                        elif sz != s:
                            raise VolumeError(
                                "surviving shards differ in size")
                if sz is not None:
                    return sz
                last = None
                for i in candidates:
                    if i in sources:
                        try:
                            return size_cache.get(vid, i, sources[i])
                        except Exception as e:  # noqa: BLE001
                            last = e
                raise last if last is not None else VolumeError(
                    f"cannot size shards of volume {vid}")

            rebuilt = None
            if mode != "full":
                if li.piggyback:
                    rebuilt = self._rebuild_streaming_piggyback(
                        vid, base, local, present, missing, sources,
                        sized, stats, slab, window, hedge_ms, root,
                        mode, li, codec)
                else:
                    rebuilt = self._rebuild_streaming_trace(
                        vid, base, local, present, missing, sources,
                        sized, stats, slab, window, hedge_ms, root,
                        mode, codec)
            full = rebuilt is None
            if full and li.piggyback:
                # full coupled decode: the body plans, then asks for
                # the gather of its plan's sources (surviving data,
                # then just enough parities: not the first k), in
                # stripes of whole sub-chunk windows
                gstats = gather.GatherStats(self.pull_budget)

                def coupled_source(src):
                    shard_size = sized(src)
                    eff_slab = slab or gather.auto_slab(
                        shard_size, default=ec_encoder.DEFAULT_SLAB)
                    return gather.StripedGatherSource(
                        [gather.LocalShardReader(base + to_ext(i), gstats)
                         if local[i] else gather.RemoteShardReader(
                             vid, i, sources[i], gstats, hedge_ms=hedge_ms)
                         for i in src], shard_size,
                        slab=max(li.window, eff_slab - eff_slab % li.window),
                        window=window, stats=gstats, parent_span=root)

                rebuilt = ec_encoder.rebuild_ec_files_piggyback(
                    base, present, missing, li, coupled_source,
                    codec=codec, stats=stats)
                from ..stats.metrics import observe_transport
                observe_transport("pull", gstats,
                                  window=window or gather.gather_window())
                if stats is not None:
                    stats["repair_mode"] = "full"
            elif full:
                gather_present = self._health_survivor_mask(
                    present, local, sources, k, stats)
                src = [i for i, p in enumerate(gather_present) if p][:k]
                gstats = gather.GatherStats(self.pull_budget)
                readers = []
                for i in src:
                    if local[i]:
                        readers.append(gather.LocalShardReader(
                            base + to_ext(i), gstats))
                    else:
                        readers.append(gather.RemoteShardReader(
                            vid, i, sources[i], gstats,
                            hedge_ms=hedge_ms))
                shard_size = sized(src)
                eff_slab = slab or gather.auto_slab(
                    shard_size, default=ec_encoder.DEFAULT_SLAB)
                source = gather.StripedGatherSource(
                    readers, shard_size, slab=eff_slab,
                    window=window, stats=gstats, parent_span=root)
                sink = None
                if deliver_to:
                    from ..ec import spread
                    sink = spread.RebuiltShardSink(
                        vid, missing, deliver_to, collection=collection,
                        parent_span=root, slab=eff_slab)
                rebuilt = ec_encoder.rebuild_ec_files_streaming(
                    base, gather_present, missing, source,
                    codec=codec, slab=eff_slab, stats=stats, sink=sink)
                from ..stats.metrics import observe_transport
                observe_transport("pull", gstats, window=source.window)
                if stats is not None:
                    stats["repair_mode"] = "full"
            from ..ops import telemetry
            telemetry.STATS.add_repair_route(
                "full" if full else
                "piggyback" if li.piggyback else "trace")
            t0 = _time.perf_counter()
            rebuild_ecx_file(base, ec_offset_width(base))
            ecx_s = _time.perf_counter() - t0
            tracing.record_span("write", ecx_s, op="ec.rebuild.ecx")
            if stats is not None and "phases" in stats:
                stats["phases"]["write"] = round(
                    stats["phases"].get("write", 0.0) + ecx_s, 6)
        return rebuilt

    @staticmethod
    def _health_survivor_mask(present, local, sources, k, stats):
        """Health-aware survivor selection for the full streaming
        gather. With more than k survivors reachable and
        SW_EC_HEALTH_ROUTING=1, the surplus shards are dropped from the
        decode plan worst-holder-first (local shards score a perfect
        1.0), so a slow or erroring holder is demoted out of the gather
        entirely when healthier survivors can cover the k. Decoding
        from any k survivors is exact, so the rebuilt bytes are
        bit-identical regardless of which surplus shards are masked.
        Ties drop the highest shard ids, matching the un-routed
        first-k selection."""
        from ..stats import health as _health
        survivors = [i for i, p in enumerate(present) if p]
        surplus = len(survivors) - k
        if surplus <= 0 or not _health.routing_enabled():
            return present

        def shard_score(i):
            if local[i] or not sources.get(i):
                return 1.0
            return max(_health.BOARD.score(u) for u in sources[i])

        masked = list(present)
        drop_order = sorted(survivors,
                            key=lambda i: (shard_score(i), -i))
        demoted = sorted(drop_order[:surplus])
        for i in demoted:
            masked[i] = False
        if stats is not None:
            stats["health_demoted_shards"] = demoted
        return masked

    def _rebuild_streaming_piggyback(self, vid, base, local, present,
                                     missing, sources, sized, stats,
                                     slab, window, hedge_ms, root, mode,
                                     li, codec=None):
        """Attempt the half-plane piggyback repair; returns the rebuilt
        shard list or None to signal 'use the full coupled decode
        instead'. A loss the route was never meant for (more than one
        shard, a parity or uncoupled shard) goes there as its own
        route; a route that was tried and abandoned is a fallback,
        counted and named in stats. Forced mode ('piggyback') converts
        both into an error."""
        from ..ec import decoder as ec_decoder
        from ..ec import gather
        from ..ops import codec as ops_codec
        from ..ops import telemetry
        from ..server.http_util import HttpError
        from ..util import tracing

        def refuse(reason: str):
            if mode == "piggyback":
                raise VolumeError(f"-repair piggyback: {reason}")

        def bail(reason: str):
            refuse(reason)
            telemetry.STATS.add("repair_fallbacks")
            if stats is not None:
                stats["repair_fallback"] = reason

        if len(missing) != 1:
            return refuse(
                f"{len(missing)} shards lost, piggyback repairs one")
        lost = missing[0]
        codec = codec or self.codec
        k, m = codec.k, codec.m
        with tracing.Stage("ec.rebuild.plan", root) as planning:
            try:
                pplan = ops_codec.piggyback_plan(
                    k, m, matrix_kind=codec.matrix_kind,
                    matrix=codec.matrix, pairs=li.pairs)
            except ValueError as e:
                return bail(f"no piggyback scheme: {e}")
        if lost >= pplan.coupled:
            return refuse(f"shard {lost} not coupled "
                          f"(coupled prefix is 0..{pplan.coupled - 1})")
        par = [k + j for j in range(m) if present[k + j]]
        if len(par) < 2:
            return bail(f"{len(par)} surviving parities, plane repair "
                        f"needs 2")
        if any(not present[i] for i in range(k) if i != lost):
            return bail("a data helper is unreachable")
        try:
            rplan = ops_codec.piggyback_repair_plan(
                k, m, lost, parity_sids=tuple(par[:2]),
                matrix_kind=pplan.matrix_kind,
                matrix=codec.matrix, pairs=li.pairs)
        except ValueError as e:
            return bail(f"no repair plan: {e}")
        shard_size = sized(rplan.helpers)
        if shard_size % li.window:
            return bail(
                f"shard size {shard_size} not aligned to sidecar "
                f"window {li.window}")
        gstats = gather.GatherStats(self.pull_budget)
        readers = []
        for i in rplan.helpers:
            if local[i]:
                readers.append(gather.LocalPlaneReader(
                    base + to_ext(i), li.alpha, li.window,
                    rplan.plane_bit, rplan.plane_side, gstats))
            else:
                readers.append(gather.RemotePlaneReader(
                    vid, i, sources[i], li.alpha, li.window,
                    rplan.plane_bit, rplan.plane_side, gstats,
                    hedge_ms=hedge_ms))
        eff_slab = slab or gather.auto_slab(
            shard_size, default=ec_encoder.DEFAULT_SLAB)
        source = gather.PlaneGatherSource(
            readers, shard_size, rplan, li.window, slab=eff_slab,
            gather_window=window, stats=gstats, parent_span=root)
        rstats: dict = {}
        try:
            rebuilt = ec_decoder.rebuild_ec_file_piggyback(
                base, lost, source, rplan, li.window, codec=codec,
                slab=source.slab, stats=rstats)
        except HttpError as e:
            if e.status in (404, 405, 501):
                # a holder predates /admin/ec/shard_plane_read (or
                # never had the shard): the repair output was already
                # cleaned up, rerun as a full coupled decode
                return bail(f"holder refused plane read ({e.status})")
            raise
        from ..stats.metrics import observe_transport
        observe_transport("pull", gstats, window=source.window)
        rstats["phases"]["plan"] = round(planning.t1 - planning.t0, 6)
        if stats is not None:
            stats.update(rstats)
        return rebuilt

    def _rebuild_streaming_trace(self, vid, base, local, present,
                                 missing, sources, sized, stats, slab,
                                 window, hedge_ms, root, mode,
                                 codec=None):
        """Attempt the trace-repair path; returns the rebuilt shard list
        or None to signal 'use the full streaming gather instead'. More
        than one lost shard was never this route's: the full gather is
        then the rebuild's own route; a route that was tried and
        abandoned is a fallback, counted and named in stats. Forced
        mode ('trace') converts both into an error."""
        from ..ec import decoder as ec_decoder
        from ..ec import gather
        from ..ops import codec as ops_codec
        from ..ops import telemetry
        from ..server.http_util import HttpError
        from ..util import tracing

        def refuse(reason: str):
            if mode == "trace":
                raise VolumeError(f"-repair trace: {reason}")

        def bail(reason: str):
            refuse(reason)
            telemetry.STATS.add("repair_fallbacks")
            if stats is not None:
                stats["repair_fallback"] = reason

        if len(missing) != 1:
            return refuse(f"{len(missing)} shards lost, trace repairs one")
        lost = missing[0]
        codec = codec or self.codec
        k, m = codec.k, codec.m
        helpers = [i for i, p in enumerate(present) if p and i != lost]
        # a scheme search of ~0.5 s the first time a (lost, helpers)
        # pair is seen, a cache hit after: a stage of the stream
        with tracing.Stage("ec.rebuild.plan", root) as planning:
            try:
                plan = ops_codec.repair_plan(
                    k, m, lost, survivors=helpers,
                    matrix_kind=codec.matrix_kind, matrix=codec.matrix)
            except ValueError as e:
                return bail(f"no repair scheme: {e}")
        if mode == "auto" and plan.frac >= 1.0:
            return bail(f"no trace gain (frac={plan.frac:.3f})")
        shard_size = sized(plan.helpers)
        gstats = gather.GatherStats(self.pull_budget)
        readers = []
        for i in plan.helpers:
            if local[i]:
                readers.append(gather.LocalRepairReader(
                    base + to_ext(i), plan.masks[i], gstats))
            else:
                readers.append(gather.RemoteRepairReader(
                    vid, i, sources[i], plan.masks[i], gstats,
                    hedge_ms=hedge_ms))
        eff_slab = slab or gather.auto_slab(
            shard_size, default=ec_encoder.DEFAULT_SLAB)
        source = gather.RepairGatherSource(
            readers, shard_size, plan, slab=eff_slab,
            window=window, stats=gstats, parent_span=root)
        rstats: dict = {}
        try:
            rebuilt = ec_decoder.rebuild_ec_file_repair(
                base, lost, source, plan, codec=codec,
                slab=eff_slab, stats=rstats)
        except HttpError as e:
            if e.status in (404, 405, 501):
                # a holder predates /admin/ec/shard_repair_read (or
                # never had the shard): the repair output was already
                # cleaned up, rerun as a plain streaming gather
                return bail(f"holder refused repair read ({e.status})")
            raise
        from ..stats.metrics import observe_transport
        observe_transport("pull", gstats, window=source.window)
        rstats["phases"]["plan"] = round(planning.t1 - planning.t0, 6)
        if stats is not None:
            stats.update(rstats)
        return rebuilt

    # -- heartbeat (reference store.go:193-247 CollectHeartbeat) -----------
    def collect_heartbeat(self) -> dict:
        volumes = []
        ec_shards: Dict[int, int] = {}
        ec_collections: Dict[int, str] = {}
        ec_geometries: Dict[int, List[int]] = {}
        max_file_key = 0
        max_volume_count = 0
        for loc in self.locations:
            max_volume_count += loc.max_volume_count
            for vid, v in list(loc.volumes.items()):
                max_file_key = max(max_file_key, v.max_file_key())
                volumes.append({
                    "id": vid,
                    "collection": v.collection,
                    "size": v.size(),
                    "file_count": v.file_count(),
                    "delete_count": v.deleted_count(),
                    "deleted_byte_count": v.deleted_size(),
                    "read_only": v.readonly,
                    "replica_placement":
                        str(v.super_block.replica_placement),
                    "ttl": v.super_block.ttl.to_uint32(),
                    "version": v.version,
                    "compact_revision": v.super_block.compaction_revision,
                    "modified_at": v.last_modified,
                })
            for vid, ev in loc.ec_volumes.items():
                bits = 0
                for sid in ev.shard_ids():
                    bits |= 1 << sid
                ec_shards[vid] = bits
                ec_collections[vid] = ev.collection
                ec_geometries[vid] = [ev.k, ev.m]
        hb = {
            "ip": self.ip, "port": self.port, "public_url": self.public_url,
            "data_center": self.data_center, "rack": self.rack,
            "max_volume_count": max_volume_count,
            "max_file_key": max_file_key,
            "volumes": volumes,
            "ec_shards": ec_shards,
            "ec_collections": ec_collections,
            # each EC volume's own RS geometry [k, m] (its .vif): the
            # master knows a volume whole at k + m shards
            "ec_geometries": ec_geometries,
        }
        if self.device_ordinal is not None:
            # the chip of a `tpu-own` server: the master passes it on
            # (/cluster/status), the shell's ec.rebuild keeps one volume
            # in flight a distinct chip
            hb["device"] = self.device()
        return hb

    def status(self) -> dict:
        hb = self.collect_heartbeat()
        hb["directories"] = [loc.directory for loc in self.locations]
        return hb

    def close(self):
        for loc in self.locations:
            loc.close()
