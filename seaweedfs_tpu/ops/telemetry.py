"""Device-dispatch counters for the EC hot paths.

The round-5 bench showed the mesh rebuild at 2 MB/s with
compute_frac=0.99 — pure dispatch overhead (per-slab bitmat re-lift +
re-upload, two matmuls per slab, no overlap), not GF math. These
counters make that overhead *observable*: every device dispatch,
bit-matrix upload and host-path small-read fallback increments a
process-global counter, and rebuild_ec_files reports the deltas
(`dispatches`, `bitmat_uploads`) in its reply, where benchmarks/run.py
reads them, so a regression back to per-slab uploads shows up as a
count instead of hiding inside wall time.

Mesh-sharded dispatches additionally record which devices a put
actually landed bytes on (`mesh_dispatches`, per-device byte map).
That is the width guard: a MeshCodec built over a width-1 mesh (or a
crossover silently routing everything to the single-device path)
compiles, runs, and is bit-identical — only the per-device byte map
distinguishes it from a dispatch that saturated the mesh, so
`delta()` derives `dispatch_width_devices` / `device_byte_share` from
it and the bench asserts on them.

The `.dat` reader counts here too, beside its span (`ec.encode.read`,
util/tracing.Stage): the bytes it handed to the pipeline and its busy
and CPU microseconds, whose ratio says whether the thread copies or
waits. Transfer and survivor-fetch bytes are not doubled here: the
stream's StageTimer (`h2d`, `d2h+mxu`) and the transport's
TransportStats already hold them.

Which route a streaming rebuild took is counted here as well
(`repair_route`: piggyback / trace / full, and `repair_fallbacks` for
an `auto` rebuild that tried its layout's single-shard route and had to
leave it): a fall-back to the full gather is bit-identical and so shows
nowhere else than in bytes moved. A loss those routes were never meant
for (more than one shard, a parity shard of a piggyback volume) takes
the full decode as its own route and counts no fallback.
`coupled_decodes` counts the full coupled decodes of piggyback volumes
(ec/encoder.rebuild_ec_files_piggyback), local or streaming.

`geometry_dispatches` counts every dispatch again under the RS
geometry of the codec that issued it (`"10+4"`, `"6+3"`: a volume's
geometry is its own, storage/store keeps a codec a geometry), at the
two sites `dispatches` is counted: a call site that fell back to the
default geometry's codec for a volume of another is bit-wrong or slow
and shows nowhere else than in this map.

`index_entries` and `index_us` count the `.ecx` builds
(ec/encoder.write_sorted_file_from_idx): the live entries written and
the microseconds from the first `.idx` record read to the last `.ecx`
record written. A volume of 1 MiB needles has a thousand entries and
the build shows nowhere; one of 4 KB needles has thirty thousand, read
as one record array and sorted once (storage/needle_map.MemDb) on the
thread that then runs the stream.

`mirror_entries` and `mirror_us` count the indexes a holder loads:
the records of a `.idx` replayed into a needle map
(storage/needle_map.NeedleMap.load, compact_map.CompactNeedleMap.load:
every volume load, mount and freeze) and the entries pushed into the
native plane's mirrors (server/native_plane: a volume's live set, an EC
volume's `.ecx`), with the wall microseconds of each load. They are read as arrays
(storage/idx_array); `mirror_loop_entries` counts those that still went
an entry a Python iteration, which a map that offers no columns (the
disk map under the plane) costs. `frozen_array_maps` counts the reloads
that chose the array map for a frozen volume of an in-memory index
(storage/volume.Volume._reload_kind): one a freeze whose lease came
back, so an `ec.encode -collection` of 32 volumes moves it by 32.

`collection_encode_inflight_us` and `collection_encode_us` count the
`ec.encode -collection` commands this process's shell ran
(shell/command_ec.ec_encode): the microseconds its volumes were in
flight, summed over the volumes, and the microseconds of the commands.
Their ratio is the time-weighted mean number of volumes in flight, which
the command's `ec.encode.collection` span carries as
`volumes_inflight_mean` beside `lanes` (the source servers that could
run one: a collection's volumes run one at a time per server a `.dat`
lies on). 1 where a command walks its volumes one by one; a collection
on four servers reads between 3 and 4, the tail of a command, when
fewer servers have volumes left, keeps it under the lanes.

`rebuild_delivered_bytes` and `rebuild_local_bytes` count the bytes of
rebuilt shards a full-gather rebuild produced, by where they went: sent
to another node's disk through the spread's sink (the node that decoded
is not the node placement names, shell/command_ec's one volume in
flight a chip) or written to this node's own files.

`slab_fresh_bytes` counts the bytes of stripe-sized host blocks that
were new memory (ec/transport._take_slab found its pool empty, or
holding nothing large enough): what these hosts charge for is memory a
thread has not touched, and once a process has run its first volume
the encode's reader and the rebuild's gather take every block from the
pool, so the count stands still.

`throttle` (`bytes`, `wait_us`) counts what the process's servers charged
to their budget for background pulls (util/throttler.ByteBudget,
`-compactionMBps`: a rebuild's remote survivor reads and sidecar
fetches, `volume.copy`, `ec.copy`) and the microseconds the charging
threads were told to wait, summed over the threads. A server started
with no budget moves neither; degraded reads and scrub are never
charged.
"""

from __future__ import annotations

from typing import Dict

from ..util.locks import make_lock


class DispatchStats:
    """Monotonic process-global counters (thread-safe)."""

    _FIELDS = ("dispatches", "bitmat_uploads", "host_fallbacks",
               "device_bytes", "mesh_dispatches",
               "read_bytes", "read_busy_us", "read_cpu_us",
               "repair_fallbacks", "coupled_decodes",
               "slab_fresh_bytes", "index_entries", "index_us",
               "holder_runs", "holder_bytes", "holder_us",
               "holder_recv_us", "holder_write_us", "holder_cpu_us",
               "lock_probe_samples", "lock_probe_elapsed_us",
               "lock_probe_late_us", "lock_probe_stalls",
               "lock_probe_stall_us",
               "mirror_entries", "mirror_us", "mirror_loop_entries",
               "rebuild_delivered_bytes", "rebuild_local_bytes",
               "frozen_array_maps",
               "collection_encode_inflight_us", "collection_encode_us")
    REPAIR_ROUTES = ("piggyback", "trace", "full")

    def __init__(self):
        self._lock = make_lock("telemetry._lock")
        for f in self._FIELDS:
            setattr(self, f, 0)
        self._mesh_device_bytes: Dict[str, int] = {}
        self._repair_route = dict.fromkeys(self.REPAIR_ROUTES, 0)
        self._geometry_dispatches: Dict[str, int] = {}
        self._throttle = {"bytes": 0, "wait_us": 0}

    def add(self, field: str, n: int = 1):
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def add_dispatch(self, geometry: str, nbytes: int):
        """One device dispatch of `nbytes` payload bytes, issued by a
        codec of this geometry (`ReedSolomonCodec.geometry`: "10+4")."""
        with self._lock:
            self.dispatches += 1
            self.device_bytes += nbytes
            self._geometry_dispatches[geometry] = \
                self._geometry_dispatches.get(geometry, 0) + 1

    def add_read(self, nbytes: int, busy_s: float, cpu_s: float):
        """One dispatch's slab left the `.dat` reader thread."""
        with self._lock:
            self.read_bytes += nbytes
            self.read_busy_us += int(busy_s * 1e6)
            self.read_cpu_us += int(cpu_s * 1e6)

    def add_index(self, entries: int, wall_s: float):
        """One `.ecx` built from a volume's `.idx`."""
        with self._lock:
            self.index_entries += entries
            self.index_us += int(wall_s * 1e6)

    def add_mirror(self, entries: int, wall_s: float,
                   loop_entries: int = 0):
        """One index loaded into a needle map or a plane's mirror."""
        with self._lock:
            self.mirror_entries += entries
            self.mirror_us += int(wall_s * 1e6)
            self.mirror_loop_entries += loop_entries

    def add_holder_run(self, nbytes: int, wall_s: float, recv_s: float,
                       write_s: float, cpu_s: float):
        """One run a holder moved from the socket to its stage file
        (also one that ended short and was rolled back: its interval
        and the bytes that did arrive)."""
        with self._lock:
            self.holder_runs += 1
            self.holder_bytes += nbytes
            self.holder_us += int(wall_s * 1e6)
            self.holder_recv_us += int(recv_s * 1e6)
            self.holder_write_us += int(write_s * 1e6)
            self.holder_cpu_us += int(cpu_s * 1e6)

    def add_probe_sample(self, elapsed_s: float, late_s: float,
                         stalled: bool):
        """One wake-up of the interpreter-lock probe."""
        with self._lock:
            self.lock_probe_samples += 1
            self.lock_probe_elapsed_us += int(elapsed_s * 1e6)
            self.lock_probe_late_us += int(late_s * 1e6)
            if stalled:
                self.lock_probe_stalls += 1
                self.lock_probe_stall_us += int(late_s * 1e6)

    def add_collection_encode(self, inflight_s: float, wall_s: float):
        """One `ec.encode -collection` command ended: its volumes'
        seconds in flight, summed, and its own."""
        with self._lock:
            self.collection_encode_inflight_us += int(inflight_s * 1e6)
            self.collection_encode_us += int(wall_s * 1e6)

    def add_repair_route(self, route: str):
        """One streaming rebuild finished on this route."""
        with self._lock:
            self._repair_route[route] += 1

    def add_throttle(self, nbytes: int, wait_s: float):
        """One charge to a server's budget for background pulls."""
        with self._lock:
            self._throttle["bytes"] += nbytes
            self._throttle["wait_us"] += int(wait_s * 1e6)

    def add_mesh_device_bytes(self, device: str, n: int):
        """Payload bytes a sharded put landed on one device."""
        with self._lock:
            self._mesh_device_bytes[device] = \
                self._mesh_device_bytes.get(device, 0) + n

    def snapshot(self) -> dict:
        with self._lock:
            snap = {f: getattr(self, f) for f in self._FIELDS}
            snap["mesh_device_bytes"] = dict(self._mesh_device_bytes)
            snap["repair_route"] = dict(self._repair_route)
            snap["geometry_dispatches"] = dict(self._geometry_dispatches)
            snap["throttle"] = dict(self._throttle)
            return snap


STATS = DispatchStats()


def delta(before: dict) -> dict:
    """Counter movement since a snapshot() — the per-operation report.

    Besides the raw field deltas, derives the mesh width facts the
    bench guards on: `dispatch_width_devices` (devices a sharded put
    landed bytes on during the window; 1 when only single-device
    dispatches ran, 0 when none did) and `device_byte_share` (each
    device's payload bytes relative to the busiest — 1.0 everywhere
    means a perfectly even shard split; it says nothing of the time a
    device was busy, which only a profiler trace shows)."""
    now = STATS.snapshot()
    out = {f: now[f] - before.get(f, 0) for f in DispatchStats._FIELDS}
    before_dev = before.get("mesh_device_bytes", {})
    per_dev = {}
    for dev, n in now["mesh_device_bytes"].items():
        moved = n - before_dev.get(dev, 0)
        if moved > 0:
            per_dev[dev] = moved
    out["mesh_device_bytes"] = per_dev
    before_geo = before.get("geometry_dispatches", {})
    moved_geo = {g: n - before_geo.get(g, 0)
                 for g, n in now["geometry_dispatches"].items()}
    out["geometry_dispatches"] = {g: n for g, n in moved_geo.items()
                                  if n > 0}
    if per_dev:
        peak = max(per_dev.values())
        out["dispatch_width_devices"] = len(per_dev)
        out["device_byte_share"] = {d: round(n / peak, 4)
                                    for d, n in sorted(per_dev.items())}
    else:
        out["dispatch_width_devices"] = 1 if out["dispatches"] > 0 else 0
        out["device_byte_share"] = {}
    return out
