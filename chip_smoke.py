#!/usr/bin/env python3
"""chip_smoke.py — the served warm-tier EC path, once, on the chip.

One deployment the repo supports: RS(10,4), flat layout, default slab
(k x 8 MiB per dispatch), ONE sealed volume (default 1 GiB, a stated cut
from the 30 GB volumes of f4 / BASELINE.json). Data is made from --seed.
Everything runs in this one process, which therefore holds the chip:
MasterServer + three VolumeServer(ec_backend="tpu") built the way
`weed server` / `weed volume` build them, the HTTP client for uploads
and GETs, and the shell's registered ec.encode / ec.rebuild commands.

    upload -> ec.encode -> compare with NumpyCodec + GET every needle
           -> lose 4 shards -> GET every needle -> ec.rebuild -> compare
           -> prove the chip did the work

Every phase raises on a mismatch; nothing is caught and carried past.
Earlier stdout lines are one JSON object each (observations, named with
the device they were made on). The LAST stdout line is, and is only,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and is printed only on a TPU after every check passed. Off the chip the
script exits non-zero and its last line says "ok": false.

`--chips 4` runs only the multi-chip path and what it is compared with:
the same volume through ec_backend="mesh" on a 4-device mesh and through
ec_backend="tpu" on one chip, shards compared with each other and with
NumpyCodec, per-device payload landing checked.
"""

import argparse
import glob
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

K, M = 10, 4
TOTAL = K + M
COLLECTION = "smoke"
FULL_VOLUME_MIB = 30 * 1024       # what f4 / BASELINE.json seal and encode
MIN_VOLUME_MIB = 256              # never cut the chip run below this
N_SERVERS = 3
NATIVE_ARTIFACTS = (
    "seaweedfs_tpu/ops/native/libseaweed_ec.so",
    "seaweedfs_tpu/server/native/libseaweed_http.so",
    "seaweedfs_tpu/server/native/loadgen",
)


class SmokeFailure(AssertionError):
    """A phase found something wrong; the run stops here."""


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def last_line(ok: bool, device: dict) -> str:
    """The contract's final stdout line: exactly {"ok", "device"} and
    device exactly {"platform", "kind", "count"} — nothing else."""
    return json.dumps({"ok": bool(ok),
                       "device": {"platform": str(device["platform"]),
                                  "kind": str(device["kind"]),
                                  "count": int(device["count"])}})


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(8 << 20), b""):
            h.update(block)
    return h.hexdigest()


def needle_payload(seed: int, i: int, needle_bytes: int) -> bytes:
    """Needle i's bytes: about needle_bytes, ragged so needles do not
    line up with the 1 MiB stripe blocks."""
    rng = np.random.default_rng([seed, i])
    size = needle_bytes + int(rng.integers(-needle_bytes // 16,
                                           needle_bytes // 16 + 1))
    return rng.bytes(max(size, 1))


def poll(pred, what: str, timeout: float = 60.0):
    deadline = time.monotonic() + timeout
    while True:
        got = pred()
        if got:
            return got
        if time.monotonic() >= deadline:
            raise SmokeFailure(f"{what} not observed within {timeout}s")
        time.sleep(0.1)


# ---------------------------------------------------------------------------
# set-up phases
# ---------------------------------------------------------------------------

def build_native() -> dict:
    """Remove any ignored binary the working tree carried along, then
    build both native libraries here, from the committed sources."""
    removed = []
    for rel in NATIVE_ARTIFACTS:
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            os.remove(path)
            removed.append(rel)
    from seaweedfs_tpu.ops import rs_native
    from seaweedfs_tpu.server import native_plane
    t0 = time.perf_counter()
    check(rs_native._load() is not None,
          "libseaweed_ec.so did not build from seaweed_ec.cc")
    check(native_plane._load() is not None,
          "libseaweed_http.so did not build from http_plane.cc")
    return {"phase": "native_build", "removed_stale": removed,
            "seconds": round(time.perf_counter() - t0, 3)}


def device_info() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def compile_cache_probe(slab: int) -> dict:
    """Compile the served slab's program twice in this process: cold,
    then again from a fresh construction (lru factories cleared), which
    the persistent cache should answer."""
    import jax
    import jax.numpy as jnp
    from seaweedfs_tpu.ops import gf256, rs_pallas, rs_tpu
    from seaweedfs_tpu.util.jax_platform import configure_compile_cache
    coeffs = gf256.build_matrix(K, TOTAL)[K:]
    seconds = []
    entry = None
    for _ in range(2):
        rs_pallas._fused_fn.cache_clear()
        rs_tpu._packed_fn.cache_clear()
        fn, const = rs_tpu.fn_and_bitmat(coeffs, slab)
        entry = fn.entry
        t0 = time.perf_counter()
        fn.raw_jit.lower(
            jax.ShapeDtypeStruct(const.shape, const.dtype),
            jax.ShapeDtypeStruct((K, slab), jnp.uint8)).compile()
        seconds.append(round(time.perf_counter() - t0, 3))
    cache = configure_compile_cache()
    return {"phase": "compile_cache", "entry": entry, "width": slab,
            "cache_dir": cache,
            "cache_dir_from_env": bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "cache_entries": len(os.listdir(cache))
            if cache and os.path.isdir(cache) else 0,
            "compile_s_cold": seconds[0], "compile_s_second": seconds[1]}


# ---------------------------------------------------------------------------
# the cluster, in this process
# ---------------------------------------------------------------------------

class Cluster:
    """Master + volume servers as `weed server` / `weed volume` build
    them (command/cli.py cmd_server, cmd_volume), all in this process so
    whichever node the shell picks computes on the chip this process
    holds."""

    def __init__(self, workdir: str, backend: str, size_mib: int,
                 seed_dir: str = None):
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        self.backend = backend
        self.master = None
        self.servers = []
        self.dirs = [os.path.join(workdir, backend, f"v{i}")
                     for i in range(N_SERVERS)]
        for d in self.dirs:
            os.makedirs(d)
        if seed_dir:
            # a sealed volume made by an earlier cluster: server 0
            # loads it from disk like any restart would
            for name in os.listdir(seed_dir):
                os.link(os.path.join(seed_dir, name),
                        os.path.join(self.dirs[0], name))
        try:
            # one volume per collection ([master.volume_growth] copy_1
            # = 1), large enough never to roll over mid-upload
            self.master = MasterServer(
                port=0, volume_size_limit_mb=max(64, size_mib * 2),
                pulse_seconds=1, growth_counts={1: 1}).start()
            for d in self.dirs:
                self.servers.append(VolumeServer(
                    port=0, directories=[d], master_url=self.master.url,
                    pulse_seconds=1, max_volume_counts=[8],
                    ec_backend=backend).start())
        except BaseException:
            self.stop()
            raise
        import seaweedfs_tpu.shell  # noqa: F401 - registers commands
        from seaweedfs_tpu.shell.command_env import CommandEnv

        class RecordingEnv(CommandEnv):
            """The shell's env, keeping each node's stats reply."""

            def node_post(self, node, path, timeout=None, body=None):
                out = super().node_post(node, path, timeout, body)
                if isinstance(out, dict) and out.get("stats"):
                    self.replies[path.split("?")[0]] = out["stats"]
                return out

        self.env = RecordingEnv(self.master.url, out=sys.stderr)
        self.env.replies = {}

    def stop(self):
        """Stop every server and join the threads that can still log
        (heartbeat, pruner, vacuum, repair loops), so nothing of this
        cluster is running when the script writes its last line."""
        threads = [vs._hb_thread for vs in self.servers]
        for vs in self.servers:
            vs.stop()
        if self.master is not None:
            threads += [getattr(self.master, name, None) for name in (
                "_pruner", "_vacuum_thread", "_repair_thread",
                "_maintenance_thread")]
            self.master.stop()
        for t in threads:
            if t is not None and t.is_alive():
                t.join(timeout=10)
        self.master, self.servers = None, []

    def shell(self, name: str, *args: str) -> dict:
        """Run a registered shell command (the handler `weed shell`
        dispatches to) WITHOUT the REPL's catch-all, so a failure stops
        the smoke. Returns the stats the computing node replied with."""
        from seaweedfs_tpu.shell.command_env import COMMANDS
        self.env.replies.clear()
        COMMANDS[name](self.env, list(args))
        return dict(self.env.replies)

    def ec_lookup(self, vid: int) -> dict:
        from seaweedfs_tpu.server.http_util import HttpError, get_json
        try:
            out = get_json(f"http://{self.master.url}/cluster/ec_lookup"
                           f"?volumeId={vid}")
        except HttpError:
            return {}
        return {int(s): urls for s, urls in out["shards"].items() if urls}

    def shard_files(self, vid: int) -> dict:
        """sid -> path over every server's directory; each shard must
        exist exactly once cluster-wide."""
        found = {}
        for d in self.dirs:
            for path in glob.glob(os.path.join(d, f"{COLLECTION}_{vid}.ec*")):
                ext = path.rsplit(".ec", 1)[1]
                if ext.isdigit():
                    check(int(ext) not in found,
                          f"shard {ext} exists twice: {path}")
                    found[int(ext)] = path
        return found


def upload_volume(cluster: Cluster, size_bytes: int, seed: int,
                  needle_bytes: int) -> dict:
    """assign + POST seeded needles until the volume holds size_bytes."""
    from seaweedfs_tpu.client import operation as op
    master = cluster.master.url
    fids, shas, sizes = [], [], []
    t0 = time.perf_counter()

    def put(job):
        target, fid, i = job
        data = needle_payload(seed, i, needle_bytes)
        op.upload(target, fid, data, filename=f"n{i}.bin")
        return fid, hashlib.sha256(data).hexdigest(), len(data)

    total, i, vid = 0, 0, None
    with ThreadPoolExecutor(8) as pool:
        while total < size_bytes:
            want = max(1, min(64, (size_bytes - total) // needle_bytes))
            a = op.assign(master, count=want, collection=COLLECTION)
            target = a.get("fastUrl") or a["url"]
            jobs = []
            for fid in op.expand_batch_fids(a["fid"],
                                            int(a.get("count", want))):
                jobs.append((target, fid, i))
                i += 1
            for fid, sha, n in pool.map(put, jobs):
                fids.append(fid)
                shas.append(sha)
                sizes.append(n)
                total += n
            this_vid = int(a["fid"].split(",")[0])
            check(vid in (None, this_vid),
                  f"uploads spilled from volume {vid} to {this_vid}")
            vid = this_vid
    dt = time.perf_counter() - t0
    dats = [p for d in cluster.dirs
            for p in glob.glob(os.path.join(d, f"{COLLECTION}_{vid}.dat"))]
    check(len(dats) == 1, f"expected one .dat for volume {vid}: {dats}")
    return {"phase": "upload", "vid": vid, "needles": len(fids),
            "payload_bytes": total, "seconds": round(dt, 3),
            "mbps": round(total / dt / 1e6, 1),
            "dat": dats[0], "fids": fids, "shas": shas}


def reference_shard_shas(dat_path: str) -> list:
    """The plain reference: stripe the .dat the way the on-disk format
    says (rows of k blocks, block j of a row -> shard j, zero-padded
    tail) and code each row with NumpyCodec — none of ec/encoder.py."""
    from seaweedfs_tpu.ec.constants import LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE
    from seaweedfs_tpu.ops.codec import NumpyCodec
    codec = NumpyCodec(K, M)
    hashers = [hashlib.sha256() for _ in range(TOTAL)]
    remaining = os.path.getsize(dat_path)

    def code_row(f, block: int):
        data = np.zeros((K, block), dtype=np.uint8)
        raw = np.frombuffer(f.read(K * block), dtype=np.uint8)
        data.reshape(-1)[:raw.size] = raw
        for h, row in zip(hashers, codec.encode_to_all(data)):
            h.update(np.ascontiguousarray(row).tobytes())

    with open(dat_path, "rb") as f:
        while remaining > K * LARGE_BLOCK_SIZE:
            code_row(f, LARGE_BLOCK_SIZE)
            remaining -= K * LARGE_BLOCK_SIZE
        while remaining > 0:
            code_row(f, SMALL_BLOCK_SIZE)
            remaining -= K * SMALL_BLOCK_SIZE
    return [h.hexdigest() for h in hashers]


def read_needles(cluster: Cluster, fids, shas, what: str) -> dict:
    from seaweedfs_tpu.client import operation as op
    master = cluster.master.url
    t0 = time.perf_counter()

    def get(job):
        fid, sha = job
        data = op.read_file(master, fid)
        check(hashlib.sha256(data).hexdigest() == sha,
              f"{what}: needle {fid} read back different bytes")
        return len(data)

    with ThreadPoolExecutor(8) as pool:
        nbytes = sum(pool.map(get, zip(fids, shas)))
    dt = time.perf_counter() - t0
    return {"needles": len(fids), "bytes": nbytes,
            "seconds": round(dt, 3), "mbps": round(nbytes / dt / 1e6, 1)}


def _stats_brackets():
    from seaweedfs_tpu.ops import device_stats, telemetry
    return (device_stats.DEVICE_STATS.snapshot(),
            telemetry.STATS.snapshot())


def _stats_delta(brackets) -> dict:
    from seaweedfs_tpu.ops import device_stats, telemetry
    dev = device_stats.delta(brackets[0])
    tel = telemetry.delta(brackets[1])
    return {"jit_dispatches": dev["dispatches"],
            "jit_compiles": dev["compiles"],
            "compile_s": round(dev["compile_seconds_total"], 3),
            "recompiles": dev["recompiles_total"],
            "telemetry": tel}


def run_cluster_phases(workdir: str, backend: str, size_bytes: int,
                       seed: int, needle_bytes: int, emit,
                       volume: dict = None) -> dict:
    """upload (unless `volume` hands over a sealed one) -> ec.encode ->
    reference compare + GET all -> lose 4 -> degraded GETs -> ec.rebuild
    -> compare. Returns the facts the chip proof needs."""
    from seaweedfs_tpu.server.http_util import post_json
    from seaweedfs_tpu.util import tracing
    size_mib = size_bytes >> 20
    cluster = Cluster(workdir, backend, size_mib,
                      seed_dir=volume["keep_dir"] if volume else None)
    try:
        poll(lambda: len(cluster.env.cluster_nodes()) == N_SERVERS,
             f"{N_SERVERS} volume servers at the master")
        if volume is None:
            volume = upload_volume(cluster, size_bytes, seed, needle_bytes)
            # keep the sealed volume's inodes: ec.encode deletes the
            # original, the reference (and a second cluster) need it
            keep = os.path.join(workdir, "sealed")
            os.makedirs(keep)
            base = volume["dat"][:-len(".dat")]
            for ext in (".dat", ".idx"):
                os.link(base + ext,
                        os.path.join(keep, os.path.basename(base) + ext))
            volume["keep_dir"] = keep
            volume["dat"] = os.path.join(
                keep, os.path.basename(base) + ".dat")
            emit({k: v for k, v in volume.items()
                  if k not in ("fids", "shas", "dat", "keep_dir")})
        vid, fids, shas = volume["vid"], volume["fids"], volume["shas"]
        poll(lambda: str(vid) in cluster.env.all_volumes(),
             f"volume {vid} at the master")

        # -- ec.encode ------------------------------------------------
        br = _stats_brackets()
        t0 = time.perf_counter()
        replies = cluster.shell("ec.encode", "-volumeId", str(vid))
        encode_s = time.perf_counter() - t0
        encode = _stats_delta(br)
        enc_stats = replies.get("/admin/ec/generate", {})
        poll(lambda: set(cluster.ec_lookup(vid)) == set(range(TOTAL)),
             "all 14 shards at the master")
        files = cluster.shard_files(vid)
        check(set(files) == set(range(TOTAL)),
              f"shard files after encode: {sorted(files)}")
        holders = {os.path.dirname(p) for p in files.values()}
        check(len(holders) == N_SERVERS,
              f"shards landed on {len(holders)} of {N_SERVERS} servers")
        dat_bytes = os.path.getsize(volume["dat"])
        emit({"phase": "ec.encode", "backend": enc_stats.get("backend"),
              "dat_bytes": dat_bytes, "seconds": round(encode_s, 3),
              "mbps": round(dat_bytes / encode_s / 1e6, 1),
              "timing": "host clock around the whole shell command; it "
                        "returns after every shard byte left the device "
                        "and is on its holder's disk",
              "node_phases": enc_stats.get("phases"), **encode})

        # -- compare with the plain reference ---------------------------
        t0 = time.perf_counter()
        got = [sha256_file(files[s]) for s in range(TOTAL)]
        if "ref_shas" not in volume:
            volume["ref_shas"] = reference_shard_shas(volume["dat"])
        check(got == volume["ref_shas"],
              "encoded shards differ from NumpyCodec's: " + str(
                  [s for s in range(TOTAL)
                   if got[s] != volume["ref_shas"][s]]))
        reference_s = time.perf_counter() - t0
        reads = read_needles(cluster, fids, shas, "after encode")
        emit({"phase": "compare", "shards_equal_numpy": TOTAL,
              "reference_s": round(reference_s, 3), "needle_reads": reads})

        # -- lose 4 seeded shards, mixed data and parity ----------------
        rng = np.random.default_rng([seed, 4])
        lost = sorted([int(s) for s in rng.choice(K, 2, replace=False)] +
                      [K + int(s) for s in rng.choice(M, 2, replace=False)])
        by_holder = {}
        holders_of = cluster.ec_lookup(vid)
        for sid in lost:
            for url in holders_of[sid]:
                by_holder.setdefault(url, []).append(sid)
        for url, sids in by_holder.items():
            post_json(f"http://{url}/admin/ec/delete_shards?volume={vid}"
                      f"&collection={COLLECTION}"
                      f"&shards={','.join(map(str, sids))}")
        poll(lambda: not set(lost) & set(cluster.ec_lookup(vid)),
             f"loss of shards {lost} at the master")
        check(not set(lost) & set(cluster.shard_files(vid)),
              "lost shard files still on disk")

        # -- degraded reads: every needle again, 4 shards short ---------
        paths = {"host": 0, "device": 0}

        def on_span(span):
            if span.get("name") in ("reconstruct", "dispatch") and \
                    span.get("tags", {}).get("path") in paths:
                paths[span["tags"]["path"]] += 1

        before = [vs.degraded.snapshot() for vs in cluster.servers]
        br = _stats_brackets()
        tracing.add_finish_hook(on_span)
        try:
            reads = read_needles(cluster, fids, shas, "degraded")
        finally:
            tracing.remove_finish_hook(on_span)
        degraded = _stats_delta(br)
        after = [vs.degraded.snapshot() for vs in cluster.servers]
        moved = {key: int(sum(a.get(key, 0) - b.get(key, 0)
                              for a, b in zip(after, before)))
                 for key in ("reads", "host_dispatches",
                             "device_dispatches", "errors")}
        check(moved["reads"] > 0, "no needle needed reconstruction")
        emit({"phase": "degraded_reads", "lost_shards": lost,
              "needle_reads": reads, "engine": moved,
              "span_paths": paths,
              "host_fallbacks": degraded["telemetry"]["host_fallbacks"],
              "served_by": "device" if moved["device_dispatches"]
              and not moved["host_dispatches"] else
              "host" if not moved["device_dispatches"] else "both",
              "note": "widths under SW_EC_SMALL_DISPATCH_BYTES go to "
                      "the host by design; engine errors are batches "
                      "planned on a holder map from before the loss, "
                      "re-planned and retried by the server",
              "jit_dispatches": degraded["jit_dispatches"]})

        # -- ec.rebuild -------------------------------------------------
        br = _stats_brackets()
        t0 = time.perf_counter()
        replies = cluster.shell("ec.rebuild", "-collection", COLLECTION)
        rebuild_s = time.perf_counter() - t0
        rebuild = _stats_delta(br)
        reb_stats = replies.get("/admin/ec/rebuild", {})
        poll(lambda: set(cluster.ec_lookup(vid)) == set(range(TOTAL)),
             "all 14 shards at the master after rebuild")
        files = cluster.shard_files(vid)
        check(set(files) == set(range(TOTAL)),
              f"shard files after rebuild: {sorted(files)}")
        again = [sha256_file(files[s]) for s in range(TOTAL)]
        check(again == got, "rebuilt shards differ from the encoded ones: "
              + str([s for s in range(TOTAL) if again[s] != got[s]]))
        shard_bytes = os.path.getsize(files[lost[0]])
        emit({"phase": "ec.rebuild", "backend": reb_stats.get("backend"),
              "rebuilt": lost, "rebuilt_bytes": shard_bytes * len(lost),
              "seconds": round(rebuild_s, 3),
              "mbps_rebuilt": round(
                  shard_bytes * len(lost) / rebuild_s / 1e6, 1),
              "node_phases": reb_stats.get("phases"), **rebuild})
        sample = np.random.default_rng([seed, 5]).choice(
            len(fids), min(len(fids), 32), replace=False)
        read_needles(cluster, [fids[i] for i in sample],
                     [shas[i] for i in sample], "after rebuild")
        return {"backend": backend, "volume": volume, "shard_shas": got,
                "encode": encode, "rebuild": rebuild,
                "encode_backend": enc_stats.get("backend"),
                "rebuild_backend": reb_stats.get("backend")}
    finally:
        cluster.stop()


# ---------------------------------------------------------------------------
# the proof that the chip did the work
# ---------------------------------------------------------------------------

def chip_proof(device: dict, chips: int, runs: list) -> list:
    """Everything that must hold on the chip and cannot hold off it.
    Returns the list of failures (empty = proven)."""
    from seaweedfs_tpu.ops.device_stats import DEVICE_STATS
    bad = []
    if device["platform"] != "tpu":
        bad.append(f"platform is {device['platform']!r}, not 'tpu'")
    if device["count"] != chips:
        bad.append(f"{device['count']} devices, expected {chips}")
    snap = DEVICE_STATS.snapshot()
    packed = snap["dispatches"].get("rs_tpu._packed_fn", 0)
    if packed:
        bad.append(f"{packed} dispatches of the CPU program "
                   f"rs_tpu._packed_fn")
    if snap["sentinel"] or sum(snap["recompiles"].values()):
        bad.append(f"recompiles after warm-up: {snap['recompiles']} "
                   f"{snap['offenders']}")
    for run in runs:
        entry = ("mesh_codec._fn" if run["backend"] == "mesh"
                 else "rs_pallas._fused_fn")
        for op in ("encode", "rebuild"):
            if run[f"{op}_backend"] != run["backend"]:
                bad.append(f"{run['backend']} {op}: node reported backend "
                           f"{run[f'{op}_backend']!r}")
            if not run[op]["jit_dispatches"].get(entry):
                bad.append(f"{run['backend']} {op}: no dispatch under "
                           f"{entry}: {run[op]['jit_dispatches']}")
    return bad


def mesh_landing(run: dict, emit) -> list:
    """Per-device payload landing of the mesh run (telemetry.STATS):
    bytes on all four devices, within a few percent of each other."""
    bad = []
    for op in ("encode", "rebuild"):
        tel = run[op]["telemetry"]
        per_dev = tel["mesh_device_bytes"]
        emit({"phase": f"mesh_landing.{op}",
              "dispatch_width_devices": tel["dispatch_width_devices"],
              "mesh_dispatches": tel["mesh_dispatches"],
              "dispatches": tel["dispatches"],
              "single_device_dispatches_by_design":
                  tel["dispatches"] - tel["mesh_dispatches"],
              "device_bytes": per_dev,
              "device_byte_share": tel["device_byte_share"]})
        if tel["dispatch_width_devices"] != 4:
            bad.append(f"mesh {op}: payload landed on "
                       f"{tel['dispatch_width_devices']} devices, not 4")
        elif min(per_dev.values()) < 0.95 * max(per_dev.values()):
            bad.append(f"mesh {op}: uneven landing {per_dev}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size-mib", type=int, default=1024,
                    help="sealed volume size (default 1024; the chip run "
                         f"is never cut below {MIN_VOLUME_MIB})")
    ap.add_argument("--needle-kib", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--workdir", default="",
                    help="scratch directory (default: a fresh temp dir)")
    args = ap.parse_args(argv)

    # This script owns the real stdout: fd 1 is pointed at stderr so no
    # print(), child or native library can write after (or between) the
    # JSON lines; only emit() and the last line use the saved handle.
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def emit(obj: dict):
        out.write(json.dumps(obj) + "\n")
        out.flush()

    # the master's repair loop would rebuild the lost shards by itself
    # before ec.rebuild is asked to; the lock-order recorder dumps at
    # exit. Both are existing knobs, set before the package reads them.
    os.environ["SW_REPAIR_INTERVAL_S"] = "0"
    os.environ["SW_EC_SCRUB_IDLE_S"] = "0"
    os.environ.pop("SW_LOCK_DEBUG", None)

    workdir = args.workdir or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(workdir, exist_ok=True)
    device = {"platform": "none", "kind": "none", "count": 0}
    failures = []
    try:
        emit(build_native())
        device = device_info()
        size_bytes = args.size_mib << 20
        emit({"phase": "config", "device": device, "geometry": f"RS({K},{M})",
              "layout": "flat", "volume_mib": args.size_mib,
              "cut": f"{args.size_mib} MiB of the {FULL_VOLUME_MIB} MiB "
                     f"volume f4 / BASELINE.json encode",
              "needle_kib": args.needle_kib, "seed": args.seed,
              "servers": N_SERVERS, "chips": args.chips,
              "env": {name: os.environ.get(name) for name in (
                  "JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                  "XLA_FLAGS")}})
        if device["platform"] == "tpu":
            check(args.size_mib >= MIN_VOLUME_MIB,
                  f"--size-mib {args.size_mib} is below the "
                  f"{MIN_VOLUME_MIB} MiB a chip run may be cut to")
        from seaweedfs_tpu.ec.encoder import DEFAULT_SLAB
        runs = []
        if args.chips == 1:
            emit(compile_cache_probe(DEFAULT_SLAB))
            runs.append(run_cluster_phases(
                workdir, "tpu", size_bytes, args.seed,
                args.needle_kib << 10, emit))
        else:
            from seaweedfs_tpu.util import config
            emit({"phase": "mesh_config", "SW_EC_MESH_SHARD_MIN_BYTES":
                  config.env_int("SW_EC_MESH_SHARD_MIN_BYTES")})
            mesh = run_cluster_phases(
                workdir, "mesh", size_bytes, args.seed,
                args.needle_kib << 10, emit)
            one = run_cluster_phases(
                workdir, "tpu", size_bytes, args.seed,
                args.needle_kib << 10, emit, volume=mesh["volume"])
            check(mesh["shard_shas"] == one["shard_shas"],
                  "mesh and one-chip shards differ")
            emit({"phase": "mesh_vs_one_chip", "shards_equal": TOTAL})
            failures += mesh_landing(mesh, emit)
            runs += [mesh, one]
        import jax
        from seaweedfs_tpu.ops import device_stats
        snap = device_stats.DEVICE_STATS.snapshot()
        emit({"phase": "device_proof", "device": device,
              "jit_dispatches": snap["dispatches"],
              "jit_compiles": snap["compiles"],
              "compile_seconds": {e: round(s, 3) for e, s in
                                  snap["compile_seconds"].items()},
              "recompiles": snap["recompiles"],
              "peak_bytes_in_use": [
                  (d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in jax.devices()]})
        failures += chip_proof(device, args.chips, runs)
    except BaseException as e:  # noqa: BLE001 - reported, then non-zero
        import traceback
        traceback.print_exc(file=sys.stderr)
        failures.append(f"{type(e).__name__}: {e}")
    finally:
        if not args.workdir:
            shutil.rmtree(workdir, ignore_errors=True)
    if failures:
        emit({"phase": "failed", "failures": failures})
    # every server is stopped (run_cluster_phases' finally) and nothing
    # else holds the saved stdout: this is the final statement
    out.write(last_line(not failures, device) + "\n")
    out.flush()
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
