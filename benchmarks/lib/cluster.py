"""The served cluster in this process, and the client calls the cells make.

Copied from `chip_smoke.py` (PR 22), where these phases ran on the chip:
master + volume servers built the way `weed server` / `weed volume` build
them, all in the process that holds the chip, so whichever node the shell
picks computes there. The benchmark takes from the program only the
system under test (servers, shell commands, HTTP client) and its spans,
counters and reply stats.
"""

import glob
import hashlib
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


class BenchFailure(AssertionError):
    """The harness could not do what the cell asks; the run stops here."""


def check(cond, what: str):
    if not cond:
        raise BenchFailure(what)


def poll(pred, what: str, timeout: float = 60.0, step: float = 0.02):
    deadline = time.monotonic() + timeout
    while True:
        got = pred()
        if got:
            return got
        if time.monotonic() >= deadline:
            raise BenchFailure(f"{what} not observed within {timeout}s")
        time.sleep(step)


def apply_env(env: dict):
    """The configuration's `SW_*` settings, before the package reads them."""
    for name, value in env.items():
        os.environ[name] = str(value)
    os.environ.pop("SW_LOCK_DEBUG", None)


def build_native():
    """Build the two native libraries where they are missing or older
    than their source (the loaders' own rule). Without them uploads crawl
    through a pure-Python CRC."""
    from seaweedfs_tpu.ops import rs_native
    from seaweedfs_tpu.server import native_plane
    t0 = time.perf_counter()
    check(rs_native._load() is not None,
          "libseaweed_ec.so did not build from seaweed_ec.cc")
    check(native_plane._load() is not None,
          "libseaweed_http.so did not build from http_plane.cc")
    return time.perf_counter() - t0


class Cluster:
    """Master + `servers` volume servers with `ec_backend=backend`."""

    def __init__(self, workdir: str, config: dict):
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        self.k = int(config["data_shards"])
        self.m = int(config["parity_shards"])
        self.total = self.k + self.m
        self.collection = config["collection"]
        self.backend = config["ec_backend"]
        self.dat_in_page_cache = bool(config["dat_in_page_cache"])
        self.master = None
        self.servers = []
        self.dirs = [os.path.join(workdir, f"v{i}")
                     for i in range(int(config["volume_servers"]))]
        for d in self.dirs:
            os.makedirs(d)
        try:
            # one volume per growth ([master.volume_growth] copy_1 = 1),
            # large enough never to roll over mid-upload
            self.master = MasterServer(
                port=0,
                volume_size_limit_mb=2 * int(config["volume_mib"]),
                pulse_seconds=int(config["pulse_seconds"]),
                growth_counts={1: 1}).start()
            for d in self.dirs:
                self.servers.append(VolumeServer(
                    port=0, directories=[d], master_url=self.master.url,
                    pulse_seconds=int(config["pulse_seconds"]),
                    max_volume_counts=[int(config["max_volumes"])],
                    ec_backend=self.backend).start())
        except BaseException:
            self.stop()
            raise
        import seaweedfs_tpu.shell  # noqa: F401 - registers the commands
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
        poll(lambda: len(self.env.cluster_nodes()) == len(self.dirs),
             f"{len(self.dirs)} volume servers at the master")

    def stop(self):
        """Stop every server and join the threads that can still log, so
        nothing of this cluster runs when the last line is written."""
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

    # -- the operator's side ------------------------------------------------

    def shell(self, name: str, *args: str) -> dict:
        """Run a registered shell command (the handler `weed shell`
        dispatches to) without the REPL's catch-all, so a failure raises.
        Returns the stats each computing node replied with, by route."""
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

    def wait_shards(self, vid: int, want: set, what: str):
        poll(lambda: set(self.ec_lookup(vid)) == want, what)

    def shard_files(self, vid: int) -> dict:
        """sid -> path over every server's directory; each shard must
        exist exactly once cluster-wide."""
        found = {}
        for d in self.dirs:
            for path in glob.glob(os.path.join(
                    d, f"{self.collection}_{vid}.ec*")):
                ext = path.rsplit(".ec", 1)[1]
                if ext.isdigit():
                    check(int(ext) not in found,
                          f"shard {ext} exists twice: {path}")
                    found[int(ext)] = path
        return found

    def shards_short_on_disk(self, vid: int, sids, nbytes: int) -> list:
        """Of the shards `sids`, those whose file is not on a server's
        disk at its full size right now. Asked the moment a command has
        returned: by then every shard it made has to be there."""
        files = self.shard_files(vid)
        return sorted(s for s in sids if s not in files
                      or os.path.getsize(files[s]) != nbytes)

    def delete_shards(self, vid: int, sids):
        """Drop shards from their holders (the disks are gone) and wait
        until the master has seen the loss."""
        from seaweedfs_tpu.server.http_util import post_json
        by_holder = {}
        holders_of = self.ec_lookup(vid)
        for sid in sids:
            for url in holders_of.get(sid, []):
                by_holder.setdefault(url, []).append(sid)
        for url, held in by_holder.items():
            post_json(f"http://{url}/admin/ec/delete_shards?volume={vid}"
                      f"&collection={self.collection}"
                      f"&shards={','.join(map(str, held))}")
        poll(lambda: not set(sids) & set(self.ec_lookup(vid)),
             f"loss of shards {sorted(sids)} of volume {vid} at the master")
        check(not set(sids) & set(self.shard_files(vid)),
              "lost shard files still on disk")

    def keep_sealed(self, vid: int, keep_dir: str) -> str:
        """Hard-link a sealed volume's `.dat`/`.idx` aside (ec.encode
        deletes the original); returns the kept base path."""
        dats = [p for d in self.dirs for p in glob.glob(
            os.path.join(d, f"{self.collection}_{vid}.dat"))]
        check(len(dats) == 1, f"expected one .dat for volume {vid}: {dats}")
        os.makedirs(keep_dir, exist_ok=True)
        base = dats[0][:-len(".dat")]
        kept = os.path.join(keep_dir, os.path.basename(base))
        for ext in (".dat", ".idx"):
            os.link(base + ext, kept + ext)
        return kept

    def clone_sealed(self, kept_base: str, vid: int, server: int):
        """A further sealed volume: the kept files linked under another
        volume id into one server's directory and mounted there."""
        from seaweedfs_tpu.server.http_util import post_json
        base = os.path.join(self.dirs[server], f"{self.collection}_{vid}")
        for ext in (".dat", ".idx"):
            os.link(kept_base + ext, base + ext)
        if self.dat_in_page_cache:
            # the configuration's `dat_in_page_cache` (a cut, listed in
            # its `reduced`): read the source through, so that a host
            # that dropped it does not put its disk into the next wall
            with open(base + ".dat", "rb") as f:
                while f.read(8 << 20):
                    pass
        out = post_json(f"http://{self.servers[server].url}"
                        f"/admin/volume/mount?volume={vid}")
        check(out.get("mounted"), f"volume {vid} did not mount: {out}")
        poll(lambda: str(vid) in self.env.all_volumes(),
             f"volume {vid} at the master")

    # -- the client's side --------------------------------------------------

    def upload_volume(self, seed: int, sizes) -> dict:
        """assign + POST one seeded needle per entry of `sizes`; all land
        in one volume of the configuration's collection."""
        from seaweedfs_tpu.client import operation as op
        master = self.master.url
        fids, shas = [], []

        def put(job):
            target, fid, i, size = job
            data = needle_payload(seed, i, size)
            op.upload(target, fid, data, filename=f"n{i}.bin")
            return fid, hashlib.sha256(data).hexdigest()

        done, vid = 0, None
        with ThreadPoolExecutor(8) as pool:
            while done < len(sizes):
                want = min(64, len(sizes) - done)
                a = op.assign(master, count=want, collection=self.collection)
                target = a.get("fastUrl") or a["url"]
                batch = list(op.expand_batch_fids(
                    a["fid"], int(a.get("count", want))))
                jobs = [(target, fid, done + n, int(sizes[done + n]))
                        for n, fid in enumerate(batch[:len(sizes) - done])]
                for fid, sha in pool.map(put, jobs):
                    fids.append(fid)
                    shas.append(sha)
                done += len(jobs)
                this_vid = int(a["fid"].split(",")[0])
                check(vid in (None, this_vid),
                      f"uploads spilled from volume {vid} to {this_vid}")
                vid = this_vid
        poll(lambda: str(vid) in self.env.all_volumes(),
             f"volume {vid} at the master")
        return {"vid": vid, "fids": fids, "shas": shas,
                "sizes": [int(s) for s in sizes]}


def needle_payload(seed: int, i: int, size: int) -> bytes:
    """Needle i's bytes, from the seed alone."""
    return np.random.default_rng([seed, i]).bytes(max(int(size), 1))

