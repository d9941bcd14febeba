"""A volume's RS geometry is its own: RS(6,3) and RS(20,4) through the
served path, beside a default 10 + 4 volume on the same servers.

One scripted life of a volume a geometry, on the CPU backend (master +
three volume servers with `ec_backend="tpu"` under JAX_PLATFORMS=cpu, a
32 MiB volume): shell `ec.encode -geometry k,m`, a set of m shards lost
and `ec.rebuild`, a GET of a needle on a lost shard, fewer than k
survivors refused, `ec.decode` back to a `.dat`. Beside it, in the
RS(6,3) run, a volume encoded with no flag (10 + 4) whose `.vif` is then
stripped of its geometry keys, as a volume encoded before them has it.
The tests read what that left; the plain reference is the benchmark's
(`benchmarks/lib/reference.py`, which imports nothing of the program).
"""

import functools
import hashlib
import io
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from lib import reference  # noqa: E402

from seaweedfs_tpu.ops import telemetry  # noqa: E402

from conftest import wait_until  # noqa: E402

# geometry -> the shards lost together (three servers hold shard i on
# server i mod 3: under RS(6,3) that is a whole holder, 3+3+3, none
# above m; under RS(20,4) a holder has 8 and its loss is not survivable,
# so four of one holder's are lost) and a data shard lost alone
CASES = {(6, 3): [0, 3, 6], (20, 4): [0, 3, 6, 21]}
NEEDLES, NEEDLE_BYTES = 32, 1_000_000


@pytest.fixture(scope="module")
def private_programs():
    """tests/conftest.private_packed_programs for the module: these
    volumes run operands and widths no other file compiles, and the
    process's recompile sentinel must not see them."""
    from seaweedfs_tpu.ops import device_stats, rs_tpu
    from seaweedfs_tpu.parallel import mesh_codec
    patch = pytest.MonkeyPatch()
    patch.setattr(device_stats, "DEVICE_STATS", device_stats.DeviceStats())
    patch.setattr(rs_tpu, "_packed_fn", functools.lru_cache(maxsize=None)(
        rs_tpu._packed_fn.__wrapped__))
    patch.setattr(mesh_codec, "_FNS", {})
    # the master's own repair loop would put a lost shard back before
    # (or while) the shell's `ec.rebuild` does: one shard file twice
    patch.setenv("SW_REPAIR_INTERVAL_S", "0")
    yield
    patch.undo()


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


class _Cluster:
    def __init__(self, tmp):
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        from seaweedfs_tpu.shell.command_env import CommandEnv
        self.master = MasterServer(port=0, volume_size_limit_mb=64,
                                   pulse_seconds=1,
                                   growth_counts={1: 1}).start()
        self.dirs = [str(tmp / f"v{i}") for i in range(3)]
        self.servers = [VolumeServer(
            port=0, directories=[d], master_url=self.master.url,
            pulse_seconds=1, max_volume_counts=[20],
            ec_backend="tpu").start() for d in self.dirs]
        self.said = io.StringIO()
        self.env = CommandEnv(self.master.url, out=self.said)
        assert wait_until(lambda: len(self.env.cluster_nodes()) == 3)

    def stop(self):
        for vs in self.servers:
            vs.stop()
        self.master.stop()

    def shell(self, name, *args):
        import seaweedfs_tpu.shell  # noqa: F401 - registers the commands
        from seaweedfs_tpu.shell.command_env import COMMANDS
        COMMANDS[name](self.env, list(args))

    def upload(self, collection, seed):
        """One volume of seeded needles; returns (vid, {fid: bytes}) and
        keeps a hard link of the sealed `.dat` for the reference."""
        from seaweedfs_tpu.client import operation as op
        a = op.assign(self.master.url, collection=collection)
        vid = int(a["fid"].split(",")[0])
        rng = np.random.default_rng(seed)
        needles = {}
        for i in range(NEEDLES):
            fid = f"{vid},{i + 1:x}00000001"
            data = rng.integers(0, 256, NEEDLE_BYTES).astype(
                np.uint8).tobytes()
            op.upload(a["url"], fid, data, filename=f"f{i}")
            needles[fid] = data
        dat, = [os.path.join(d, f"{collection}_{vid}.dat")
                for d in self.dirs
                if os.path.exists(os.path.join(d,
                                               f"{collection}_{vid}.dat"))]
        kept = dat + ".kept"
        os.link(dat, kept)
        return vid, needles, kept

    def lookup(self, vid):
        from seaweedfs_tpu.server.http_util import HttpError, get_json
        try:
            ec = get_json(f"http://{self.master.url}/cluster/ec_lookup"
                          f"?volumeId={vid}")
        except HttpError:
            return {}
        return {int(s): u for s, u in ec.get("shards", {}).items() if u}

    def files(self, collection, vid):
        from seaweedfs_tpu.ec.constants import MAX_SHARDS, to_ext
        found = {}
        for d in self.dirs:
            for sid in range(MAX_SHARDS):
                path = os.path.join(d, f"{collection}_{vid}" + to_ext(sid))
                if os.path.exists(path):
                    assert sid not in found, f"shard {sid} twice"
                    found[sid] = path
        return found

    def shas(self, collection, vid):
        return {sid: _sha(p) for sid, p in
                sorted(self.files(collection, vid).items())}

    def vifs(self, collection, vid):
        out = []
        for d in self.dirs:
            path = os.path.join(d, f"{collection}_{vid}.vif")
            if os.path.exists(path):
                with open(path) as f:
                    out.append((path, json.load(f)))
        return out

    def lose(self, collection, vid, sids):
        from seaweedfs_tpu.server.http_util import post_json
        holders = self.lookup(vid)
        for holder in {holders[s][0] for s in sids}:
            held = [s for s in sids if holders[s][0] == holder]
            post_json(f"http://{holder}/admin/ec/delete_shards"
                      f"?volume={vid}&collection={collection}"
                      f"&shards={','.join(map(str, held))}")
        assert wait_until(lambda: not set(sids) & set(self.lookup(vid)))

    def rebuild(self, collection, vid, total):
        """Shell `ec.rebuild` of the collection; returns the rebuilding
        node's reply (through the timings a caller may pass) and the
        counters it moved."""
        from seaweedfs_tpu.shell.command_ec import do_ec_rebuild
        shards = self.lookup(vid)
        missing = [s for s in range(total) if s not in shards]
        before = telemetry.STATS.snapshot()
        timings = {}
        do_ec_rebuild(self.env, vid, collection, shards, missing,
                      timings=timings)
        moved = telemetry.delta(before)
        assert wait_until(lambda: len(self.lookup(vid)) == total)
        return timings, moved


@pytest.fixture(scope="module", params=sorted(CASES),
                ids=lambda g: f"rs{g[0]}-{g[1]}")
def life(request, tmp_path_factory, private_programs):
    from seaweedfs_tpu.server.http_util import (HttpError, get_json,
                                                http_call)
    k, m = request.param
    total, lost = k + m, CASES[request.param]
    name = f"g{k}x{m}"
    cluster = _Cluster(tmp_path_factory.mktemp(name))
    out = {"k": k, "m": m, "lost": lost, "name": name}
    try:
        vid, needles, kept = cluster.upload(name, seed=k)
        out["want"] = reference.shard_shas(kept, k, m)
        out["shard_bytes"] = reference.shard_bytes(
            os.path.getsize(kept), k)
        before = telemetry.STATS.snapshot()
        cluster.shell("ec.encode", "-volumeId", str(vid),
                      "-geometry", f"{k},{m}")
        out["encode_moved"] = telemetry.delta(before)
        out["encode_said"] = cluster.said.getvalue()
        assert wait_until(lambda: len(cluster.lookup(vid)) == total)
        out["holders"] = cluster.lookup(vid)
        out["encoded"] = cluster.shas(name, vid)
        out["sizes"] = {os.path.getsize(p)
                        for p in cluster.files(name, vid).values()}
        out["vifs"] = [info for _, info in cluster.vifs(name, vid)]
        out["status"] = get_json(
            f"http://{cluster.master.url}/cluster/ec_status"
        )["volumes"][str(vid)]
        out["lookup"] = get_json(
            f"http://{cluster.master.url}/cluster/ec_lookup?volumeId={vid}")
        out["dat_left"] = [d for d in cluster.dirs if os.path.exists(
            os.path.join(d, f"{name}_{vid}.dat"))]

        # a 10 + 4 volume beside it, on the same three servers
        if (k, m) == (6, 3):
            dvid, _, dkept = cluster.upload("plain", seed=104)
            cluster.shell("ec.encode", "-volumeId", str(dvid))
            assert wait_until(lambda: len(cluster.lookup(dvid)) == 14)
            out["plain_want"] = reference.shard_shas(dkept, 10, 4)
            out["plain_encoded"] = cluster.shas("plain", dvid)
            out["plain_vifs"] = [i for _, i in cluster.vifs("plain", dvid)]
            # as a volume encoded before the keys existed has it
            for path, info in cluster.vifs("plain", dvid):
                info.pop("ec_data_shards"), info.pop("ec_parity_shards")
                with open(path, "w") as f:
                    json.dump(info, f)
            cluster.lose("plain", dvid, [1, 4, 11, 13])
            cluster.lose(name, vid, lost)
            # one `ec.rebuild` with no collection: both volumes, each on
            # its own geometry, both shards sets put back
            before = telemetry.STATS.snapshot()
            cluster.shell("ec.rebuild")
            out["both_moved"] = telemetry.delta(before)
            assert wait_until(lambda: len(cluster.lookup(dvid)) == 14)
            assert wait_until(lambda: len(cluster.lookup(vid)) == total)
            out["plain_rebuilt"] = cluster.shas("plain", dvid)
            out["plain_rebuilt_vifs"] = [
                i for _, i in cluster.vifs("plain", dvid)]
            out["after_both"] = cluster.shas(name, vid)

        # the named set lost, rebuilt by the flat full gather
        cluster.lose(name, vid, lost)
        out["rebuild"], out["rebuild_moved"] = cluster.rebuild(
            name, vid, total)
        out["rebuilt"] = cluster.shas(name, vid)

        # a needle on a lost data shard: needle 1 starts the `.dat`, so
        # it lives on shard 0; asked of a server that still has the index
        cluster.lose(name, vid, [0])
        fid = next(iter(needles))
        asked = cluster.lookup(vid)[1][0]
        out["degraded_get"] = http_call("GET", f"http://{asked}/{fid}")
        out["degraded_want"] = needles[fid]
        vs, = [s for s in cluster.servers if s.url == asked]
        out["degraded_reads"] = vs.degraded.snapshot()["reads"]
        out["single"], out["single_moved"] = cluster.rebuild(
            name, vid, total)
        out["after_single"] = cluster.shas(name, vid)

        # back to a plain volume, then coded again and broken for good
        cluster.shell("ec.decode", "-volumeId", str(vid))
        dat, = [os.path.join(d, f"{name}_{vid}.dat") for d in cluster.dirs
                if os.path.exists(os.path.join(d, f"{name}_{vid}.dat"))]
        out["decoded_sha"], out["kept_sha"] = _sha(dat), _sha(kept)
        out["shards_after_decode"] = cluster.files(name, vid)
        assert wait_until(lambda: str(vid) in cluster.env.all_volumes())
        out["get_after_decode"] = http_call(
            "GET", f"http://{cluster.env.all_volumes()[str(vid)][0]['url']}"
                   f"/{fid}")
        cluster.shell("ec.encode", "-volumeId", str(vid),
                      "-geometry", f"{k},{m}")
        assert wait_until(lambda: len(cluster.lookup(vid)) == total)
        out["encoded_again"] = cluster.shas(name, vid)
        cluster.lose(name, vid, list(range(m + 1)))
        cluster.said.truncate(0), cluster.said.seek(0)
        cluster.shell("ec.rebuild", "-collection", name)
        out["too_few_said"] = cluster.said.getvalue()
        holder = cluster.lookup(vid)[total - 1][0]
        try:
            from seaweedfs_tpu.server.http_util import post_json
            post_json(f"http://{holder}/admin/ec/rebuild?volume={vid}"
                      f"&collection={name}",
                      {"sources": {str(s): u for s, u in
                                   cluster.lookup(vid).items()
                                   if holder not in u}})
            out["too_few_node"] = None
        except HttpError as e:
            out["too_few_node"] = str(e)
        out["after_too_few"] = sorted(cluster.files(name, vid))
    finally:
        cluster.stop()
    return out


def test_every_shard_file_is_the_plain_references(life):
    total = life["k"] + life["m"]
    assert sorted(life["encoded"]) == list(range(total))
    assert [life["encoded"][s] for s in range(total)] == life["want"]
    assert life["sizes"] == {life["shard_bytes"]}
    assert not life["dat_left"]


def test_the_shards_lie_round_robin_over_the_three_servers(life):
    by_holder = {}
    for sid, urls in life["holders"].items():
        by_holder.setdefault(urls[0], []).append(sid)
    total = life["k"] + life["m"]
    assert sorted(sorted(h) for h in by_holder.values()) == [
        list(range(i, total, 3)) for i in range(3)]
    # RS(6,3): 3+3+3, no holder above m; RS(20,4): 8+8+8, every one
    assert (max(map(len, by_holder.values())) <= life["m"]) == \
        ((life["k"], life["m"]) == (6, 3))


def test_the_vif_and_the_master_name_the_geometry(life):
    k, m = life["k"], life["m"]
    assert len(life["vifs"]) == 3          # it travelled with the .vif
    for info in life["vifs"]:
        assert (info["ec_data_shards"], info["ec_parity_shards"]) == (k, m)
        assert info["ec_layout"] == "flat"
    assert (life["status"]["data_shards"],
            life["status"]["parity_shards"]) == (k, m)
    assert (life["lookup"]["data_shards"],
            life["lookup"]["parity_shards"]) == (k, m)
    assert f"streamed {k + m} shards" in life["encode_said"]


def test_every_dispatch_ran_on_the_volumes_own_geometry(life):
    label = f"{life['k']}+{life['m']}"
    for moved in (life["encode_moved"], life["rebuild_moved"],
                  life["single_moved"]):
        assert moved["dispatches"] > 0
        assert moved["geometry_dispatches"] == {label: moved["dispatches"]}


def test_a_lost_set_is_rebuilt_by_the_flat_full_gather(life):
    k, m, lost = life["k"], life["m"], life["lost"]
    reply = life["rebuild"]
    assert reply["repair_mode"] == "full" and reply["lost"] == lost
    assert (reply["k"], reply["m"]) == (k, m)
    assert reply["operand"] == [len(lost), k]
    assert "repair_fallback" not in reply
    assert reply["repair_bytes"] == reply["repair_baseline_bytes"] == \
        reply["survivor_bytes"] == k * life["shard_bytes"]
    assert reply["rebuilt_bytes"] == len(lost) * life["shard_bytes"]
    assert life["rebuilt"] == life["encoded"]


def test_one_lost_shard_takes_the_trace_route_of_its_geometry(life):
    reply = life["single"]
    assert reply["repair_mode"] == "trace" and "repair_fallback" not in reply
    assert reply["repair_bytes"] < reply["repair_baseline_bytes"] == \
        life["k"] * life["shard_bytes"]
    assert life["after_single"] == life["encoded"]


def test_a_get_of_a_needle_on_a_lost_shard_returns_its_bytes(life):
    assert life["degraded_get"] == life["degraded_want"]
    assert life["degraded_reads"] >= 1


def test_ec_decode_gives_the_sealed_dat_back(life):
    assert life["decoded_sha"] == life["kept_sha"]
    assert not life["shards_after_decode"]
    assert life["get_after_decode"] == life["degraded_want"]
    assert life["encoded_again"] == life["encoded"]


def test_fewer_than_k_survivors_are_refused_by_name(life):
    k, m = life["k"], life["m"]
    assert f"only {k - 1} shards left, cannot rebuild" in \
        life["too_few_said"]
    assert life["too_few_node"] is not None
    assert f"only {k - 1} of {k + m} shards reachable" in \
        life["too_few_node"]
    assert life["after_too_few"] == list(range(m + 1, k + m))


# the RS(6,3) run carries the second volume
rs6_3_only = pytest.mark.parametrize("life", [(6, 3)], indirect=True,
                                     ids=["rs6-3"])


@rs6_3_only
def test_a_10_4_and_a_6_3_volume_live_on_one_server_and_both_rebuild(life):
    assert [life["plain_encoded"][s] for s in range(14)] == \
        life["plain_want"]
    assert life["plain_rebuilt"] == life["plain_encoded"]
    assert life["after_both"] == life["encoded"]
    moved = life["both_moved"]["geometry_dispatches"]
    assert set(moved) == {"6+3", "10+4"} and all(moved.values())
    assert sum(moved.values()) == life["both_moved"]["dispatches"]


@rs6_3_only
def test_a_vif_that_names_no_geometry_is_10_4(life):
    # written at encode time, also for the default ...
    assert all((i["ec_data_shards"], i["ec_parity_shards"]) == (10, 4)
               for i in life["plain_vifs"])
    # ... then stripped: the rebuild above ran on such sidecars, and
    # leaves them as it found them
    assert life["plain_rebuilt_vifs"] and not any(
        "ec_data_shards" in i for i in life["plain_rebuilt_vifs"])
