"""A holder lost in a piggyback-coded tier on four volume servers, through
the served path (beside tests/test_repair_spans.py, whose cluster has
three).

One cycle on the CPU backend (master + four volume servers with
`ec_backend="tpu"` under JAX_PLATFORMS=cpu, a 32 MiB volume): shell
`ec.encode`, every shard of holder set A lost, shell `ec.rebuild` with no
`-repair` flag, then one data shard lost and `ec.rebuild` again. The tests
read what it left: the spread over the holders, the replies, the route
counters, and the stages under `ec.rebuild.stream`.
"""

import hashlib
import io
import os

import numpy as np
import pytest

from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.util import tracing

from conftest import wait_until

SET_A = [0, 4, 8, 12]
SETS = [SET_A, [1, 5, 9, 13], [2, 6, 10], [3, 7, 11]]


@pytest.fixture(scope="module")
def cycle(tmp_path_factory):
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.ec.constants import TOTAL_SHARDS, to_ext
    from seaweedfs_tpu.server.http_util import get_json, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.command_ec import do_ec_encode, do_ec_rebuild
    from seaweedfs_tpu.shell.command_env import CommandEnv

    tmp = tmp_path_factory.mktemp("holder_loss")
    was = os.environ.get("SW_EC_LAYOUT")
    os.environ["SW_EC_LAYOUT"] = "piggyback"
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1, growth_counts={1: 1}).start()
    dirs = [str(tmp / f"v{i}") for i in range(4)]
    servers = [VolumeServer(
        port=0, directories=[d], master_url=master.url, pulse_seconds=1,
        max_volume_counts=[20], ec_backend="tpu").start() for d in dirs]
    spans, out = [], {}
    try:
        assert wait_until(
            lambda: len(CommandEnv(master.url).cluster_nodes()) == 4)
        a = op.assign(master.url, collection="hl")
        vid = int(a["fid"].split(",")[0])
        rng = np.random.default_rng(32)
        for i in range(32):
            op.upload(a["url"], f"{vid},{i + 1:x}00000001",
                      rng.integers(0, 256, 1_000_000).astype(
                          np.uint8).tobytes(), filename=f"f{i}")
        env = CommandEnv(master.url, out=io.StringIO())

        def lookup():
            ec = get_json(f"http://{master.url}/cluster/ec_lookup"
                          f"?volumeId={vid}")
            return {int(s): u for s, u in ec.get("shards", {}).items()
                    if u}

        def shas():
            found = {}
            for d in dirs:
                for sid in range(TOTAL_SHARDS):
                    path = os.path.join(d, f"hl_{vid}" + to_ext(sid))
                    if os.path.exists(path):
                        assert sid not in found, f"shard {sid} twice"
                        with open(path, "rb") as f:
                            found[sid] = hashlib.sha256(
                                f.read()).hexdigest()
            return found

        def lose(sids):
            holders = lookup()
            for holder in {holders[s][0] for s in sids}:
                held = [s for s in sids if holders[s][0] == holder]
                post_json(f"http://{holder}/admin/ec/delete_shards"
                          f"?volume={vid}&collection=hl"
                          f"&shards={','.join(map(str, held))}")
            assert wait_until(lambda: not set(sids) & set(lookup()))

        def rebuild(sids) -> dict:
            before = telemetry.STATS.snapshot()
            timings = {}
            do_ec_rebuild(env, vid, "hl", lookup(), sids, timings=timings)
            after = telemetry.STATS.snapshot()
            assert wait_until(lambda: len(lookup()) == TOTAL_SHARDS)
            return {"reply": timings, "fallbacks":
                    after["repair_fallbacks"] - before["repair_fallbacks"],
                    "routes": {r: after["repair_route"][r] -
                               before["repair_route"][r]
                               for r in after["repair_route"]}}

        do_ec_encode(env, vid, timings={})
        assert wait_until(lambda: len(lookup()) == TOTAL_SHARDS)
        out["holders"] = lookup()
        out["encoded"] = shas()
        lose(SET_A)
        tracing.add_finish_hook(spans.append)
        out["holder_loss"] = rebuild(SET_A)
        tracing.remove_finish_hook(spans.append)
        out["after_holder_loss"] = shas()
        lose([5])
        out["shard_loss"] = rebuild([5])
        out["after_shard_loss"] = shas()
    finally:
        tracing.remove_finish_hook(spans.append)
        for vs in servers:
            vs.stop()
        master.stop()
        if was is None:
            os.environ.pop("SW_EC_LAYOUT", None)
        else:
            os.environ["SW_EC_LAYOUT"] = was
    out["spans"] = spans
    return out


def test_four_holders_get_no_more_than_m_shards_each(cycle):
    by_holder = {}
    for sid, urls in cycle["holders"].items():
        by_holder.setdefault(urls[0], []).append(sid)
    # the shell's round-robin: shard i on holder i mod 4, so any one
    # holder's loss leaves k = 10 shards
    assert sorted(sorted(held) for held in by_holder.values()) == \
        sorted(SETS)
    assert max(map(len, by_holder.values())) == 4


def test_a_lost_holder_is_rebuilt_by_the_full_coupled_decode(cycle):
    got = cycle["holder_loss"]
    reply = got["reply"]
    assert reply["repair_mode"] == "full" and reply["lost"] == SET_A
    assert reply["operand"] == [128, 320] and reply["layout"] == "piggyback"
    assert "repair_fallback" not in reply and got["fallbacks"] == 0
    assert got["routes"] == {"piggyback": 0, "trace": 0, "full": 1}
    assert reply["coupled_decodes"] == 1
    shard = reply["rebuilt_bytes"] // len(SET_A)
    assert reply["repair_bytes"] == reply["repair_baseline_bytes"] == \
        reply["survivor_bytes"] == 10 * shard
    assert set(reply["phases"]) == {"gather", "plan", "dispatch", "drain",
                                    "write"}
    assert cycle["after_holder_loss"] == cycle["encoded"]


def test_one_lost_data_shard_still_takes_the_plane_route(cycle):
    got = cycle["shard_loss"]
    assert got["reply"]["repair_mode"] == "piggyback"
    assert got["reply"]["operand"] == [32, 176]
    assert "repair_fallback" not in got["reply"] and got["fallbacks"] == 0
    assert got["routes"] == {"piggyback": 1, "trace": 0, "full": 0}
    assert cycle["after_shard_loss"] == cycle["encoded"]


def test_the_decodes_stages_hang_under_the_stream(cycle):
    reply = cycle["holder_loss"]["reply"]
    root, = [s for s in cycle["spans"] if s["name"] == "ec.rebuild.stream"]
    under = {}
    for s in cycle["spans"]:
        if s.get("parent_id") == root["span_id"] and "thread" in s["tags"]:
            under.setdefault(s["name"], []).append(s)
    stripes = reply["dispatches"]
    assert stripes > 1
    for name in ("ec.rebuild.assemble", "ec.rebuild.pb_split", "ec.h2d",
                 "ec.d2h", "ec.rebuild.pb_merge", "ec.rebuild.write"):
        assert len(under[name]) == stripes, name
    assert len(under["ec.rebuild.plan"]) == 1
    # the full range's fetch names, never a single-shard route's
    fetches = {n for n in under if n.startswith("ec.rebuild.fetch")}
    assert "ec.rebuild.fetch.remote" in fetches and fetches <= {
        "ec.rebuild.fetch.remote", "ec.rebuild.fetch.local"}
    assert sum(len(under[n]) for n in fetches) == 10 * stripes
    threads = {name: {s["tags"]["thread"] for s in got}
               for name, got in under.items()}
    assert threads["ec.rebuild.pb_split"] == {"pipeline-producer"}
    assert not threads["ec.rebuild.pb_merge"] & {"pipeline-producer"}
    assert threads["ec.rebuild.pb_merge"] == threads["ec.rebuild.write"]
    assert all(t.startswith("pipeline-drain") for t in threads["ec.d2h"])
    assert sum(s["tags"]["bytes"] for s in under["ec.rebuild.write"]) == \
        reply["rebuilt_bytes"]
    assert sum(s["tags"]["bytes"] for n in fetches for s in under[n]) == \
        reply["repair_bytes"]
    # holders served whole ranges, under the rebuild's trace
    served = [s for s in cycle["spans"]
              if s["name"] == "GET /admin/ec/shard_read"]
    assert served and all(s["trace_id"] == root["trace_id"] for s in served)
