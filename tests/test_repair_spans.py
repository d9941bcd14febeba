"""Spans, reply stats and counters of the piggybacked encode and of the two
single-shard repair routes.

One served cycle a layout on the CPU backend (master + three volume
servers with `ec_backend="tpu"` under JAX_PLATFORMS=cpu): shell
`ec.encode`, one data shard lost, shell `ec.rebuild` with no `-repair`
flag. The tests read what it left: the stages under `ec.encode.stream` /
`ec.rebuild.stream`, the holders' server spans with the bytes they read
and sent, `operand` and the route in the node's reply, the route counters.
"""

import io
import os

import numpy as np
import pytest

from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.util import tracing

from conftest import wait_until

ROUTE = {"piggyback": "piggyback", "flat": "trace"}
FETCH = {"piggyback": "ec.rebuild.fetch.plane", "flat": "ec.rebuild.fetch.trace"}
RELAYOUT = {"piggyback": "ec.rebuild.pb_merge",
            "flat": "ec.rebuild.trace_unpack"}
HOLDER = {"piggyback": "POST /admin/ec/shard_plane_read",
          "flat": "POST /admin/ec/shard_repair_read"}


@pytest.fixture(scope="module", params=["piggyback", "flat"])
def cycle(request, tmp_path_factory):
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.ec.constants import TOTAL_SHARDS
    from seaweedfs_tpu.server.http_util import get_json, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.command_ec import do_ec_encode, do_ec_rebuild
    from seaweedfs_tpu.shell.command_env import CommandEnv

    layout = request.param
    tmp = tmp_path_factory.mktemp("repair_" + layout)
    was = os.environ.get("SW_EC_LAYOUT")
    os.environ["SW_EC_LAYOUT"] = layout
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1, growth_counts={1: 1}).start()
    servers = [VolumeServer(
        port=0, directories=[str(tmp / f"v{i}")], master_url=master.url,
        pulse_seconds=1, max_volume_counts=[20],
        ec_backend="tpu").start() for i in range(3)]
    spans, out = [], {"layout": layout}
    try:
        assert wait_until(
            lambda: len(CommandEnv(master.url).cluster_nodes()) == 3)
        a = op.assign(master.url, collection="rp")
        vid = int(a["fid"].split(",")[0])
        rng = np.random.default_rng(27)
        for i in range(12):
            op.upload(a["url"], f"{vid},{i + 1:x}00000001",
                      rng.integers(0, 256, 1_000_000).astype(
                          np.uint8).tobytes(), filename=f"f{i}")
        env = CommandEnv(master.url, out=io.StringIO())

        def lookup():
            ec = get_json(f"http://{master.url}/cluster/ec_lookup"
                          f"?volumeId={vid}")
            return {int(s): u for s, u in ec.get("shards", {}).items()
                    if u}

        tracing.add_finish_hook(spans.append)
        timings = {}
        do_ec_encode(env, vid, timings=timings)
        out["encode"] = dict(timings)
        assert wait_until(lambda: len(lookup()) == TOTAL_SHARDS)
        lost = 3
        holder = lookup()[lost][0]
        post_json(f"http://{holder}/admin/ec/delete_shards?volume={vid}"
                  f"&collection=rp&shards={lost}")
        assert wait_until(lambda: lost not in lookup())
        before = telemetry.STATS.snapshot()
        timings = {}
        do_ec_rebuild(env, vid, "rp", lookup(), [lost], timings=timings)
        out["rebuild"] = dict(timings)
        after = telemetry.STATS.snapshot()
        out["routes"] = {r: after["repair_route"][r] -
                         before["repair_route"][r]
                         for r in after["repair_route"]}
        out["fallbacks"] = after["repair_fallbacks"] - \
            before["repair_fallbacks"]
        assert wait_until(lambda: len(lookup()) == TOTAL_SHARDS)
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


def _named(cycle, name):
    return [s for s in cycle["spans"] if s["name"] == name]


def _root(cycle, name):
    roots = _named(cycle, name)
    assert len(roots) == 1, (name, len(roots))
    return roots[0]


def test_the_reply_names_the_route_and_the_operand(cycle):
    layout = cycle["layout"]
    enc, reb = cycle["encode"], cycle["rebuild"]
    assert enc["operand"] == ([128, 320] if layout == "piggyback"
                              else [4, 10])
    assert reb["repair_mode"] == ROUTE[layout]
    assert "repair_fallback" not in reb
    # a device codec: the trace combine is padded to its row bucket
    assert reb["operand"] == ([32, 176] if layout == "piggyback"
                              else [8, 56])
    share = reb["repair_bytes"] / reb["repair_baseline_bytes"]
    assert share <= (0.56 if layout == "piggyback" else 0.70)
    assert cycle["routes"] == {"piggyback": 0, "trace": 0, "full": 0,
                               ROUTE[layout]: 1}
    assert cycle["fallbacks"] == 0
    assert reb["phases"]["plan"] >= 0 and reb["phases"]["write"] > 0


def test_piggyback_encode_stages_hang_under_the_stream(cycle):
    root = _root(cycle, "ec.encode.stream")
    staged = {name: _named(cycle, name) for name in (
        "ec.encode.pb_recut", "ec.encode.pb_split", "ec.encode.pb_merge")}
    if cycle["layout"] == "flat":
        assert not any(staged.values())
        return
    reads = _named(cycle, "ec.encode.read")
    for name, spans in staged.items():
        # one a dispatch, as the reader's
        assert len(spans) == len(reads) == cycle["encode"]["dispatches"]
        assert all(s["parent_id"] == root["span_id"] and
                   s["trace_id"] == root["trace_id"] for s in spans)
        assert all(s["tags"]["bytes"] > 0 and s["tags"]["cpu_s"] >= 0
                   for s in spans)
    # the split runs where the reader does, the merge on the consumer
    assert {s["tags"]["thread"] for s in staged["ec.encode.pb_split"]} == \
        {s["tags"]["thread"] for s in reads} == {"pipeline-producer"}
    assert not {s["tags"]["thread"] for s in staged["ec.encode.pb_merge"]} \
        & {"pipeline-producer"}
    assert sum(s["tags"]["bytes"]
               for s in staged["ec.encode.pb_split"]) == \
        10 * cycle["encode"]["shard_size"]
    assert sum(s["tags"]["bytes"]
               for s in staged["ec.encode.pb_merge"]) == \
        4 * cycle["encode"]["shard_size"]
    # the consumer's account holds the merge with the write
    writes = sum(s["duration_s"] for s in _named(cycle, "ec.encode.write"))
    merges = sum(s["duration_s"] for s in staged["ec.encode.pb_merge"])
    assert cycle["encode"]["phases"]["write"] == pytest.approx(
        writes + merges, rel=1e-3)


def test_repair_stages_hang_under_the_stream(cycle):
    layout = cycle["layout"]
    root = _root(cycle, "ec.rebuild.stream")
    under = [s for s in cycle["spans"]
             if s.get("parent_id") == root["span_id"]]
    names = {s["name"] for s in under}
    assert {"ec.rebuild.plan", RELAYOUT[layout], "ec.rebuild.write",
            "ec.rebuild.assemble", "ec.h2d", "ec.d2h"} <= names
    # the route's own fetch names, never the full range's
    fetches = {n for n in names if n.startswith("ec.rebuild.fetch")}
    assert fetches and fetches <= {FETCH[layout] + ".remote",
                                   FETCH[layout] + ".local"}
    assert FETCH[layout] + ".remote" in fetches
    relayout = _named(cycle, RELAYOUT[layout])
    writes = _named(cycle, "ec.rebuild.write")
    assert len(relayout) == len(writes) == cycle["rebuild"]["dispatches"]
    assert sum(s["tags"]["bytes"] for s in writes) == \
        cycle["rebuild"]["rebuilt_bytes"]
    fetched = sum(s["tags"]["bytes"] for s in under
                  if s["name"].startswith("ec.rebuild.fetch"))
    assert fetched == cycle["rebuild"]["repair_bytes"]


def test_holders_tag_what_they_read_and_sent(cycle):
    layout = cycle["layout"]
    served = _named(cycle, HOLDER[layout])
    trace_id = _root(cycle, "ec.rebuild.stream")["trace_id"]
    assert served and all(s["trace_id"] == trace_id for s in served)
    read = sum(s["tags"]["bytes_read"] for s in served)
    sent = sum(s["tags"]["bytes_sent"] for s in served)
    remote = sum(s["tags"]["bytes"]
                 for s in _named(cycle, FETCH[layout] + ".remote"))
    assert sent == remote and 0 < sent < read
    if layout == "piggyback":
        assert sent * 2 == read
    # and the holder of the other layout's route served nothing
    other = HOLDER["flat" if layout == "piggyback" else "piggyback"]
    assert not _named(cycle, other)
