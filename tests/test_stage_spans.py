"""Stage spans of the EC stream: real intervals on the worker threads.

One served cycle on the CPU backend (master + three volume servers with
`ec_backend="tpu"` under JAX_PLATFORMS=cpu, shell `ec.encode`, loss of two
remote-held shards, shell `ec.rebuild`) is run once for the module; the
tests read the spans it left. Beside it: the primitive alone (the
profiler mirror, a timer without a root), the ring's drop count, and the
reader's telemetry counters taken at the boundary of its span.
"""

import io
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.util import tracing
from seaweedfs_tpu.util.profiling import StageTimer

from conftest import wait_until

# span -> (prefix of the thread's name, the stream span it hangs under)
STAGES = {
    "ec.encode.read": ("pipeline-producer", "ec.encode.stream"),
    "ec.h2d": (None, None),                    # consumer, both streams
    "ec.d2h": ("pipeline-drain", None),        # both streams
    "ec.encode.write": (None, "ec.encode.stream"),
    "ec.rebuild.write": (None, "ec.rebuild.stream"),
    "ec.spread.send": ("ec-push-", "ec.encode.stream"),
    "ec.rebuild.fetch.remote": ("ec-pull", "ec.rebuild.stream"),
    "ec.rebuild.fetch.local": ("ec-pull", "ec.rebuild.stream"),
    "ec.rebuild.assemble": ("pipeline-producer", "ec.rebuild.stream"),
}
SHELL_STAGES = {"ec.encode.freeze": "ec.encode", "ec.encode.mount": "ec.encode",
                "ec.encode.drop": "ec.encode", "ec.rebuild.mount": "ec.rebuild"}
WORKER_PREFIXES = ("pipeline-producer", "pipeline-drain", "ec-push-",
                   "ec-pull")


@pytest.fixture(scope="module")
def cycle(tmp_path_factory):
    """{"spans": every span of the cycle, "encode"/"rebuild": the node's
    reply stats, "counters": telemetry movement per command}."""
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.ec.constants import TOTAL_SHARDS
    from seaweedfs_tpu.server.http_util import get_json, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.command_ec import do_ec_encode, do_ec_rebuild
    from seaweedfs_tpu.shell.command_env import CommandEnv

    tmp = tmp_path_factory.mktemp("stages")
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1, growth_counts={1: 1}).start()
    servers = [VolumeServer(
        port=0, directories=[str(tmp / f"v{i}")], master_url=master.url,
        pulse_seconds=1, max_volume_counts=[20],
        ec_backend="tpu").start() for i in range(3)]
    spans, out = [], {}
    try:
        assert wait_until(
            lambda: len(CommandEnv(master.url).cluster_nodes()) == 3)
        a = op.assign(master.url, collection="st")
        vid = int(a["fid"].split(",")[0])
        rng = np.random.default_rng(25)
        for i in range(12):
            op.upload(a["url"], f"{vid},{i + 1:x}00000001",
                      rng.integers(0, 256, 2_000_000).astype(
                          np.uint8).tobytes(), filename=f"f{i}")
        env = CommandEnv(master.url, out=io.StringIO())

        def lookup():
            ec = get_json(f"http://{master.url}/cluster/ec_lookup"
                          f"?volumeId={vid}")
            return {int(s): u for s, u in ec.get("shards", {}).items()
                    if u}

        tracing.add_finish_hook(spans.append)
        before = telemetry.STATS.snapshot()
        timings = {}
        do_ec_encode(env, vid, timings=timings)
        out["encode"] = dict(timings)
        out["encode_counters"] = telemetry.delta(before)
        assert wait_until(lambda: len(lookup()) == TOTAL_SHARDS)
        shards = lookup()
        # lose two shards of the holder with the fewest, so that the
        # rebuilder keeps local survivors and fetches remote ones
        by_holder = {}
        for sid, urls in shards.items():
            by_holder.setdefault(urls[0], []).append(sid)
        victim, held = min(by_holder.items(), key=lambda kv: len(kv[1]))
        lost = sorted(held)[:2]
        post_json(f"http://{victim}/admin/ec/delete_shards?volume={vid}"
                  f"&collection=st&shards={','.join(map(str, lost))}")
        assert wait_until(lambda: not set(lost) & set(lookup()))
        shard_map = lookup()
        before = telemetry.STATS.snapshot()
        timings = {}
        do_ec_rebuild(env, vid, "st", shard_map, lost, timings=timings)
        out["rebuild"] = dict(timings)
        out["rebuild_counters"] = telemetry.delta(before)
        out["traces"] = get_json(
            f"http://{servers[0].url}/admin/traces?n=5")
        out["one_trace"] = get_json(
            f"http://{servers[0].url}/admin/traces"
            f"?trace={out['encode']['trace_id']}")
    finally:
        tracing.remove_finish_hook(spans.append)
        for vs in servers:
            vs.stop()
        master.stop()
    out["spans"] = spans
    return out


def _named(cycle, name):
    return [s for s in cycle["spans"] if s["name"] == name]


def _one(cycle, name, trace_id):
    got = [s for s in _named(cycle, name) if s["trace_id"] == trace_id]
    assert len(got) == 1, (name, len(got))
    return got[0]


def _inside(span, parent, slack=1e-6):
    return parent["start"] - slack <= span["start"] and \
        span["start"] + span["duration_s"] <= \
        parent["start"] + parent["duration_s"] + slack


@pytest.mark.parametrize("name", sorted(STAGES))
def test_stage_span_is_a_real_interval_on_its_thread(cycle, name):
    prefix, stream = STAGES[name]
    got = _named(cycle, name)
    assert got, f"no {name} span in an encode + rebuild"
    roots = {s["trace_id"]: s for s in cycle["spans"]
             if s["name"] in ("ec.encode", "ec.rebuild")}
    for span in got:
        # one trace with the shell's root span, hung under the stream's
        assert span["trace_id"] in roots
        streams = [s for s in cycle["spans"]
                   if s["span_id"] == span["parent_id"]]
        assert len(streams) == 1
        parent = streams[0]
        assert parent["name"] in ("ec.encode.stream", "ec.rebuild.stream")
        if stream:
            assert parent["name"] == stream
        # true start and end, on the parent's clock
        assert span["duration_s"] > 0
        assert _inside(span, parent), (span, parent)
        tags = span["tags"]
        assert tags["bytes"] > 0
        assert 0 <= tags["cpu_s"] <= span["duration_s"]
        if prefix:
            assert tags["thread"].startswith(prefix), tags["thread"]
        else:                       # the consumer: no worker thread
            assert not tags["thread"].startswith(WORKER_PREFIXES)


@pytest.mark.parametrize("name", sorted(SHELL_STAGES))
def test_shell_orchestration_stage(cycle, name):
    got = _named(cycle, name)
    assert len(got) == 1
    root = _one(cycle, SHELL_STAGES[name], got[0]["trace_id"])
    assert got[0]["parent_id"] == root["span_id"]
    assert _inside(got[0], root)
    assert got[0]["tags"]["thread"] == threading.current_thread().name


# command, the timer's stage, the spans taken over the same intervals
STAGE_MAXES = [
    ("encode", "disk_read", ("ec.encode.read",)),
    ("encode", "d2h+mxu", ("ec.d2h",)),
    ("encode", "shard_write", ("ec.encode.write",)),
    ("rebuild", "gather", ("ec.rebuild.fetch.remote",
                           "ec.rebuild.fetch.local")),
    ("rebuild", "d2h+mxu", ("ec.d2h",)),
    ("rebuild", "shard_write", ("ec.rebuild.write",)),
]


@pytest.mark.parametrize("command,stage,names", STAGE_MAXES,
                         ids=[f"{c}-{s}" for c, s, _ in STAGE_MAXES])
def test_reply_names_the_longest_interval_of_a_stage(cycle, command, stage,
                                                     names):
    """`stage_max_s` beside `phases`: the one slow fetch or drain that a
    stage's sum averages away, from the interval the span was cut from."""
    reply = cycle[command]
    took = [s["duration_s"] for s in cycle["spans"]
            if s["name"] in names and s["trace_id"] == reply["trace_id"]]
    assert len(took) >= 2
    longest = reply["stage_max_s"][stage]
    assert longest == pytest.approx(max(took), abs=2e-6)
    assert sum(took) / len(took) <= longest <= sum(took)


def test_every_stage_of_a_reply_has_its_longest_interval(cycle):
    enc, reb = cycle["encode"], cycle["rebuild"]
    assert {"disk_read", "h2d", "d2h+mxu", "shard_write", "read_wait",
            "spread"} <= set(enc["stage_max_s"])
    assert {"gather", "h2d", "d2h+mxu", "shard_write", "read_wait"} \
        <= set(reb["stage_max_s"])
    for reply in (enc, reb):
        assert all(0 < v <= reply["stream_s"] + 1e-3
                   for v in reply["stage_max_s"].values())
    # a run's send is no longer than all of them, no shorter than their
    # mean (the reply rounds the sum to the millisecond)
    assert enc["spread_send_s"] / enc["spread_sends"] - 1e-3 \
        <= enc["stage_max_s"]["spread"] <= enc["spread_send_s"] + 1e-3
    # and `phases`, which the gather shares read, is what it was, with
    # the `.ecx` build before the stream as a phase of its own (PR 42)
    assert set(enc["phases"]) == {"gather", "dispatch", "drain", "write",
                                  "index"}
    assert enc["phases"]["index"] == pytest.approx(
        enc["stage_max_s"]["index"], abs=2e-6)
    assert set(reb["phases"]) == set(tracing.PHASES)


def test_a_holders_appends_are_split_and_counted(cycle):
    """Every remote run of the encode's spread: `recv_s`, `write_s`,
    `cpu_s` on the holder's server span, and the `holder_*` counters of
    the process (all three servers are its) moved by the same."""
    enc = cycle["encode_counters"]
    tid = cycle["encode"]["trace_id"]
    runs = [s for s in _named(cycle, "POST /admin/ec/shard_write")
            if s["trace_id"] == tid and "bytes" in s["tags"]]
    assert runs
    for span in runs:
        tags = span["tags"]
        assert tags["recv_s"] > 0 and tags["write_s"] > 0
        assert tags["recv_s"] + tags["write_s"] <= span["duration_s"]
        assert 0 <= tags["cpu_s"] <= span["duration_s"]
    assert enc["holder_runs"] == len(runs)
    assert enc["holder_bytes"] == sum(s["tags"]["bytes"] for s in runs) \
        == cycle["encode"]["spread_remote_shards"] \
        * cycle["encode"]["shard_size"]
    assert enc["holder_recv_us"] + enc["holder_write_us"] \
        <= enc["holder_us"] <= 1e6 * sum(s["duration_s"] for s in runs)
    assert cycle["rebuild_counters"]["holder_runs"] == 0
    # the probe of the process ticked through both commands
    assert enc["lock_probe_samples"] > 0
    assert cycle["rebuild_counters"]["lock_probe_samples"] > 0


def test_span_count_is_per_dispatch_not_per_block(cycle):
    enc, reb = cycle["encode_counters"], cycle["rebuild_counters"]
    n_enc, n_reb = enc["dispatches"], reb["dispatches"]
    assert n_enc >= 2 and n_reb >= 2      # several stripes, or no test
    tid = cycle["encode"]["trace_id"]
    count = lambda name, t: len([s for s in _named(cycle, name)
                                 if s["trace_id"] == t])
    # one span per dispatch's slab, though each slab is 10 block reads
    for name in ("ec.encode.read", "ec.h2d", "ec.d2h", "ec.encode.write"):
        assert count(name, tid) == n_enc, name
    # a drained batch per target at least, one per queued chunk at
    # most — and never a zero-duration one per run, as `spread.run` was
    sends = [s for s in _named(cycle, "ec.spread.send")
             if s["trace_id"] == tid]
    assert 3 <= len(sends) <= 14 * n_enc
    assert all(s["duration_s"] > 0 and s["tags"]["target"] for s in sends)
    assert sum(s["tags"]["bytes"] for s in sends) == \
        14 * cycle["encode"]["shard_size"]
    assert not _named(cycle, "spread.run")
    # the whole encode stays far under the ring's cap for one trace
    in_trace = [s for s in cycle["spans"] if s["trace_id"] == tid]
    assert len(in_trace) < tracing.RING.max_spans // 2
    rtid = cycle["rebuild"]["trace_id"]
    for name in ("ec.h2d", "ec.d2h", "ec.rebuild.write",
                 "ec.rebuild.assemble"):
        assert count(name, rtid) == n_reb, name
    # one fetch per survivor per stripe: k = 10 of them a dispatch
    assert count("ec.rebuild.fetch.remote", rtid) + \
        count("ec.rebuild.fetch.local", rtid) == 10 * n_reb


@pytest.mark.parametrize("name", ["ec.spread.finish",
                                  "ec.spread.finalize"])
def test_the_spreads_tail_has_its_spans(cycle, name):
    """PR 39: `sink.finish()` is two stages on the consumer under the
    stream's root — the wait for every lane to join, then the finalize
    of every shard — once an encode, in that order, and no send ends
    after the join does."""
    tid = cycle["encode"]["trace_id"]
    span = _one(cycle, name, tid)
    stream = _one(cycle, "ec.encode.stream", tid)
    assert span["parent_id"] == stream["span_id"]
    assert _inside(span, stream)
    assert not span["tags"]["thread"].startswith(WORKER_PREFIXES)
    join = _one(cycle, "ec.spread.finish", tid)
    join_end = join["start"] + join["duration_s"]
    sends = [s for s in _named(cycle, "ec.spread.send")
             if s["trace_id"] == tid]
    assert max(s["start"] + s["duration_s"] for s in sends) \
        <= join_end + 1e-6
    assert _one(cycle, "ec.spread.finalize", tid)["start"] \
        >= join_end - 1e-6


def test_a_send_names_its_target_and_its_lane(cycle):
    """5 + 5 + 4 over three servers: two lanes a target, each on a
    thread of its own; the reply counts them and the sends' sum."""
    tid = cycle["encode"]["trace_id"]
    sends = [s for s in _named(cycle, "ec.spread.send")
             if s["trace_id"] == tid]
    lanes = {(s["tags"]["target"], s["tags"]["lane"]) for s in sends}
    assert len(lanes) == 6 and {lane for _, lane in lanes} == {0, 1}
    assert len({s["tags"]["thread"] for s in sends}) == 6
    enc = cycle["encode"]
    assert enc["spread_lanes"] == 6 and enc["spread_connects"] == 4
    assert enc["spread_send_s"] >= enc["spread_busy_s"]
    # the reply's sum is the sends' own intervals (the spans hold whole
    # batches, so they are no shorter)
    assert enc["spread_send_s"] <= \
        sum(s["duration_s"] for s in sends) + 1e-3


def test_phases_keep_their_keys_and_their_sum(cycle):
    reb = cycle["rebuild"]
    assert set(reb["phases"]) == {"gather", "plan", "dispatch", "drain",
                                  "write"}
    assert sum(reb["phases"].values()) >= 0.9 * reb["stream_s"]
    # the write phase is still the consumer's time in the shard writes
    # (+ the .ecx rebuild the store adds), now summed by the timer
    writes = sum(s["duration_s"] for s in _named(cycle, "ec.rebuild.write"))
    assert reb["phases"]["write"] >= writes * 0.99
    enc = cycle["encode"]
    assert set(enc["phases"]) == {"gather", "dispatch", "drain", "write",
                                  "index"}
    writes = sum(s["duration_s"] for s in _named(cycle, "ec.encode.write"))
    # (the reply rounds a phase to the microsecond, and since PR 30 a
    # stripe's write is queueing 14 views: tens of microseconds)
    assert enc["phases"]["write"] == pytest.approx(writes, rel=1e-3,
                                                   abs=1e-6)
    # (the .ecx build comes before the stream: `stream_s` starts after it)
    assert sum(enc["phases"].values()) - enc["phases"]["index"] \
        <= enc["stream_s"] * 1.01
    # the phase spans themselves are still there, one of each a command
    tid = enc["trace_id"]
    for phase in ("gather", "dispatch", "write"):
        assert _one(cycle, phase, tid)["duration_s"] == \
            pytest.approx(enc["phases"][phase], abs=1e-5)


def test_counters_at_the_stage_boundaries(cycle):
    enc, reb = cycle["encode_counters"], cycle["rebuild_counters"]
    tid = cycle["encode"]["trace_id"]
    reads = [s for s in _named(cycle, "ec.encode.read")
             if s["trace_id"] == tid]
    assert enc["read_bytes"] == sum(s["tags"]["bytes"] for s in reads) \
        == 10 * cycle["encode"]["shard_size"]
    assert enc["read_busy_us"] == pytest.approx(
        1e6 * sum(s["duration_s"] for s in reads), abs=len(reads))
    assert 0 < enc["read_cpu_us"] <= enc["read_busy_us"]
    assert reb["read_bytes"] == 0
    # transfers and survivor fetches are not doubled in telemetry: the
    # spans carry the bytes the transport's own stats already count
    assert set(enc) - {"mesh_device_bytes", "dispatch_width_devices",
                       "device_byte_share", "geometry_dispatches"} == {
        "dispatches", "bitmat_uploads", "host_fallbacks", "device_bytes",
        "mesh_dispatches", "read_bytes", "read_busy_us", "read_cpu_us",
        "repair_fallbacks", "coupled_decodes", "slab_fresh_bytes",
        # PR 42: the .ecx build (tests/test_collection_served.py)
        "index_entries", "index_us",
        # PR 40: what a thread waits for (tests/test_wait_probe.py)
        "holder_runs", "holder_bytes", "holder_us", "holder_recv_us",
        "holder_write_us", "holder_cpu_us", "lock_probe_samples",
        "lock_probe_elapsed_us", "lock_probe_late_us",
        "lock_probe_stalls", "lock_probe_stall_us",
        # PR 43: indexes loaded as arrays (tests/test_idx_array.py)
        "mirror_entries", "mirror_us", "mirror_loop_entries",
        # PR 45: decode apart from storage (tests/test_fanned_served.py)
        "rebuild_delivered_bytes", "rebuild_local_bytes",
        # PR 47: a frozen volume's map stays an array
        # (tests/test_frozen_map.py)
        "frozen_array_maps",
        # PR 50: a collection's volumes in flight, one a source server
        # (tests/test_encode_lanes.py)
        "collection_encode_inflight_us", "collection_encode_us"}
    fetched = sum(s["tags"]["bytes"] for s in cycle["spans"]
                  if s["name"].startswith("ec.rebuild.fetch."))
    assert fetched == cycle["rebuild"]["survivor_bytes"] > 0
    assert sum(s["tags"]["bytes"] for s in _named(cycle, "ec.h2d")) >= \
        enc["device_bytes"] + reb["device_bytes"] > 0
    # the node's reply carries them beside `phases`, not inside it
    assert cycle["encode"]["read_bytes"] == enc["read_bytes"]


def test_admin_traces_shows_the_drop_count(cycle):
    assert cycle["traces"]["dropped_spans"] == 0
    assert all(t["dropped_spans"] == 0 for t in cycle["traces"]["traces"])
    assert cycle["one_trace"]["dropped_spans"] == 0
    names = {s["name"] for s in cycle["one_trace"]["spans"]}
    assert {"ec.encode", "ec.encode.stream", "ec.encode.read",
            "ec.spread.send"} <= names


# -- the primitive alone ------------------------------------------------------

class FakeAnnotation:
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("open", self.name, threading.current_thread().name))

    def __exit__(self, *exc):
        self.log.append(("close", self.name,
                         threading.current_thread().name))


@pytest.fixture
def no_mirror():
    was = tracing._stage_mirror
    tracing.set_stage_mirror(None)
    FakeAnnotation.log = []
    yield
    tracing.set_stage_mirror(was)


def test_mirror_is_a_no_op_without_a_factory(no_mirror):
    got = []
    tracing.add_finish_hook(got.append)
    try:
        with tracing.span("root") as root:
            with tracing.Stage("ec.h2d", root) as st:
                st.nbytes = 7
    finally:
        tracing.remove_finish_hook(got.append)
    assert st._mirror is None
    assert [s["name"] for s in got] == ["ec.h2d", "root"]
    assert got[0]["tags"]["bytes"] == 7


def test_mirror_brackets_the_stage_on_its_own_thread(no_mirror):
    tracing.set_stage_mirror(FakeAnnotation)
    with tracing.span("root") as root:
        timer = StageTimer(root=root)

        def work():
            with timer.stage("h2d", 3, span="ec.h2d"):
                FakeAnnotation.log.append(("work",))

        t = threading.Thread(target=work, name="a-worker")
        t.start()
        t.join()
    assert FakeAnnotation.log == [("open", "sw:ec.h2d", "a-worker"),
                                  ("work",),
                                  ("close", "sw:ec.h2d", "a-worker")]
    assert timer.totals["h2d"] > 0 and timer.bytes["h2d"] == 3


def test_a_timer_without_a_root_leaves_no_span(no_mirror):
    tracing.set_stage_mirror(FakeAnnotation)
    got = []
    tracing.add_finish_hook(got.append)
    try:
        timer = StageTimer()
        with timer.stage("h2d", 5, span="ec.h2d") as st:
            pass
        with tracing.Stage("ec.h2d", timer.root) as up:
            up.nbytes = 1
        assert up.t1 > up.t0 and up._mirror is None
    finally:
        tracing.remove_finish_hook(got.append)
    assert got == [] and FakeAnnotation.log == []
    # the totals and the interval are taken as before
    assert timer.totals["h2d"] == st.t1 - st.t0 > 0
    assert timer.intervals["h2d"] == [(st.t0, st.t1)]


def test_stage_span_carries_an_error_and_still_closes(no_mirror):
    tracing.set_stage_mirror(FakeAnnotation)
    got = []
    tracing.add_finish_hook(got.append)
    try:
        with tracing.span("root") as root:
            with pytest.raises(IOError):
                with tracing.Stage("ec.rebuild.fetch.remote", root):
                    raise IOError("short read")
    finally:
        tracing.remove_finish_hook(got.append)
    assert got[0]["tags"]["error"] == "OSError"
    assert [e[0] for e in FakeAnnotation.log] == ["open", "close"]


def test_ring_counts_the_spans_it_drops():
    ring = tracing.TraceRing(max_traces=2, max_spans=3)
    for i in range(5):
        ring.add({"trace_id": "a", "span_id": str(i), "name": "s",
                  "duration_s": 0.0})
    ring.add({"trace_id": "b", "span_id": "0", "name": "s",
              "duration_s": 0.0})
    assert ring.dropped == 2
    assert ring.dropped_of("a") == 2 and ring.dropped_of("b") == 0
    assert len(ring.get("a")) == 3
    listed = {t["trace_id"]: t for t in ring.recent(5)}
    assert listed["a"]["dropped_spans"] == 2
    assert listed["a"]["span_count"] == 3
    # an evicted trace takes its count with it; the total stays
    ring.add({"trace_id": "c", "span_id": "0", "name": "s",
              "duration_s": 0.0})
    assert ring.dropped_of("a") == 0 and ring.dropped == 2
    ring.clear()
    assert ring.dropped == 0


# -- a rebuild decoded apart from where it is stored (PR 45) -----------------

@pytest.fixture(scope="module")
def delivered(tmp_path_factory):
    """Three servers on `tpu-own` (a host device each); one volume coded,
    two shards of one holder lost, and the rebuild placed by hand: a
    survivor holder computes, the holder that lost them is the target."""
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.ec.constants import TOTAL_SHARDS
    from seaweedfs_tpu.ops import device_stats
    from seaweedfs_tpu.server.http_util import get_json, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.command_ec import (chips_of, do_ec_encode,
                                                do_ec_rebuild)
    from seaweedfs_tpu.shell.command_env import CommandEnv

    tmp = tmp_path_factory.mktemp("delivered")
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1, growth_counts={1: 1}).start()
    servers = [VolumeServer(
        port=0, directories=[str(tmp / f"v{i}")], master_url=master.url,
        pulse_seconds=1, max_volume_counts=[20],
        ec_backend="tpu-own").start() for i in range(3)]
    spans, out = [], {}
    try:
        env = CommandEnv(master.url, out=io.StringIO())
        assert wait_until(lambda: len(env.cluster_nodes()) == 3)
        a = op.assign(master.url, collection="dl")
        vid = int(a["fid"].split(",")[0])
        rng = np.random.default_rng(45)
        for i in range(10):
            op.upload(a["url"], f"{vid},{i + 1:x}00000001",
                      rng.integers(0, 256, 2_000_000).astype(
                          np.uint8).tobytes(), filename=f"f{i}")

        def lookup():
            ec = get_json(f"http://{master.url}/cluster/ec_lookup"
                          f"?volumeId={vid}")
            return {int(s): u for s, u in ec.get("shards", {}).items()
                    if u}

        do_ec_encode(env, vid)
        assert wait_until(lambda: len(lookup()) == TOTAL_SHARDS)
        by_holder = {}
        for sid, urls in lookup().items():
            by_holder.setdefault(urls[0], []).append(sid)
        target, held = min(by_holder.items(), key=lambda kv: len(kv[1]))
        node = next(u for u in by_holder if u != target)
        lost = sorted(held)[:2]
        post_json(f"http://{target}/admin/ec/delete_shards?volume={vid}"
                  f"&collection=dl&shards={','.join(map(str, lost))}")
        assert wait_until(lambda: not set(lost) & set(lookup()))
        chips = chips_of(env.cluster_nodes())
        tracing.add_finish_hook(spans.append)
        before = telemetry.STATS.snapshot()
        jit = device_stats.DEVICE_STATS.snapshot()["dispatches"]
        timings = {}
        do_ec_rebuild(env, vid, "dl", lookup(), lost, timings=timings,
                      placement=(node, target))
        out.update(
            reply=dict(timings), counters=telemetry.delta(before),
            jit={e: n - jit.get(e, 0) for e, n in
                 device_stats.DEVICE_STATS.snapshot()["dispatches"].items()
                 if n - jit.get(e, 0)},
            node=node, target=target, lost=lost, chips=chips,
            index={vs.url: vs.store.device()["index"] for vs in servers})
        assert wait_until(lambda: len(lookup()) == TOTAL_SHARDS)
        out["holders"] = lookup()
    finally:
        tracing.remove_finish_hook(spans.append)
        for vs in servers:
            vs.stop()
        master.stop()
    out["spans"] = spans
    return out


def test_the_deliver_stage_is_the_consumers_under_the_stream(delivered):
    tid = delivered["reply"]["trace_id"]
    mine = [s for s in delivered["spans"] if s["trace_id"] == tid]
    stream = [s for s in mine if s["name"] == "ec.rebuild.stream"]
    assert len(stream) == 1
    assert stream[0]["tags"]["deliver_to"] == delivered["target"]
    delivers = [s for s in mine if s["name"] == "ec.rebuild.deliver"]
    assert delivers and all(s["parent_id"] == stream[0]["span_id"] and
                            _inside(s, stream[0]) for s in delivers)
    # where a local rebuild has ec.rebuild.write, and on the same thread
    assert not [s for s in mine if s["name"] == "ec.rebuild.write"]
    threads = {s["tags"]["thread"] for s in delivers}
    assert threads == {s["tags"]["thread"] for s in mine
                       if s["name"] == "ec.h2d"}
    reply = delivered["reply"]
    assert sum(s["tags"]["bytes"] for s in delivers) == \
        reply["rebuilt_bytes"] == 2 * reply["survivor_bytes"] // 10
    assert sum(s["duration_s"] for s in delivers) == \
        pytest.approx(reply["phases"]["deliver"], abs=1e-4)
    assert reply["delivered_to"] == delivered["target"]
    assert 0 <= reply["deliver_blocked_s"] <= reply["phases"]["deliver"] + 1e-3
    assert reply["stage_max_s"]["deliver"] > 0


def test_the_targets_appends_join_the_rebuilds_trace(delivered):
    tid = delivered["reply"]["trace_id"]
    appends = [s for s in delivered["spans"] if s["trace_id"] == tid
               and s["name"] == "POST /admin/ec/shard_write"]
    # two shards: at least an append and a finalize each
    assert len(appends) >= 4
    sends = [s for s in delivered["spans"] if s["trace_id"] == tid
             and s["name"] == "ec.spread.send"]
    assert sends and {s["tags"]["target"] for s in sends} == \
        {delivered["target"]}
    pulls = [s for s in delivered["spans"] if s["trace_id"] == tid
             and s["name"] == "POST /admin/ec/copy"]
    assert not pulls        # the target kept survivors: its index is there
    for sid in delivered["lost"]:
        assert delivered["holders"][sid] == [delivered["target"]]


def test_the_volumes_span_names_who_computed_for_whom(delivered):
    tid = delivered["reply"]["trace_id"]
    root = next(s for s in delivered["spans"] if s["trace_id"] == tid
                and s["name"] == "ec.rebuild")
    assert root["tags"]["computed_on"] == delivered["node"]
    assert root["tags"]["target"] == delivered["target"]
    assert root["tags"]["device"] == delivered["chips"][delivered["node"]]
    assert "fallback" not in root["tags"]
    # counted by where the bytes went, and by the chip that dispatched
    counters = delivered["counters"]
    assert counters["rebuild_delivered_bytes"] == \
        delivered["reply"]["rebuilt_bytes"]
    assert counters["rebuild_local_bytes"] == 0
    dev = f"dev{delivered['index'][delivered['node']]}"
    assert {e for e in delivered["jit"] if e.startswith("dev")} == {dev}
    assert delivered["jit"][dev] == delivered["jit"]["rs_tpu._packed_fn"] \
        == counters["dispatches"]
