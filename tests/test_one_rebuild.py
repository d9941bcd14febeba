"""One rebuild (PR 48): the shell has one flow a command, the node one
flat body whatever form the POST has, every route's reply is written by
one helper, and a rebuild that fails leaves no part of a shard."""

import hashlib
import io
import os

import numpy as np
import pytest
from conftest import wait_until

from seaweedfs_tpu.ec import encoder, rebuild_ec_files, to_ext, \
    write_ec_files
from seaweedfs_tpu.ec.transport import GatherStats, LocalShardReader, \
    RemoteShardReader, RemoteShardWriter
from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.ops.codec import NumpyCodec
from seaweedfs_tpu.server.http_util import HttpError, get_json, post_json
from seaweedfs_tpu.shell.command_env import CommandEnv
from seaweedfs_tpu.util import tracing
from seaweedfs_tpu.util.profiling import StageTimer

VID = 3


def _shas(base, total):
    return [hashlib.sha256(open(base + to_ext(i), "rb").read()).hexdigest()
            if os.path.exists(base + to_ext(i)) else None
            for i in range(total)]


def _sealed_volume(store, vid=VID):
    from seaweedfs_tpu.storage.needle import Needle
    v = store.add_volume(vid)
    rng = np.random.default_rng(vid)
    for i in range(1, 9):
        v.write_needle(Needle(cookie=i, id=i, data=rng.integers(
            0, 256, 50_000).astype(np.uint8).tobytes()))
    store.mark_volume_readonly(vid)
    return v.file_name()


# -- the node: one flat body, whatever form the POST has ----------------------

@pytest.fixture
def node(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[7], ec_backend="numpy").start()
    yield vs
    vs.stop()
    master.stop()


@pytest.mark.parametrize("layout,k,m,lost", [
    # a lone lost shard too: the form that names no route is the full
    # decode's, not the single-shard repair `auto` would pick
    ("flat", 10, 4, [3]),
    ("flat", 6, 3, [0, 7]),
    ("piggyback", 10, 4, [2]),
    ("piggyback", 6, 3, [1, 7])])
def test_the_query_only_post_is_the_full_decode_of_local_survivors(
        node, monkeypatch, layout, k, m, lost):
    monkeypatch.setenv("SW_EC_LAYOUT", layout)
    base = _sealed_volume(node.store)
    post_json(f"http://{node.url}/admin/ec/generate?volume={VID}"
              f"&geometry={k},{m}")
    assert node.store._volume_layout(base).piggyback == \
        (layout == "piggyback")
    want = _shas(base, k + m)
    shard = os.path.getsize(base + to_ext(0))
    for sid in lost:
        os.remove(base + to_ext(sid))
    before = telemetry.STATS.snapshot()
    out = post_json(f"http://{node.url}/admin/ec/rebuild?volume={VID}")
    assert out["rebuilt"] == lost and _shas(base, k + m) == want
    reply = out["stats"]
    assert reply["repair_mode"] == "full" and \
        "repair_fallback" not in reply
    assert (reply["k"], reply["m"], reply["lost"]) == (k, m, lost)
    # the gather's account, of a gather that read nothing over the wire
    assert reply["repair_bytes"] == reply["repair_baseline_bytes"] == \
        reply["survivor_bytes"] == k * shard
    assert reply["repair_remote_bytes"] == 0
    assert reply["gather_remote_shards"] == 0
    assert reply["rebuilt_bytes"] == len(lost) * shard
    moved = telemetry.delta(before)
    assert moved["coupled_decodes"] == (layout == "piggyback")
    assert moved["rebuild_local_bytes"] == \
        (0 if layout == "piggyback" else len(lost) * shard)
    assert moved["repair_fallbacks"] == 0


def test_a_volume_that_is_not_here_is_refused_by_name(node):
    with pytest.raises(HttpError, match="only 0 of 14 shards"):
        post_json(f"http://{node.url}/admin/ec/rebuild?volume=99")
    assert os.listdir(node.store.locations[0].directory) == []


# -- all or nothing, the local entry too --------------------------------------

K, M, SLAB = 10, 4, 8 << 10


@pytest.fixture
def local(tmp_path):
    rng = np.random.default_rng(5)
    base = str(tmp_path / "1")
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes())
    write_ec_files(base, codec=NumpyCodec(K, M), large_block=64 << 10,
                   small_block=8 << 10, slab=SLAB, pipelined=False)
    assert os.path.getsize(base + to_ext(0)) >= 3 * SLAB
    want = _shas(base, K + M)
    for sid in (2, 11):
        os.remove(base + to_ext(sid))
    return base, want


@pytest.mark.parametrize("where", ["source", "decode"])
def test_a_local_rebuild_that_fails_leaves_no_part_of_a_shard(
        local, monkeypatch, where):
    base, want = local
    codec = NumpyCodec(K, M)
    with monkeypatch.context() as failing:
        if where == "source":
            read_into = LocalShardReader.read_into

            def second_stripe_is_unreadable(self, off, n, idx, dest):
                if idx == 1:
                    raise IOError(f"short read of {self.path} at {off}")
                return read_into(self, off, n, idx, dest)

            failing.setattr(LocalShardReader, "read_into",
                            second_stripe_is_unreadable)
        else:
            matmul, calls = codec._matmul, []

            def second_product_fails(coeffs, data):
                calls.append(data.shape)
                if len(calls) == 2:
                    raise RuntimeError("the decode died")
                return matmul(coeffs, data)

            failing.setattr(codec, "_matmul", second_product_fails)
        with pytest.raises((IOError, RuntimeError)):
            rebuild_ec_files(base, codec=codec, slab=SLAB, pipelined=False)
    # the first stripe's rows had been written: nothing of them stays
    assert not os.path.exists(base + to_ext(2))
    assert not os.path.exists(base + to_ext(11))
    # so that the next rebuild counts survivors, not a truncated shard
    assert rebuild_ec_files(base, codec=codec, slab=SLAB,
                            pipelined=False) == [2, 11]
    assert _shas(base, K + M) == want


# -- the reply is written once ------------------------------------------------

class _NoGather:
    """What close_rebuild asks of a gather, of one that read nothing."""
    shard_size = 0

    def __init__(self):
        self.stats = GatherStats()


def _common_keys():
    """The keys close_rebuild writes for every route: read from the
    helper, handed a rebuild that did nothing and has no key of its
    own."""
    reply = {}
    encoder.close_rebuild(reply, StageTimer(), 0.0,
                          telemetry.STATS.snapshot(), _NoGather(),
                          NumpyCodec(K, M), np.zeros((1, K), np.uint8), [0])
    return set(reply)


@pytest.mark.parametrize("route,layout,lost,own", [
    ("full", "flat", [1, 12], {"survivor_bytes"}),
    ("full", "piggyback", [1, 12], {"survivor_bytes", "layout"}),
    ("trace", "flat", [1], {"repair_helpers", "repair_bits",
                            "repair_total_bits", "repair_bytes_frac",
                            "repair_mbps"}),
    ("piggyback", "piggyback", [1], {"repair_helpers", "layout",
                                     "repair_bytes_frac", "repair_mbps"})])
def test_every_route_replies_with_the_helpers_keys(tmp_path, monkeypatch,
                                                   route, layout, lost, own):
    from seaweedfs_tpu.storage.store import Store
    monkeypatch.setenv("SW_EC_LAYOUT", layout)
    store = Store([str(tmp_path)], ec_backend="numpy")
    base = _sealed_volume(store)
    store.generate_ec_shards(VID)
    want = _shas(base, K + M)
    for sid in lost:
        os.remove(base + to_ext(sid))
    reply = {}
    assert store.rebuild_ec_shards_streaming(VID, stats=reply) == lost
    assert _shas(base, K + M) == want
    common = _common_keys()
    assert {"phases", "stage_max_s", "stream_s", "rebuilt_bytes", "lost",
            "operand", "overlap_frac", "repair_bytes",
            "repair_baseline_bytes", "gather_bytes",
            "dispatches"} <= common
    assert common <= set(reply), sorted(common - set(reply))
    # what is one route's own is not the helper's, and is there
    assert own <= set(reply) - common
    assert reply["repair_mode"] == route and reply["lost"] == lost
    assert set(reply["phases"]) == {"plan", "gather", "dispatch", "drain",
                                    "write"}
    # the phases tile the stream: the plan came before it
    in_stream = sum(reply["phases"].values()) - reply["phases"]["plan"]
    assert in_stream == pytest.approx(reply["stream_s"], abs=2e-3)
    assert reply["rebuilt_bytes"] == \
        len(lost) * os.path.getsize(base + to_ext(0))
    store.close()


# -- the shell: one flow a command --------------------------------------------

class RecordingEnv(CommandEnv):
    """A CommandEnv that notes every call it makes to a node."""

    def __init__(self, master_url):
        super().__init__(master_url, out=io.StringIO())
        self.calls = []

    def node_post(self, node, path, timeout=None, body=None):
        self.calls.append((path.split("?")[0], node, path, body))
        return super().node_post(node, path, timeout, body)


@pytest.fixture
def cluster3(tmp_path):
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1,
                          growth_counts={1: 1}).start()
    servers = [
        VolumeServer(port=0, directories=[str(tmp_path / f"v{i}")],
                     master_url=master.url, pulse_seconds=1,
                     max_volume_counts=[30], ec_backend="numpy").start()
        for i in range(3)]
    rng = np.random.default_rng(11)
    for i in range(6):
        fid = op.upload_data(
            master.url, rng.integers(0, 256, 150_000).astype(
                np.uint8).tobytes(), filename=f"f{i}", collection="one")
    yield master, servers, int(fid.split(",")[0])
    for vs in servers:
        vs.stop()
    master.stop()


def _files(servers, *endings):
    return sorted(f for vs in servers for loc in vs.store.locations
                  for f in os.listdir(loc.directory)
                  if f.endswith(endings))


SHARD_FILES = tuple(to_ext(s) for s in range(K + M))


def _lose(master, servers, vid, sids):
    """The shards `sids` gone from their holders' disks and the master's
    view."""
    for vs in servers:
        ev = vs.store.find_ec_volume(vid)
        mine = [s for s in sids if ev is not None and s in ev.shards]
        if mine:
            post_json(f"http://{vs.url}/admin/ec/delete_shards?volume={vid}"
                      f"&collection=one&shards={','.join(map(str, mine))}")
    assert wait_until(lambda: not set(map(str, sids)) & set(get_json(
        f"http://{master.url}/cluster/ec_lookup?volumeId={vid}")["shards"]))


@pytest.mark.parametrize("flag", ["-mode", "-bogus"])
def test_mode_is_a_flag_the_commands_do_not_have(cluster3, flag):
    """`-mode copy` is to `ec.encode` and `ec.rebuild` what any flag
    they never had is (the shell's parse_flags keeps what a command
    does not ask for, and refuses none): the one flow runs."""
    from seaweedfs_tpu.shell.command_env import HELP, run_command
    master, servers, vid = cluster3
    assert "-mode" not in HELP["ec.encode"] + HELP["ec.rebuild"]
    env = RecordingEnv(master.url)
    run_command(env, f"ec.encode -volumeId {vid} {flag} copy")
    assert "streamed 14 shards" in env.out.getvalue()
    generates = [c for c in env.calls if c[0] == "/admin/ec/generate"]
    assert len(generates) == 1 and generates[0][3]["assignment"]
    assert len(_files(servers, *SHARD_FILES)) == K + M
    _lose(master, servers, vid, [0, 13])
    del env.calls[:]
    run_command(env, f"ec.rebuild -collection one {flag} copy")
    assert "rebuilt shards [0, 13]" in env.out.getvalue()
    rebuilds = [c for c in env.calls if c[0] == "/admin/ec/rebuild"]
    assert len(rebuilds) == 1 and rebuilds[0][3]["sources"]
    # no survivor was copied whole for it
    assert not [c for c in env.calls if c[0] == "/admin/ec/copy"]
    assert len(_files(servers, *SHARD_FILES)) == K + M


def test_an_encode_whose_stream_fails_unwinds_and_is_not_tried_again(
        cluster3, monkeypatch):
    from seaweedfs_tpu.shell.command_ec import do_ec_encode
    master, servers, vid = cluster3
    holder = next(vs for vs in servers
                  if vs.store.find_volume(vid) is not None)
    assert not holder.store.find_volume(vid).readonly
    send, sent = RemoteShardWriter.send, []

    def dies_mid_shard(self, url, off, chunks, link=None):
        sent.append(off)
        if len(sent) > 2:       # bytes are acknowledged: no replay
            raise HttpError(500, "the holder is gone")
        return send(self, url, off, chunks, link)

    monkeypatch.setattr(RemoteShardWriter, "send", dies_mid_shard)
    env = RecordingEnv(master.url)
    spans = []
    tracing.add_finish_hook(spans.append)
    try:
        with pytest.raises(HttpError):
            do_ec_encode(env, vid)
    finally:
        tracing.remove_finish_hook(spans.append)
    assert len(sent) > 2
    assert [c[0] for c in env.calls].count("/admin/ec/generate") == 1
    assert _files(servers, *SHARD_FILES, ".part", ".ecx") == []
    volume = holder.store.find_volume(vid)
    assert volume is not None and not volume.readonly   # thawed, and there
    root = next(s for s in spans if s["name"] == "ec.encode")
    assert root["tags"]["error"] == "HttpError"
    assert "fallback" not in root["tags"] and "mode" not in root["tags"]
    # and the command is as good as it was: the same volume, encoded
    monkeypatch.setattr(RemoteShardWriter, "send", send)
    do_ec_encode(env, vid)
    assert len(_files(servers, *SHARD_FILES)) == K + M


def test_a_rebuild_whose_stream_fails_raises_and_copies_nothing(
        cluster3, monkeypatch):
    from seaweedfs_tpu.shell.command_ec import do_ec_encode, do_ec_rebuild
    master, servers, vid = cluster3
    env = RecordingEnv(master.url)
    do_ec_encode(env, vid)
    _lose(master, servers, vid, [4, 5])
    before = _files(servers, *SHARD_FILES)
    assert len(before) == K + M - 2

    def unreadable(self, off, n, stripe_idx, dest):
        raise HttpError(503, "the holder answers nothing")

    monkeypatch.setattr(RemoteShardReader, "read_into", unreadable)
    shards = {int(s): urls for s, urls in
              env.ec_volumes()[str(vid)]["shards"].items()}
    del env.calls[:]
    spans = []
    tracing.add_finish_hook(spans.append)
    try:
        with pytest.raises(HttpError):
            do_ec_rebuild(env, vid, "one", shards, [4, 5])
    finally:
        tracing.remove_finish_hook(spans.append)
    assert [c[0] for c in env.calls] == ["/admin/ec/rebuild"]
    assert _files(servers, *SHARD_FILES, ".part") == before
    root = next(s for s in spans if s["name"] == "ec.rebuild")
    assert root["tags"]["error"] == "HttpError"
    assert "fallback" not in root["tags"] and "mode" not in root["tags"]
