"""Parity drills for the unified stripe transport (ec/transport.py):
gather and spread are thin clients over ONE windowed data-mover, so
the failover, hedging, window-bounding and stats machinery must be
literally shared — not two lookalike implementations. These tests pin
that: structural identity of the classes, an injected stall failing
over on BOTH sides, the bounded in-flight window on BOTH sides,
push-side hedging (new in the shared layer), the producer MB/s pacing
the tier demotion rides on, and a pull→push round trip that keeps
shard bytes bit-identical through both halves of the transport."""

import hashlib
import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import gather, spread, transport
from seaweedfs_tpu.ec import to_ext, write_ec_files
from seaweedfs_tpu.ec.encoder import write_ec_files_spread
from seaweedfs_tpu.ec.spread import StripedSpreadSink
from seaweedfs_tpu.ops.codec import NumpyCodec
from test_streaming_gather import FakeHolder, _seed_shards
from test_streaming_spread import ENC, LOCAL, FakeTarget, _digest, \
    _seed_oracle

# referenced by tools/analyze.py's route lint: the tiering view these
# drills feed rides GET /cluster/tiering (exercised in test_tiering.py)


# -- one transport layer, not two lookalikes ---------------------------------

def test_gather_and_spread_are_one_transport():
    # pull side: the gather sources ARE the shared pull pump
    assert issubclass(gather.StripedGatherSource, transport.StripedPull)
    assert issubclass(gather.RepairGatherSource, transport.StripedPull)
    assert gather.LocalShardReader is transport.LocalShardReader
    assert gather.RemoteShardReader is transport.RemoteShardReader
    # push side: the spread sink IS the shared push pump
    assert issubclass(StripedSpreadSink, transport.StripedPush)
    assert spread.LocalShardWriter is transport.LocalShardWriter
    assert spread.RemoteShardWriter is transport.RemoteShardWriter
    # both sides account into the same stats type, so the
    # ec_transport_* metric family reads either without translation
    assert issubclass(gather.GatherStats, transport.TransportStats)
    assert issubclass(spread.SpreadStats, transport.TransportStats)
    # both window knobs resolve through the shared floor-at-1 parser
    assert gather.gather_window() >= 1
    assert spread.spread_window() >= 1


def test_window_knobs_shared_semantics(monkeypatch):
    for env, fn in ((transport.PULL_WINDOW_ENV, transport.pull_window),
                    (transport.PUSH_WINDOW_ENV, transport.push_window)):
        monkeypatch.delenv(env, raising=False)
        assert fn() == transport.DEFAULT_WINDOW
        monkeypatch.setenv(env, "0")
        assert fn() == 1          # floor, never unbounded-at-zero
        monkeypatch.setenv(env, "junk")
        assert fn() == transport.DEFAULT_WINDOW


# -- injected stall: both sides fail over through the shared path ------------

def test_stall_fails_over_on_both_sides(tmp_path):
    k, m = 6, 3
    (tmp_path / "pull").mkdir()
    base, digests = _seed_shards(tmp_path / "pull", k, m, 60_000)
    dead_h = FakeHolder(str(tmp_path / "pull"))
    live_h = FakeHolder(str(tmp_path / "pull"))
    try:
        dead_h.fail = True
        pull_stats = transport.GatherStats()
        r = transport.RemoteShardReader(
            1, 0, [dead_h.url, live_h.url], pull_stats, hedge_ms=0)
        with open(base + to_ext(0), "rb") as f:
            ref = f.read(4096)
        assert r.read(0, 4096, stripe_idx=0) == ref
        assert pull_stats.retries >= 1
        assert pull_stats.holder_errors.get(dead_h.url, 0) >= 1
    finally:
        dead_h.stop()
        live_h.stop()

    codec = NumpyCodec(k, m)
    src = tmp_path / "push-src"
    src.mkdir()
    pbase, oracle = _seed_oracle(src, codec, k * (16 << 10) * 4)
    ddir, sdir = tmp_path / "push-dead", tmp_path / "push-spare"
    ddir.mkdir()
    sdir.mkdir()
    dead_t, spare_t = FakeTarget(str(ddir)), FakeTarget(str(sdir))
    try:
        dead_t.fail = True
        assignment = {sid: dead_t.url if sid == 7 else LOCAL
                      for sid in range(k + m)}
        push_stats = transport.SpreadStats()
        sink = StripedSpreadSink(1, pbase, assignment, k + m,
                                 local_url=LOCAL, spares=[spare_t.url],
                                 window=2, stats=push_stats,
                                 slab=ENC["slab"])
        write_ec_files_spread(pbase, sink, codec=codec, **ENC)
        assert _digest(os.path.join(str(sdir), f"1{to_ext(7)}")) \
            == oracle[7]
        assert sink.assignment()[7] == spare_t.url
        assert push_stats.failovers >= 1
        assert push_stats.holder_errors.get(dead_t.url, 0) >= 1
    finally:
        dead_t.stop()
        spare_t.stop()


# -- bounded in-flight window on both sides ----------------------------------

@pytest.mark.parametrize("rows_a_slab", [1, 4])
def test_bounded_window_both_sides(tmp_path, rows_a_slab):
    """``rows_a_slab``: the push side's stream hands its sink rows a
    slab wide (one chip) or a quarter of it (the mesh's pieces): the
    window counts bytes, so the bound is the same stripes of slab
    width."""
    window, k, slab, n_stripes = 2, 4, 8 << 10, 12

    class SlowReader:
        remote = False

        def __init__(self):
            self.stats = None
            self.span = None

        def read_into(self, off, n, stripe_idx, dest):
            time.sleep(0.01)
            dest[:] = 0

    pull_stats = transport.GatherStats()
    src = transport.StripedPull([SlowReader() for _ in range(k)],
                                shard_size=slab * n_stripes, slab=slab,
                                window=window, stats=pull_stats)
    total = sum(block.nbytes for _, block in src.slabs())
    assert total == k * slab * n_stripes
    assert pull_stats.peak_buffered <= window * k * slab
    assert pull_stats.peak_buffered < total

    codec = NumpyCodec(k, 2)
    sdir = tmp_path / "src"
    sdir.mkdir()
    base, oracle = _seed_oracle(sdir, codec,
                                k * (16 << 10) * 10 * rows_a_slab)
    tdir = tmp_path / "tgt"
    tdir.mkdir()
    tgt = FakeTarget(str(tdir))
    tgt.delay = 0.02
    try:
        assignment = {sid: tgt.url for sid in range(codec.total)}
        push_stats = transport.SpreadStats()
        push_slab = rows_a_slab * ENC["slab"]
        sink = StripedSpreadSink(1, base, assignment, codec.total,
                                 local_url=LOCAL, window=window,
                                 stats=push_stats, slab=push_slab)
        write_ec_files_spread(base, sink, codec=codec, **ENC)
        # queued + in-hand batch + the stripe being routed — never the
        # whole volume
        assert push_stats.peak_buffered <= \
            (2 * window + 1) * codec.total * push_slab
        assert push_stats.peak_buffered < push_stats.bytes // 2
        assert max(tgt.sizes) <= window * push_slab
        for sid in range(codec.total):
            assert _digest(os.path.join(str(tdir), f"1{to_ext(sid)}")) \
                == oracle[sid]
    finally:
        tgt.stop()


# -- push-side hedging: straggler target raced by a spare --------------------

def test_push_hedge_spare_wins(tmp_path, monkeypatch):
    k, m = 6, 3
    codec = NumpyCodec(k, m)
    src = tmp_path / "src"
    src.mkdir()
    base, oracle = _seed_oracle(src, codec, k * (16 << 10) * 4)
    slow_d, fast_d = tmp_path / "slow", tmp_path / "fast"
    slow_d.mkdir()
    fast_d.mkdir()
    slow, fast = FakeTarget(str(slow_d)), FakeTarget(str(fast_d))
    try:
        slow.delay = 0.6
        monkeypatch.setenv("SW_EC_HEDGE_MS", "60")
        assignment = {sid: slow.url if sid == 8 else LOCAL
                      for sid in range(k + m)}
        stats = transport.SpreadStats()
        sink = StripedSpreadSink(1, base, assignment, k + m,
                                 local_url=LOCAL, spares=[fast.url],
                                 window=2, stats=stats,
                                 slab=ENC["slab"])
        t0 = time.perf_counter()
        write_ec_files_spread(base, sink, codec=codec, **ENC)
        wall = time.perf_counter() - t0
        # the spare won the race and owns the shard from then on
        assert stats.hedges_fired >= 1
        assert stats.hedges_won >= 1
        assert sink.assignment()[8] == fast.url
        assert _digest(os.path.join(str(fast_d), f"1{to_ext(8)}")) \
            == oracle[8]
        # hedged, not waited out: well under the straggler's delay
        # summed over this shard's runs
        assert wall < 2.0
        # loser drain: the straggler's duplicate stage is aborted, not
        # finalized — wait for its in-flight send to finish draining
        from conftest import wait_until
        assert wait_until(
            lambda: not any(f.endswith(to_ext(8))
                            for f in os.listdir(str(slow_d))),
            timeout=5)
    finally:
        slow.stop()
        fast.stop()


# -- PR 41: the push window counts bytes ---------------------------------------
# A lane admits rows while what it holds queued is under ``window`` stripes
# of the sink's ``slab`` a shard, whatever the width of the rows it is
# handed: slab wide (one chip) or a quarter of it (the mesh's pieces).

class GatedWriter(transport.LocalShardWriter):
    """A local shard whose appends wait at a gate: notes when the first
    is there and every run's bytes, and fails on request."""

    def __init__(self, path, gate=None):
        super().__init__(path)
        self.gate = gate or threading.Event()
        self.entered = threading.Event()
        self.fail = False
        self.runs = []

    def send(self, url, off, chunks, link=None):
        self.entered.set()
        assert self.gate.wait(10), "the test never opened the gate"
        if self.fail:
            raise transport.SpreadError("injected lane failure")
        n = super().send(url, off, chunks, link)
        self.runs.append(n)
        return n


def _gated_sink(tmp_path, total, window, slab):
    writers = [GatedWriter(str(tmp_path / f"s{to_ext(i)}"))
               for i in range(total)]
    stats = transport.SpreadStats()
    sink = transport.StripedPush(writers, {None: list(range(total))},
                                 window=window, stats=stats, slab=slab)
    return writers, stats, sink


def _stripes(n, total, w, seed=41):
    return np.random.default_rng(seed).integers(
        0, 256, (n, total, w), dtype=np.uint8)


def _writer_thread(sink, stripes, k, finish=False):
    """Route ``stripes`` on a thread of its own: (thread, how many it
    has routed so far, what it raised)."""
    routed, raised = [], []

    def run():
        try:
            for st in stripes:
                sink.write_stripe(st[:k], st[k:])
                routed.append(1)
            if finish:
                sink.finish()
        except BaseException as e:  # noqa: BLE001 - the test reads it
            raised.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, routed, raised


def _stalled_at(routed, n):
    from conftest import wait_until
    if not wait_until(lambda: len(routed) >= n, timeout=5):
        return False
    time.sleep(0.2)             # four of _put's looks at the window
    return len(routed) == n


def _shard_bytes(stripes, sid):
    return b"".join(st[sid].tobytes() for st in stripes)


@pytest.mark.parametrize("rows_a_slab", [1, 4])
def test_a_lane_admits_a_window_of_bytes(tmp_path, rows_a_slab):
    """Slab-wide rows: the producer blocks at the stripe it always
    blocked at (``window`` queued behind the one in the lane's hand).
    Quarter-slab rows: four times the rows are admitted, the next run
    is the whole window — ``window`` x ``slab`` bytes of the shard —
    and the bytes held stay under the same bound."""
    window, slab = 2, 4096
    w = slab // rows_a_slab
    (gw,), stats, sink = _gated_sink(tmp_path, 1, window, slab)
    stripes = _stripes(6 * window * rows_a_slab, 1, w)
    sink.write_stripe(stripes[0][:1], stripes[0][1:])
    assert gw.entered.wait(5)       # the first row is in the lane's hand
    t, routed, raised = _writer_thread(sink, stripes[1:], 1)
    assert _stalled_at(routed, window * rows_a_slab)
    # in hand + a window queued + the row being routed
    assert sink._buffered == w + window * slab + w
    gw.gate.set()
    t.join(5)
    assert not t.is_alive() and not raised
    sink.finish()
    assert gw.runs[:2] == [w, window * slab]
    assert max(gw.runs) == window * slab
    assert stats.sends == len(gw.runs) and stats.bytes == stripes.size
    assert stats.peak_buffered <= (2 * window + 1) * slab
    assert sink.blocked_s > 0 and sink._buffered == 0
    with open(gw.path, "rb") as f:
        assert f.read() == _shard_bytes(stripes, 0)


@pytest.mark.parametrize("window", [1, 2])
def test_a_row_wider_than_the_window_passes_an_empty_lane(tmp_path,
                                                          window):
    """No width can deadlock: a row that no window has room for is
    admitted when the lane holds nothing, one at a time."""
    slab = 4096
    w = window * slab + slab // 2
    (gw,), stats, sink = _gated_sink(tmp_path, 1, window, slab)
    stripes = _stripes(5, 1, w)
    sink.write_stripe(stripes[0][:1], stripes[0][1:])
    assert gw.entered.wait(5)
    t, routed, raised = _writer_thread(sink, stripes[1:], 1, finish=True)
    # the second is queued behind the one in hand, the third waits
    assert _stalled_at(routed, 1)
    gw.gate.set()
    t.join(5)
    assert not t.is_alive() and not raised
    assert gw.runs == [w] * 5 and stats.peak_buffered == 3 * w
    with open(gw.path, "rb") as f:
        assert f.read() == _shard_bytes(stripes, 0)


@pytest.mark.parametrize("rows_a_slab", [1, 4])
@pytest.mark.parametrize("what", ["finish", "abort", "failure"])
def test_full_windows_hold_up_no_ending(tmp_path, what, rows_a_slab):
    """Every lane's window full to the byte and every holder stuck:
    ``finish()`` queues its sentinels behind them and returns as soon
    as the holders move, ``abort()`` likewise, and a producer waiting
    at a full lane hears of another lane's failure at its next look."""
    window, slab, total = 1, 4096, 2    # a shard a holder and lane
    w = slab // rows_a_slab
    gates = [threading.Event(), threading.Event()]
    writers = [GatedWriter(str(tmp_path / f"s{to_ext(i)}"), gates[i])
               for i in range(total)]
    stats = transport.SpreadStats()
    sink = transport.StripedPush(writers, {"holder-a": [0],
                                           "holder-b": [1]},
                                 window=window, stats=stats, slab=slab)
    stripes = _stripes(2 + window * rows_a_slab, total, w)
    sink.write_stripe(stripes[0][:1], stripes[0][1:])
    assert all(gw.entered.wait(5) for gw in writers)
    for st in stripes[1:-1]:
        sink.write_stripe(st[:1], st[1:])
    assert sink.blocked_s == 0          # all of it fitted, to the byte
    assert [lane._held for lane in sink.workers] == [lane.room for lane
                                                     in sink.workers]
    opener = threading.Timer(0.3, lambda: [g.set() for g in gates])
    t0 = time.perf_counter()
    if what == "finish":
        opener.start()
        sink.finish()
        for i, gw in enumerate(writers):
            assert gw.runs == [w, window * slab]
            with open(gw.path, "rb") as f:
                assert f.read() == _shard_bytes(stripes[:-1], i)
    elif what == "abort":
        opener.start()
        sink.abort()
        assert os.listdir(str(tmp_path)) == []
    else:
        t, routed, raised = _writer_thread(sink, stripes[-1:], 1)
        assert _stalled_at(routed, 0) and t.is_alive()
        writers[1].fail = True
        gates[1].set()                  # lane 1 fails; lane 0 stays stuck
        t.join(2)
        assert not t.is_alive() and not routed
        assert isinstance(raised[0], transport.SpreadError)
        assert not gates[0].is_set()
        opener.start()
        sink.abort()
        assert os.listdir(str(tmp_path)) == []
    assert time.perf_counter() - t0 < 3.0
    assert not [lane for lane in sink.workers if lane.is_alive()]


# -- producer pacing: the tier demotion's MB/s cap ---------------------------

def test_push_rate_cap_paces_producer(tmp_path):
    total, w, n_stripes = 2, 64 << 10, 8
    writers = [transport.LocalShardWriter(
        str(tmp_path / f"s{i}.ec0{i}")) for i in range(total)]
    stats = transport.SpreadStats()
    rate = 2.0  # MB/s; 2 shards * 8 * 64KiB = 1 MiB -> ~0.52s floor
    sink = transport.StripedPush(
        writers, {None: list(range(total))}, window=4, stats=stats,
        rate_mbps=rate)
    rng = np.random.default_rng(5)
    t0 = time.perf_counter()
    for _ in range(n_stripes):
        row = rng.integers(0, 256, (1, w), dtype=np.uint8)
        sink.write_stripe(row, row)
    sink.finish()
    elapsed = time.perf_counter() - t0
    expected = total * n_stripes * w / (rate * 1e6)
    assert elapsed >= 0.8 * expected, \
        f"rate cap not engaged: {elapsed:.3f}s < {expected:.3f}s"
    for i in range(total):
        assert os.path.getsize(str(tmp_path / f"s{i}.ec0{i}")) \
            == n_stripes * w


def test_rate_zero_means_unpaced(tmp_path):
    writers = [transport.LocalShardWriter(str(tmp_path / "s0.ec00"))]
    sink = transport.StripedPush(writers, {None: [0]}, window=4)
    row = np.zeros((1, 4096), dtype=np.uint8)
    t0 = time.perf_counter()
    for _ in range(4):
        sink.write_stripe(row, row[:0])
    sink.finish()
    assert time.perf_counter() - t0 < 1.0


def test_lanes_lose_no_update_of_what_they_share(tmp_path, monkeypatch):
    """Eight lanes of one target (more threads than this box has
    cores) under a shortened switch interval: the target's acknowledged
    bytes, the stats and every stripe's countdown come out exact."""
    import sys
    total, w, n_stripes = 16, 512, 300
    monkeypatch.setattr(transport, "LANES", 8)
    writers = [transport.LocalShardWriter(str(tmp_path / f"s{to_ext(i)}"))
               for i in range(total)]
    stats = transport.SpreadStats()
    done = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        sink = transport.StripedPush(writers, {None: list(range(total))},
                                     window=2, stats=stats)
        rows = np.arange(total * w, dtype=np.uint8).reshape(total, w)
        for i in range(n_stripes):
            sink.write_stripe(rows[:10], rows[10:],
                              done=lambda i=i: done.append(i))
        sink.finish()
    finally:
        sys.setswitchinterval(interval)
    assert not [t for t in sink.workers if t.is_alive()]
    assert len(sink.workers) == 8 == stats.lanes
    assert sink.targets[0].acked == stats.bytes == total * n_stripes * w
    assert sorted(done) == list(range(n_stripes))
    assert sink._buffered == 0
    for i in range(total):
        with open(str(tmp_path / f"s{to_ext(i)}"), "rb") as f:
            assert f.read() == rows[i].tobytes() * n_stripes


# -- pull -> push round trip: bit-identical through both halves --------------

def test_pull_push_roundtrip_bit_identical(tmp_path):
    k, m = 4, 2
    hdir = tmp_path / "holders"
    hdir.mkdir()
    base, digests = _seed_shards(hdir, k, m, 96_000)
    shard_size = os.path.getsize(base + to_ext(0))
    a, b = FakeHolder(str(hdir)), FakeHolder(str(hdir))
    tdir = tmp_path / "targets"
    tdir.mkdir()
    tgt = FakeTarget(str(tdir))
    try:
        # pull all k+m shards through the shared pull pump...
        readers = [transport.RemoteShardReader(1, i, [a.url, b.url],
                                               hedge_ms=0)
                   for i in range(k + m)]
        src = transport.StripedPull(readers, shard_size, slab=16 << 10,
                                    window=3)
        shards = [bytearray() for _ in range(k + m)]
        for (_, off, w), block in src.slabs():
            for i in range(k + m):
                shards[i] += block[i].tobytes()
        # ...and push the identical rows back out through the shared
        # push pump to a fresh holder under a different volume id
        writers = [transport.RemoteShardWriter(2, i) for i in
                   range(k + m)]
        sink = transport.StripedPush(
            writers, {tgt.url: list(range(k + m))}, window=3)
        step = 16 << 10
        for off in range(0, shard_size, step):
            w = min(step, shard_size - off)
            rows = np.stack([np.frombuffer(
                bytes(shards[i][off:off + w]), dtype=np.uint8)
                for i in range(k + m)])
            sink.write_stripe(rows[:k], rows[k:])
        sink.finish()
        for i in range(k + m):
            with open(os.path.join(str(tdir), f"2{to_ext(i)}"),
                      "rb") as f:
                assert hashlib.sha256(f.read()).hexdigest() \
                    == digests[i], f"shard {i} corrupted in transit"
    finally:
        a.stop()
        b.stop()
        tgt.stop()


# -- PR 30: the push side under TLS, and the kept connection's edges ---------

def test_push_over_tls_real_holders(tmp_path):
    """The views-on-a-kept-connection sender and the streaming holder
    under TLS, through a real volume server: shards bit-identical."""
    from seaweedfs_tpu.server.http_util import configure_tls, reset_tls
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from test_aux_subsystems import _make_cert
    cert, key = _make_cert(tmp_path)
    k, m = 6, 3
    codec = NumpyCodec(k, m)
    src = tmp_path / "src"
    src.mkdir()
    base, oracle = _seed_oracle(src, codec, k * (16 << 10) * 6 + 11)
    master = holder = None
    try:
        configure_tls(cert, key)
        master = MasterServer(port=0, pulse_seconds=1).start()
        holder = VolumeServer(
            port=0, directories=[str(tmp_path / "h")],
            master_url=master.url, pulse_seconds=1,
            max_volume_counts=[5], ec_backend="numpy").start()
        remote = (1, 4, 8)
        assignment = {sid: holder.url if sid in remote else LOCAL
                      for sid in range(k + m)}
        stats = {}
        sink = StripedSpreadSink(1, base, assignment, k + m,
                                 local_url=LOCAL, window=2,
                                 slab=ENC["slab"])
        write_ec_files_spread(base, sink, codec=codec, stats=stats,
                              **ENC)
        hdir = holder.store.locations[0].directory
        for sid in range(k + m):
            d = hdir if sid in remote else str(src)
            assert _digest(os.path.join(d, f"1{to_ext(sid)}")) \
                == oracle[sid], f"shard {sid} diverged"
        # one connection a lane and holder: three shards ride two lanes
        assert stats["spread_connects"] == 2
        assert stats["spread_retries"] == 0
    finally:
        for s in (holder, master):
            if s is not None:
                s.stop()
        reset_tls()


def test_stale_kept_connection_is_one_retry(tmp_path):
    """A holder that closed the kept connection between two runs (its
    idle timeout, a restart): the next run fails on it and the
    worker's existing retry opens the next — one more connect, one
    retry, nothing lost or doubled."""
    from seaweedfs_tpu.server.http_util import KeptConnection
    tdir = tmp_path / "t"
    tdir.mkdir()
    tgt = FakeTarget(str(tdir))
    try:
        stats = transport.SpreadStats()
        w = transport.RemoteShardWriter(5, 0, stats=stats)
        link = KeptConnection(tgt.url)
        assert w.send(tgt.url, 0, [memoryview(b"a" * 5000)], link) == 5000
        tgt.server.httpd.close_all_connections()    # the holder's side
        time.sleep(0.05)
        with pytest.raises(Exception):
            w.send(tgt.url, 5000, [memoryview(b"b" * 7000)], link)
        # the sender's retry: same run, same offset, a new connection
        assert w.send(tgt.url, 5000, [memoryview(b"b" * 7000)],
                      link) == 7000
        assert link.connects == 2 and stats.connects == 2
        assert os.path.getsize(
            os.path.join(str(tdir), f"5{to_ext(0)}.part")) == 12000
        link.close()
    finally:
        tgt.stop()


# -- PR 33: the gather's hand-over — a survivor byte lands once --------------

@pytest.fixture
def empty_slab_pool():
    transport._SLAB_POOL.clear()
    yield transport._SLAB_POOL
    transport._SLAB_POOL.clear()


class ShortHolder(FakeHolder):
    """Answers every shard_read with half the bytes asked for, all 0xAA:
    what it sends reaches the reader's row before the read fails."""

    def _shard_read(self, req):
        from seaweedfs_tpu.server.http_util import Response
        with self._lock:
            self.calls += 1
        return Response(b"\xaa" * (int(req.query["size"]) // 2))


def _range_of(base, sid, off, n):
    with open(base + to_ext(sid), "rb") as f:
        f.seek(off)
        return f.read(n)


@pytest.mark.parametrize("mix", ["local", "remote", "mixed"])
def test_a_gathered_block_is_its_readers_bytes(tmp_path, empty_slab_pool,
                                               mix):
    """Every reader fills its own row of a pooled block: the block's
    rows are the shards' ranges, the short last stripe's too, none was
    copied, and a block handed back is the next stripe's memory."""
    k, m, slab = 6, 3, 16 << 10
    base, _ = _seed_shards(tmp_path, k, m, 300_000 + 77)
    shard_size = os.path.getsize(base + to_ext(0))
    assert shard_size % slab          # the last stripe is short
    holder = FakeHolder(str(tmp_path))
    try:
        stats = transport.GatherStats()
        remote = {"local": (), "remote": range(k), "mixed": (1, 3, 4)}[mix]
        readers = [
            transport.RemoteShardReader(1, i, [holder.url], stats,
                                        hedge_ms=0) if i in remote
            else transport.LocalShardReader(base + to_ext(i), stats)
            for i in range(k)]
        src = gather.StripedGatherSource(readers, shard_size, slab=slab,
                                         window=2, stats=stats)
        bases, n = set(), 0
        for (idx, off, w), block in src.slabs():
            assert block.shape == (k, w) and block.flags.c_contiguous
            for i in range(k):
                assert block[i].tobytes() == _range_of(base, i, off, w), \
                    f"stripe {idx} row {i}"
            bases.add(id(block.base))
            transport._give_slab(block)
            n += 1
        assert n == -(-shard_size // slab)
        assert stats.snapshot()["rows_in_place"] == k * n
        assert stats.snapshot()["rows_copied"] == 0
        assert stats.bytes == k * shard_size
        assert stats.fetches == k * n
        assert holder.calls == len(remote) * n
        # handed back as it went: the window's blocks served every stripe
        assert len(bases) <= 2 + 1 < n
    finally:
        holder.stop()


def test_a_hedged_read_is_copied_into_its_row(tmp_path):
    """Where a second holder arms the hedge, both attempts read into
    buffers of their own: the row gets the winner's bytes by a copy, and
    the loser, finishing later, writes nowhere near it."""
    base, _ = _seed_shards(tmp_path, 6, 3, 60_000)
    a, b = FakeHolder(str(tmp_path)), FakeHolder(str(tmp_path))
    try:
        a.delay = 0.4       # the straggler leads stripe 0
        stats = transport.GatherStats()
        r = transport.RemoteShardReader(1, 1, [a.url, b.url], stats,
                                        hedge_ms=50)
        row = np.full(8192, 0xEE, dtype=np.uint8)
        t0 = time.perf_counter()
        r.read_into(0, 8192, 0, row)
        assert time.perf_counter() - t0 < 0.35
        assert row.tobytes() == _range_of(base, 1, 0, 8192)
        assert stats.hedges_won == 1
        snap = stats.snapshot()
        assert snap["rows_copied"] == 1 and snap["rows_in_place"] == 0
        row[:] = 0x55       # the row is the next stripe's now
        deadline = time.time() + 5
        while stats.fetches < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert stats.fetches == 2       # the loser's read ran to its end
        assert bool((row == 0x55).all())
        assert stats.snapshot()["rows_copied"] == 1
    finally:
        a.stop()
        b.stop()


@pytest.mark.parametrize("first", ["refuses", "sends-half"])
def test_a_failed_over_read_fills_the_row(tmp_path, first):
    """One holder a time: the failed attempt has returned, whatever it
    wrote, before the next fills the same row."""
    base, _ = _seed_shards(tmp_path, 6, 3, 60_000)
    bad = FakeHolder(str(tmp_path)) if first == "refuses" \
        else ShortHolder(str(tmp_path))
    bad.fail = True
    live = FakeHolder(str(tmp_path))
    try:
        stats = transport.GatherStats()
        r = transport.RemoteShardReader(1, 2, [bad.url, live.url], stats,
                                        hedge_ms=0)
        row = np.full(4096, 0xEE, dtype=np.uint8)
        r.read_into(0, 4096, 0, row)
        assert row.tobytes() == _range_of(base, 2, 0, 4096)
        assert bad.calls == 1 and stats.retries == 1
        assert stats.holder_errors == {bad.url: 1}
        snap = stats.snapshot()
        assert snap["rows_in_place"] == 1 and snap["rows_copied"] == 0
        assert stats.fetches == 1 and stats.bytes == 4096
        # no holder left: the short read's own error comes out
        if first == "sends-half":
            from seaweedfs_tpu.server.http_util import HttpError
            alone = transport.RemoteShardReader(1, 2, [bad.url], stats,
                                                hedge_ms=0)
            with pytest.raises(HttpError, match="short shard read 1.2 "
                                                "from .* 2048 < 4096"):
                alone.read_into(0, 4096, 0, row)
            assert bool((row[:2048] == 0xAA).all())
    finally:
        bad.stop()
        live.stop()


def test_a_failed_stream_hands_no_block_back(tmp_path, monkeypatch,
                                             empty_slab_pool):
    """A reader raises mid-stream: the blocks the stream still had —
    the failed stripe's, and those being filled behind it — are
    dropped, for reads left in flight may still write into them."""
    k, m, slab = 4, 2, 8 << 10
    base, _ = _seed_shards(tmp_path, k, m, 400_000)
    shard_size = os.path.getsize(base + to_ext(0))
    taken, real_take = [], transport._take_slab

    def noting_take(rows, width, **kw):
        out = real_take(rows, width, **kw)
        taken.append(out.base)
        return out

    class Flaky(transport.LocalShardReader):
        def read_into(self, off, n, stripe_idx, dest):
            if stripe_idx == 3:
                raise IOError("disk went away")
            if stripe_idx > 3:
                time.sleep(0.05)    # still writing when the stream fails
            super().read_into(off, n, stripe_idx, dest)

    monkeypatch.setattr(transport, "_take_slab", noting_take)
    stats = transport.GatherStats()
    readers = [transport.LocalShardReader(base + to_ext(i), stats)
               for i in range(k - 1)] + \
        [Flaky(base + to_ext(k - 1), stats)]
    src = gather.StripedGatherSource(readers, shard_size, slab=slab,
                                     window=3, stats=stats)
    given = []
    with pytest.raises(IOError, match="disk went away"):
        for _, block in src.slabs():
            given.append(block.base)
            transport._give_slab(block)
    assert len(given) == 3
    lost = [b for b in taken if not any(b is g for g in given)]
    assert lost, "the failed stripe's block was taken before its fetches"
    time.sleep(0.2)                     # the reads left in flight end
    assert not [b for b in lost
                if any(b is p for p in empty_slab_pool)]
    assert len(empty_slab_pool) <= 3


def test_read_into_reads_the_body_off_the_socket(tmp_path):
    """http_read_into: the pool's connection kept, the status checked,
    the body's length returned for the caller to hold against what it
    asked for — short, exact or past the buffer."""
    from seaweedfs_tpu.server import http_util as hu
    base, _ = _seed_shards(tmp_path, 4, 2, 60_000)
    holder = FakeHolder(str(tmp_path))
    try:
        hu.clear_conn_pool()
        url = (f"http://{holder.url}/admin/ec/shard_read?volume=1&shard=0"
               f"&offset=100&size=5000")
        before = hu.pool_stats_snapshot()
        for size, want in ((5000, 5000), (8000, 5000), (3000, 5000)):
            buf = np.full(size, 0xEE, dtype=np.uint8)
            assert hu.http_read_into("GET", url, buf) == want
            n = min(size, want)
            assert buf[:n].tobytes() == _range_of(base, 0, 100, 5000)[:n]
            assert bool((buf[n:] == 0xEE).all())
        after = hu.pool_stats_snapshot()
        assert after["created"] - before["created"] == 1
        assert after["reused"] - before["reused"] == 2
        holder.fail = True
        buf = np.full(5000, 0xEE, dtype=np.uint8)
        with pytest.raises(hu.HttpError, match="injected failure") as ei:
            hu.http_read_into("GET", url, buf)
        assert ei.value.status == 503 and bool((buf == 0xEE).all())
    finally:
        hu.clear_conn_pool()
        holder.stop()
