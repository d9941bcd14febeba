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
import time

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
                                 window=2, stats=push_stats)
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

def test_bounded_window_both_sides(tmp_path):
    window, k, slab, n_stripes = 2, 4, 8 << 10, 12

    class SlowReader:
        remote = False

        def __init__(self):
            self.stats = None
            self.span = None

        def read(self, off, n, stripe_idx=0):
            time.sleep(0.01)
            return bytes(n)

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
    base, _ = _seed_oracle(sdir, codec, k * (16 << 10) * 10)
    tdir = tmp_path / "tgt"
    tdir.mkdir()
    tgt = FakeTarget(str(tdir))
    tgt.delay = 0.02
    try:
        assignment = {sid: tgt.url for sid in range(codec.total)}
        push_stats = transport.SpreadStats()
        sink = StripedSpreadSink(1, base, assignment, codec.total,
                                 local_url=LOCAL, window=window,
                                 stats=push_stats)
        write_ec_files_spread(base, sink, codec=codec, **ENC)
        # queued + in-hand batch + the stripe being routed — never the
        # whole volume
        assert push_stats.peak_buffered <= \
            (2 * window + 1) * codec.total * ENC["slab"]
        assert push_stats.peak_buffered < push_stats.bytes // 2
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
                                 window=2, stats=stats)
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
    import numpy as np
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
    import numpy as np
    row = np.zeros((1, 4096), dtype=np.uint8)
    t0 = time.perf_counter()
    for _ in range(4):
        sink.write_stripe(row, row[:0])
    sink.finish()
    assert time.perf_counter() - t0 < 1.0


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
        import numpy as np
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
                                 local_url=LOCAL, window=2)
        write_ec_files_spread(base, sink, codec=codec, stats=stats,
                              **ENC)
        hdir = holder.store.locations[0].directory
        for sid in range(k + m):
            d = hdir if sid in remote else str(src)
            assert _digest(os.path.join(d, f"1{to_ext(sid)}")) \
                == oracle[sid], f"shard {sid} diverged"
        assert stats["spread_connects"] == 1
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
