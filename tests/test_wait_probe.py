"""What a thread waits for (PR 40): a holder's streamed run split at its
two system calls (tags on the server span, `holder_*` in
`ops/telemetry.STATS`), the probe of the wait for the interpreter lock
(`util/tracing.LockProbe`, `lock_probe_*`, the `process.stall` span), and
the longest interval a stage in `StageTimer`. The served encode + rebuild
whose replies carry `stage_max_s` is `tests/test_stage_spans.py`'s."""

import os
import random
import socket
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import to_ext
from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.server.http_util import KeptConnection
from seaweedfs_tpu.util import tracing
from seaweedfs_tpu.util.profiling import StageTimer

from conftest import wait_until

HOLDER = ("holder_runs", "holder_bytes", "holder_us", "holder_recv_us",
          "holder_write_us", "holder_cpu_us")
APPEND = "POST /admin/ec/shard_write"


@pytest.fixture
def master(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    m = MasterServer(port=0, pulse_seconds=1).start()
    yield m
    m.stop()


def _volume_server(master, path):
    from seaweedfs_tpu.server.volume_server import VolumeServer
    return VolumeServer(port=0, directories=[str(path)],
                        master_url=master.url, pulse_seconds=1,
                        max_volume_counts=[5], ec_backend="numpy")


@pytest.fixture
def holder(master, tmp_path):
    vs = _volume_server(master, tmp_path / "v").start()
    yield vs
    vs.stop()


@pytest.fixture
def appends():
    """The server spans of the holder's appends, as they finish."""
    spans = []

    def keep(span):
        if span["name"] == APPEND:
            spans.append(span)

    tracing.add_finish_hook(keep)
    yield spans
    tracing.remove_finish_hook(keep)


def _moved(before: dict) -> dict:
    now = telemetry.STATS.snapshot()
    return {f: now[f] - before[f] for f in HOLDER}


# -- the holder's run ---------------------------------------------------------

def test_a_streamed_run_is_split_at_its_two_system_calls(
        holder, appends, monkeypatch):
    from seaweedfs_tpu.server import volume_server
    monkeypatch.setattr(volume_server, "SHARD_WRITE_PIECE", 64 << 10)
    rng = np.random.default_rng(40)
    run = rng.integers(0, 256, (5 << 16) + 9, dtype=np.uint8)
    before = telemetry.STATS.snapshot()
    link = KeptConnection(holder.url)
    try:
        link.post_parts("/admin/ec/shard_write?volume=81&collection="
                        "&shard=2&offset=0", [memoryview(run)])
    finally:
        link.close()
    assert wait_until(lambda: len(appends) == 1)
    span = appends[0]
    tags = span["tags"]
    assert tags["bytes"] == run.size and tags["pieces"] == 6
    # both sides were timed, and the interpreter between them is what is
    # left of the span
    assert tags["recv_s"] > 0 and tags["write_s"] > 0
    assert tags["recv_s"] + tags["write_s"] <= span["duration_s"]
    assert 0 <= tags["cpu_s"] <= span["duration_s"]
    moved = _moved(before)
    assert moved["holder_runs"] == 1
    assert moved["holder_bytes"] == run.size
    assert moved["holder_recv_us"] == int(tags["recv_s"] * 1e6)
    assert moved["holder_write_us"] == int(tags["write_s"] * 1e6)
    assert moved["holder_cpu_us"] == int(tags["cpu_s"] * 1e6)
    assert moved["holder_recv_us"] + moved["holder_write_us"] \
        <= moved["holder_us"] <= span["duration_s"] * 1e6
    part = os.path.join(holder.store.locations[0].directory,
                        f"81{to_ext(2)}.part")
    with open(part, "rb") as f:
        assert f.read() == run.tobytes()


def test_a_run_that_ends_short_counts_its_interval_and_what_arrived(
        holder, appends, monkeypatch):
    from seaweedfs_tpu.server import volume_server
    monkeypatch.setattr(volume_server, "SHARD_WRITE_PIECE", 64 << 10)
    body = b"r" * (3 << 16)
    target = "/admin/ec/shard_write?volume=82&collection=&shard=1&offset=0"
    head = (f"POST {target} HTTP/1.1\r\nHost: x\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    before = telemetry.STATS.snapshot()
    host, port = holder.url.split(":")
    with socket.create_connection((host, int(port)), timeout=10) as s:
        # two whole pieces and a part of the third, then the sender dies
        s.sendall(head + body[:(2 << 16) + 1000])
        s.shutdown(socket.SHUT_WR)
        reply = b""
        while chunk := s.recv(65536):
            reply += chunk
    assert reply.startswith(b"HTTP/1.1 400"), reply[:200]
    assert wait_until(lambda: len(appends) == 1)
    tags = appends[0]["tags"]
    # the pieces that arrived whole, none of the one that did not
    assert tags["bytes"] == 2 << 16 and tags["pieces"] == 2
    moved = _moved(before)
    assert moved["holder_runs"] == 1
    assert moved["holder_bytes"] == 2 << 16
    assert moved["holder_us"] > 0
    assert moved["holder_recv_us"] + moved["holder_write_us"] \
        <= moved["holder_us"]
    part = os.path.join(holder.store.locations[0].directory,
                        f"82{to_ext(1)}.part")
    assert os.path.getsize(part) == 0           # rolled back


def test_a_refused_run_counts_nothing(holder, appends):
    from seaweedfs_tpu.server.http_util import HttpError
    link = KeptConnection(holder.url)
    target = "/admin/ec/shard_write?volume=83&collection=&shard=0"
    try:
        link.post_parts(f"{target}&offset=0", [memoryview(b"a" * 1000)])
        before = telemetry.STATS.snapshot()
        with pytest.raises(HttpError) as ei:        # diverged offset
            link.post_parts(f"{target}&offset=10", [memoryview(b"b" * 7)])
        assert ei.value.status == 409
    finally:
        link.close()
    assert wait_until(lambda: len(appends) == 2)
    assert not any(_moved(before).values())
    assert "recv_s" not in appends[1]["tags"]


# -- the probe ----------------------------------------------------------------

class Samples:
    """What a probe counted, kept by the test itself."""

    def __init__(self):
        self.n = 0
        self.elapsed = self.late = 0.0
        self.stalls = []

    def __call__(self, elapsed_s, late_s, stalled):
        self.n += 1
        self.elapsed += elapsed_s
        self.late += late_s
        if stalled:
            self.stalls.append(late_s)


class Script:
    """A clock and a sleep for a probe driven by hand: every sleep moves
    the clock by what was asked for and by the next lateness of the
    script. Nothing of it is real time."""

    def __init__(self, lateness):
        self.now = 1000.0
        self.lateness = list(lateness)
        self.asked = []

    def clock(self):
        return self.now

    def sleep(self, seconds):
        self.asked.append(seconds)
        self.now += seconds + self.lateness.pop(0)


def _scripted(lateness, count=None):
    script = Script(lateness)
    got = count or Samples()
    probe = tracing.LockProbe(got, clock=script.clock, sleep=script.sleep)
    for _ in range(len(script.lateness)):
        probe.sample()
    assert not script.lateness and not probe.thread.is_alive()
    return got, script


@pytest.fixture
def stall_spans():
    spans = []

    def keep(span):
        if span["name"] == "process.stall":
            spans.append(span)

    tracing.add_finish_hook(keep)
    yield spans
    tracing.remove_finish_hook(keep)


def _span_of(stall_spans, late_s):
    """The span that one stall of this test's probe left (a server that
    another test of this worker left running keeps the process's own
    probe up, whose spans are not this test's)."""
    span = min(stall_spans, key=lambda s: abs(s["duration_s"] - late_s))
    assert span["duration_s"] == pytest.approx(late_s, abs=2e-6)
    return span


def test_every_sample_is_a_period_and_its_lateness():
    lateness = [0.0, 0.0004, 0.0031, 0.0, 0.0112]
    got, script = _scripted(lateness)
    assert script.asked == [tracing.PROBE_PERIOD] * 5
    assert got.n == 5 and not got.stalls
    assert got.late == pytest.approx(sum(lateness))
    # the samples tile the probe's time
    assert got.elapsed == pytest.approx(script.now - 1000.0)
    assert got.elapsed == pytest.approx(
        got.n * tracing.PROBE_PERIOD + got.late)


@pytest.mark.parametrize("late_s, stalled", [
    (tracing.PROBE_STALL - 0.001, False),
    (tracing.PROBE_STALL + 0.001, True),
    (0.2071, True),
    (3.5, True),
])
def test_a_sample_later_than_the_limit_is_a_stall_and_a_span(
        stall_spans, late_s, stalled):
    got, _ = _scripted([0.0003, late_s, 0.0])
    assert got.n == 3
    assert got.stalls == pytest.approx([late_s] if stalled else [])
    if not stalled:
        assert not any(abs(s["duration_s"] - late_s) < 1e-5
                       for s in stall_spans)
        return
    span = _span_of(stall_spans, late_s)
    assert span["parent_id"] is None        # a trace of its own
    assert tracing.RING.get(span["trace_id"]) == [span]
    # it ended when the probe woke, so it began when it should have
    assert span["start"] == pytest.approx(time.time() - late_s,
                                               abs=5.0)


def test_a_sleep_that_returns_early_is_not_negative_lateness():
    got, _ = _scripted([-0.001, 0.0])
    assert got.n == 2 and got.late == 0.0
    assert got.elapsed == pytest.approx(2 * tracing.PROBE_PERIOD - 0.001)


def test_scripted_samples_reach_the_telemetry_counters():
    # (the process's own probe may count beside this one: at least)
    before = telemetry.STATS.snapshot()
    _scripted([0.0, 0.002, 0.070, 0.001], telemetry.STATS.add_probe_sample)
    now = telemetry.STATS.snapshot()
    moved = {f: now[f] - before[f] for f in now if f.startswith("lock_probe")}
    assert set(moved) == {
        "lock_probe_samples", "lock_probe_elapsed_us", "lock_probe_late_us",
        "lock_probe_stalls", "lock_probe_stall_us"}
    assert moved["lock_probe_samples"] >= 4
    assert moved["lock_probe_elapsed_us"] >= \
        4 * tracing.PROBE_PERIOD * 1e6 + 72_990
    assert moved["lock_probe_late_us"] >= 72_990
    assert moved["lock_probe_stalls"] >= 1
    assert 69_990 <= moved["lock_probe_stall_us"] \
        <= moved["lock_probe_late_us"]


def _probe_for(seconds: float, meanwhile=None) -> Samples:
    """A real probe on the real clock (the two cases that use it hold it
    to wide limits: a test worker's host is neither idle nor steady)."""
    got = Samples()
    probe = tracing.LockProbe(got)
    probe.thread.start()
    try:
        time.sleep(seconds / 2)
        if meanwhile is not None:
            meanwhile()
        time.sleep(seconds / 2)
    finally:
        probe.stop()
    assert not probe.thread.is_alive()
    return got


def test_the_probe_counts_about_a_sample_a_period_when_idle():
    t0 = time.perf_counter()
    got = _probe_for(0.6)
    wall = time.perf_counter() - t0
    assert got.elapsed == pytest.approx(
        got.n * tracing.PROBE_PERIOD + got.late, rel=1e-6)
    assert 0.5 * wall <= got.elapsed <= wall
    assert 0.3 * wall / tracing.PROBE_PERIOD <= got.n \
        <= wall / tracing.PROBE_PERIOD
    # the mean lateness is the host's wake-up latency, under a period
    assert got.late / got.n < tracing.PROBE_PERIOD
    assert sum(got.stalls) <= got.late


def _hold_the_interpreter(seconds: float):
    """One C call that keeps the interpreter lock for about `seconds`:
    `sorted` over a shuffled list of floats never runs the evaluation
    loop, so no other thread gets a turn until it returns. Sized by a
    trial, returns how long the real call held."""
    rng = random.Random(40)
    trial = [rng.random() for _ in range(200_000)]
    t0 = time.perf_counter()
    sorted(trial)
    per_item = (time.perf_counter() - t0) / len(trial)
    data = [rng.random() for _ in range(int(1.15 * seconds / per_item))]
    held = []

    def hold():
        t0 = time.perf_counter()
        sorted(data)
        held.append(time.perf_counter() - t0)

    return hold, held


def test_a_thread_that_holds_the_interpreter_is_a_stall(stall_spans):
    hold, held = _hold_the_interpreter(0.2)

    def meanwhile():
        t = threading.Thread(target=hold)
        t.start()
        t.join()

    got = _probe_for(0.4, meanwhile)
    assert len(held) == 1 and held[0] > 2 * tracing.PROBE_STALL
    # the probe woke as late as the call was long, less the part of its
    # sleep that was left when the call began (and more where the host
    # stopped the process beside it; a stop elsewhere is a stall more)
    low, high = held[0] - 2 * tracing.PROBE_PERIOD - 0.02, held[0] + 0.15
    mine = [late for late in got.stalls if low <= late <= high]
    assert len(mine) == 1, (got.stalls, held)
    assert _span_of(stall_spans, mine[0])["parent_id"] is None


def test_two_servers_of_a_process_run_one_probe(master, tmp_path,
                                                monkeypatch):
    # servers another test of this worker left running are not this
    # test's: it counts from a process with no probe
    monkeypatch.setattr(tracing, "_probe", None)
    monkeypatch.setattr(tracing, "_probe_users", 0)
    a = _volume_server(master, tmp_path / "a")
    b = _volume_server(master, tmp_path / "b")
    a.start()
    probe = tracing._probe
    assert probe is not None and probe.thread.is_alive()
    assert probe.thread.name == "lock-probe" and probe.thread.daemon
    b.start()
    assert tracing._probe is probe and tracing._probe_users == 2
    before = telemetry.STATS.snapshot()["lock_probe_samples"]
    assert wait_until(lambda: telemetry.STATS.snapshot()[
        "lock_probe_samples"] > before)
    a.stop()
    a.stop()                # a second stop of one server takes no user
    assert tracing._probe is probe and probe.thread.is_alive()
    b.stop()
    assert tracing._probe is None and not probe.thread.is_alive()
    assert probe.thread not in threading.enumerate()


def test_the_probe_series_are_on_the_scrape(holder):
    from seaweedfs_tpu.server.http_util import http_call
    assert wait_until(lambda: telemetry.STATS.snapshot()[
        "lock_probe_samples"] > 0)
    text = http_call("GET", f"http://{holder.url}/metrics").decode()
    for kind in HOLDER + ("lock_probe_samples", "lock_probe_late_us",
                          "lock_probe_stalls", "lock_probe_stall_us"):
        assert ('SeaweedFS_volumeServer_ec_device_telemetry_total'
                f'{{kind="{kind}"}}') in text, kind


# -- the longest interval of a stage ------------------------------------------

def test_stage_timer_keeps_the_longest_interval_a_stage():
    timer = StageTimer()
    for dt in (0.002, 0.009, 0.004):
        timer.add("fetch", dt, interval=(0.0, dt))
    timer.add("write", 0.003)
    with timer.stage("relayout"):
        time.sleep(0.002)
    got = timer.max_s()
    assert got["fetch"] == 0.009 and got["write"] == 0.003
    assert got["relayout"] == round(timer.totals["relayout"], 6) >= 0.002
    assert set(got) == set(timer.totals)
    for stage, longest in got.items():
        assert longest <= timer.totals[stage] + 1e-6
    assert got["fetch"] >= timer.totals["fetch"] / 3
