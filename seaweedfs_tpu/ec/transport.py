"""One windowed stripe-transport layer for every EC data mover.

``ec/gather.py`` (rebuild/repair pull) and ``ec/spread.py`` (encode
push) each grew a private copy of the same transport: a bounded
in-flight window with peak-buffer accounting, per-holder rotation +
failover, ``SW_EC_HEDGE_MS`` hedging with loser-drain health
attribution, contiguous-run merging and local fast paths. This module
is that transport, once — a *pull* side (``StripedPull``: stripe
readers fan out over a pool, stripes yield strictly in order) and a
*push* side (``StripedPush``: a target's shards ride on lanes, worker
threads that drain bounded send queues and merge contiguous runs).
Gather, spread, scrub and the tier demotion pipeline are thin clients;
hedging and health routing are therefore available on the write path
too, not just the read path.

Shape of the stream on both sides: a *stripe* is one slab-aligned byte
range ``[off, off+w)`` of every shard. The pull side materializes it as
a ``(k, w)`` uint8 block for the decode; the push side receives it as
``(k, w)`` data + ``(m, w)`` parity rows from the encode. In-flight
memory is O(window * shards * slab) on either side, never O(volume).

Both sides move a shard byte once on the host. The blocks are slabs of
one pool (``_take_slab`` / ``_give_slab``, below): the pull side hands
every reader its row of a stripe's block to fill (``read_into``), the
push side sends views of the encode's rows, and a block goes back to
the pool when its last reader is done with it.

Straggler defenses (shared):
  * rotation: stripe ``s`` leads with holder ``s % len(holders)`` so
    consecutive stripes split across replicas instead of hammering one.
  * failover: a failed pull retries the remaining holders in rotation
    order; a push target that dies before acking any byte hands its
    shard set to a spare and replays from offset 0.
  * hedging (``SW_EC_HEDGE_MS``, default off): a pull past the deadline
    races a duplicate on the next holder; a first push run past the
    deadline races a duplicate stage on a spare target. The loser is
    never cancelled — its response drains in the hedge pool so the
    socket parks back in the keep-alive pool — and the loss is charged
    to the slow holder on the health scoreboard.
  * health routing (``SW_EC_HEALTH_ROUTING``): unhealthy holders sort
    to the back of the pull failover order; the healthiest spare is
    picked first on push failover.
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import deque
from concurrent.futures import (FIRST_COMPLETED, ThreadPoolExecutor,
                                TimeoutError as _FutureTimeout, wait)
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ops.telemetry import STATS
from ..stats import health as _health
from ..util import config, tracing
from ..util.locks import make_lock
from ..util.profiling import StageTimer

DEFAULT_WINDOW = 4
PULL_WINDOW_ENV = "SW_EC_GATHER_WINDOW"
PUSH_WINDOW_ENV = "SW_EC_SPREAD_WINDOW"
HEDGE_MS_ENV = "SW_EC_HEDGE_MS"

_STAGED_RE = re.compile(r"staged=(\d+)")

_SENTINEL = object()


def pull_window() -> int:
    return max(1, config.env_int(PULL_WINDOW_ENV))


def push_window() -> int:
    return max(1, config.env_int(PUSH_WINDOW_ENV))


def default_hedge_ms() -> float:
    return config.env_float(HEDGE_MS_ENV)


# hedged duplicates run here rather than in the mover's own pool: a
# stripe worker submitting back into its (possibly saturated) pool
# could deadlock the window
_HEDGE_POOL: Optional[ThreadPoolExecutor] = None
_HEDGE_LOCK = make_lock("transport._HEDGE_LOCK")


def hedge_pool() -> ThreadPoolExecutor:
    global _HEDGE_POOL
    with _HEDGE_LOCK:
        if _HEDGE_POOL is None:
            _HEDGE_POOL = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="ec-transport-hedge")
        return _HEDGE_POOL


class SpreadError(Exception):
    """A transport operation failed beyond what retry/failover can
    absorb. (Historic name — the push side raised it first; the shared
    layer kept it so existing handlers don't churn.)"""


# ---------------------------------------------------------------------------
# the slab pool: one for both directions

# Stripe-sized host blocks outlive the stream that filled them. glibc
# maps a block as large as a (k, 8 MiB) slab anew at every allocation
# and unmaps it when freed: a page fault per 4 KiB on the way in, a TLB
# shootdown across every core on the way out, and on the v5e hosts
# (VMs) both stall the whole process — fresh slabs cost a fifth of
# encode_mbps there (PERF.md, PR 26). A pool that died with its stream
# recovers little of it: 9 of a GiB volume's 13 slabs are live before
# the first is written. One pool serves the encode's reader
# (ec/encoder._dat_slabs) and the rebuild's gather (StripedPull), which
# never run in one stream, and it holds as many as one stream keeps in
# flight. An encode: the one being read, the pipeline's read-ahead (3)
# and depth (4), the one being written, one in the producer's hand —
# and, since the spread queues views of a slab's rows and not copies of
# them (PR 30), the stripes its lanes have not had acknowledged: a
# window in the slowest lane's queue, a window in its hand, the one
# being routed (19; a lane's window counts the bytes of its own shards'
# rows, a window of slab-wide stripes' worth, so neither more lanes a
# target nor narrower rows hold more). A gather: a window (4)
# the readers are filling, one in the producer's hand, the read-ahead
# (3) and depth (4), the one whose rebuilt rows are being appended: 13
# of the 19.
_SLAB_POOL: "deque[np.ndarray]" = deque(
    maxlen=10 + 2 * DEFAULT_WINDOW + 1)


def _take_slab(k: int, width: int, room: int = 0) -> np.ndarray:
    """A (k, width) uint8 slab with whatever bytes its last user left.
    One the pool could not serve is new memory, counted in
    ops/telemetry (``slab_fresh_bytes``): none once a process has run
    its first volume. ``room``: the bytes of the stream's widest call;
    a new slab is made that large, so that a volume's narrower last
    call leaves the pool nothing the next volume's wide calls must
    drop."""
    n = k * width
    while True:
        try:
            buf = _SLAB_POOL.pop()
        except IndexError:
            buf = np.empty(max(n, room), dtype=np.uint8)
            STATS.add("slab_fresh_bytes", buf.size)
            break
        if buf.size >= n:   # a smaller one served another geometry: dropped
            break
    return buf[:n].reshape(k, width)


def _give_slab(data: np.ndarray):
    """Hand a slab of _take_slab back once nothing reads or writes it
    any more. An encode's: after its stripe's rows are on their
    holders' disks (a spread sends views of them). A gathered stripe's:
    after the decode's output for it has been drained (on the CPU
    backend the device array may alias the host memory until then), or
    after a host re-layout has made its copy. Never earlier; a stream
    that fails or is abandoned hands none of its blocks back, their
    readers may still be writing."""
    _SLAB_POOL.append(data.base)


class TransportStats:
    """Counters + busy-time accounting shared by every endpoint of one
    transport run. Busy time is the UNION of transfer intervals
    (transfers overlap across stripes/rows/targets), so
    ``bytes / busy_s`` is the effective bandwidth, comparable to what a
    serialized copy phase would need. ``stage`` names the role
    ("gather"/"spread"/...) and prefixes the snapshot keys, so one
    class serves both metric families plus the merged ``ec_transport_*``
    export."""

    stage = "transport"

    def __init__(self, budget=None):
        self.timer = StageTimer()
        self._lock = make_lock("transport.TransportStats._lock")
        # the server's budget for what it pulls in the background
        # (util/throttler.ByteBudget, -compactionMBps) where this run is
        # a rebuild's gather: its remote readers charge what they
        # received and wait as told. None (a degraded read, a scrub
        # pass, a server started with no budget): nobody is charged
        self.budget = budget
        self._pace_carry = [0, 0.0]
        self.fetches = 0
        self.sends = 0
        self.connects = 0
        self.lanes = 0
        self.bytes = 0
        self.remote_bytes = 0
        self.hedges_fired = 0
        self.hedges_won = 0
        self.hedges_lost = 0
        self.retries = 0
        self.failovers = 0
        self.stripes = 0
        self.peak_buffered = 0
        self.remote_shards = 0
        self.local_shards = 0
        # per-holder accounting feeds the health scoreboard drill:
        # "routing on issues strictly fewer reads to the slow holder"
        # is only assertable if someone counts transfers per holder
        self.holder_fetches: Dict[str, int] = {}
        self.holder_errors: Dict[str, int] = {}

    def add_fetch(self, nbytes: int, t0: float, t1: float,
                  remote: bool = False, holder: Optional[str] = None):
        self.timer.add(self.stage, t1 - t0, nbytes, interval=(t0, t1))
        with self._lock:
            self.fetches += 1
            self.bytes += nbytes
            if remote:
                self.remote_bytes += nbytes
            if holder:
                self.holder_fetches[holder] = \
                    self.holder_fetches.get(holder, 0) + 1

    def add_send(self, nbytes: int, t0: float, t1: float,
                 holder: Optional[str] = None):
        self.timer.add(self.stage, t1 - t0, nbytes, interval=(t0, t1))
        with self._lock:
            self.sends += 1
            self.bytes += nbytes
            if holder:
                self.holder_fetches[holder] = \
                    self.holder_fetches.get(holder, 0) + 1

    def add_pace(self, nbytes: int, t0: float, t1: float):
        """One wait the budget imposed on a pull thread, after the
        ``nbytes`` it had fetched. Returns the (bytes, seconds) a span
        should carry — this wait and the shorter ones before it that
        left none — or None while they sum to under ``PACE_SPAN_MIN_S``."""
        self.timer.add("pace", t1 - t0, nbytes, interval=(t0, t1))
        with self._lock:
            carry = self._pace_carry
            carry[0] += nbytes
            carry[1] += t1 - t0
            if carry[1] < PACE_SPAN_MIN_S:
                return None
            self._pace_carry = [0, 0.0]
        return tuple(carry)

    def pace_snapshot(self) -> Dict[str, float]:
        """The budget's part in this run, as every rebuild replies it:
        the rate (MiB/s as the flag gives it, 0 with no budget), the
        waits summed over the pull threads and their union, and the most
        the budget would have let through between the run's first fetch
        and its last fetch or wait: the rate over that wall plus the one
        refill window of credit a run may start with."""
        if self.budget is None:
            return {"pace_rate_mbps": 0, "paced_s": 0.0,
                    "paced_wall_s": 0.0, "pace_budget_bytes": 0}
        ivs = [iv for stage in (self.stage, "pace")
               for iv in list(self.timer.intervals.get(stage, ()))]
        wall = max(e for _, e in ivs) - min(s for s, _ in ivs) \
            if ivs else 0.0
        bps = self.budget.bps
        return {"pace_rate_mbps": bps / (1 << 20),
                "paced_s": round(self.timer.totals.get("pace", 0.0), 6),
                "paced_wall_s": round(self.timer.busy_time("pace"), 6),
                "pace_budget_bytes": int(bps * (wall + self.budget.WINDOW))}

    def add_connects(self, n: int):
        """Connections a remote writer opened: one a push lane and
        holder when nothing fails, one more a retry or failover."""
        if n:
            with self._lock:
                self.connects += n

    def add_holder_error(self, holder: str):
        with self._lock:
            self.holder_errors[holder] = \
                self.holder_errors.get(holder, 0) + 1

    def add_hedge_fired(self):
        with self._lock:
            self.hedges_fired += 1

    def add_hedge_won(self):
        with self._lock:
            self.hedges_won += 1

    def add_hedge_lost(self):
        with self._lock:
            self.hedges_lost += 1

    def add_retry(self):
        with self._lock:
            self.retries += 1

    def add_failover(self):
        with self._lock:
            self.failovers += 1

    def busy_s(self) -> float:
        return self.timer.busy_time(self.stage)

    def mbps(self) -> float:
        busy = self.busy_s()
        if busy <= 0:
            return 0.0
        return self.bytes / busy / 1e6

    def snapshot(self) -> Dict[str, float]:
        s = self.stage
        with self._lock:
            return {
                f"{s}_bytes": self.bytes,
                f"{s}_remote_bytes": self.remote_bytes,
                f"{s}_fetches": self.fetches,
                f"{s}_sends": self.sends,
                f"{s}_stripes": self.stripes,
                f"{s}_retries": self.retries,
                f"{s}_failovers": self.failovers,
                f"peak_{s}_buffer": self.peak_buffered,
                "hedges_fired": self.hedges_fired,
                "hedges_won": self.hedges_won,
                "hedges_lost": self.hedges_lost,
                "holder_fetches": dict(self.holder_fetches),
                "holder_errors": dict(self.holder_errors),
            }


class GatherStats(TransportStats):
    """Pull-side role of the shared stats: snapshot keys are
    ``gather_*`` (what ``observe_gather`` and the rebuild/repair stats
    dicts have always carried), beside how a gather's rows reached
    their stripe's block: ``rows_in_place``, written there by the
    reader that fetched them, and ``rows_copied``, fetched into a
    buffer of the attempt's own and copied over (a read that may hedge;
    none where every shard has one holder). Rows a subclass's
    ``_assemble`` re-lays from the readers' buffers count under
    neither."""

    stage = "gather"

    def __init__(self, budget=None):
        super().__init__(budget)
        self.rows_in_place = 0
        self.rows_copied = 0

    def add_row(self, copied: bool = False):
        with self._lock:
            if copied:
                self.rows_copied += 1
            else:
                self.rows_in_place += 1

    def snapshot(self) -> Dict[str, float]:
        out = super().snapshot()
        out["rows_in_place"] = self.rows_in_place
        out["rows_copied"] = self.rows_copied
        out.update(self.pace_snapshot())
        return out

    def overlap(self, stream_s: float, gather_wait_s: float) -> dict:
        """How far a streaming rebuild's gather hid behind its compute,
        as every rebuild's stats report it: the gather's busy union,
        the stream's wall less the consumer's wait for stripes, and the
        clamped serialized-against-wall estimate."""
        gather_busy = self.busy_s()
        compute_busy = max(stream_s - gather_wait_s, 0.0)
        serialized = gather_busy + compute_busy
        overlap = 0.0
        if serialized > 0:
            overlap = max(0.0, min(1.0,
                                   (serialized - stream_s) / serialized))
        return {"gather_busy_s": round(gather_busy, 3),
                "compute_busy_s": round(compute_busy, 3),
                "overlap_frac": round(overlap, 4),
                "gather_mbps": round(self.mbps(), 1),
                "gather_remote_shards": self.remote_shards}


class SpreadStats(TransportStats):
    """Push-side role of the shared stats: snapshot keys are
    ``spread_*`` (what ``observe_spread`` and the encode stats dicts
    have always carried). ``spread_send_s`` is the SUM of the send
    intervals whose union is the busy time: over ``spread_busy_s`` it
    is the mean number of runs in flight while the spread is busy.
    ``spread_lanes``: the lanes that carried at least one run."""

    stage = "spread"

    def send_s(self) -> float:
        return self.timer.totals.get(self.stage, 0.0)

    def snapshot(self) -> Dict[str, float]:
        out = super().snapshot()
        out["spread_connects"] = self.connects
        out["spread_lanes"] = self.lanes
        out["spread_send_s"] = round(self.send_s(), 3)
        return out


# ---------------------------------------------------------------------------
# pull side: stripe readers

# one span per survivor range read, on the thread that made it (an
# `ec-pull` worker; a hedged duplicate on the hedge pool), from the
# interval the reader takes for its stats: `.remote` / `.local` for a
# full range, `.plane.remote|local` for a piggyback half-plane and
# `.trace.remote|local` for projected trace bits (ec/gather.py), so a
# reader of one name never averages a full range with a part of one
FETCH_SPAN = "ec.rebuild.fetch"
# one span per wait the server's budget imposed on a pull thread (tags
# `bytes`: what the thread had fetched, `thread`), under the rebuild's
# root; a wait under a millisecond rides in the next one's
PACE_SPAN = "ec.rebuild.pace"
PACE_SPAN_MIN_S = 0.001


class LocalShardReader:
    """Range reads of a shard already on this node's disk. Opens per
    call — the pull pool reads several stripes of one shard
    concurrently, and a shared seek pointer would race; a descriptor
    kept for the reader's life would have to outlive the reads an
    abandoned stream leaves in flight. ``read_into`` reads at a
    position (``os.preadv``: no seek), straight into the row it is
    handed."""

    remote = False
    fetch_span = FETCH_SPAN + ".local"
    span = None          # set by StripedPull: trace parent

    def __init__(self, path: str, stats: Optional[TransportStats] = None):
        self.path = path
        self.stats = stats or GatherStats()

    def read(self, off: int, n: int, stripe_idx: int = 0) -> bytes:
        with tracing.Stage(self.fetch_span, self.span) as st:
            with open(self.path, "rb") as f:
                f.seek(off)
                data = f.read(n)
            if len(data) != n:
                raise IOError(f"short read of {self.path} at {off}: "
                              f"{len(data)} < {n}")
            st.nbytes = n
        self.stats.add_fetch(n, st.t0, st.t1)
        return data

    def read_into(self, off: int, n: int, stripe_idx: int,
                  dest: np.ndarray):
        """The shard's ``[off, off + n)`` into ``dest`` (n writable
        bytes: the reader's row of the stripe's block)."""
        with tracing.Stage(self.fetch_span, self.span) as st:
            fd = os.open(self.path, os.O_RDONLY)
            try:
                got = 0
                while got < n:
                    step = os.preadv(fd, [dest[got:]], off + got)
                    if step <= 0:
                        raise IOError(f"short read of {self.path} at "
                                      f"{off}: {got} < {n}")
                    got += step
            finally:
                os.close(fd)
            st.nbytes = n
        self.stats.add_fetch(n, st.t0, st.t1)
        self.stats.add_row()


class RemoteShardReader:
    """Ranged reads of one shard from its holder set, with round-robin
    striping, failover retries and optional hedging."""

    remote = True
    fetch_span = FETCH_SPAN + ".remote"

    def __init__(self, vid: int, sid: int, holders: Sequence[str],
                 stats: Optional[TransportStats] = None,
                 timeout: float = 300.0,
                 hedge_ms: Optional[float] = None):
        if not holders:
            raise ValueError(f"shard {vid}.{sid}: no holders")
        self.vid = vid
        self.sid = sid
        self.holders = list(holders)
        self.stats = stats or GatherStats()
        self.span = None     # set by StripedPull: trace parent
        self.timeout = timeout
        self.hedge_s = (default_hedge_ms() if hedge_ms is None
                        else float(hedge_ms)) / 1000.0

    # transport hooks — RemoteRepairReader overrides to hit the
    # projected-read route with a different method/response size while
    # inheriting rotation, failover and hedging unchanged
    _method = "GET"
    # health-scoreboard latency kind for fetches issued by this reader
    _health_kind = "shard_read"

    def _url(self, holder: str, off: int, n: int) -> str:
        return (f"http://{holder}/admin/ec/shard_read?volume={self.vid}"
                f"&shard={self.sid}&offset={off}&size={n}")

    def _expect_len(self, n: int) -> int:
        """Response bytes expected for an n-byte shard range."""
        return n

    def _read_one(self, holder: str, off: int, n: int,
                  dest: Optional[np.ndarray] = None):
        """One attempt at one holder: the body as ``bytes``, or read
        off the socket into ``dest`` where one is given."""
        from ..server.http_util import HttpError, http_call, \
            http_read_into
        # pool/hedge worker threads don't inherit the tracing
        # contextvar — carry the caller span's traceparent explicitly
        # so the holders' shard_read spans join the caller's trace
        hdrs = None
        if self.span is not None:
            hdrs = {tracing.TRACEPARENT_HEADER: self.span.traceparent()}
        expect = self._expect_len(n)
        url = self._url(holder, off, n)
        with tracing.Stage(self.fetch_span, self.span) as st:
            try:
                if dest is None:
                    data = http_call(self._method, url, headers=hdrs,
                                     timeout=self.timeout)
                    got = len(data)
                else:
                    data = None
                    got = http_read_into(self._method, url, dest,
                                         headers=hdrs,
                                         timeout=self.timeout)
                if got != expect:
                    raise HttpError(
                        502, f"short shard read {self.vid}.{self.sid} "
                             f"from {holder} at {off}: "
                             f"{got} < {expect}")
            except Exception:
                self.stats.add_holder_error(holder)
                _health.BOARD.record_error(holder, self._health_kind)
                raise
            st.nbytes = got
        self.stats.add_fetch(got, st.t0, st.t1, remote=True,
                             holder=holder)
        _health.BOARD.record_latency(holder, self._health_kind,
                                     st.t1 - st.t0)
        if self.stats.budget is not None:
            self._pace(got)
        return data

    def _pace(self, nbytes: int):
        """What crossed the socket is charged to the server's budget,
        after it arrived, on the thread that fetched it (a hedged read:
        both attempts), and the thread waits as long as it is told: a
        paced fetch is a slow fetch, and the window, the decode and the
        writer hide under it as they do under a slow holder. The wait is
        no part of the fetch's span or of the holder's latency."""
        wait = self.stats.budget.reserve(nbytes)
        if wait <= 0:
            return
        t0 = time.perf_counter()
        time.sleep(wait)
        spanned = self.stats.add_pace(nbytes, t0, time.perf_counter())
        if spanned is not None and self.span is not None:
            tracing.record_span(
                PACE_SPAN, spanned[1], parent=self.span,
                bytes=spanned[0],
                thread=threading.current_thread().name)

    def _read_failover(self, order: Sequence[str], off: int, n: int,
                       dest: Optional[np.ndarray] = None):
        """The holders of ``order`` in turn until one answers. A failed
        attempt has returned before the next begins, so they may share
        ``dest``."""
        last = None
        for i, holder in enumerate(order):
            if i:
                self.stats.add_retry()
            try:
                return self._read_one(holder, off, n, dest)
            except Exception as e:  # noqa: BLE001 - try the next holder
                last = e
        raise last

    def _attribute_hedge_loss(self, loser_future, loser: str,
                              winner: str):
        """The race is decided: whenever the losing duplicate finishes
        draining (maybe much later), charge the loss to the losing
        holder.  The loser's full latency is recorded by its own
        _read_one when the drained duplicate completes — the timing
        that used to be discarded — so the callback only needs to add
        the hedge-loss attribution."""
        self.stats.add_hedge_lost()

        def _done(_f):
            _health.BOARD.record_hedge_loss(loser, winner)

        loser_future.add_done_callback(_done)

    def _order(self, stripe_idx: int) -> List[str]:
        h = self.holders
        # rotation both spreads load (consecutive stripes of a
        # replicated shard split across its holders) and fixes the
        # failover/hedge order for this stripe
        order = [h[(stripe_idx + j) % len(h)] for j in range(len(h))]
        if len(order) > 1 and _health.routing_enabled():
            # demote unhealthy holders to the back of the failover /
            # hedge order (stable within each class, so the rotation's
            # load-spreading survives among healthy peers)
            order = _health.BOARD.order_by_health(order)
        return order

    def _may_hedge(self, order: Sequence[str]) -> bool:
        return self.hedge_s > 0 and len(order) >= 2

    def read(self, off: int, n: int, stripe_idx: int = 0) -> bytes:
        order = self._order(stripe_idx)
        if not self._may_hedge(order):
            return self._read_failover(order, off, n)
        return self._read_hedged(order, off, n)

    def read_into(self, off: int, n: int, stripe_idx: int,
                  dest: np.ndarray):
        """The range into ``dest`` (as many writable bytes as the
        holder answers with: the reader's row of the stripe's block).
        Two writers never share a row: where a second holder arms the
        hedge, every attempt reads into a buffer of its own — the loser
        of a race finishes whenever it does — and the winner's is
        copied over."""
        order = self._order(stripe_idx)
        if not self._may_hedge(order):
            self._read_failover(order, off, n, dest)
            self.stats.add_row()
            return
        dest[:] = np.frombuffer(self._read_hedged(order, off, n),
                                dtype=np.uint8)
        self.stats.add_row(copied=True)

    def _read_hedged(self, order: Sequence[str], off: int,
                     n: int) -> bytes:
        ex = hedge_pool()
        primary = ex.submit(self._read_one, order[0], off, n)
        try:
            return primary.result(timeout=self.hedge_s)
        except _FutureTimeout:
            pass
        except Exception:  # noqa: BLE001 - fast failure: plain failover
            self.stats.add_retry()
            return self._read_failover(order[1:], off, n)
        # leading holder is past the hedge deadline: race a duplicate on
        # the next holder; first success wins, the loser drains its
        # response body in the pool thread and its socket goes back to
        # the connection pool
        self.stats.add_hedge_fired()
        secondary = ex.submit(self._read_one, order[1], off, n)
        pending = {primary, secondary}
        last = None
        while pending:
            done, pending = wait(pending, return_when=FIRST_COMPLETED)
            for f in done:
                err = f.exception()
                if err is None:
                    if f is secondary:
                        self.stats.add_hedge_won()
                        self._attribute_hedge_loss(
                            primary, order[0], order[1])
                    else:
                        self._attribute_hedge_loss(
                            secondary, order[1], order[0])
                    return f.result()
                last = err
        if len(order) > 2:
            self.stats.add_retry()
            return self._read_failover(order[2:], off, n)
        raise last


class StripedPull:
    """The pull pump: ``slabs()`` yields ``(meta, block)`` stripes in
    strict order, fetching up to ``window`` stripes ahead across a
    shared thread pool. ``readers`` are per-row endpoints (local files
    and remote holders mixed freely).

    A stripe's block comes from the slab pool (``_take_slab``) before
    its fetches are submitted, and every reader is handed the row its
    bytes belong in (``read_into``): a fetched byte is written once, by
    the thread that fetched it, and when the stripe's futures are done
    the block *is* the stripe. Whoever consumes the stream hands each
    block back (``_give_slab``) once nothing reads it; a stream that
    fails or is abandoned drops the blocks still with it — reads left
    in flight by ``shutdown(wait=False)`` may yet write into them.

    Subclasses reshape the stream via the ``_stripe_nbytes`` /
    ``_block_shape`` / ``_assemble`` hooks without touching the
    window/pool/ordering machinery; one whose readers' bytes have to be
    re-laid (``lands_in_place`` false) gets them as buffers
    (``read``) and writes its block in ``_assemble``."""

    span_name = "gather.stripe"
    span_op = "ec.rebuild.gather"
    # one span per stripe around ``_assemble``, on the thread that
    # iterates `slabs()` (the pipeline's producer): bookkeeping where
    # the readers filled the block, the re-layout where a subclass does
    # one
    assemble_span = "ec.rebuild.assemble"
    # the readers write their rows of the block themselves
    lands_in_place = True

    def __init__(self, readers: Sequence, shard_size: int,
                 slab: int = 8 << 20, window: Optional[int] = None,
                 stats: Optional[TransportStats] = None,
                 parent_span=None):
        if not readers:
            raise ValueError("no survivor readers")
        self.readers = list(readers)
        self.shard_size = int(shard_size)
        self.slab = max(1, int(slab))
        self.window = max(1, int(window) if window else pull_window())
        self.stats = stats if stats is not None else GatherStats()
        self.parent_span = parent_span
        for r in self.readers:
            r.stats = self.stats
            r.span = parent_span
        self.stats.remote_shards = sum(
            1 for r in self.readers if getattr(r, "remote", False))
        self.stats.local_shards = len(self.readers) - \
            self.stats.remote_shards
        self._buffered = 0
        self._lock = make_lock("transport.StripedPull._lock")

    def _note_buffered(self, delta: int):
        with self._lock:
            self._buffered += delta
            if self._buffered > self.stats.peak_buffered:
                self.stats.peak_buffered = self._buffered

    # stream-shape hooks
    def _stripe_nbytes(self, w: int) -> int:
        """Buffered bytes one in-flight stripe accounts for."""
        return len(self.readers) * w

    def _block_shape(self, w: int) -> Tuple[int, int]:
        """(rows, columns) of the block a ``w``-byte stripe becomes."""
        return len(self.readers), w

    def _assemble(self, block: np.ndarray, bufs: List, w: int
                  ) -> np.ndarray:
        """The stripe's block, complete. ``bufs`` is what the readers'
        ``read`` returned, for a subclass to lay into ``block``; here
        the readers have filled their rows and nothing is left to do."""
        return block

    def _take_block(self, w: int) -> np.ndarray:
        rows, cols = self._block_shape(w)
        full = self._block_shape(min(self.slab, self.shard_size))
        return _take_slab(rows, cols, room=full[0] * full[1])

    def _fetch(self, r: int, off: int, w: int, idx: int,
               block: Optional[np.ndarray]):
        if block is None:
            return self.readers[r].read(off, w, idx)
        return self.readers[r].read_into(off, w, idx, block[r])

    def slabs(self):
        k = len(self.readers)
        stripes: List[Tuple[int, int]] = [
            (off, min(self.slab, self.shard_size - off))
            for off in range(0, self.shard_size, self.slab)]
        self.stats.stripes = len(stripes)
        if not stripes:
            return
        workers = min(16, max(2, min(self.window, len(stripes)) * k))
        pool = ThreadPoolExecutor(max_workers=workers,
                                  thread_name_prefix="ec-pull")
        pending: deque = deque()

        def submit(idx: int):
            off, w = stripes[idx]
            # account BEFORE the fetches start: in-flight rows are
            # buffered memory too, and the bound must hold even when
            # every submitted row completes before the consumer drains
            self._note_buffered(self._stripe_nbytes(w))
            t_sub = time.perf_counter()
            block = self._take_block(w) if self.lands_in_place else None
            futs = [pool.submit(self._fetch, r, off, w, idx, block)
                    for r in range(k)]
            pending.append((idx, off, w, t_sub, block, futs))

        try:
            nxt = 0
            while nxt < len(stripes) and len(pending) < self.window:
                submit(nxt)
                nxt += 1
            while pending:
                idx, off, w, t_sub, block, futs = pending.popleft()
                bufs = [f.result() for f in futs]
                with tracing.Stage(self.assemble_span,
                                   self.parent_span) as st:
                    if block is None:
                        block = self._take_block(w)
                    data = self._assemble(block, bufs, w)
                    st.nbytes = data.nbytes
                bufs = None     # a subclass's fetched buffers: let go
                tracing.record_span(
                    self.span_name, time.perf_counter() - t_sub,
                    parent=self.parent_span, op=self.span_op,
                    stripe=idx, offset=off,
                    bytes=self._stripe_nbytes(w))
                self._note_buffered(-self._stripe_nbytes(w))
                if nxt < len(stripes):
                    submit(nxt)
                    nxt += 1
                yield (idx, off, w), data
        finally:
            # blocks still pending are dropped, never handed back
            pool.shutdown(wait=False, cancel_futures=True)


# ---------------------------------------------------------------------------
# push side: stripe writers


class LocalShardWriter:
    """Fast path for shards this node keeps: append to the local
    ``.part`` stage file, atomic-rename on finalize — the same
    no-partial-shards contract the remote protocol gives."""

    remote = False

    def __init__(self, path: str,
                 stats: Optional[TransportStats] = None):
        self.path = path
        self.part = path + ".part"
        self.stats = stats or SpreadStats()
        self.span = None
        self._f = None

    def send(self, url: Optional[str], off: int, chunks: Sequence,
             link=None) -> int:
        """Append one run: ``chunks`` are buffers (row views of the
        encode's slabs), written as they lie."""
        t0 = time.perf_counter()
        if self._f is None:
            self._f = open(self.part, "wb" if off == 0 else "ab")
        if self._f.tell() != off:
            raise SpreadError(
                f"local shard write offset mismatch for {self.path}: "
                f"staged={self._f.tell()} offset={off}")
        n = 0
        for c in chunks:
            self._f.write(c)
            n += len(c)
        self.stats.add_send(n, t0, time.perf_counter())
        return n

    def finalize(self, url: Optional[str], size: int):
        if self._f is not None:
            self._f.close()
            self._f = None
        staged = os.path.getsize(self.part) if os.path.exists(self.part) \
            else -1
        if staged != size:
            raise SpreadError(
                f"local shard {self.path}: staged {staged} != {size}")
        os.replace(self.part, self.path)

    def abort(self, url: Optional[str]):
        if self._f is not None:
            self._f.close()
            self._f = None
        for p in (self.part,):
            try:
                os.remove(p)
            except OSError:
                pass


class RemoteShardWriter:
    """Pushes one shard's slab ranges to its holder: each run of
    contiguous chunks goes out as ONE POST to ``/admin/ec/shard_write``
    (append-at-expected-offset, 409 on mismatch) whose body is the
    chunks themselves — row views of the encode's slabs under a
    Content-Length, written to the socket as they lie — on the
    connection its push lane keeps to that holder (``link``, an
    http_util.KeptConnection; without one, a connection for this run
    alone: the hedged duplicates). It carries the caller span's
    traceparent so the holder's spans join the trace. Every send feeds
    the health scoreboard under the ``shard_write`` kind — the push
    path sees slow holders with the same eyes the pull path does."""

    remote = True
    _health_kind = "shard_write"

    def __init__(self, vid: int, sid: int, collection: str = "",
                 stats: Optional[TransportStats] = None,
                 timeout: float = 300.0):
        self.vid = vid
        self.sid = sid
        self.collection = collection
        self.stats = stats or SpreadStats()
        self.span = None     # set by StripedPush: trace parent
        self.timeout = timeout

    def _target(self, query: str) -> str:
        return (f"/admin/ec/shard_write?volume={self.vid}"
                f"&collection={self.collection}&shard={self.sid}&{query}")

    def _url(self, holder: str, query: str) -> str:
        return f"http://{holder}{self._target(query)}"

    def _headers(self) -> Optional[dict]:
        # the lanes' threads don't inherit the tracing contextvar —
        # carry the caller span's traceparent explicitly
        if self.span is None:
            return None
        return {tracing.TRACEPARENT_HEADER: self.span.traceparent()}

    def send(self, url: str, off: int, chunks: Sequence,
             link=None) -> int:
        from ..server.http_util import HttpError, KeptConnection
        n = sum(len(c) for c in chunks)
        once = link is None
        if once:
            link = KeptConnection(url, timeout=self.timeout)
        opened = link.connects
        t0 = time.perf_counter()
        try:
            link.post_parts(self._target(f"offset={off}"), chunks,
                            headers=self._headers())
        except HttpError as e:
            if e.status == 409:
                # the holder's staged size disagrees; if it already
                # covers this run the previous delivery merely lost its
                # ack — don't re-append, don't fail
                m = _STAGED_RE.search(str(e))
                if m and int(m.group(1)) == off + n:
                    self.stats.add_send(n, t0, time.perf_counter(),
                                        holder=url)
                    return n
            self.stats.add_holder_error(url)
            _health.BOARD.record_error(url, self._health_kind)
            raise
        except Exception:
            self.stats.add_holder_error(url)
            _health.BOARD.record_error(url, self._health_kind)
            raise
        finally:
            self.stats.add_connects(link.connects - opened)
            if once:
                link.close()
        t1 = time.perf_counter()
        self.stats.add_send(n, t0, t1, holder=url)
        _health.BOARD.record_latency(url, self._health_kind, t1 - t0)
        return n

    def finalize(self, url: str, size: int):
        from ..server.http_util import http_call
        http_call("POST",
                  self._url(url, f"action=finalize&size={size}"),
                  headers=self._headers(), timeout=self.timeout)

    def abort(self, url: str):
        from ..server.http_util import http_call
        try:
            http_call("POST", self._url(url, "action=abort"),
                      headers=self._headers(), timeout=30.0)
        except Exception:  # noqa: BLE001 - best-effort cleanup
            pass


# lanes a target's shards are divided between (a target with fewer
# shards has a lane a shard). Two: while a holder's handler writes one
# lane's run to its ``.part`` the other lane's run arrives, and the
# sender's steps between an acknowledgement and the next request run
# beside a send. A lane a shard was tried on the chip host and kept
# out (PERF.md, PR 39).
LANES = 2


class PushTarget:
    """One holder of a push and what its lanes share: the url, so that
    failover and the first-run hedge (re-assigning every shard of a
    dead or slow target to a spare) stay a single-variable swap
    whichever lane makes it, and the bytes it has acknowledged. The
    target's FIRST run goes out on one lane alone — whichever claims it
    — and only that run may be hedged or failed over; the other lanes
    send once it is acknowledged, to whichever holder won, so a dead
    holder's shards never land on two spares."""

    def __init__(self, url: Optional[str], sids: Sequence[int]):
        self.url = url
        self.sids = list(sids)
        self.acked = 0
        self._lock = make_lock("transport.PushTarget._lock")
        self._claimed = False
        self.opened = threading.Event()     # the first run is in

    def claim_first_run(self) -> bool:
        """True for the one lane that sends the target's first run."""
        with self._lock:
            first, self._claimed = not self._claimed, True
        return first

    def add_acked(self, n: int):
        with self._lock:
            self.acked += n
        self.opened.set()


class TargetWorker(threading.Thread):
    """One lane of a target: drains its bounded send queue — pops
    queued ``(sid, off, chunk, stripe)`` items of the shards it
    carries, merges per-shard contiguous runs, and sends each run as
    one POST on the connection it keeps to the target's holder. A
    shard's rows always ride the same lane, so its runs reach the
    holder in ascending offsets on one connection. A chunk is a view of
    its stripe's rows, held until the run it went out in is
    acknowledged; then the stripe hears of it
    (``StripedPush._row_done``). The url and the acknowledged bytes are
    the target's (``PushTarget``), shared with its other lanes. The
    FIRST run to a remote target may be hedged: past the
    ``SW_EC_HEDGE_MS`` deadline the same run races a duplicate stage on
    a spare, the first ack wins the target's shard set, and the loser's
    stage is aborted once its send drains."""

    def __init__(self, sink: "StripedPush", target: PushTarget,
                 lane: int, sids: List[int], shard_room: int):
        name = target.url or "local"
        super().__init__(daemon=True, name=f"ec-push-{name}-{lane}")
        self.sink = sink
        self.target = target
        self.lane = lane
        self.sids = list(sids)
        # the lane's window, in bytes: what it may hold queued, and so
        # the most a drained batch carries (``shard_room`` a shard)
        self.room = shard_room * len(sids)
        self._queued: list = []     # rows and sentinels, in order
        self._held = 0              # bytes of the rows in _queued
        self._cv = threading.Condition(
            make_lock("transport.TargetWorker._cv"))
        self.runs = 0
        self.error: Optional[BaseException] = None
        self._opened = False  # this lane may send: the first run is in
        self._link = None    # the kept connection, and to which holder

    def link(self):
        """The connection this lane keeps to its holder: opened by the
        first run, closed by a failed one (the retry opens the next)
        and when the holder changes."""
        from ..server.http_util import KeptConnection
        url = self.target.url
        if url is None:
            return None
        if self._link is None or self._link.netloc != url:
            self._close_link()
            self._link = KeptConnection(url)
        return self._link

    def _close_link(self):
        link, self._link = self._link, None
        if link is not None:
            link.close()

    def offer(self, item, nbytes: int = 0,
              timeout: Optional[float] = None) -> bool:
        """Queue ``item``, a row of ``nbytes`` bytes, once the window
        has room for it or the lane holds nothing (so no width can
        deadlock); False when ``timeout`` passed first. A sentinel
        weighs nothing and is never held up."""
        with self._cv:
            if nbytes and not self._cv.wait_for(
                    lambda: not self._held
                    or self._held + nbytes <= self.room, timeout):
                return False
            self._queued.append(item)
            self._held += nbytes
            self._cv.notify_all()
        return True

    def _drain(self, timeout: float) -> list:
        """Everything queued, in order, once there is anything (else
        [] after ``timeout``): a window's bytes at most, by what
        ``offer`` admits."""
        with self._cv:
            self._cv.wait_for(lambda: self._queued, timeout)
            batch, self._queued, self._held = self._queued, [], 0
            self._cv.notify_all()
        return batch

    def _wait_turn(self) -> bool:
        """Hold this lane's first run until the target's first run —
        another lane's, unless this one claims it — is acknowledged.
        False: the spread failed meanwhile."""
        if self.target.claim_first_run():
            return True
        while not self.target.opened.wait(0.05):
            if self.sink.failed is not None:
                return False
        return True

    def run(self):
        try:
            stop = False
            while not stop:
                batch = self._drain(0.1)
                if not batch:
                    if self.sink.failed is not None:
                        return
                    continue
                for i, item in enumerate(batch):
                    if item is _SENTINEL:
                        stop = True
                        del batch[i:]
                        break
                if not batch:
                    break
                if not self._opened:
                    self._opened = self._wait_turn()
                if self.sink.failed is not None:
                    return      # another lane's failure ended the spread
                # one stage per drained batch (span ``ec.spread.send``):
                # its merged runs go out back to back on this thread,
                # to the holder it names
                with tracing.Stage(self.sink.send_span,
                                   self.sink.parent_span,
                                   target=self.target.url or "local",
                                   lane=self.lane) as st:
                    for sid, off, chunks, stripes in merge_runs(batch):
                        n = self._send_run(sid, off, chunks)
                        self.runs += 1
                        for chunk, stripe in zip(chunks, stripes):
                            self.sink._row_done(stripe, len(chunk))
                        st.nbytes += n
                batch = None
        except BaseException as e:  # noqa: BLE001 - surfaced to consumer
            self.error = e
            self.sink._fail(e)
        finally:
            self._close_link()

    def _send_run(self, sid: int, off: int, chunks) -> int:
        writer = self.sink.writers[sid]
        target = self.target
        n = sum(len(c) for c in chunks)
        if (self.sink.hedge_s > 0 and target.url is not None
                and target.acked == 0 and off == 0):
            if self._send_run_hedged(writer, off, chunks, n):
                return n
        while True:
            last = None
            for attempt in range(2):
                if attempt:
                    self.sink.stats.add_retry()
                try:
                    writer.send(target.url, off, chunks, self.link())
                    target.add_acked(n)
                    return n
                except BaseException as e:  # noqa: BLE001 - retry/failover
                    last = e
            if target.acked > 0 or off != 0 or target.url is None:
                # bytes already landed on this target (or it's the local
                # disk): the dead holder's prefix is unreplayable — the
                # stripe stream never kept it. Abort; the caller falls
                # back to the copy flow.
                raise last
            spare = self.sink._take_spare(target.url)
            if spare is None:
                raise last
            dead, target.url = target.url, spare
            self.sink.stats.add_failover()
            writer.abort(dead)

    def _send_run_hedged(self, writer, off: int, chunks,
                         n: int) -> bool:
        """Hedge the first run of this target: if the leading holder
        has not acked within the deadline, race the same run against a
        spare's stage. Returns True when the run landed (possibly after
        swapping the target's url to the winning spare); False hands the
        run to the plain retry/failover path — a duplicate re-send is
        safe because the holder's 409 ``staged=`` reply identifies a
        delivered-but-unacked run."""
        target = self.target
        ex = hedge_pool()
        primary = ex.submit(writer.send, target.url, off, chunks)
        try:
            primary.result(timeout=self.sink.hedge_s)
            target.add_acked(n)
            return True
        except _FutureTimeout:
            pass
        except Exception:  # noqa: BLE001 - fast failure: plain failover
            return False
        spare = self.sink._take_spare(target.url)
        if spare is None:
            # no rival to race: wait the slow send out
            try:
                primary.result()
            except Exception:  # noqa: BLE001 - plain path owns retries
                return False
            target.add_acked(n)
            return True
        self.sink.stats.add_hedge_fired()
        secondary = ex.submit(writer.send, spare, off, chunks)
        pending = {primary, secondary}
        while pending:
            done, pending = wait(pending, return_when=FIRST_COMPLETED)
            for f in done:
                if f.exception() is not None:
                    continue
                self.sink.stats.add_hedge_lost()
                if f is secondary:
                    # the spare won: it owns the target's shard set
                    # (every lane's) from here on; the slow holder's
                    # stage is aborted once its duplicate drains (the
                    # send is idempotent there — nothing else
                    # references the stage)
                    slow, target.url = target.url, spare
                    self.sink.stats.add_hedge_won()
                    self.sink.stats.add_failover()
                    _health.BOARD.record_hedge_loss(slow, spare)
                    primary.add_done_callback(
                        lambda _f, dead=slow: writer.abort(dead))
                else:
                    _health.BOARD.record_hedge_loss(spare, target.url)

                    def _cleanup(_f, spare=spare):
                        writer.abort(spare)
                        self.sink._return_spare(spare)

                    secondary.add_done_callback(_cleanup)
                target.add_acked(n)
                return True
        # both failed: the plain path retries and fails over; give the
        # consumed spare back first so failover can still reach it
        self.sink._return_spare(spare)
        return False


def merge_runs(batch):
    """Merge a drained batch of ``(sid, off, chunk, stripe)`` items into
    per-shard contiguous runs ``(sid, off, [chunks], [stripes])``,
    preserving per-shard order (queue order is stripe order, so each
    shard's offsets arrive ascending and contiguous)."""
    runs = []          # [sid, start_off, [chunks], [stripes], next_off]
    open_run: Dict[int, list] = {}
    for sid, off, chunk, stripe in batch:
        run = open_run.get(sid)
        if run is not None and run[4] == off:
            run[2].append(chunk)
            run[3].append(stripe)
            run[4] += len(chunk)
        else:
            run = [sid, off, [chunk], [stripe], off + len(chunk)]
            runs.append(run)
            open_run[sid] = run
    return [tuple(run[:4]) for run in runs]


class StripedPush:
    """The push pump: ``write_stripe`` routes each shard row of the
    arriving stripe to the bounded send queue of its shard's lane; the
    lanes push the ranges while the producer makes the next stripes. A
    target's shards are divided between ``LANES`` lanes in shard order,
    alternately (5 -> 3 + 2, 3 -> 2 + 1, a lone shard one lane), each a
    worker thread with a queue and a kept connection of its own, so a
    holder serves a target's runs on as many handler threads and one
    run's file write overlaps another's arrival; the local target is
    laned the same way. A lane's window is ``window`` stripes of
    ``slab`` width (the stream's own) of its own shards, counted in
    bytes: what is outstanding grows neither with the lanes nor where
    a stream hands its stripes over in narrower pieces (the mesh drains
    a dispatch a device), and a run is as long there as anywhere.
    Subclasses build the ``writers`` list (one endpoint per shard) and
    the ``by_target`` grouping; everything else — window accounting,
    blocked-time, failover spares, hedging, finalize/abort discipline,
    optional MB/s pacing — lives here."""

    # one span per batch a lane drains from its queue and sends as
    # merged runs (TargetWorker.run), tagged with target and lane
    send_span = "ec.spread.send"
    # finish(): the wait for every lane to drain and join, then the
    # finalize of every shard — the tail of an encode after its last
    # stripe is queued
    finish_span = "ec.spread.finish"
    finalize_span = "ec.spread.finalize"

    def __init__(self, writers: List, by_target: Dict[Optional[str],
                                                      List[int]],
                 spares: Optional[Sequence[str]] = None,
                 window: Optional[int] = None,
                 stats: Optional[TransportStats] = None,
                 parent_span=None, hedge_ms: Optional[float] = None,
                 rate_mbps: float = 0.0, slab: int = 8 << 20):
        self.total = len(writers)
        self.window = max(1, int(window) if window else push_window())
        self.slab = int(slab)
        self.stats = stats if stats is not None else SpreadStats()
        self.parent_span = parent_span
        self.hedge_s = (default_hedge_ms() if hedge_ms is None
                        else float(hedge_ms)) / 1000.0
        # producer-side MB/s ceiling (tier demotions under live
        # traffic): same discipline as the scrub's pacing — sleep the
        # producer so cumulative pushed bytes stay under the cap
        self.rate_mbps = float(rate_mbps or 0.0)
        self._rate_t0 = None
        self._rate_bytes = 0
        self.offset = 0
        self.failed: Optional[BaseException] = None
        self._spares = [s for s in (spares or []) if s]
        self._lock = make_lock("transport.StripedPush._lock")
        self._buffered = 0
        self.writers = list(writers)
        for w in self.writers:
            w.stats = self.stats
            w.span = parent_span
        self.stats.remote_shards = sum(
            1 for w in self.writers if w.remote)
        self.stats.local_shards = self.total - self.stats.remote_shards
        self.targets = [PushTarget(url, sids)
                        for url, sids in by_target.items()]
        self.workers: List[TargetWorker] = []
        self._target_of: Dict[int, PushTarget] = {}
        self._worker_of: Dict[int, TargetWorker] = {}
        for t in self.targets:
            lanes = min(LANES, len(t.sids))
            for lane in range(lanes):
                w = TargetWorker(self, t, lane, t.sids[lane::lanes],
                                 self.window * self.slab)
                self.workers.append(w)
                for sid in w.sids:
                    self._worker_of[sid] = w
            for sid in t.sids:
                self._target_of[sid] = t
        self.blocked_s = 0.0     # producer time lost to full windows
        for w in self.workers:
            w.start()

    # -- shared bookkeeping -------------------------------------------------
    def _note_buffered(self, delta: int):
        with self._lock:
            self._buffered += delta
            if self._buffered > self.stats.peak_buffered:
                self.stats.peak_buffered = self._buffered

    def _fail(self, e: BaseException):
        with self._lock:
            if self.failed is None:
                self.failed = e

    def _take_spare(self, dead: Optional[str]) -> Optional[str]:
        with self._lock:
            cands = self._spares
            if len(cands) > 1 and _health.routing_enabled():
                # healthiest spare first — a failover onto the next
                # struggling holder just moves the stall
                cands = _health.BOARD.order_by_health(list(cands))
            for s in cands:
                if s != dead:
                    self._spares.remove(s)
                    return s
        return None

    def _return_spare(self, url: str):
        with self._lock:
            if url and url not in self._spares:
                self._spares.append(url)

    def assignment(self) -> Dict[int, str]:
        """Final shard placement (post-failover): sid -> holder url,
        '' for shards kept locally."""
        return {sid: (self._target_of[sid].url or "")
                for sid in range(self.total)}

    def _put(self, worker: TargetWorker, item, nbytes: int = 0):
        t0 = time.perf_counter()
        waited = False
        while True:
            if self.failed is not None:
                raise SpreadError(
                    f"shard spread failed: {self.failed!r}") \
                    from self.failed
            if worker.offer(item, nbytes, timeout=0.05):
                break
            waited = True
        if waited:
            self.blocked_s += time.perf_counter() - t0

    def _pace(self, nbytes: int):
        """Hold the producer under ``rate_mbps``: sleep until the
        cumulative pushed bytes fit the elapsed-time budget. Pacing the
        producer (not the workers) keeps the whole pipeline — encode
        compute included — at the cap, which is the point of running a
        demotion under live traffic. One producer's pacing of what it
        SENDS, set by the caller of one push (the tierer's rate): not
        the server's budget for what it pulls (-compactionMBps), which
        sits in the puller's loop as upstream's throttle does."""
        if self.rate_mbps <= 0:
            return
        now = time.perf_counter()
        if self._rate_t0 is None:
            self._rate_t0 = now
        self._rate_bytes += nbytes
        need = self._rate_bytes / (self.rate_mbps * 1e6)
        # sleep until the cumulative budget is caught up — in slices,
        # so a coarse stripe (few big slabs) still honors the cap
        # instead of charging at most one bounded sleep per stripe
        while True:
            spent = time.perf_counter() - self._rate_t0
            if need <= spent:
                break
            time.sleep(min(need - spent, 0.25))

    # -- the stream ---------------------------------------------------------
    def write_stripe(self, data, parity, done=None):
        """Route one stripe: row i of ``data``/``parity`` is the next
        ``w`` bytes of shard i / shard k+i, queued on its shard's lane.
        The rows are queued as views, not copies: the stripe's arrays
        belong to the sink until every row's run is acknowledged, and
        ``done()`` is called then (from a lane's thread) — the caller's
        leave to write into them again. A stripe of a failed spread is
        never done."""
        k = data.shape[0]
        w = data.shape[1]
        off = self.offset
        stripe = [self.total, done]     # rows still unacknowledged
        stripe_bytes = 0
        for sid in range(self.total):
            row = memoryview(
                data[sid] if sid < k else parity[sid - k]).cast("B")
            stripe_bytes += len(row)
            self._note_buffered(len(row))
            self._put(self._worker_of[sid], (sid, off, row, stripe),
                      len(row))
        self.offset = off + w
        with self._lock:
            self.stats.stripes += 1
        self._pace(stripe_bytes)

    def _row_done(self, stripe: list, nbytes: int):
        """One row of ``stripe`` is on its holder's disk."""
        with self._lock:
            self._buffered -= nbytes
            stripe[0] -= 1
            last = stripe[0] == 0
        if last and stripe[1] is not None:
            stripe[1]()

    def finish(self):
        """Drain every window, join every lane of every target, then
        finalize all shards (atomic ``.part`` -> shard rename on every
        holder, in shard order). Raises if any push or finalize failed:
        no shard is renamed unless every lane ended clean."""
        with tracing.Stage(self.finish_span, self.parent_span) as st:
            for w in self.workers:
                self._put(w, _SENTINEL)
            for w in self.workers:
                w.join()
        self.blocked_s += st.t1 - st.t0
        self.stats.lanes = sum(1 for w in self.workers if w.runs)
        if self.failed is not None:
            raise SpreadError(
                f"shard spread failed: {self.failed!r}") from self.failed
        with tracing.Stage(self.finalize_span, self.parent_span):
            for sid in range(self.total):
                self.writers[sid].finalize(self._target_of[sid].url,
                                           self.offset)

    def abort(self):
        """Stop the lanes and leave no partial shards: best-effort
        ``.part`` cleanup on every holder and on the local disk."""
        self._fail(SpreadError("spread aborted"))
        for w in self.workers:
            w.offer(_SENTINEL)
        for w in self.workers:
            w.join(timeout=10.0)
        for sid in range(self.total):
            try:
                self.writers[sid].abort(self._target_of[sid].url)
            except Exception:  # noqa: BLE001 - best-effort cleanup
                pass
