"""Byte-rate throttles for background copies (reference
weed/util/throttler.go).

Vacuum/compaction copies gigabytes right next to live reads; the
reference rate-limits those writes with a bytes-per-second budget
(compactionBytePerSecond, weed/storage/volume_vacuum.go:37), and hands
the same flag to the write loop of every file a server copies from
another (volume_grpc_copy.go doCopyFile). Two shapes of it here:
``WriteThrottler`` for one copy loop on one thread, ``ByteBudget`` for
what many threads of one server pull at once. 0 = unthrottled.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from .locks import make_lock


class WriteThrottler:
    """One copy loop's throttle: feed ``maybe_slowdown(n)`` after each
    write and it sleeps whenever the running budget goes negative. An
    instance is ONE thread's (``_budget`` and ``_last`` are unlocked):
    vacuum's two copy loops each build their own for the call. Callers
    on several threads that have to share one rate take a
    ``ByteBudget``."""

    WINDOW = 0.1  # budget granularity, seconds

    def __init__(self, bytes_per_second: int = 0):
        self.bps = int(bytes_per_second)
        self._budget = self.bps * self.WINDOW
        self._last = time.monotonic()

    def maybe_slowdown(self, n: int):
        if self.bps <= 0:
            return
        self._budget -= n
        if self._budget >= 0:
            return
        # refill from elapsed time; sleep off any remaining debt
        now = time.monotonic()
        self._budget += (now - self._last) * self.bps
        self._last = now
        if self._budget < 0:
            debt = -self._budget / self.bps
            slept = min(debt, 2.0)
            time.sleep(slept)
            # the sleep itself must not count as refill time on the
            # next call (that would halve the effective throttle), and
            # debt beyond the 2s cap CARRIES — forgiving it would let a
            # stream of large blobs run at a multiple of the limit
            self._last = time.monotonic()
            self._budget += slept * self.bps


class ByteBudget:
    """One rate for every thread that charges it: a server's budget for
    the bytes it pulls in the background (``-compactionMBps``: a
    rebuild's survivor reads on up to 16 ``ec-pull`` threads a stream,
    every stream the server runs at once, ``volume.copy``, ``ec.copy``).

    ``WriteThrottler``'s semantics, shared: bytes a second, credit
    refilled from the clock, debt carried in full. A caller charges what
    it has received and is told how long it owes (``reserve``), or
    sleeps that off itself (``charge``); the arithmetic runs under one
    lock and nobody sleeps inside it. Credit never exceeds one refill
    ``WINDOW``, so idle seconds bank nothing: whenever a burst starts,
    at most ``WINDOW x rate`` bytes pass free, and by any time ``t``
    after that at most ``rate x t`` more have been let through — a
    charge returns no earlier than the moment the bytes charged up to
    and including it are paid for.

    ``bytes`` and ``wait_s`` count what was charged and what the
    callers were made to wait; ``on_charge(nbytes, wait_s)`` hears of
    each charge (the volume server hands in ops/telemetry's counter)."""

    WINDOW = 0.1  # the most credit an idle budget holds, seconds

    def __init__(self, bytes_per_second: int,
                 on_charge: Optional[Callable[[int, float], None]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        if int(bytes_per_second) <= 0:
            raise ValueError("a budget has a rate; unthrottled is no "
                             "budget at all")
        self.bps = int(bytes_per_second)
        self.bytes = 0
        self.wait_s = 0.0
        self._on_charge = on_charge
        self._clock = clock
        self._sleep = sleep
        self._credit = self.bps * self.WINDOW
        self._last = clock()
        self._lock = make_lock("throttler.ByteBudget._lock")

    def reserve(self, n: int) -> float:
        """Charge ``n`` bytes already received; the seconds the caller
        has to wait before it goes on (0.0: within the budget)."""
        with self._lock:
            now = self._clock()
            self._credit = min(self.bps * self.WINDOW,
                               self._credit + (now - self._last) * self.bps)
            self._last = now
            self._credit -= n
            wait = -self._credit / self.bps if self._credit < 0 else 0.0
            self.bytes += n
            self.wait_s += wait
        if self._on_charge is not None:
            self._on_charge(n, wait)
        return wait

    def charge(self, n: int) -> float:
        """``reserve`` and sleep the wait off on this thread."""
        wait = self.reserve(n)
        if wait > 0:
            self._sleep(wait)
        return wait
