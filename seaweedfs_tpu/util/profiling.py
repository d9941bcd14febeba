"""Profiling hooks (reference §5.1 analog: weed/util/pprof.go).

The reference wires Go pprof behind -cpuprofile/-memprofile flags
(reference weed/command/volume.go:71-72, weed/util/pprof.go). The TPU
build's equivalents:

  * ``SamplingProfiler`` — an all-thread stack sampler for the servers
    (cProfile only sees the calling thread, useless for a threaded
    server): samples ``sys._current_frames()`` on an interval and dumps
    a collapsed-stack report (flamegraph.pl / speedscope compatible).
    Wired behind ``-cpuprofile`` on the server CLIs and
    ``POST /admin/profile``. Off unless asked for.
  * ``StageTimer`` — the EC streams' per-stage totals, busy unions and
    longest intervals; always on.
"""

from __future__ import annotations

import contextlib
import threading
from . import tracing
from .locks import make_lock
import time
from typing import Dict, List, Optional, Tuple


def mirror_stages_to_profiler():
    """Called where JAX is already imported (the pipelined stream): from
    then on every stage span also opens a `jax.profiler.TraceAnnotation`
    `sw:<name>` on its thread — a flag test while no trace is running."""
    from jax.profiler import TraceAnnotation
    tracing.set_stage_mirror(TraceAnnotation)


class SamplingProfiler:
    """All-thread wall-clock stack sampler.

    A daemon thread snapshots every thread's Python stack
    (``sys._current_frames()``) every ``interval`` seconds and counts
    collapsed stacks. ``stop()`` writes one ``frame;frame;... count``
    line per distinct stack — the folded format flamegraph.pl and
    speedscope ingest directly. Overhead is one GIL-held walk per
    sample (~10-50us), fine at the default 10ms period. A stack says
    where a thread stands, not whether it holds the interpreter lock or
    waits for it: that is ``tracing.LockProbe``'s to say.
    """

    def __init__(self, path: Optional[str], interval: float = 0.01):
        self.path = path
        self.interval = float(interval)
        self.counts: Dict[str, int] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="sampling-profiler")

    def start(self) -> "SamplingProfiler":
        self._thread.start()
        return self

    def _run(self):
        import sys
        me = threading.get_ident()
        while not self._stop.wait(self.interval):
            for tid, top in sys._current_frames().items():
                if tid == me:
                    continue
                frames = []
                f = top
                while f is not None and len(frames) < 64:
                    code = f.f_code
                    frames.append(
                        f"{code.co_name} "
                        f"({code.co_filename.rsplit('/', 1)[-1]}:"
                        f"{f.f_lineno})")
                    f = f.f_back
                key = ";".join(reversed(frames))
                self.counts[key] = self.counts.get(key, 0) + 1

    def report(self) -> str:
        """Collapsed-stack text (``frame;frame;... count`` per line,
        hottest first) from the samples gathered so far."""
        return "".join(
            f"{stack} {n}\n"
            for stack, n in sorted(self.counts.items(),
                                   key=lambda kv: -kv[1]))

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)
        if self.path:
            with open(self.path, "w") as out:
                out.write(self.report())

    @classmethod
    def run_for(cls, seconds: float,
                interval: float = 0.01) -> str:
        """Sample every thread for ``seconds`` and return the collapsed
        stacks — the `POST /admin/profile` path, no file involved."""
        prof = cls(None, interval=interval).start()
        try:
            time.sleep(max(0.0, float(seconds)))
        finally:
            prof.stop()
        return prof.report()


class StageTimer:
    """Accumulates wall time per named stage plus timestamped intervals
    for stages whose concurrency matters (d2h drains overlap each other;
    the interesting figure is the union of their busy windows, which is
    the link's effective busy time).

    Handed a ``root`` span (the stream's: ``ec.encode.stream`` /
    ``ec.rebuild.stream``), the intervals taken through ``stage`` also
    leave as real spans under it, on the thread that did the work
    (``tracing.Stage``). Without a root nothing of that runs."""

    def __init__(self, root: Optional[tracing.Span] = None):
        self.root = root
        self.totals: Dict[str, float] = {}
        self.maxes: Dict[str, float] = {}
        self.bytes: Dict[str, int] = {}
        self.intervals: Dict[str, List[Tuple[float, float]]] = {}
        self._lock = make_lock("profiling._lock")  # stages report from worker threads

    def add(self, stage: str, dt: float, nbytes: int = 0,
            interval: Optional[Tuple[float, float]] = None):
        with self._lock:
            self.totals[stage] = self.totals.get(stage, 0.0) + dt
            if dt > self.maxes.get(stage, 0.0):
                self.maxes[stage] = dt
            if nbytes:
                self.bytes[stage] = self.bytes.get(stage, 0) + nbytes
            if interval is not None:
                self.intervals.setdefault(stage, []).append(interval)

    @contextlib.contextmanager
    def stage(self, name: str, nbytes: int = 0,
              span: Optional[str] = None):
        """``with timer.stage("h2d", n, span="ec.h2d") as st:`` — the
        block's interval into the totals under ``name`` and, where the
        timer has a root, out as the span ``span``: one
        ``tracing.Stage``, one pair of clock reads for both. ``nbytes``
        may be set on the yielded stage before the block ends."""
        st = tracing.Stage(span or name, self.root if span else None)
        st.nbytes = nbytes
        try:
            with st:
                yield st
        finally:
            self.add(name, st.t1 - st.t0, st.nbytes,
                     interval=(st.t0, st.t1))

    def max_s(self) -> Dict[str, float]:
        """The longest single interval of each stage: the one slow fetch
        or drain that a stage's total averages away (a reply's
        ``stage_max_s``)."""
        with self._lock:
            return {stage: round(dt, 6)
                    for stage, dt in self.maxes.items()}

    def busy_time(self, stage: str) -> float:
        """Union length of the stage's intervals (overlaps collapsed)."""
        ivs = sorted(self.intervals.get(stage, []))
        total, cur_start, cur_end = 0.0, None, None
        for s, e in ivs:
            if cur_end is None or s > cur_end:
                if cur_end is not None:
                    total += cur_end - cur_start
                cur_start, cur_end = s, e
            else:
                cur_end = max(cur_end, e)
        if cur_end is not None:
            total += cur_end - cur_start
        return total
