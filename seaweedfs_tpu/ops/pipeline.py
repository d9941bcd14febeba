"""Pipelined host↔device streaming for the TPU EC path.

The reference's encode hot loop (reference ec_encoder.go:192-229) is a
synchronous read→GF→write cycle per 256KB batch. The TPU-first design
(SURVEY hard part #3) overlaps four stages instead:

    disk read (reader thread) → h2d + MXU dispatch (async) → d2h drain →
    shard-file write

JAX dispatch is asynchronous: ``fn(bitmat, dev)`` returns a future-like
device array immediately, so keeping a bounded deque of in-flight slabs
means the device computes slab t+1..t+depth while the host blocks on
fetching slab t's output and writing files. The reader thread overlaps
disk I/O with the rest (the encode's preadv drops the GIL once per row).

PipelinedMatmul computes ``coeffs @ data`` over GF(2^8) for a stream of
data slabs with a fixed coefficient matrix — encode (coeffs = parity
rows) and rebuild (coeffs = fused decode-plan rows vs survivors) both
reduce to this. Only the r output rows round-trip back to the host; for
encode that is m/k of the h2d traffic.

The device kernel is the codec's: the stream runs through
``codec.device_fn()`` — single-chip TpuCodec and the SPMD MeshCodec
(sharded payloads, replicated device-resident coefficients) both
pipeline through the same loop.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Iterator, Optional, Tuple

import numpy as np

from .telemetry import STATS
from ..util import tracing
from ..util.profiling import StageTimer, mirror_stages_to_profiler

_SENTINEL = object()


class PipelinedMatmul:
    """Streams (meta, data (k, w) uint8) slabs through a device GF matmul.

    stream() yields (meta, data, out (r, w)) in input order with up to
    ``depth`` slabs in flight on the device and ``prefetch`` slabs of
    read-ahead in the reader queue.
    """

    def __init__(self, coeffs: np.ndarray, codec,
                 max_width: Optional[int] = 32 << 20, depth: int = 4,
                 prefetch: int = 3, drain_threads: int = 2,
                 timer: Optional[StageTimer] = None,
                 pieces: bool = False):
        if not codec.pipelined:
            raise TypeError(
                f"PipelinedMatmul streams slabs through a device codec's "
                f"device_fn; the {codec.backend!r} codec computes on the "
                f"host and has none — call its encode/reconstruct instead")
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        self.r, self.k = coeffs.shape
        # the widest slab stream() accepts, and the bucket full slabs
        # get; None: any width, each padded to its power-of-two bucket
        self.max_width = None if max_width is None else int(max_width)
        self.depth = int(depth)
        self.prefetch = int(prefetch)
        self.drain_threads = int(drain_threads)
        self.timer = timer  # optional per-stage breakdown (bench/profiling)
        self.codec = codec  # device fn + shardings come from the codec
        # pieces mode: stream() yields (meta, data, [(col_off, piece)])
        # instead of one (r, w) array — mesh-sharded outputs drain one
        # piece per device shard (codec.drain_pieces) so consumers start
        # on the first device's stripes without the host ever staging
        # the full slab; codecs without drain_pieces yield one piece
        self.pieces = bool(pieces)
        self._coeffs = coeffs

    def _bucket(self, width: int) -> int:
        return self.codec.pipeline_width_bucket(width, self.max_width)

    def stream(self, slabs: Iterable[Tuple[object, np.ndarray]]
               ) -> Iterator[Tuple[object, np.ndarray, np.ndarray]]:
        mirror_stages_to_profiler()

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        err: list = []
        stop = threading.Event()

        def produce():
            try:
                for item in slabs:
                    if stop.is_set():
                        break
                    q.put(item)
            except BaseException as e:  # noqa: BLE001 - relay to consumer
                err.append(e)
            finally:
                q.put(_SENTINEL)

        reader = threading.Thread(target=produce, daemon=True,
                                  name="pipeline-producer")
        reader.start()

        # d2h runs in a small pool so fetches start the moment each
        # output is dispatched instead of serializing behind the next
        # dispatch (host↔device links degrade badly when a single thread
        # interleaves uploads and downloads)
        drain_pool = ThreadPoolExecutor(max_workers=self.drain_threads,
                                        thread_name_prefix="pipeline-drain")
        pending: deque = deque()
        timer = self.timer
        drain_pieces = getattr(self.codec, "drain_pieces", None) \
            if self.pieces else None

        def d2h(out, w):
            if drain_pieces is not None:
                return drain_pieces(out, w)
            if self.pieces:
                full = np.asarray(out)
                return [(0, full[:, :w] if full.shape[1] > w else full)]
            return np.asarray(out)

        def fetch(out, nbytes, w):
            if timer is None:
                return d2h(out, w)
            # kernel wait + transfer + host re-layout, on a drain thread
            with timer.stage("d2h+mxu", nbytes, span="ec.d2h"):
                return d2h(out, w)

        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                if timer is not None:
                    timer.add("read_wait", time.perf_counter() - t0)
                if item is _SENTINEL:
                    break
                meta, data = item
                w = data.shape[1]
                if self.max_width is not None and w > self.max_width:
                    raise ValueError(
                        f"slab width {w} exceeds max_width {self.max_width}")
                bucket = self._bucket(w)
                # the span is pad + put; the `h2d` total, which the
                # `dispatch` phase is made of, stays the put alone
                with tracing.Stage(
                        "ec.h2d", timer and timer.root) as up:
                    if w < bucket:
                        padded = np.zeros((self.k, bucket), dtype=np.uint8)
                        padded[:, :w] = data
                    else:
                        padded = data
                    # the codec's kernel for this width (mesh-sharded
                    # program or single-chip kernel), its constant
                    # (uploaded on first use, device-resident after) and
                    # its put: chosen at stream time, once the backend
                    # is known
                    fn, const, put = self.codec.device_fn(self._coeffs,
                                                          bucket)
                    t0 = time.perf_counter()
                    dev = put(padded)                # h2d (blocking copy)
                    if timer is not None:
                        end = time.perf_counter()
                        timer.add("h2d", end - t0, padded.nbytes,
                                  interval=(t0, end))
                    up.nbytes = padded.nbytes
                STATS.add_dispatch(self.codec.geometry, data.nbytes)
                out = fn(const, dev)                 # async dispatch
                fut = drain_pool.submit(fetch, out, self.r * bucket, w)
                pending.append((meta, data, fut, w))
                if len(pending) >= self.depth:
                    yield self._drain(pending.popleft())
            while pending:
                yield self._drain(pending.popleft())
            if err:
                raise err[0]
        finally:
            drain_pool.shutdown(wait=False)
            # stop the reader (at most one more in-flight slab) and
            # unblock it if the consumer bailed early
            stop.set()
            while reader.is_alive():
                try:
                    q.get(timeout=0.1)
                except queue.Empty:
                    pass
            reader.join(timeout=10)

    def _drain(self, entry):
        meta, data, fut, w = entry
        t0 = time.perf_counter()
        full = fut.result()  # blocks until device + d2h complete
        if self.timer is not None:
            self.timer.add("drain_wait", time.perf_counter() - t0)
        if self.pieces:
            return meta, data, full  # already clipped to w by fetch
        if full.shape[1] != w:
            full = full[:, :w]
        return meta, data, full
