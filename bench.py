"""Benchmark: RS(10,4) EC encode, TPU vs native CPU (BASELINE.md).

The HEADLINE (value/vs_baseline of the one JSON line) is
`device_kernel_chained`: the chained-slope device kernel rate (>=3
chain lengths of serially-dependent encodes in one dispatch,
least-squares slope with R^2/deviation diagnostics, so the fixed
per-dispatch cost cancels) against the native CPU in-memory encode. The
end-to-end run (disk + h2d + MXU + d2h + shard writes, all 14 shard
files sha256-compared against the CPU path) reports as context under
"e2e". The device phases need an accelerator: where JAX computes on the
CPU the run stops with a non-zero exit and emits no line. No phase is
caught and carried past — a failed drill fails the run.

Prints ONE JSON line:
  {"metric": "ec_encode_rs10_4_mbps", "value": <MB/s>, "unit": "MB/s",
   "vs_baseline": <value / cpu denominator>, "headline_kind": ...}

Env knobs: SW_BENCH_DAT_MB (volume size, default 4096),
SW_BENCH_SLAB_MB (device slab per shard row, default 8),
SW_BENCH_TRIALS (best-of trials per timed pass, default 2),
SW_BENCH_DIR (workdir).
BASELINE configs 3-5 scale via SW_BENCH_GEO_MB (RS(6,3)/RS(20,4)
volume size, default 256; device figures are chained-slope too),
SW_BENCH_SMALL_VOLS/SW_BENCH_SMALL_NEEDLES (batched 4KB-needle
volumes, default 4 x 8192), SW_BENCH_CLUSTER_MB/
SW_BENCH_CLUSTER_SERVERS (live-cluster ec.rebuild with the MESH
backend: on an 8-device virtual CPU mesh in a subprocess, and on the
chip in this process; gather/compute phase fractions reported).

This file predates the chip the builders now have; its cells, medians
and bounds are the benchmark PR's to define (ROADMAP speed item 1).
"""

import hashlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from seaweedfs_tpu.ops.rs_native import native_available  # noqa: E402
from seaweedfs_tpu.util import config  # noqa: E402

K, M = 10, 4
TOTAL = K + M
TRIALS = config.env_int("SW_BENCH_TRIALS")


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def generate_dat(path: str, size_mb: int) -> int:
    """Write size_mb MB of deterministic pseudo-random bytes, streamed."""
    rng = np.random.default_rng(0)
    chunk = 128 << 20
    total = size_mb << 20
    t = time.perf_counter()
    with open(path, "wb") as f:
        written = 0
        while written < total:
            n = min(chunk, total - written)
            f.write(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
            written += n
    log(f"generated {size_mb}MB .dat in {time.perf_counter() - t:.1f}s")
    return total


def shard_digests(base: str) -> list:
    from seaweedfs_tpu.ec import to_ext
    from seaweedfs_tpu.util import file_sha256
    out = []
    for i in range(TOTAL):
        with open(base + to_ext(i), "rb") as f:
            out.append(file_sha256(f))
    return out


def remove_shards(base: str, ids=range(TOTAL)):
    from seaweedfs_tpu.ec import to_ext
    for i in ids:
        p = base + to_ext(i)
        if os.path.exists(p):
            os.remove(p)


def measure_cpu_e2e(base: str, dat_size: int) -> float:
    """End-to-end native encode. Slab 1MB: the native path is fastest when
    rows fit in LLC (the reference streams 256KB buffers for the same
    reason), so the denominator gets its best configuration."""
    from seaweedfs_tpu.ec import write_ec_files
    from seaweedfs_tpu.ops.codec import get_codec
    backend = "native" if native_available() else "numpy"
    codec = get_codec(K, M, backend=backend)  # native: all hw threads
    best = 0.0
    for trial in range(TRIALS):
        os.sync()  # settle writeback so each trial starts clean
        t = time.perf_counter()
        write_ec_files(base, codec=codec, slab=1 << 20, pipelined=False)
        dt = time.perf_counter() - t
        best = max(best, dat_size / dt / 1e6)
        log(f"cpu[{backend}] e2e encode trial {trial}: "
            f"{dat_size / dt / 1e6:.0f} MB/s ({dt:.1f}s)")
    return best


def require_accelerator():
    """First device touch. The device phases measure the chip: where JAX
    computes on the CPU they do not run, and the bench exits non-zero
    without emitting a line. A backend-init error propagates."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        log("FATAL: JAX computes on the CPU; the device phases of "
            "bench.py need an accelerator (no CPU number is emitted "
            "in a device metric's place)")
        raise SystemExit(2)
    return devices


def measure_tpu_e2e(base: str, dat_size: int, slab_mb: int):
    """Returns (best MB/s, stage dict of the best trial). Each trial logs
    a per-stage breakdown and the pipeline efficiency against the
    host<->device transfer bound measured *inside* that trial (effective
    h2d / d2h rates over the stages' busy windows)."""
    from seaweedfs_tpu.ec import write_ec_files
    from seaweedfs_tpu.ops.rs_tpu import TpuCodec
    from seaweedfs_tpu.util.profiling import StageTimer, maybe_trace
    codec = TpuCodec(K, M)
    # warm the compile cache for every power-of-two bucket the coalesced
    # stream can hit (steady-state batches are exactly slab wide; the tail
    # batch is a smaller multiple of the 1MB small block) so no JIT
    # compile lands inside the timed region
    from seaweedfs_tpu.ops.pipeline import PipelinedMatmul
    warm = PipelinedMatmul(codec.matrix[K:], max_width=slab_mb << 20)
    widths, w = [], slab_mb << 20
    while w >= 1 << 20:
        widths.append(w)
        w >>= 1
    list(warm.stream(iter(
        [(0, np.zeros((K, wi), dtype=np.uint8)) for wi in widths])))
    best, best_stages = 0.0, {}
    for trial in range(TRIALS):
        os.sync()  # settle prior-pass writeback so timing starts clean
        timer = StageTimer()
        t = time.perf_counter()
        with maybe_trace(f"tpu_e2e_encode_t{trial}"):
            write_ec_files(base, codec=codec, slab=slab_mb << 20,
                           pipelined=True, timer=timer)
        dt = time.perf_counter() - t
        mbps = dat_size / dt / 1e6
        log(f"tpu e2e encode trial {trial} (disk+h2d+mxu+d2h+write): "
            f"{mbps:.0f} MB/s ({dt:.1f}s, "
            f"{slab_mb}MB coalesced batches per device call)")
        log(f"  stages: {timer.summary()}")
        h2d_eff = timer.rate_mbps("h2d", use_busy=True)
        d2h_eff = timer.rate_mbps("d2h+mxu", use_busy=True)
        stages = {
            "h2d_eff_mbps": round(h2d_eff, 1),
            "d2h_eff_mbps": round(d2h_eff, 1),
            "d2h_busy_frac": round(timer.busy_time("d2h+mxu") / dt, 2),
            "disk_read_mbps": round(timer.rate_mbps("disk_read", True), 1),
            "shard_write_mbps": round(
                timer.rate_mbps("shard_write", True), 1),
        }
        if h2d_eff and d2h_eff:
            bound = min(h2d_eff, d2h_eff / (M / K))
            stages["in_run_transfer_bound_mbps"] = round(bound, 1)
            stages["e2e_vs_transfer_bound"] = round(mbps / bound, 2)
            log(f"  in-run transfer bound min(h2d, d2h/{M / K}) = "
                f"{bound:.0f} MB/s -> e2e at {mbps / bound:.0%} of bound")
        if mbps > best:
            best, best_stages = mbps, stages
    return best, best_stages


def _measure_rebuild(base: str, dat_size: int, codec, label: str,
                     seed: int, slab: int, pipelined: bool) -> float:
    """Shared BASELINE-config-2 harness: drop M seeded-random shards,
    rebuild with the given codec, digest-verify, report MB/s of volume
    bytes."""
    import random
    from seaweedfs_tpu.ec import rebuild_ec_files
    before = shard_digests(base)
    dropped = sorted(random.Random(seed).sample(range(TOTAL), M))
    remove_shards(base, dropped)
    t = time.perf_counter()
    rebuilt = rebuild_ec_files(base, codec=codec, slab=slab,
                               pipelined=pipelined)
    dt = time.perf_counter() - t
    assert sorted(rebuilt) == dropped, (rebuilt, dropped)
    if shard_digests(base) != before:
        raise AssertionError(
            f"{label} rebuild of shards {dropped} not byte-identical")
    mbps = dat_size / dt / 1e6
    log(f"{label} e2e rebuild of {M} shards: {mbps:.0f} MB/s of volume "
        f"bytes ({dt:.1f}s, dropped {dropped}, digests verified)")
    return mbps


def measure_tpu_rebuild(base: str, dat_size: int, slab_mb: int):
    """Drop 4 random shards, rebuild through the device, verify digests."""
    from seaweedfs_tpu.ops.rs_tpu import TpuCodec
    return _measure_rebuild(base, dat_size, TpuCodec(K, M), "tpu",
                            seed=42, slab=slab_mb << 20, pipelined=True)


def measure_cpu_rebuild(base: str, dat_size: int) -> float:
    """BASELINE config 2 on the CPU path: drop M random shards of the
    just-encoded volume, rebuild with the native codec, verify digests.
    Runs in every mode so the fallback artifact still carries a
    rebuild number (device runs add the TPU variant on top)."""
    from seaweedfs_tpu.ops.codec import get_codec
    backend = "native" if native_available() else "numpy"
    return _measure_rebuild(base, dat_size,
                            get_codec(K, M, backend=backend),
                            f"cpu[{backend}]", seed=7, slab=1 << 20,
                            pipelined=False)


def measure_cpu_inmem(slab_mb: int, iters: int = 6) -> float:
    """Like-for-like denominator for the device-resident figure: the
    native AVX2-style codec on in-memory buffers, no file I/O."""
    from seaweedfs_tpu.ops.codec import get_codec
    if not native_available():
        return 0.0
    codec = get_codec(K, M, backend="native")
    n = slab_mb << 20
    rng = np.random.default_rng(2)
    bufs = [rng.integers(0, 256, (K, n), dtype=np.uint8) for _ in range(3)]
    codec.encode(bufs[0])  # warm threads
    times = []
    for i in range(iters):
        t = time.perf_counter()
        codec.encode(bufs[i % len(bufs)])
        times.append(time.perf_counter() - t)
    best = (K * n) / min(times) / 1e6
    log(f"cpu[native] in-memory encode (no I/O): best {best:.0f} MB/s")
    return best


def measure_device_resident(slab_mb: int, iters: int = 8):
    """Honest device-resident figure: per-iteration sync, rotating fresh
    buffers so no result can be served from an unexecuted cached launch.
    Returns (median, best, pipelined) MB/s."""
    import jax.numpy as jnp
    from seaweedfs_tpu.ops.rs_tpu import make_encode_fn
    n = slab_mb << 20
    fn, bitmat = make_encode_fn(K, M, n)
    bm = jnp.asarray(bitmat)
    rng = np.random.default_rng(1)
    bufs = [jnp.asarray(rng.integers(0, 256, (K, n), dtype=np.uint8))
            for _ in range(3)]
    for b in bufs:
        b.block_until_ready()
    fn(bm, bufs[0]).block_until_ready()  # compile
    times = []
    for i in range(iters):
        t = time.perf_counter()
        fn(bm, bufs[i % len(bufs)]).block_until_ready()
        times.append(time.perf_counter() - t)
    best = (K * n) / min(times) / 1e6
    med = (K * n) / sorted(times)[len(times) // 2] / 1e6
    log(f"tpu device-resident encode (per-iter sync, rotating buffers): "
        f"median {med:.0f} MB/s, best {best:.0f} MB/s")
    # throughput view: dispatch all, sync once — still honest (distinct
    # rotating inputs, every dispatched executable runs) but without a
    # host sync per iteration
    t = time.perf_counter()
    outs = [fn(bm, bufs[i % len(bufs)]) for i in range(iters)]
    for o in outs:
        o.block_until_ready()
    thr = (K * n * iters) / (time.perf_counter() - t) / 1e6
    log(f"tpu device-resident encode (pipelined dispatch, one sync): "
        f"{thr:.0f} MB/s")
    return med, best, thr


def measure_device_chained(slab_mb: int, k: int = K, m: int = M,
                           lens=(5, 15, 25), min_r2: float = 0.98):
    """Dispatch-independent kernel figure: run N serially-dependent
    encodes inside ONE dispatch (each iteration xors its parity back into
    the payload, so no iteration can be elided or reordered), timed at
    >= 3 chain lengths; the least-squares slope cancels the fixed
    per-dispatch cost. Every byte of every extra iteration is real
    serialized device work, so the slope is an honest steady-state
    compute rate — and the R^2 / max-deviation diagnostics pin that the
    three points actually lie on a line (one hiccup landing on a single
    point would otherwise skew a two-point subtraction silently).

    Returns (rate_mbps, fit_diagnostics)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from seaweedfs_tpu.ops.rs_tpu import make_encode_fn
    n = slab_mb << 20
    fn, bitmat = make_encode_fn(k, m, n)
    bm = jnp.asarray(bitmat)

    def make(iters):
        @jax.jit
        def chained(bm, x0):
            def body(_, x):
                y = fn(bm, x)
                return x.at[:m, :].set(x[:m, :] ^ y)
            return lax.fori_loop(0, iters, body, x0)[0, 0]
        return chained

    # distinct input per timed call, so no result can be served from a
    # launch that did not execute
    xs = [jax.random.randint(jax.random.PRNGKey(i), (k, n), 0, 256,
                             dtype=jnp.int32).astype(jnp.uint8)
          for i in range(4)]
    for x in xs:
        x.block_until_ready()

    def best_time(iters, reps=3):
        ch = make(iters)
        int(ch(bm, xs[3]))   # compile + materialize
        ts = []
        for i in range(reps):
            t = time.perf_counter()
            # int() fetches the scalar to the host: the chain has
            # executed by then
            int(ch(bm, xs[i % 3]))
            ts.append(time.perf_counter() - t)
        return min(ts)

    def fit():
        times = [best_time(it) for it in lens]
        its = np.asarray(lens, dtype=np.float64)
        ts = np.asarray(times, dtype=np.float64)
        slope, intercept = np.polyfit(its, ts, 1)
        pred = slope * its + intercept
        ss_res = float(((ts - pred) ** 2).sum())
        ss_tot = float(((ts - ts.mean()) ** 2).sum()) or 1e-12
        r2 = 1.0 - ss_res / ss_tot
        max_dev = float(np.abs(ts - pred).max() / ts.mean())
        return slope, times, r2, max_dev

    slope, times, r2, max_dev = fit()
    if slope <= 0 or r2 < min_r2:   # one hiccup: one retry
        log(f"chained fit noisy (slope {slope:.4g}, r2 {r2:.3f}); "
            f"retrying")
        slope, times, r2, max_dev = fit()
    if slope <= 0 or r2 < min_r2:
        raise RuntimeError(
            f"chained timings not linear in chain length: "
            f"lens {list(lens)} -> {[round(t, 4) for t in times]} "
            f"(slope {slope:.4g}, r2 {r2:.3f})")
    rate = k * n / slope
    diag = {"chain_lens": list(lens),
            "times_s": [round(t, 4) for t in times],
            "r2": round(r2, 4), "max_dev_frac": round(max_dev, 3)}
    log(f"tpu chained-slope rs({k},{m}) encode ({list(lens)} serial "
        f"iters, {slab_mb}MB slab): {rate / 1e9:.1f} GB/s payload "
        f"(r2 {r2:.4f}, max dev {max_dev:.1%})")
    return rate / 1e6, diag


def measure_geometries(size_mb: int, chained_by_geo: dict = None) -> dict:
    """BASELINE config 4: RS(6,3) and RS(20,4) — correctness is pinned by
    tests/test_rs_codec.py; this measures MB/s on the native backend
    (e2e encode of a real .dat). The device figure per geometry is the
    CHAINED-SLOPE kernel rate measured pre-e2e on a quiet device and
    injected here (`chained_by_geo`); per-call numbers carry the fixed
    dispatch cost and are comparable to nothing."""
    import shutil as _shutil
    from seaweedfs_tpu.ec import write_ec_files
    from seaweedfs_tpu.ops.codec import get_codec
    out = {}
    for k, m in ((6, 3), (20, 4)):
        gdir = tempfile.mkdtemp(prefix=f"swgeo_{k}_{m}_")
        base = os.path.join(gdir, "1")
        try:
            size = generate_dat(base + ".dat", size_mb)
            codec = get_codec(k, m, backend="native"
                              if native_available() else "numpy")
            t = time.perf_counter()
            write_ec_files(base, codec=codec, slab=1 << 20,
                           pipelined=False)
            native_mbps = size / (time.perf_counter() - t) / 1e6
            entry = {"native_e2e_mbps": round(native_mbps)}
            chained = (chained_by_geo or {}).get((k, m))
            if chained:
                rate, diag = chained
                entry["device_chained_mbps"] = round(rate)
                entry["chained_fit"] = diag
            out[f"rs_{k}_{m}"] = entry
            log(f"rs({k},{m}) on {size_mb}MB: {entry}")
        finally:
            _shutil.rmtree(gdir, ignore_errors=True)
    return out


def measure_batched_small_needles(n_volumes: int = 4,
                                  needles_per_volume: int = 8192) -> dict:
    """BASELINE config 3 (scaled): volumes full of 4KB needles encoded
    through the coalesced-batch streaming path. The full 1M x 4KB x 32
    volumes run is the same code at bigger constants (env-scalable via
    SW_BENCH_SMALL_VOLS / SW_BENCH_SMALL_NEEDLES)."""
    import shutil as _shutil
    from seaweedfs_tpu.ec import write_ec_files
    from seaweedfs_tpu.ops.codec import get_codec
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume
    workdir = tempfile.mkdtemp(prefix="swsmall_")
    try:
        rng = np.random.default_rng(9)
        payload = rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
        total_bytes = 0
        t_build = time.perf_counter()
        for vi in range(n_volumes):
            v = Volume(workdir, "", vi + 1, create=True)
            for i in range(1, needles_per_volume + 1):
                v.write_needle(Needle(id=i, cookie=1, data=payload))
            total_bytes += v.size()
            v.close()
        build_s = time.perf_counter() - t_build
        codec = get_codec(K, M, backend="native"
                          if native_available() else "numpy")
        t = time.perf_counter()
        for vi in range(n_volumes):
            write_ec_files(os.path.join(workdir, str(vi + 1)),
                           codec=codec, slab=1 << 20, pipelined=False)
        dt = time.perf_counter() - t
        mbps = total_bytes / dt / 1e6
        log(f"batched small-needle encode: {n_volumes} volumes x "
            f"{needles_per_volume} x 4KB = {total_bytes / 1e6:.0f} MB, "
            f"{mbps:.0f} MB/s (write {build_s:.1f}s, encode {dt:.1f}s)")
        return {"volumes": n_volumes, "needles_per_volume":
                needles_per_volume, "total_mb": round(total_bytes / 1e6),
                "encode_mbps": round(mbps)}
    finally:
        _shutil.rmtree(workdir, ignore_errors=True)


def _cluster_holder_health(master_url: str) -> dict:
    """Per-holder {holder: score} from the master's /cluster/health
    fold (forcing a scrape so the drill's fetches are in the EWMAs);
    empty on any failure — health reporting must never fail a bench."""
    from seaweedfs_tpu.server.http_util import get_json
    try:
        view = get_json(f"http://{master_url}/cluster/health?refresh=1")
        return {holder: h.get("score")
                for holder, h in (view.get("holders") or {}).items()}
    except Exception:  # noqa: BLE001
        return {}


def measure_cluster_rebuild(size_mb: int = 256, n_servers: int = 4,
                            backend: str = None) -> dict:
    """BASELINE config 5 (scaled): EC volume spread over a live cluster,
    shards on one server destroyed, rebuilt on another — the parallel
    survivor gather, the GF rebuild compute and the mount are timed as
    phases (via do_ec_rebuild's timings hook) so the network/compute
    split is reported, not guessed. Backend for the rebuild compute:
    SW_BENCH_CLUSTER_BACKEND or the `backend` arg (default mesh — the
    device-mesh serving path; the driver's virtual-CPU-mesh run goes
    through run_cluster_drill_subprocess)."""
    import shutil as _shutil
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.http_util import get_json, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    backend = backend or config.env_str("SW_BENCH_CLUSTER_BACKEND")
    workdir = tempfile.mkdtemp(prefix="swcluster_")
    master = MasterServer(port=0, volume_size_limit_mb=size_mb * 2,
                          pulse_seconds=1).start()
    servers = []
    try:
        for i in range(n_servers):
            servers.append(VolumeServer(
                port=0, directories=[os.path.join(workdir, f"v{i}")],
                master_url=master.url, pulse_seconds=1,
                max_volume_counts=[10], ec_backend=backend).start())
        # one volume filled with data
        a = op.assign(master.url, collection="bench")
        vid = int(a["fid"].split(",")[0])
        rng = np.random.default_rng(4)
        chunk = rng.integers(0, 256, 4 << 20, dtype=np.uint8).tobytes()
        written = 0
        i = 0
        while written < (size_mb << 20):
            i += 1
            op.upload(a["url"], f"{vid},{i:x}00000001", chunk,
                      filename=f"b{i}")
            written += len(chunk)
        # encode + spread via the shell orchestration
        import seaweedfs_tpu.shell  # noqa: F401
        from seaweedfs_tpu.shell.command_env import CommandEnv, run_command
        # shell progress to stderr: stdout carries ONLY the bench JSON
        env = CommandEnv(master.url, out=sys.stderr)
        # keep the drill bounded (the interactive shell default is a
        # generous 3600s; a wedged server would stall the whole bench
        # on it)
        env.admin_timeout = config.env_float("SW_BENCH_DRILL_TIMEOUT")
        from seaweedfs_tpu.shell.command_ec import do_ec_encode
        # device-runtime bracketing: every drill server runs in-process,
        # so the process-global DEVICE_STATS sees the rebuilder's
        # compiles directly. The deltas split XLA compile wall out of
        # each phase headline and gate recompiles == 0 after warmup.
        from seaweedfs_tpu.ops import device_stats as _dstats
        dsnap0 = _dstats.DEVICE_STATS.snapshot()
        enc_timings = {}
        t_encode = time.perf_counter()
        do_ec_encode(env, vid, timings=enc_timings)
        encode_s = time.perf_counter() - t_encode
        enc_dev = _dstats.delta(dsnap0)
        dsnap1 = _dstats.DEVICE_STATS.snapshot()

        # shard ownership reaches the master via the store-change
        # immediate push; poll with a deadline instead of sleeping a
        # pulse (fixed sleeps race on loaded hosts)
        def poll(pred, what, timeout=30.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                try:
                    got = pred()
                except Exception:  # noqa: BLE001 - master mid-update
                    got = None
                if got is not None:
                    return got
                time.sleep(0.1)
            raise TimeoutError(f"cluster drill: {what} not observed "
                               f"within {timeout}s")

        def lookup_shards():
            out = get_json(f"http://{master.url}/cluster/ec_lookup"
                           f"?volumeId={vid}")
            return {int(s): urls for s, urls in out["shards"].items()}

        ec = {"shards": poll(
            lambda: (lambda m: m if set(m) == set(range(TOTAL))
                     else None)(lookup_shards()),
            "all 14 encoded shards at the master")}
        by_holder = {}
        for sid, urls in ec["shards"].items():
            for u in urls:
                by_holder.setdefault(u, []).append(int(sid))
        victim, lost = max(by_holder.items(), key=lambda kv: len(kv[1]))
        # cap the destroyed set at the parity count — losing more than M
        # shards is unrecoverable by construction (RS(10,4)), and a
        # small server count concentrates >M shards per holder
        lost = sorted(lost)[:M]
        post_json(f"http://{victim}/admin/ec/unmount?volume={vid}"
                  f"&shards={','.join(map(str, sorted(lost)))}")
        post_json(f"http://{victim}/admin/ec/delete_shards?volume={vid}"
                  f"&collection=bench"
                  f"&shards={','.join(map(str, sorted(lost)))}")
        # loss visible at the master (immediate push again) before the
        # rebuilder plans which shards to regenerate
        shard_map = poll(
            lambda: (lambda m: m if not any(
                victim in m.get(s, []) for s in lost) else None)(
                lookup_shards()),
            "shard loss at the master")
        # rebuild (shell picks the rebuilder, pulls survivors in
        # parallel, runs the GF rebuild) — phase-timed
        from seaweedfs_tpu.shell.command_ec import do_ec_rebuild
        missing = [s for s in range(TOTAL) if s not in shard_map]
        timings = {}
        t_rebuild = time.perf_counter()
        do_ec_rebuild(env, vid, "bench", shard_map, missing,
                      timings=timings)
        rebuild_s = time.perf_counter() - t_rebuild
        reb_dev = _dstats.delta(dsnap1)
        dsnap2 = _dstats.DEVICE_STATS.snapshot()
        ec2 = get_json(f"http://{master.url}/cluster/ec_lookup"
                       f"?volumeId={vid}")
        have = {int(s) for s in ec2["shards"]}
        ok = have == set(range(TOTAL))
        gather_s = timings.get("gather_s", 0.0)
        compute_s = timings.get("compute_s", 0.0)
        # device telemetry reported by the rebuilder (rebuild_ec_files
        # via /admin/ec/rebuild): dispatch discipline must be VISIBLE in
        # vs_baseline — a regression back to per-slab bitmat uploads or
        # two-dispatch slabs shows here before it shows in wall time
        stream_s = timings.get("stream_s", 0.0)
        survivor_bytes = timings.get("survivor_bytes", 0)
        # mesh-sharded dispatch width: recompute from the per-device
        # byte map (survives _merge_rebuild_stats' dict overwrite
        # semantics) with the rebuilder's derived value as fallback —
        # width 1 here means the codec fell back to a single device and
        # the "one dispatch drives all devices" property regressed
        mesh_bytes = {d: b for d, b in
                      (timings.get("mesh_device_bytes") or {}).items()
                      if b}
        if mesh_bytes:
            peak = max(mesh_bytes.values())
            width_devices = len(mesh_bytes)
            busy_frac = {d: round(b / peak, 3)
                         for d, b in sorted(mesh_bytes.items())}
        else:
            width_devices = timings.get("dispatch_width_devices", 0)
            busy_frac = timings.get("device_byte_share", {})

        # -- single-shard repair drill: the overwhelmingly common
        # failure at fleet scale. Destroy exactly ONE shard and rebuild
        # with -repair auto — the trace path ships projected sub-shard
        # symbols from all survivors, so repair_bytes_frac must land
        # well under 1.0 (the k*shard full-gather baseline).
        shard_map2 = poll(
            lambda: (lambda m: m if set(m) == set(range(TOTAL))
                     else None)(lookup_shards()),
            "all shards back at the master before the repair drill")
        lone_sid = sorted(shard_map2)[0]
        lone_holder = shard_map2[lone_sid][0]
        post_json(f"http://{lone_holder}/admin/ec/unmount?volume={vid}"
                  f"&shards={lone_sid}")
        post_json(f"http://{lone_holder}/admin/ec/delete_shards"
                  f"?volume={vid}&collection=bench&shards={lone_sid}")
        # a lone-held shard vanishes from the lookup map entirely once
        # its only holder drops it (lookup_ec_shards omits empty holder
        # lists), so "key absent" IS the loss signal — a [lone_holder]
        # default here would wait forever
        shard_map2 = poll(
            lambda: (lambda m: m if lone_holder not in
                     m.get(lone_sid, []) else None)(
                lookup_shards()),
            "single-shard loss at the master")
        repair_timings = {}
        t_repair = time.perf_counter()
        do_ec_rebuild(env, vid, "bench", shard_map2, [lone_sid],
                      timings=repair_timings, repair="auto")
        repair_wall_s = time.perf_counter() - t_repair
        ok = ok and set(poll(
            lambda: (lambda m: m if set(m) == set(range(TOTAL))
                     else None)(lookup_shards()),
            "all shards back after the repair drill")) == set(range(TOTAL))

        # -- piggyback layout drill: a second (smaller) volume encoded
        # with SW_EC_LAYOUT=piggyback, one data shard destroyed, -repair
        # auto routed to the plane repair. Its repair_bytes_frac lands
        # at the coupled layout's (k+1)/(2k) floor — 0.55 for RS(10,4)
        # — reported beside the trace drill's frac and the full-gather
        # baseline (1.0) so all three repair strategies sit in one
        # record.
        pb_mb = max(size_mb // 4, 8)
        a2 = op.assign(master.url, collection="bench")
        vid2 = int(a2["fid"].split(",")[0])
        written = 0
        i = 0
        while written < (pb_mb << 20):
            i += 1
            op.upload(a2["url"], f"{vid2},{i:x}00000001", chunk,
                      filename=f"p{i}")
            written += len(chunk)
        os.environ["SW_EC_LAYOUT"] = "piggyback"
        try:
            pb_enc = {}
            do_ec_encode(env, vid2, timings=pb_enc)
        finally:
            os.environ.pop("SW_EC_LAYOUT", None)

        def lookup_shards2():
            out2 = get_json(f"http://{master.url}/cluster/ec_lookup"
                            f"?volumeId={vid2}")
            return {int(s): urls for s, urls in out2["shards"].items()}

        pb_map = poll(
            lambda: (lambda m: m if set(m) == set(range(TOTAL))
                     else None)(lookup_shards2()),
            "all piggyback shards at the master")
        pb_sid = 0  # a coupled data shard: the plane-repair fast path
        pb_holder = pb_map[pb_sid][0]
        post_json(f"http://{pb_holder}/admin/ec/unmount?volume={vid2}"
                  f"&shards={pb_sid}")
        post_json(f"http://{pb_holder}/admin/ec/delete_shards"
                  f"?volume={vid2}&collection=bench&shards={pb_sid}")
        pb_map = poll(
            lambda: (lambda m: m if pb_holder not in
                     m.get(pb_sid, []) else None)(lookup_shards2()),
            "piggyback shard loss at the master")
        pb_rep = {}
        t_pb = time.perf_counter()
        do_ec_rebuild(env, vid2, "bench", pb_map, [pb_sid],
                      timings=pb_rep, repair="auto")
        pb_repair_wall_s = time.perf_counter() - t_pb
        ok = ok and set(poll(
            lambda: (lambda m: m if set(m) == set(range(TOTAL))
                     else None)(lookup_shards2()),
            "piggyback shard back after plane repair")) \
            == set(range(TOTAL))
        rep_dev = _dstats.delta(dsnap2)
        # compile/steady split: the headline MB/s must measure the
        # serving path a warm fleet runs, so compile wall (a once-per-
        # process warmup cost, reported on its own) is subtracted from
        # the rebuild wall before the bandwidth division.
        encode_compile_s = enc_dev["compile_seconds_total"]
        rebuild_compile_s = reb_dev["compile_seconds_total"]
        repair_compile_s = rep_dev["compile_seconds_total"]
        rebuild_steady_s = max(rebuild_s - rebuild_compile_s, 1e-9)
        recompiles = (enc_dev["recompiles_total"]
                      + reb_dev["recompiles_total"]
                      + rep_dev["recompiles_total"])
        dstats_now = _dstats.DEVICE_STATS.snapshot()
        if recompiles:
            raise RuntimeError(
                f"cluster rebuild: {recompiles} XLA recompile(s) after "
                f"warmup — width-bucketing regressed "
                f"(offenders: {dstats_now['offenders']})")
        out = {"servers": n_servers, "volume_mb": size_mb,
               "backend": backend, "lost_shards": len(lost),
               "encode_spread_s": round(encode_s, 1),
               # streaming encode+spread split (busy times + overlap;
               # copy mode reports its two serialized phase walls and
               # overlap 0) — the write-path mirror of the gather
               # accounting below
               "encode_mode": enc_timings.get("mode", "stream"),
               "encode_s": round(
                   enc_timings.get("encode_busy_s", 0.0), 2),
               "spread_s": round(
                   enc_timings.get("spread_busy_s", 0.0), 2),
               "encode_overlap_frac": round(
                   enc_timings.get("overlap_frac", 0.0), 3),
               "spread_mbps": round(
                   enc_timings.get("spread_mbps", 0.0), 1),
               "rebuild_wall_s": round(rebuild_s, 1),
               # XLA compile wall split out of every headline: the
               # steady-state bandwidth is what a warm fleet sustains,
               # compile_s is the once-per-process warmup it pays
               "encode_compile_s": round(encode_compile_s, 2),
               "compile_s": round(rebuild_compile_s, 2),
               "repair_compile_s": round(repair_compile_s, 2),
               "rebuild_steady_s": round(rebuild_steady_s, 1),
               "recompiles": recompiles,
               "recompile_sentinel": dstats_now["sentinel"],
               "xla_compiles": enc_dev["compiles_total"]
               + reb_dev["compiles_total"] + rep_dev["compiles_total"],
               "rebuild_mbps_volume_bytes": round(
                   (size_mb << 20) / rebuild_steady_s / 1e6),
               "gather_s": round(gather_s, 2),
               "compute_s": round(compute_s, 2),
               "mount_s": round(timings.get("mount_s", 0.0), 2),
               "gather_frac": round(gather_s / rebuild_s, 2),
               "compute_frac": round(compute_s / rebuild_s, 2),
               "gathered_shards": timings.get("gathered_shards", 0),
               "dispatches": timings.get("dispatches", 0),
               "bitmat_uploads": timings.get("bitmat_uploads", 0),
               "mesh_dispatches": timings.get("mesh_dispatches", 0),
               "dispatch_width_devices": width_devices,
               "device_byte_share": busy_frac,
               "rebuild_device_mbps": round(
                   survivor_bytes / stream_s / 1e6) if stream_s else 0,
               # streaming-gather overlap accounting: gather_s/compute_s
               # above are BUSY times in stream mode, so their sum
               # estimates what the serialized copy-then-rebuild flow
               # would have cost; overlap_frac = saved/serialized
               "overlap_frac": round(
                   timings.get("overlap_frac", 0.0), 3),
               "gather_mbps": round(timings.get("gather_mbps", 0.0), 1),
               "gather_busy_s": round(
                   timings.get("gather_busy_s", 0.0), 2),
               "serialized_estimate_s": round(gather_s + compute_s, 2),
               "hedges_fired": timings.get("hedges_fired", 0),
               # hedge-loss attribution + per-holder health (fleet
               # health plane): which holders lost hedge races, how
               # many range reads each holder served, and the cluster
               # /cluster/health worst-observer scores — snapshots of
               # slow-holder detection over time
               "hedges_won": timings.get("hedges_won", 0),
               "hedges_lost": timings.get("hedges_lost", 0),
               "holder_fetches": timings.get("holder_fetches", {}),
               "holder_errors": timings.get("holder_errors", {}),
               "holder_health": _cluster_holder_health(master.url),
               # per-phase {name: seconds} from the rebuilder's spans
               # (gather/plan/dispatch/drain/write) plus the trace id —
               # the full span timeline is at the rebuilder's
               # /admin/traces?trace=<id>
               "phases": timings.get("phases", {}),
               "trace_id": timings.get("trace_id"),
               # single-shard repair drill (trace repair vs the k*shard
               # full-gather baseline; repair_bytes_frac < 1.0 iff the
               # trace path was taken and paid off)
               "repair_mode": repair_timings.get("repair_mode", "?"),
               "repair_bytes_frac": round(
                   repair_timings.get("repair_bytes_frac", 1.0), 3),
               "repair_mbps": round(
                   repair_timings.get("repair_mbps", 0.0), 1),
               "repair_wall_s": round(repair_wall_s, 2),
               "repair_helpers": repair_timings.get("repair_helpers", 0),
               "repair_fallback": repair_timings.get("repair_fallback"),
               # piggyback layout drill (plane repair on the coupled
               # sub-chunk layout vs the same k*shard baseline; the
               # construction's floor is (k+1)/(2k) = 0.55 for RS(10,4),
               # between trace's measured frac and full's 1.0)
               "piggyback_volume_mb": pb_mb,
               "piggyback_repair_mode": pb_rep.get("repair_mode", "?"),
               "piggyback_repair_bytes_frac": round(
                   pb_rep.get("repair_bytes_frac", 1.0), 3),
               "piggyback_repair_wall_s": round(pb_repair_wall_s, 2),
               "piggyback_repair_helpers": pb_rep.get(
                   "repair_helpers", 0),
               "piggyback_repair_fallback": pb_rep.get("repair_fallback"),
               "full_repair_bytes_frac": 1.0,
               "all_shards_restored": ok}
        log(f"cluster rebuild: {out}")
        return out
    finally:
        for vs in servers:
            vs.stop()
        master.stop()
        _shutil.rmtree(workdir, ignore_errors=True)


def measure_cluster_degraded_read(n_needles: int = None,
                                  needle_kb: int = None,
                                  n_servers: int = 3,
                                  readers: int = None,
                                  rounds: int = None) -> dict:
    """Degraded-read serving drill: needles on a destroyed shard served
    by reconstruct-on-read under concurrency. Reports healthy p50/p99,
    the naive per-read reconstruct (SW_EC_DEGRADED_MODE=naive), the
    batched DegradedReadEngine cold and warm, plus batch width, slab
    cache hit ratio and survivor bytes per read — the loss-masked-read
    p99 story next to cluster_rebuild's repair story."""
    import shutil as _shutil
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.ec.constants import (LARGE_BLOCK_SIZE,
                                            SMALL_BLOCK_SIZE)
    from seaweedfs_tpu.server.http_util import (get_json, http_call,
                                                post_json)
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.storage.types import parse_file_id
    n_needles = n_needles or config.env_int("SW_BENCH_DEGRADED_NEEDLES")
    needle_kb = needle_kb or config.env_int("SW_BENCH_DEGRADED_KB")
    readers = readers or config.env_int("SW_BENCH_DEGRADED_READERS")
    rounds = rounds or config.env_int("SW_BENCH_DEGRADED_ROUNDS")
    backend = config.env_str("SW_BENCH_DEGRADED_BACKEND")
    workdir = tempfile.mkdtemp(prefix="swdegraded_")
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1).start()
    servers = []
    saved_mode = os.environ.get("SW_EC_DEGRADED_MODE")
    try:
        for i in range(n_servers):
            servers.append(VolumeServer(
                port=0, directories=[os.path.join(workdir, f"v{i}")],
                master_url=master.url, pulse_seconds=1,
                max_volume_counts=[30], ec_backend=backend).start())
        rng = np.random.default_rng(11)
        payloads = {}
        for i in range(n_needles):
            data = rng.integers(0, 256, needle_kb << 10,
                                dtype=np.uint8).tobytes()
            fid = op.upload_data(master.url, data, filename=f"d{i}",
                                 collection="bench")
            payloads[fid] = data
        # assignment round-robins over volumes: encode and drill the
        # volume that received the most needles
        by_vid = {}
        for fid in payloads:
            by_vid.setdefault(int(fid.split(",")[0]), []).append(fid)
        vid = max(by_vid, key=lambda v: len(by_vid[v]))
        fids = by_vid[vid]
        payloads = {f: payloads[f] for f in fids}
        import seaweedfs_tpu.shell  # noqa: F401
        from seaweedfs_tpu.shell.command_env import CommandEnv
        from seaweedfs_tpu.shell.command_ec import do_ec_encode
        env = CommandEnv(master.url, out=sys.stderr)
        env.admin_timeout = config.env_float("SW_BENCH_DRILL_TIMEOUT")
        # device-runtime bracketing (servers run in-process): compile
        # wall reports separately per phase, recompiles gate at zero —
        # trivially so on the numpy backend, meaningfully on device ones
        from seaweedfs_tpu.ops import device_stats as _dstats
        dsnap0 = _dstats.DEVICE_STATS.snapshot()
        do_ec_encode(env, vid)
        enc_dev = _dstats.delta(dsnap0)

        def poll(pred, what, timeout=30.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                try:
                    got = pred()
                except Exception:  # noqa: BLE001 - master mid-update
                    got = None
                if got is not None:
                    return got
                time.sleep(0.1)
            raise TimeoutError(f"degraded drill: {what} not observed "
                               f"within {timeout}s")

        def lookup_shards():
            out = get_json(f"http://{master.url}/cluster/ec_lookup"
                           f"?volumeId={vid}")
            return {int(s): urls for s, urls in out["shards"].items()}

        shard_map = poll(
            lambda: (lambda m: m if set(m) == set(range(TOTAL))
                     else None)(lookup_shards()),
            "all 14 encoded shards at the master")

        # per-needle target shard (first interval), via any server
        # holding the ec volume
        locate_vs = next(s for s in servers
                         if s.store.find_ec_volume(vid) is not None)
        ev = locate_vs.store.find_ec_volume(vid)
        by_sid = {}
        for fid in fids:
            _, key, _ = parse_file_id(fid)
            _, _, ivs = ev.locate_needle(key)
            sid, _ = ivs[0].to_shard_id_and_offset(
                LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE)
            by_sid.setdefault(sid, []).append(fid)
        target_sid, degraded_fids = max(by_sid.items(),
                                        key=lambda kv: len(kv[1]))
        holders = set(shard_map[target_sid])
        serving = next(s for s in servers if s.url not in holders and
                       s.store.find_ec_volume(vid) is not None)

        def drill(fid_list, mode_note, base_url=None):
            base = base_url or serving.url
            lat, errs = [], []
            lock = threading.Lock()

            def worker(tid):
                order = list(fid_list)
                trng = np.random.default_rng(100 + tid)
                for _ in range(rounds):
                    trng.shuffle(order)
                    for fid in order:
                        t0 = time.perf_counter()
                        try:
                            got = http_call(
                                "GET", f"http://{base}/{fid}",
                                timeout=60)
                        except Exception as e:  # noqa: BLE001
                            with lock:
                                errs.append(f"{mode_note} {fid}: {e!r}")
                            continue
                        dt = time.perf_counter() - t0
                        with lock:
                            lat.append(dt)
                        if got != payloads[fid]:
                            with lock:
                                errs.append(
                                    f"{mode_note} {fid}: bytes differ")

            t_wall = time.perf_counter()
            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(readers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t_wall
            if errs:
                raise RuntimeError(errs[0])
            lat.sort()
            return (lat[len(lat) // 2] * 1e3,
                    lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3,
                    wall)

        healthy_p50, healthy_p99, _ = drill(fids, "healthy")

        # destroy the target shard everywhere
        for holder in sorted(holders):
            post_json(f"http://{holder}/admin/ec/unmount?volume={vid}"
                      f"&shards={target_sid}")
            post_json(f"http://{holder}/admin/ec/delete_shards"
                      f"?volume={vid}&collection=bench"
                      f"&shards={target_sid}")
        poll(lambda: (True if not lookup_shards().get(target_sid)
                      else None),
             "shard loss at the master")

        # naive per-read reconstruct (exactly-k fetch, one-row decode,
        # but no batching / caching / hedging)
        os.environ["SW_EC_DEGRADED_MODE"] = "naive"
        dsnap_naive = _dstats.DEVICE_STATS.snapshot()
        naive_p50, naive_p99, naive_wall = drill(degraded_fids, "naive")
        naive_dev = _dstats.delta(dsnap_naive)

        # batched engine, cold cache
        os.environ.pop("SW_EC_DEGRADED_MODE", None)
        eng = serving.degraded
        eng.invalidate(vid)
        base = eng.snapshot()
        dsnap_batch = _dstats.DEVICE_STATS.snapshot()
        batch_p50, batch_p99, batch_wall = drill(degraded_fids, "batch")
        batch_dev = _dstats.delta(dsnap_batch)
        snap = eng.snapshot()
        d_reads = max(1, snap["reads"] - base["reads"])
        # warm re-read: the slab LRU serves without another gather
        warm_p50, warm_p99, _ = drill(degraded_fids, "warm")
        warm = eng.snapshot()

        # plane trial set: the same warm reads served entirely by the
        # native plane's slab cache — 200 straight from C++, never the
        # 307 hop back to Python
        plane = {}
        if serving.fast_plane is not None and \
                serving.fast_plane.cache_stats() is not None:
            import http.client as _hc

            def plane_status(fid):
                """One-shot GET without redirect following, so the
                plane's own verdict (200 vs 307) is observable."""
                host, port = serving.fast_url.rsplit(":", 1)
                c = _hc.HTTPConnection(host, int(port), timeout=30)
                try:
                    c.request("GET", f"/{fid}")
                    r = c.getresponse()
                    r.read()
                    return r.status
                finally:
                    c.close()

            # warm the plane (a followed read re-publishes any slab
            # evicted since the cold batch), then keep the fids it can
            # serve end-to-end: fully covered by cached + local shards
            for fid in degraded_fids:
                http_call("GET", f"http://{serving.fast_url}/{fid}",
                          timeout=60)
            plane_fids = [f for f in degraded_fids
                          if plane_status(f) == 200]
            if plane_fids:
                cbase = serving.fast_plane.cache_stats()
                tele_base = serving.fast_plane.redirected
                pw_p50, pw_p99, _ = drill(plane_fids, "plane-warm",
                                          base_url=serving.fast_url)
                csnap = serving.fast_plane.cache_stats()
                n_reads = readers * rounds * len(plane_fids)
                served_d = (csnap["degraded_served"]
                            - cbase["degraded_served"])
                plane = {
                    "plane_fids": len(plane_fids),
                    "plane_warm_p50_ms": round(pw_p50, 2),
                    "plane_warm_p99_ms": round(pw_p99, 2),
                    "plane_reads": n_reads,
                    "plane_served": served_d,
                    "plane_degraded_redirects": (
                        csnap["degraded_redirected"]
                        - cbase["degraded_redirected"]),
                    # the acceptance triple: every read served in-plane,
                    # zero hops back to Python, counter == reads exactly
                    "plane_zero_redirect": bool(
                        served_d == n_reads
                        and csnap["degraded_redirected"]
                        == cbase["degraded_redirected"]
                        and serving.fast_plane.redirected == tele_base),
                    "plane_speedup_vs_python_warm": round(
                        warm_p99 / max(pw_p99, 1e-6), 2),
                    "plane_beats_python_warm": bool(pw_p99 < warm_p99),
                }

        # compile/steady split + the recompile gate: compiles may land
        # in the first (naive) degraded phase — that's warmup; a SECOND
        # compile of any (entry, width-bucket) pair anywhere in the
        # drill means bucketing broke and the drill fails loudly.
        recompiles = (enc_dev["recompiles_total"]
                      + naive_dev["recompiles_total"]
                      + batch_dev["recompiles_total"])
        dstats_now = _dstats.DEVICE_STATS.snapshot()
        if recompiles:
            raise RuntimeError(
                f"cluster degraded read: {recompiles} XLA recompile(s) "
                f"after warmup — width-bucketing regressed "
                f"(offenders: {dstats_now['offenders']})")
        naive_compile_s = naive_dev["compile_seconds_total"]
        batch_compile_s = batch_dev["compile_seconds_total"]
        out = {"servers": n_servers, "backend": backend,
               "needles": n_needles, "needle_kb": needle_kb,
               "degraded_needles": len(degraded_fids),
               "readers": readers, "rounds": rounds,
               "healthy_p50_ms": round(healthy_p50, 2),
               "healthy_p99_ms": round(healthy_p99, 2),
               "degraded_naive_p50_ms": round(naive_p50, 2),
               "degraded_naive_p99_ms": round(naive_p99, 2),
               "naive_wall_s": round(naive_wall, 2),
               "degraded_p50_ms": round(batch_p50, 2),
               "degraded_p99_ms": round(batch_p99, 2),
               "batch_wall_s": round(batch_wall, 2),
               "encode_compile_s": round(
                   enc_dev["compile_seconds_total"], 2),
               "compile_s": round(naive_compile_s + batch_compile_s, 2),
               "naive_steady_s": round(
                   max(naive_wall - naive_compile_s, 0.0), 2),
               "batch_steady_s": round(
                   max(batch_wall - batch_compile_s, 0.0), 2),
               "recompiles": recompiles,
               "recompile_sentinel": dstats_now["sentinel"],
               "batch_width_max": snap["max_batch_requests"],
               "batch_width_avg": round(
                   (snap["batched_requests"] - base["batched_requests"])
                   / max(1, snap["batches"] - base["batches"]), 2),
               "survivor_bytes_per_read": round(
                   (snap["survivor_bytes"] - base["survivor_bytes"])
                   / d_reads),
               "cache_hit_ratio_warm": round(warm["cache_hit_ratio"], 3),
               "warm_p50_ms": round(warm_p50, 2),
               "warm_p99_ms": round(warm_p99, 2),
               "batched_beats_naive": bool(batch_wall < naive_wall
                                           and batch_p99 < naive_p99)}
        out.update(plane)
        log(f"cluster degraded read: {out}")
        return out
    finally:
        if saved_mode is None:
            os.environ.pop("SW_EC_DEGRADED_MODE", None)
        else:
            os.environ["SW_EC_DEGRADED_MODE"] = saved_mode
        for vs in servers:
            vs.stop()
        master.stop()
        _shutil.rmtree(workdir, ignore_errors=True)


def measure_cluster_scrub_repair(n_volumes: int = None,
                                 n_needles: int = None,
                                 needle_kb: int = None,
                                 n_servers: int = 3,
                                 readers: int = None) -> dict:
    """Rolling-failure integrity drill: many EC volumes under live
    reads, one gets a byte flipped on disk and another loses a shard.
    Reports corruption detection latency, scrub MB/s, scrub overhead on
    the foreground p99, and time-to-re-protection p50/p99 across both
    incident kinds — the integrity-plane story next to the degraded
    and rebuild drills."""
    import shutil as _shutil
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.ec import to_ext
    from seaweedfs_tpu.server.http_util import get_json, http_call, \
        post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    n_volumes = n_volumes or config.env_int("SW_BENCH_SCRUB_VOLUMES")
    n_needles = n_needles or config.env_int("SW_BENCH_SCRUB_NEEDLES")
    needle_kb = needle_kb or config.env_int("SW_BENCH_SCRUB_KB")
    readers = readers or config.env_int("SW_BENCH_SCRUB_READERS")
    rate_mbps = config.env_float("SW_EC_SCRUB_RATE_MBPS")
    workdir = tempfile.mkdtemp(prefix="swscrub_")
    saved = {k: os.environ.get(k)
             for k in ("SW_REPAIR_INTERVAL_S", "SW_EC_SCRUB_IDLE_S")}
    os.environ["SW_REPAIR_INTERVAL_S"] = "0.5"
    os.environ["SW_EC_SCRUB_IDLE_S"] = "0"  # manual triggers only
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1).start()
    servers = []
    try:
        for i in range(n_servers):
            servers.append(VolumeServer(
                port=0, directories=[os.path.join(workdir, f"v{i}")],
                master_url=master.url, pulse_seconds=1,
                max_volume_counts=[30], ec_backend="numpy").start())
        rng = np.random.default_rng(23)
        payloads = {}   # fid -> bytes
        by_vid = {}     # vid -> [fids]
        vid_coll = {}   # vid -> collection (volumes are per-collection)
        for v in range(n_volumes):
            coll = f"sc{v}"
            for i in range(n_needles):
                data = rng.integers(0, 256, needle_kb << 10,
                                    dtype=np.uint8).tobytes()
                fid = op.upload_data(master.url, data,
                                     filename=f"s{v}_{i}",
                                     collection=coll)
                payloads[fid] = data
                vid = int(fid.split(",")[0])
                by_vid.setdefault(vid, []).append(fid)
                vid_coll[vid] = coll
        import seaweedfs_tpu.shell  # noqa: F401
        from seaweedfs_tpu.shell.command_env import CommandEnv
        from seaweedfs_tpu.shell.command_ec import do_ec_encode
        env = CommandEnv(master.url, out=sys.stderr)
        env.admin_timeout = config.env_float("SW_BENCH_DRILL_TIMEOUT")
        for vid in sorted(by_vid):
            do_ec_encode(env, vid)

        def poll(pred, what, timeout=60.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                try:
                    got = pred()
                except Exception:  # noqa: BLE001 - cluster mid-update
                    got = None
                if got is not None:
                    return got
                time.sleep(0.1)
            raise TimeoutError(f"scrub drill: {what} not observed "
                               f"within {timeout}s")

        def lookup_shards(vid):
            out = get_json(f"http://{master.url}/cluster/ec_lookup"
                           f"?volumeId={vid}")
            return {int(s): urls for s, urls in out["shards"].items()}

        for vid in sorted(by_vid):
            poll(lambda v=vid: (lambda m: m if set(m) ==
                                set(range(TOTAL)) else None)(
                lookup_shards(v)),
                f"all {TOTAL} shards of volume {vid} at the master")

        def read_all(fids, note):
            lat = []
            errs = []
            lock = threading.Lock()

            def worker(tid):
                order = list(fids)
                trng = np.random.default_rng(300 + tid)
                trng.shuffle(order)
                for fid in order:
                    vs = servers[tid % len(servers)]
                    t0 = time.perf_counter()
                    try:
                        got = http_call("GET",
                                        f"http://{vs.url}/{fid}",
                                        timeout=60)
                    except Exception as e:  # noqa: BLE001
                        with lock:
                            errs.append(f"{note} {fid}: {e!r}")
                        continue
                    dt = time.perf_counter() - t0
                    with lock:
                        lat.append(dt)
                    if got != payloads[fid]:
                        with lock:
                            errs.append(f"{note} {fid}: bytes differ")

            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(readers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errs:
                raise RuntimeError(errs[0])
            lat.sort()
            return (lat[len(lat) // 2] * 1e3,
                    lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3)

        all_fids = list(payloads)
        healthy_p50, healthy_p99 = read_all(all_fids * 3, "healthy")

        # foreground p99 while a rate-limited scrub pass runs
        scrub_threads = [threading.Thread(
            target=lambda s=s: s.scrub.run_pass(force=True),
            daemon=True) for s in servers]
        for t in scrub_threads:
            t.start()
        scrub_p50, scrub_p99 = read_all(all_fids * 3, "during_scrub")
        for t in scrub_threads:
            t.join(timeout=300)
        scrub_mbps = max(s.scrub.snapshot()["last_pass_mbps"]
                         for s in servers)
        clean_findings = sum(s.scrub.snapshot()["findings"]
                             for s in servers)
        if clean_findings:
            raise RuntimeError(
                f"false positives: {clean_findings} findings on clean "
                f"volumes")

        # incident 1: silent corruption — flip one byte on disk
        vid_a = sorted(by_vid)[0]
        victim = next(s for s in servers
                      if s.store.find_ec_volume(vid_a) is not None)
        ev = victim.store.find_ec_volume(vid_a)
        sid_a = sorted(ev.shards)[0]
        path = ev.base_name + to_ext(sid_a)
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
        t_corrupt = time.perf_counter()
        post_json(f"http://{victim.url}/admin/ec/scrub?volume={vid_a}")

        def corrupt_incident():
            view = get_json(f"http://{master.url}/cluster/repairs")
            for inc in view["open"] + view["resolved_recent"]:
                if inc["kind"] == "corruption" \
                        and inc["volume"] == vid_a:
                    return inc
            return None

        poll(corrupt_incident, "corruption incident at the master")
        detection_s = time.perf_counter() - t_corrupt

        def corrupt_resolved():
            view = get_json(f"http://{master.url}/cluster/repairs")
            for inc in view["resolved_recent"]:
                if inc["kind"] == "corruption" \
                        and inc["volume"] == vid_a:
                    return inc
            return None

        inc_a = poll(corrupt_resolved, "corruption repair", timeout=120)
        read_all(by_vid[vid_a], "restored_corruption")

        # incident 2: shard loss on a different volume
        vid_b = sorted(by_vid)[-1]
        shards_b = lookup_shards(vid_b)
        sid_b = max(shards_b)
        for holder in shards_b[sid_b]:
            post_json(f"http://{holder}/admin/ec/unmount"
                      f"?volume={vid_b}&shards={sid_b}")
            post_json(f"http://{holder}/admin/ec/delete_shards"
                      f"?volume={vid_b}&collection={vid_coll[vid_b]}"
                      f"&shards={sid_b}")

        def lost_resolved():
            view = get_json(f"http://{master.url}/cluster/repairs"
                            f"?refresh=1")
            for inc in view["resolved_recent"]:
                if inc["kind"] == "lost_shard" \
                        and inc["volume"] == vid_b \
                        and inc["shard"] == sid_b:
                    return inc
            return None

        inc_b = poll(lost_resolved, "lost-shard repair", timeout=120)
        read_all(by_vid[vid_b], "restored_loss")

        view = get_json(f"http://{master.url}/cluster/repairs")
        ttr = view["time_to_re_protection"]
        out = {"servers": n_servers, "volumes": len(by_vid),
               "needles": len(payloads),
               "needle_kb": needle_kb, "readers": readers,
               "scrub_rate_mbps": rate_mbps,
               "scrub_mbps": round(scrub_mbps, 2),
               "healthy_p50_ms": round(healthy_p50, 2),
               "healthy_p99_ms": round(healthy_p99, 2),
               "during_scrub_p50_ms": round(scrub_p50, 2),
               "during_scrub_p99_ms": round(scrub_p99, 2),
               "detection_latency_s": round(detection_s, 3),
               "corruption_ttr_s": inc_a["time_to_re_protection_s"],
               "lost_shard_ttr_s": inc_b["time_to_re_protection_s"],
               "ttr_p50_s": ttr["p50_s"], "ttr_p99_s": ttr["p99_s"],
               "false_positives": 0,
               "restored_bit_identical": True}
        log(f"cluster scrub/repair: {out}")
        return out
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        # master first: its repair loop must die before holders vanish,
        # or it floods the log with doomed rebuilds against a collapsing
        # topology
        master.stop()
        for vs in servers:
            vs.stop()
        _shutil.rmtree(workdir, ignore_errors=True)


def measure_cluster_tiering(n_needles: int = None,
                            needle_kb: int = None,
                            n_servers: int = 3,
                            readers: int = None,
                            writers: int = None,
                            rate_mbps: float = None) -> dict:
    """f4 write-through tiering drill: one sealed hot volume is demoted
    to EC through the shared stripe transport — rate-capped — WHILE
    foreground readers hammer its needles and foreground writers keep
    landing new data in other volumes. There is no drain window: reads
    hit the hot replica until the EC mount flips (the replica delete),
    then the stripe. Reports foreground p50/p99 during demotion vs
    healthy, the demotion MB/s under the cap, zero failed/blocked
    client writes, and bit-identical read-back across the flip."""
    import shutil as _shutil
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.http_util import get_json, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    n_needles = n_needles or config.env_int("SW_BENCH_TIER_NEEDLES")
    needle_kb = needle_kb or config.env_int("SW_BENCH_TIER_KB")
    readers = readers or config.env_int("SW_BENCH_TIER_READERS")
    writers = writers or config.env_int("SW_BENCH_TIER_WRITERS")
    if rate_mbps is None:
        rate_mbps = config.env_float("SW_BENCH_TIER_RATE_MBPS")
    workdir = tempfile.mkdtemp(prefix="swtier_")
    master = MasterServer(
        port=0, volume_size_limit_mb=config.env_int("SW_BENCH_TIER_MB"),
        pulse_seconds=1).start()
    servers = []
    try:
        for i in range(n_servers):
            servers.append(VolumeServer(
                port=0, directories=[os.path.join(workdir, f"v{i}")],
                master_url=master.url, pulse_seconds=1,
                max_volume_counts=[20], ec_backend="numpy").start())

        # fill ONE volume of its own collection: assigns round-robin
        # across the collection's volumes, keep only the first vid
        rng = np.random.default_rng(47)
        a0 = op.assign(master.url, collection="tier")
        vid = int(a0["fid"].split(",")[0])
        payloads = {}
        hot_bytes = 0
        attempts = 0
        while len(payloads) < n_needles and attempts < n_needles * 30:
            attempts += 1
            a = a0 or op.assign(master.url, collection="tier")
            a0 = None
            if int(a["fid"].split(",")[0]) != vid:
                continue
            data = rng.integers(0, 256, needle_kb << 10,
                                dtype=np.uint8).tobytes()
            op.upload(a["url"], a["fid"], data,
                      filename=f"t{len(payloads)}")
            payloads[a["fid"]] = data
            hot_bytes += len(data)
        if len(payloads) < n_needles:
            raise RuntimeError(
                f"could not land {n_needles} needles on volume {vid}")

        # seal it — readonly on every holder, then wait for the
        # master's heartbeat view (the tierer scans that view)
        for vs in servers:
            if vs.store.find_volume(vid):
                post_json(f"http://{vs.url}/admin/volume/readonly"
                          f"?volume={vid}")
                vs.heartbeat_once()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            vols = get_json(
                f"http://{master.url}/cluster/volumes")["volumes"]
            if any(r.get("read_only")
                   for r in vols.get(str(vid), [])):
                break
            time.sleep(0.1)
        else:
            raise TimeoutError(f"volume {vid} never sealed at master")

        def pct(lat):
            lat = sorted(lat)
            if not lat:
                return 0.0, 0.0
            return (lat[len(lat) // 2] * 1e3,
                    lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3)

        def fg_load(run, note):
            """The foreground: readers hammer the sealed volume's
            needles, writers keep landing fresh needles (assigns avoid
            the sealed volume by construction) — while run() executes
            in this thread. The SAME load shape runs for the healthy
            baseline and the demotion window, so the p99 ratio
            isolates the demotion itself, not the writer traffic."""
            stop = threading.Event()
            lat, rerr, wlat, wfail = [], [], [], []
            lock = threading.Lock()
            fids = list(payloads)

            def hammer(tid):
                i = tid
                while not stop.is_set():
                    fid = fids[i % len(fids)]
                    t0 = time.perf_counter()
                    try:
                        got = op.read_file(master.url, fid)
                    except Exception as e:  # noqa: BLE001
                        with lock:
                            rerr.append(f"{note} {fid}: {e!r}")
                        continue
                    dt = time.perf_counter() - t0
                    with lock:
                        lat.append(dt)
                        if got != payloads[fid]:
                            rerr.append(f"{note} {fid}: bytes differ")
                    i += 1

            def writer(tid):
                wrng = np.random.default_rng(700 + tid)
                while not stop.is_set():
                    data = wrng.integers(0, 256, 8 << 10,
                                         dtype=np.uint8).tobytes()
                    t0 = time.perf_counter()
                    try:
                        op.upload_data(master.url, data,
                                       filename=f"w{tid}")
                    except Exception as e:  # noqa: BLE001
                        with lock:
                            wfail.append(repr(e))
                        continue
                    with lock:
                        wlat.append(time.perf_counter() - t0)

            fg = [threading.Thread(target=hammer, args=(t,),
                                   daemon=True)
                  for t in range(readers)]
            fg += [threading.Thread(target=writer, args=(t,),
                                    daemon=True)
                   for t in range(writers)]
            for t in fg:
                t.start()
            try:
                ret = run()
            finally:
                stop.set()
                for t in fg:
                    t.join(timeout=30)
            if rerr:
                raise RuntimeError(rerr[0])
            return ret, lat, wlat, wfail

        # pacing floor: the producer cap applies to SHARD bytes — all
        # k+m rows, padded up to the EC block layout (a small volume
        # still pushes TOTAL x 1MB-small-block shards)
        from seaweedfs_tpu.ec.encoder import ec_shard_base_size
        shard_bytes = TOTAL * ec_shard_base_size(hot_bytes)
        paced_floor_s = shard_bytes / (rate_mbps * 1e6) \
            if rate_mbps else 0.0
        # healthy baseline under the identical foreground load, for
        # about as long as the demotion will run
        _, lat_h, wlat_h, wfail_h = fg_load(
            lambda: time.sleep(max(2.0, paced_floor_s)), "healthy")
        healthy_p50, healthy_p99 = pct(lat_h)

        # same load across the whole demotion, run synchronously here
        master.tierer.age_s = 0.0        # sealed counts immediately
        master.tierer.rate_mbps = rate_mbps
        states, lat_d, wlat_d, wfail_d = fg_load(
            master.tierer.run_pass, "during_demotion")
        if states.get(vid) != "warm":
            raise RuntimeError(f"demotion did not land: {states}")
        during_p50, during_p99 = pct(lat_d)
        w_lat = wlat_h + wlat_d
        w_fail = wfail_h + wfail_d
        # a write is "blocked" if it stalled well past the per-request
        # noise floor — the no-drain claim is that client writes never
        # wait on the data mover
        blocked = sum(1 for dt in wlat_d if dt > 2.0)

        # across the flip: hot replicas are gone, every byte must come
        # back identical off the EC stripe
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and any(
                vs.store.find_volume(vid) for vs in servers):
            time.sleep(0.1)
        bit_identical = all(op.read_file(master.url, fid) == data
                            for fid, data in payloads.items())
        if not bit_identical:
            raise RuntimeError("post-flip read-back differs")

        snap = master.tierer.snapshot()["volumes"][str(vid)]
        out = {"servers": n_servers, "needles": len(payloads),
               "needle_kb": needle_kb,
               "hot_mb": round(hot_bytes / 1e6, 2),
               "readers": readers, "writers": writers,
               "rate_cap_mbps": rate_mbps,
               "healthy_p50_ms": round(healthy_p50, 2),
               "healthy_p99_ms": round(healthy_p99, 2),
               "during_demotion_p50_ms": round(during_p50, 2),
               "during_demotion_p99_ms": round(during_p99, 2),
               "p99_ratio": round(during_p99 / healthy_p99, 2)
               if healthy_p99 else None,
               "reads_during_demotion": len(lat_d),
               "writes_ok": len(w_lat),
               "failed_writes": len(w_fail),
               "blocked_writes": blocked,
               "max_write_ms": round(max(w_lat) * 1e3, 2)
               if w_lat else 0.0,
               "demotion_wall_s": snap["wall_s"],
               "demotion_mbps": snap["demote_mbps"],
               "rate_cap_engaged": bool(
                   paced_floor_s
                   and snap["wall_s"] >= 0.9 * paced_floor_s),
               "bit_identical": True}
        log(f"cluster tiering: {out}")
        return out
    finally:
        # master first: its tierer/repair loops must die before the
        # holders vanish under them
        master.stop()
        for vs in servers:
            vs.stop()
        _shutil.rmtree(workdir, ignore_errors=True)


def bench_diff_gate(record: dict, drill: str = None):
    """Transport-parity gate: write this run's record next to the
    historical BENCH_r*.json series and auto-diff against the newest
    prior record via tools/bench_diff.py. Classified metrics that
    regressed >20% exit 2 — the gate the unified-transport refactor
    must hold (rebuild/encode throughput within noise of the pre-
    refactor records). SW_BENCH_DIFF=0 disables the diff (the record
    is still written). Standalone drills write BENCH_last_<drill>.json
    wrapped as {drill: record} so their metric names line up with the
    full records' nested extras; full runs append the next
    BENCH_r<NN>.json."""
    import glob
    import re
    repo = os.path.dirname(os.path.abspath(__file__))
    tools = os.path.join(repo, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    try:
        import bench_diff
    except Exception as e:  # noqa: BLE001 - the gate must not kill emit
        log(f"bench_diff unavailable, gate skipped: {e!r}")
        return
    prior = sorted(glob.glob(os.path.join(repo, "BENCH_r[0-9]*.json")))
    wrapped = {drill: record} if drill else dict(record)
    if drill:
        out_path = os.path.join(repo, f"BENCH_last_{drill}.json")
    else:
        nums = [int(re.search(r"BENCH_r(\d+)", p).group(1))
                for p in prior]
        out_path = os.path.join(
            repo, f"BENCH_r{(max(nums) if nums else 0) + 1:02d}.json")
    with open(out_path, "w") as f:
        json.dump(wrapped, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"bench record written: {out_path}")
    if not config.env_bool("SW_BENCH_DIFF"):
        return
    if not prior:
        log("bench_diff: no prior BENCH_r*.json, gate skipped")
        return
    old_path = prior[-1]
    try:
        report = bench_diff.diff_records(
            bench_diff.load_record(old_path),
            bench_diff.load_record(out_path), threshold=0.2)
    except Exception as e:  # noqa: BLE001 - unreadable prior record
        log(f"bench_diff failed against {old_path}: {e!r}")
        return
    log(bench_diff.render_text(report, old_path, out_path))
    if report["regressions"]:
        log(f"bench_diff GATE: {len(report['regressions'])} metrics "
            f"regressed >20% vs {os.path.basename(old_path)}")
        raise SystemExit(2)


def _jax_provenance() -> dict:
    """Stamp every emitted record with the device the math ran on, as
    JAX reports it."""
    import jax
    devs = jax.devices()
    return {"jax_platform": devs[0].platform,
            "jax_backend": devs[0].device_kind,
            "jax_device_count": len(devs)}


def emit(value: float, vs_baseline: float, kind: str, **extras):
    """ONE JSON line whose value/vs_baseline carry the like-for-like
    comparison: `device_kernel_chained`, the chained-slope device kernel
    rate vs the native CPU in-memory encode — both free of per-dispatch
    cost and file I/O."""
    line = {"metric": "ec_encode_rs10_4_mbps",
            "value": round(value, 1), "unit": "MB/s",
            "vs_baseline": round(vs_baseline, 2),
            "headline_kind": kind}
    line.update(_jax_provenance())
    line.update(extras)
    print(json.dumps(line))
    # every emitted record is written to the next BENCH_r<NN>.json and
    # auto-diffed against the newest prior one (exit 2 on >20%
    # regressions; SW_BENCH_DIFF=0 to disable)
    bench_diff_gate(line)


def run_cluster_drill_subprocess(size_mb: int, n_servers: int) -> dict:
    """BASELINE config 5 with `-ec.backend mesh` on the 8-device
    virtual CPU mesh — in a fresh process, because the device-count
    flag must precede the first jax initialization. The parent may hold
    the chip by now (a chip belongs to one process); the child is told
    JAX_PLATFORMS=cpu and never needs it."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["SW_BENCH_CLUSTER_MB"] = str(size_mb)
    env["SW_BENCH_CLUSTER_SERVERS"] = str(n_servers)
    env["SW_BENCH_CLUSTER_BACKEND"] = "mesh"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--cluster-drill"],
        env=env, capture_output=True, text=True, timeout=1800)
    for raw in out.stdout.splitlines():
        if raw.startswith("CLUSTER_DRILL "):
            got = json.loads(raw.split(" ", 1)[1])
            got["devices"] = "8x virtual cpu"
            log(f"cluster rebuild (cpu mesh subprocess): {got}")
            return got
    raise RuntimeError(
        f"cluster drill subprocess rc={out.returncode}: "
        f"{out.stdout[-200:]} {out.stderr[-300:]}")


def _dp_durable_trial(mode: str, seconds: float, batch_us: int,
                      plane: bool = True) -> dict:
    """One write-phase trial with SW_PLANE_FSYNC_MODE=mode on a SINGLE
    volume, so the fsync-per-append baselines genuinely serialize each
    append behind its own fdatasync — the throughput crater group
    commit exists to fix. The group trial runs with batch_us=0: natural
    batching, riders accumulate while the previous fdatasync is in
    flight (Haystack's needle-log sync discipline). plane=False runs
    the same load against the Python append path (fast_port=-1): the
    pre-PR durable configuration, where every write pays its own
    fdatasync pair inside the Python server."""
    import io
    import shutil as _shutil
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.command.benchmark import run_native_benchmark
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    knobs = {"SW_PLANE_FSYNC_MODE": mode,
             "SW_PLANE_FSYNC_BATCH_US": str(batch_us)}
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    workdir = tempfile.mkdtemp(prefix=f"swdpdur_{mode}_")
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = None
    try:
        vs = VolumeServer(port=0,
                          directories=[os.path.join(workdir, "v")],
                          master_url=master.url, pulse_seconds=1,
                          max_volume_counts=[1],
                          fast_port=0 if plane else -1).start()
        deadline = time.monotonic() + 15
        while True:
            try:
                # same collection the benchmark writes into: with a
                # single volume slot, an assign in "" would consume it
                op.assign(master.url, collection="benchmark")
                break
            except Exception:  # noqa: BLE001 - cluster still assembling
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)
        buf = io.StringIO()
        run_native_benchmark(master.url, file_size=1024,
                             concurrency=config.env_int(
                                 "SW_BENCH_DP_DURABLE_CONNS"),
                             seconds=seconds, pool=1024, out=buf)
        trial = {"mode": mode, "batch_us": batch_us, "plane": plane}
        for raw in buf.getvalue().splitlines():
            if raw.startswith("{") and '"write"' in raw:
                p = json.loads(raw)
                trial["write_rps"] = p["rps"]
                trial["write_errors"] = p["errors"]
        snap = vs.fast_plane.sync_stats() if vs.fast_plane else None
        if snap and snap["batches"]:
            trial["fsync_batches"] = snap["batches"]
            trial["fsync_riders"] = snap["riders"]
            trial["riders_per_batch"] = round(
                snap["riders"] / snap["batches"], 1)
        return trial
    finally:
        if vs is not None:
            vs.stop()
        master.stop()
        _shutil.rmtree(workdir, ignore_errors=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def measure_dp_durability(seconds: float = None) -> dict:
    """Durable-mode trial set. The headline claim: group-commit write
    RPS must beat the measured fsync-per-append baseline >=10x while
    holding >=0.4x the non-durable plane path under identical
    load/volume shape. The primary baseline is the pre-PR durable
    configuration — the Python append path paying an fdatasync pair
    per write (plane disabled, mode=always); the >=0.4x-of-off guard
    keeps that ratio from being credited to the native plane itself.
    The native plane's own always mode is reported as a second,
    stricter baseline (informational: on single-core hosts with
    sub-200us fdatasync it converges toward the CPU ceiling)."""
    seconds = seconds or config.env_float("SW_BENCH_DP_DURABLE_SECONDS")

    def isolated(mode, plane=True):
        # drain the previous trial's dirty pages first: background
        # writeback steals CPU from the next trial and a busy journal
        # lets per-append fsyncs piggyback on in-flight commits, so
        # back-to-back trials contaminate each other in BOTH directions
        os.sync()
        time.sleep(1.0)
        return _dp_durable_trial(mode, seconds, 0, plane=plane)

    trials = {"off": isolated("off"),
              "fsync_per_append": isolated("always", plane=False),
              "always": isolated("always"),
              "group": isolated("group")}
    grp = trials["group"].get("write_rps", 0.0)
    base = trials["fsync_per_append"].get("write_rps", 0.0)
    alw = trials["always"].get("write_rps", 0.0)
    off = trials["off"].get("write_rps", 0.0)
    out = {"modes": trials,
           "group_vs_fsync_per_append":
               round(grp / base, 2) if base else None,
           "group_vs_always_native":
               round(grp / alw, 2) if alw else None,
           "group_vs_off": round(grp / off, 2) if off else None,
           "targets": {"group_vs_fsync_per_append_min": 10.0,
                       "group_vs_off_min": 0.4}}
    out["ok"] = bool(base and off and grp / base >= 10.0
                     and grp / off >= 0.4)
    log(f"data-plane durability: group={grp} fsync_per_append={base} "
        f"always_native={alw} off={off} "
        f"-> group_vs_fsync_per_append="
        f"{out['group_vs_fsync_per_append']} "
        f"group_vs_always_native={out['group_vs_always_native']} "
        f"group_vs_off={out['group_vs_off']} ok={out['ok']}")
    return out


def measure_dp_crash_consistency(runs: int = None) -> dict:
    """The group-commit ack contract under fail-stop: kill -9 a durable
    (SW_PLANE_FSYNC_MODE=group) volume server subprocess mid-burst,
    restart on the same directories, and verify EXACT counts — every
    acked needle reads back bit-identical (acked is a subset of
    recovered); needles never acked are reported separately and never
    counted as durable (an unacked duplicate on disk is harmless)."""
    import http.client
    import shutil as _shutil
    import signal as _signal
    import subprocess
    import threading
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    runs = runs if runs is not None \
        else config.env_int("SW_BENCH_DP_CRASH_RUNS")
    out = {"runs": [], "acked_total": 0, "acked_lost_total": 0}
    for run_no in range(runs):
        workdir = tempfile.mkdtemp(prefix="swdpcrash_")
        master = MasterServer(port=0, pulse_seconds=1).start()
        child, vs2 = None, None
        try:
            env = dict(os.environ)
            # the parent may hold the chip; this child serves plain
            # needle writes and never needs it
            env["JAX_PLATFORMS"] = "cpu"
            env["SW_PLANE_FSYNC_MODE"] = "group"
            env["SW_BENCH_DP_DIR"] = os.path.join(workdir, "v")
            env["SW_BENCH_DP_MASTER"] = master.url
            child = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--dp-crash-server"],
                env=env, stdout=subprocess.PIPE, text=True)
            ready = None
            for raw in child.stdout:
                if raw.startswith("DP_CRASH_READY "):
                    ready = json.loads(raw.split(" ", 1)[1])
                    break
            if ready is None:
                raise RuntimeError("crash-server child never came up")
            fast = ready["fast_url"]
            deadline = time.monotonic() + 15
            while True:
                try:
                    a = op.assign(master.url, count=4000)
                    break
                except Exception:  # noqa: BLE001 - child still pulsing
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)
            fids = list(op.expand_batch_fids(a["fid"], int(a["count"])))
            acked = {}        # fid -> payload bytes (response was read)
            attempted = set()  # posted, ack unknown
            lock = threading.Lock()
            killed = threading.Event()
            boundary = "swdpcrashb"
            ctype = f"multipart/form-data; boundary={boundary}"

            def body_for(fid, i):
                data = (f"{fid}|{i}|".encode() * 64)[:1024]
                raw = (f"--{boundary}\r\nContent-Disposition: "
                       f'form-data; name="file"; filename="c.bin"\r\n'
                       f"Content-Type: application/octet-stream"
                       f"\r\n\r\n").encode() + data + \
                    f"\r\n--{boundary}--\r\n".encode()
                return raw, data

            def writer(tid):
                conn = http.client.HTTPConnection(fast, timeout=10)
                for i in range(tid, len(fids), 8):
                    if killed.is_set():
                        break
                    fid = fids[i]
                    raw, data = body_for(fid, i)
                    with lock:
                        attempted.add(fid)
                    try:
                        conn.request("POST", f"/{fid}", body=raw,
                                     headers={"Content-Type": ctype})
                        r = conn.getresponse()
                        r.read()
                        if r.status == 200:
                            with lock:
                                acked[fid] = data
                    except Exception:  # noqa: BLE001 - ack unknown
                        conn.close()
                        if killed.is_set():
                            break
                        conn = http.client.HTTPConnection(fast,
                                                          timeout=10)
                conn.close()

            def killer():
                # fire mid-burst: enough acks to be meaningful, well
                # before the pool drains
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline:
                    with lock:
                        if len(acked) >= 200:
                            break
                    time.sleep(0.002)
                os.kill(child.pid, _signal.SIGKILL)
                killed.set()

            threads = [threading.Thread(target=writer, args=(t,))
                       for t in range(8)] + \
                [threading.Thread(target=killer)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            child.wait(timeout=30)
            # restart on the SAME directories: torn (unacked) tails may
            # truncate, every acked needle must survive bit-identical
            vs2 = VolumeServer(port=0,
                               directories=[os.path.join(workdir, "v")],
                               master_url=master.url, pulse_seconds=1,
                               max_volume_counts=[8]).start()
            lost = []
            for fid, want in acked.items():
                conn = http.client.HTTPConnection(vs2.url, timeout=10)
                conn.request("GET", f"/{fid}")
                r = conn.getresponse()
                got = r.read()
                conn.close()
                if r.status != 200 or got != want:
                    lost.append(fid)
            unacked = [f for f in attempted if f not in acked]
            unacked_landed = 0
            for fid in unacked:
                conn = http.client.HTTPConnection(vs2.url, timeout=10)
                conn.request("GET", f"/{fid}")
                r = conn.getresponse()
                r.read()
                conn.close()
                if r.status == 200:
                    unacked_landed += 1
            rec = {"acked": len(acked), "acked_lost": len(lost),
                   "unacked_attempts": len(unacked),
                   "unacked_landed_harmless": unacked_landed}
            if lost:
                rec["lost_fids"] = lost[:10]
            out["runs"].append(rec)
            out["acked_total"] += len(acked)
            out["acked_lost_total"] += len(lost)
            log(f"crash drill run {run_no + 1}/{runs}: {rec}")
        finally:
            if child is not None and child.poll() is None:
                child.kill()
                child.wait()
            if vs2 is not None:
                vs2.stop()
            master.stop()
            _shutil.rmtree(workdir, ignore_errors=True)
    out["ok"] = out["acked_lost_total"] == 0 and out["acked_total"] > 0
    return out


def measure_data_plane(seconds: float = None) -> dict:
    """The reference's published headline benchmark (README.md:477-522,
    `weed benchmark`: 15,708 writes/s and 47,019 reads/s of 1KB files):
    an in-process master+volume server driven by the C++ keep-alive
    load engine (`weed benchmark -native`), so the number measures the
    servers, not the Python client. Writes land on the native plane's
    fast POST path, reads on its fast GET path; `errors` must be 0 for
    the number to count."""
    import io
    import shutil as _shutil
    from seaweedfs_tpu.command.benchmark import run_native_benchmark
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    seconds = seconds or config.env_float("SW_BENCH_DP_SECONDS")
    workdir = tempfile.mkdtemp(prefix="swdp_")
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = None
    try:
        vs = VolumeServer(port=0,
                          directories=[os.path.join(workdir, "v")],
                          master_url=master.url, pulse_seconds=1,
                          max_volume_counts=[8]).start()
        # writable volume available (growth on demand + immediate
        # heartbeat push) — poll an assign instead of sleeping a pulse
        from seaweedfs_tpu.client import operation as op
        deadline = time.monotonic() + 15
        while True:
            try:
                op.assign(master.url)
                break
            except Exception:  # noqa: BLE001 - cluster still assembling
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)
        buf = io.StringIO()
        run_native_benchmark(master.url, file_size=1024,
                             concurrency=config.env_int("SW_BENCH_DP_CONNS"),
                             seconds=seconds, pool=2048, out=buf)
        out = {}
        for raw in buf.getvalue().splitlines():
            if not raw.startswith("{"):
                continue
            p = json.loads(raw)
            key = "write" if p["phase"] == "write" else "read"
            out[f"{key}_rps"] = p["rps"]
            out[f"{key}_errors"] = p["errors"]
        # reference README req/s on its MacBook-i7 run (BASELINE.md)
        out["vs_ref_write_15708"] = round(out["write_rps"] / 15708.23, 2)
        out["vs_ref_read_47019"] = round(out["read_rps"] / 47019.38, 2)
        out["file_size"] = 1024
        out["note"] = ("native C++ data plane under the native load "
                       "engine, 1KB files; reference numbers were "
                       "measured on different hardware (MacBook i7)")
        log(f"data plane: {out}")
    finally:
        if vs is not None:
            vs.stop()
        master.stop()
        _shutil.rmtree(workdir, ignore_errors=True)
    # durable-mode trial set + kill -9 crash-consistency drill
    if config.env_float("SW_BENCH_DP_DURABLE_SECONDS") > 0:
        out["durability"] = measure_dp_durability()
    if config.env_int("SW_BENCH_DP_CRASH_RUNS") > 0:
        out["crash_consistency"] = measure_dp_crash_consistency()
    return out


def _plane_quantile_us(buckets, total: int, q: float) -> float:
    """Quantile estimate from the plane's non-cumulative latency
    buckets ([(bound_us or None, count), ...]); returns the upper bound
    of the bucket the quantile falls in."""
    if not total:
        return 0.0
    target = q * total
    cum = 0
    last = 0.0
    for bound, count in buckets:
        cum += count
        if cum >= target:
            return float(bound) if bound is not None else last * 2
        if bound is not None:
            last = float(bound)
    return last


def measure_cluster_plane_read() -> dict:
    """`cluster_plane_read`: the hot-path observability drill — keep-
    alive GETs against the native plane with telemetry on, reporting the
    plane's OWN latency quantiles (from the in-plane histogram), the
    redirect ratio and slow-ring depth, then the same read pass with
    telemetry off (the SW_PLANE_STATS=0 escape hatch toggles the same
    atomic) to assert the counters+clock cost is in-noise."""
    import http.client
    import shutil as _shutil
    from seaweedfs_tpu.server import native_plane
    from seaweedfs_tpu.server.http_util import post_json, post_multipart
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    if not native_plane.available():
        raise RuntimeError("native plane unavailable")
    workdir = tempfile.mkdtemp(prefix="swplane_")
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = None
    try:
        vs = VolumeServer(port=0,
                          directories=[os.path.join(workdir, "v")],
                          master_url=master.url, pulse_seconds=1,
                          max_volume_counts=[8],
                          ec_backend="numpy").start()
        assert vs.fast_plane is not None, "plane failed to start"
        paths = []
        deadline = time.monotonic() + 15
        for i in range(128):
            while True:
                try:
                    a = post_json(f"http://{master.url}/dir/assign", {})
                    break
                except Exception:  # noqa: BLE001 - cluster assembling
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)
            post_multipart(f"http://{a['url']}/{a['fid']}", "b.bin",
                           b"plane-bench|%04d|" % i * 64,
                           "application/octet-stream")
            paths.append("/" + a["fid"])
        host, port = vs.fast_url.split(":")

        def read_pass(n):
            lat = []
            c = http.client.HTTPConnection(host, int(port), timeout=10)
            try:
                for i in range(n):
                    t0 = time.perf_counter()
                    c.request("GET", paths[i % len(paths)])
                    r = c.getresponse()
                    r.read()
                    lat.append(time.perf_counter() - t0)
                    if r.status != 200:
                        raise RuntimeError(f"plane status {r.status}")
            finally:
                c.close()
            lat.sort()
            return lat

        read_pass(200)   # warm the mirror, the page cache, the client
        n = 2000
        on_p50, off_p50 = [], []
        client_lat = None
        for _ in range(max(2, config.env_int("SW_BENCH_TRIALS"))):
            vs.fast_plane.set_stats_enabled(True)
            lat = read_pass(n)
            client_lat = lat
            on_p50.append(lat[len(lat) // 2])
            vs.fast_plane.set_stats_enabled(False)
            lat = read_pass(n)
            off_p50.append(lat[len(lat) // 2])
        vs.fast_plane.set_stats_enabled(True)
        snap = vs.fast_plane.stats()
        total = snap["lat_count"]
        requests = max(1, snap["requests"])
        out = {
            "reads": n * len(on_p50),
            "plane_p50_us": _plane_quantile_us(snap["buckets"], total,
                                               0.50),
            "plane_p99_us": _plane_quantile_us(snap["buckets"], total,
                                               0.99),
            "client_p50_us": round(client_lat[len(client_lat) // 2]
                                   * 1e6, 1),
            "client_p99_us": round(
                client_lat[int(len(client_lat) * 0.99)] * 1e6, 1),
            "redirect_ratio": round(snap["redirects"] / requests, 4),
            "slow_ring_depth": len(vs.fast_plane.slow_requests()),
        }
        # best-of-trials is stable against scheduler noise; the
        # telemetry cost per request is tens of ns against a >=50us
        # loopback request, so anything past 15%+10us is a regression,
        # not noise
        best_on, best_off = min(on_p50), min(off_p50)
        out["stats_on_p50_us"] = round(best_on * 1e6, 1)
        out["stats_off_p50_us"] = round(best_off * 1e6, 1)
        out["overhead_pct"] = round(
            (best_on - best_off) / best_off * 100, 2)
        out["in_noise"] = best_on <= best_off * 1.15 + 10e-6
        assert out["in_noise"], \
            f"plane telemetry overhead out of noise: {out}"
        log(f"cluster plane read: {out}")
        return out
    finally:
        if vs is not None:
            vs.stop()
        master.stop()
        _shutil.rmtree(workdir, ignore_errors=True)


def secondary_configs(chained_by_geo: dict) -> dict:
    """BASELINE configs 3-5 plus the reference's own req/s headline,
    each scaled by env. They report alongside the headline; a drill that
    fails fails the run."""
    return {
        "data_plane": measure_data_plane(),
        "rs_geometries": measure_geometries(
            config.env_int("SW_BENCH_GEO_MB"), chained_by_geo),
        "batched_small_needles": measure_batched_small_needles(
            config.env_int("SW_BENCH_SMALL_VOLS"),
            config.env_int("SW_BENCH_SMALL_NEEDLES")),
        # hot-path observability drill: the plane's own latency
        # quantiles, redirect ratio and slow-ring depth, plus the
        # telemetry-overhead in-noise assertion vs SW_PLANE_STATS=0
        "cluster_plane_read": measure_cluster_plane_read(),
        # loss-masked reads under live traffic: healthy vs degraded
        # p99, batched engine vs naive per-read reconstruct
        "cluster_degraded_read": measure_cluster_degraded_read(),
        # rolling-failure integrity drill: scrub detection latency,
        # scrub overhead on the foreground p99, time-to-re-protection
        "cluster_scrub_repair": measure_cluster_scrub_repair(),
        # f4 write-through tiering: hot->warm demotion through the
        # shared stripe transport under live reads/writes
        "cluster_tiering": measure_cluster_tiering(),
        # config 5 with a DEVICE backend: the virtual CPU mesh in a
        # subprocess, and the mesh over this process's chip(s)
        "cluster_rebuild": run_cluster_drill_subprocess(
            config.env_int("SW_BENCH_CLUSTER_MB"),
            config.env_int("SW_BENCH_CLUSTER_SERVERS")),
        "cluster_rebuild_device": measure_cluster_rebuild(
            config.env_int("SW_BENCH_CLUSTER_TPU_MB"),
            config.env_int("SW_BENCH_CLUSTER_SERVERS"), backend="mesh"),
    }


def main():
    dat_mb = config.env_int("SW_BENCH_DAT_MB")
    slab_mb = config.env_int("SW_BENCH_SLAB_MB")
    user_dir = config.env_str("SW_BENCH_DIR")
    workdir = user_dir or tempfile.mkdtemp(prefix="swbench_")
    os.makedirs(workdir, exist_ok=True)
    base = os.path.join(workdir, "1")
    try:
        dat_size = generate_dat(base + ".dat", dat_mb)

        cpu_mbps = measure_cpu_e2e(base, dat_size)
        cpu_digests = shard_digests(base)
        cpu_rebuild = measure_cpu_rebuild(base, dat_size)
        remove_shards(base)
        cpu_inmem = measure_cpu_inmem(slab_mb)

        log(f"devices: {require_accelerator()}")
        # chained kernel figures FIRST, on a quiet device, for all three
        # geometries, so every per-geometry number is slope-derived
        chained_by_geo = {
            (k, m): measure_device_chained(slab_mb, k, m)
            for k, m in ((K, M), (6, 3), (20, 4))}
        chained, chained_diag = chained_by_geo[(K, M)]
        tpu_mbps, stages = measure_tpu_e2e(base, dat_size, slab_mb)
        # a digest mismatch is data corruption and fails the bench
        if shard_digests(base) != cpu_digests:
            raise AssertionError("TPU shards != native shards")
        log("all 14 shard digests identical to the native path")
        measure_tpu_rebuild(base, dat_size, slab_mb)
        _, _, thr = measure_device_resident(slab_mb)
        extras = {"e2e": {"tpu_e2e_mbps": round(tpu_mbps, 1),
                          "cpu_e2e_mbps": round(cpu_mbps, 1),
                          "vs_cpu_e2e": round(tpu_mbps / cpu_mbps, 2),
                          "stages": stages},
                  "cpu_inmem_mbps": round(cpu_inmem),
                  "cpu_rebuild_mbps": round(cpu_rebuild),
                  "device_percall_mbps": round(thr)}
        extras.update(secondary_configs(chained_by_geo))
        emit(chained, chained / cpu_inmem, "device_kernel_chained",
             chained_fit=chained_diag, **extras)
    finally:
        if not config.env_bool("SW_BENCH_KEEP"):
            if user_dir:
                from seaweedfs_tpu.ec import to_ext
                # caller-provided dir may hold unrelated files: remove only
                # what the bench created
                for p in [base + ".dat"] + [
                        base + to_ext(i) for i in range(TOTAL)]:
                    if os.path.exists(p):
                        os.remove(p)
            else:
                shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    # SIGUSR1 dumps all thread stacks to stderr — first diagnostic for
    # a wedged bench run (drill deadlocks)
    import faulthandler
    import signal
    faulthandler.register(signal.SIGUSR1)
    if "--cluster-drill" in sys.argv:
        # subprocess mode: BASELINE config 5 under whatever JAX_PLATFORMS
        # / XLA_FLAGS the parent set (virtual CPU mesh), one line out
        result = measure_cluster_rebuild(
            config.env_int("SW_BENCH_CLUSTER_MB"),
            config.env_int("SW_BENCH_CLUSTER_SERVERS"))
        print("CLUSTER_DRILL " + json.dumps(result), flush=True)
    elif "--dp-crash-server" in sys.argv:
        # crash-drill child: a volume server the parent kill -9s
        # mid-burst (group-commit fsync mode comes in via the env)
        from seaweedfs_tpu.server.volume_server import VolumeServer
        _vs = VolumeServer(
            port=0, directories=[config.env_str("SW_BENCH_DP_DIR")],
            master_url=config.env_str("SW_BENCH_DP_MASTER"),
            pulse_seconds=1, max_volume_counts=[8]).start()
        print("DP_CRASH_READY " + json.dumps(
            {"url": _vs.url, "fast_url": _vs.fast_url}), flush=True)
        signal.pause()
    elif "data_plane" in sys.argv:
        # standalone data-plane bench: the saturation pass plus the
        # durable-mode trial set and the kill -9 crash-consistency drill
        result = measure_data_plane()
        result.update(_jax_provenance())
        print(json.dumps(result), flush=True)
        bench_diff_gate(result, drill="data_plane")
    elif "cluster_scrub_repair" in sys.argv:
        # standalone integrity drill: detection latency, scrub MB/s,
        # scrub overhead on the foreground p99, TTR per incident kind
        result = measure_cluster_scrub_repair()
        result.update(_jax_provenance())
        print(json.dumps(result), flush=True)
        bench_diff_gate(result, drill="cluster_scrub_repair")
    elif "cluster_tiering" in sys.argv:
        # standalone f4 tiering drill: foreground p50/p99 during a
        # rate-capped hot->warm demotion vs healthy, demotion MB/s,
        # zero failed/blocked writes, bit-identical across the flip
        result = measure_cluster_tiering()
        result.update(_jax_provenance())
        print(json.dumps(result), flush=True)
        bench_diff_gate(result, drill="cluster_tiering")
    else:
        main()
