"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests never touch the real TPU; multi-chip sharding is validated on
8 virtual CPU devices (chip_smoke.py --chips 4 is the run on real ones).

The env vars are set permanently (not save/restored) on purpose: tests
spawn server subprocesses that must inherit the CPU platform. They are
set here, before anything imports jax, which is all it takes. The
explicit JAX_PLATFORMS=cpu is also what lets `-ec.backend tpu|mesh` run
its device programs off the TPU (seaweedfs_tpu/util/jax_platform.py).

Timing knobs (registered in seaweedfs_tpu/util/config.py) are defaulted
near-zero here so the suite doesn't spend its wall clock inside stdlib
poll loops and retry backoffs.  setdefault, not assignment: an explicit
SW_* in the caller's environment still wins.  Knobs deliberately NOT
set:

- SW_PULSE_S: tests pass pulse_seconds explicitly where it matters;
  a global near-zero pulse would make dead-node pruning (pulse x 5)
  race GIL-heavy JAX compiles.
- SW_REPAIR_INTERVAL_S / SW_EC_SCRUB_IDLE_S=near-zero: background
  repair/scrub would resurrect shards that tests intentionally
  corrupt or delete.  Scrub's idle loop is instead disabled outright
  (SW_EC_SCRUB_IDLE_S=0 means "manual triggers only").

SW_LOCK_DEBUG=1 swaps every make_lock()/make_rlock() in the package
for a recording wrapper; pytest_sessionfinish merges the in-process
lock-acquisition graph with per-subprocess dumps (SW_LOCK_GRAPH_DIR)
and fails the session on any lock-order cycle — see
seaweedfs_tpu/util/locks.py and tools/analyze.py --lock-report.
"""

import os
import sys
import tempfile

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from seaweedfs_tpu.util.jax_platform import (  # noqa: E402
    set_host_device_count_flag)

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = set_host_device_count_flag(8)

# Server accept-loops poll at 20 ms so every httpd.shutdown() in a test
# teardown costs ~0.02 s instead of the stdlib's 0.5 s default.
os.environ.setdefault("SW_HTTP_POLL_S", "0.02")
# Filer deletion sweep: same poll-bound shutdown story.
os.environ.setdefault("SW_FILER_TICK_S", "0.02")
# Retries spin instead of sleeping; tests assert on outcomes, not pacing.
os.environ.setdefault("SW_RETRY_BACKOFF_SCALE", "0")
# 0 disables the idle scrub loop entirely (tests trigger scrubs manually).
os.environ.setdefault("SW_EC_SCRUB_IDLE_S", "0")
# Idle HTTP pool sockets would otherwise pin teardown-ordered servers.
os.environ.setdefault("SW_HTTP_POOL_MAX_IDLE_S", "5")

# Lock-order recording: in-process via util.locks.RECORDER, subprocess
# servers dump their graphs to this dir at exit (they inherit the env).
_LOCK_GRAPH_DIR = None
if os.environ.get("SW_LOCK_DEBUG", "") == "":
    os.environ["SW_LOCK_DEBUG"] = "1"
if os.environ["SW_LOCK_DEBUG"] == "1" and not os.environ.get("SW_LOCK_GRAPH_DIR"):
    _LOCK_GRAPH_DIR = tempfile.mkdtemp(prefix="sw_lockgraph_")
    os.environ["SW_LOCK_GRAPH_DIR"] = _LOCK_GRAPH_DIR


def wait_until(pred, timeout=8.0, interval=0.02):
    """Event-driven converge helper: poll an asynchronously-updated
    predicate (pulse propagation to the master, queue drains, lock
    expiry) instead of sleeping across a pulse boundary. Returns the
    first truthy value pred() produces, or its final (falsy) value at
    the deadline — callers assert on the result, so a converged cluster
    costs milliseconds and a broken one still fails loudly."""
    import time
    deadline = time.monotonic() + timeout
    while True:
        v = pred()
        if v or time.monotonic() >= deadline:
            return v
        time.sleep(interval)


@pytest.fixture
def private_packed_programs(monkeypatch):
    """For a test that runs a tiny geometry through the `tpu` and the
    `mesh` codec in turn: the mesh pads a width to its eight CPU devices,
    so the two compile two exact widths of one width bucket, which is
    what the recompile sentinel latches on — and tests/test_device_stats.py
    asserts the process's own is unlatched when it shares an xdist worker
    with that test. The test's programs go to a factory cache and a
    DeviceStats of its own (as tests/test_mesh_codec._private_programs
    and tests/test_rs_pallas._own_device_stats have it); the process's
    are put back untouched."""
    import functools

    from seaweedfs_tpu.ops import device_stats, rs_tpu
    from seaweedfs_tpu.parallel import mesh_codec
    monkeypatch.setattr(device_stats, "DEVICE_STATS",
                        device_stats.DeviceStats())
    monkeypatch.setattr(rs_tpu, "_packed_fn", functools.lru_cache(
        maxsize=None)(rs_tpu._packed_fn.__wrapped__))
    monkeypatch.setattr(mesh_codec, "_FNS", {})


def pytest_sessionfinish(session, exitstatus):
    """Fail the run if the merged lock-acquisition graph has a cycle."""
    from seaweedfs_tpu.util import locks as _locks

    if not _locks.debug_enabled():
        return
    extra = _locks.load_graph_dir(os.environ.get("SW_LOCK_GRAPH_DIR", ""))
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
    from analyze import LOCK_ORDER_ALLOWED_EDGES  # noqa: E402

    cycles = _locks.RECORDER.cycles(
        extra_edges=extra, allowed=LOCK_ORDER_ALLOWED_EDGES)
    if cycles:
        rep = session.config.pluginmanager.get_plugin("terminalreporter")
        lines = ["lock-order cycles detected (potential ABBA deadlock):"]
        for cyc in cycles:
            lines.append("  " + " -> ".join(list(cyc) + [cyc[0]]))
        msg = "\n".join(lines)
        if rep is not None:
            rep.write_sep("=", "lock-order check FAILED", red=True)
            rep.write_line(msg)
        else:  # pragma: no cover - no terminal plugin
            print(msg, file=sys.stderr)
        session.exitstatus = 3
