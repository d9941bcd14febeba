"""Gated chaos drills (SW_CHAOS_TESTS=1): live clusters under failure
injection with full byte-verification at the end.

These are the round-3 drills that caught real bugs (maintenance-window
write failures, an EC wrong-needle read via cross-thread fd reuse, a
FUSE EIO from stale watch-map routes) — kept runnable so regressions
in the failure paths stay discoverable. Each takes ~1 minute; they are
gated out of the default suite for runtime, not flakiness: every drill
asserts ZERO client-visible errors and ZERO corruption.
"""

import io
import os
import random
import tempfile
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.client import operation as op
from seaweedfs_tpu.server.filer_server import FilerServer
from seaweedfs_tpu.server.http_util import HttpError, http_call
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer

# the two longer drills stay opt-in; the node-death drill runs by
# default on a compressed schedule (keep at least one
# live-cluster failure drill in every `pytest tests` run)
_FULL = bool(os.environ.get("SW_CHAOS_TESTS"))
gated = pytest.mark.skipif(
    not _FULL,
    reason="~1 min/drill of live-cluster chaos; set SW_CHAOS_TESTS=1")


def _spawn_cluster(tmp, n_vols=3, replication="001"):
    master = MasterServer(port=0, volume_size_limit_mb=48,
                          pulse_seconds=1,
                          default_replication=replication).start()
    dirs = [os.path.join(tmp, f"v{i}") for i in range(n_vols)]
    servers = [VolumeServer(port=0, directories=[dirs[i]],
                            master_url=master.url, pulse_seconds=1,
                            max_volume_counts=[20],
                            ec_backend="numpy").start()
               for i in range(n_vols)]
    # converge on heartbeat registration instead of sleeping across a
    # pulse boundary (conftest knob policy: poll, don't sleep)
    from conftest import wait_until
    from seaweedfs_tpu.server.http_util import get_json
    assert wait_until(
        lambda: len(get_json(f"http://{master.url}/cluster/status")
                    .get("nodes", [])) >= n_vols, timeout=15)
    filer = FilerServer(port=0, master_url=master.url,
                        chunk_size=64 << 10,
                        replication=replication).start()
    return master, servers, dirs, filer


def _client_pool(filer, model, mlock, errors, stop, counter, n=5,
                 deletes=False):
    def client(tid):
        rng = random.Random(tid)
        while not stop.is_set():
            r = rng.random()
            try:
                if r < 0.5:
                    with mlock:
                        counter[0] += 1
                        path = f"/c/t{tid}/f{counter[0]}.bin"
                    data = bytes([tid]) * rng.randrange(1, 150_000)
                    http_call("PUT", f"http://{filer.url}{path}", data,
                              {"Content-Type":
                               "application/octet-stream"}, timeout=60)
                    with mlock:
                        model[path] = data
                elif deletes and r > 0.9:
                    with mlock:
                        if not model:
                            continue
                        path = rng.choice(sorted(model))
                        del model[path]
                    http_call("DELETE", f"http://{filer.url}{path}",
                              timeout=60)
                else:
                    with mlock:
                        if not model:
                            continue
                        path, data = rng.choice(sorted(model.items()))
                    got = http_call("GET", f"http://{filer.url}{path}",
                                    timeout=60)
                    if got != data:
                        errors.append(f"MISMATCH {path}")
            except HttpError as e:
                if e.status != 404:
                    errors.append(f"c{tid}: {e.status} {str(e)[:110]}")
            except Exception as e:  # noqa: BLE001 - recorded
                errors.append(f"c{tid}: {repr(e)[:100]}")
    return [threading.Thread(target=client, args=(i,)) for i in range(n)]


def _verify_all(filer, model):
    bad = []
    for path, data in sorted(model.items()):
        try:
            if http_call("GET", f"http://{filer.url}{path}") != data:
                bad.append(path)
        except Exception:  # noqa: BLE001
            bad.append(path)
    return bad


def test_chaos_node_death_and_revival(monkeypatch):
    """Hard-kill one volume server mid-load, revive it on the same
    port/dir: every acknowledged write verifies, zero client errors.
    Runs in every suite invocation (compressed schedule); the full
    schedule under SW_CHAOS_TESTS=1."""
    # the filer's re-assign walk (filer/upload._assign_and_upload) backs
    # off across the master's heartbeat-expiry window, which is what
    # hides a node's death from writers; the conftest zeroes that
    # backoff for every other test, and with it zeroed six re-assigns
    # spin through in a millisecond and a write onto the dead node's
    # volumes fails about one run in three
    monkeypatch.setenv("SW_RETRY_BACKOFF_SCALE", "1")
    warm_s, dead_s, tail_s = (10, 12, 12) if _FULL else (3, 6, 5)
    tmp = tempfile.mkdtemp(prefix="chaos_nd_")
    master, servers, dirs, filer = _spawn_cluster(tmp)
    ports = [vs.port for vs in servers]
    model, mlock = {}, threading.Lock()
    errors, stop, counter = [], threading.Event(), [0]
    threads = _client_pool(filer, model, mlock, errors, stop, counter)
    for t in threads:
        t.start()
    try:
        time.sleep(warm_s)
        victim = servers[0]
        victim._stop.set()
        victim.server.stop()
        time.sleep(dead_s)
        revived = VolumeServer(port=ports[0], directories=[dirs[0]],
                               master_url=master.url, pulse_seconds=1,
                               max_volume_counts=[20],
                               ec_backend="numpy").start()
        time.sleep(tail_s)
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors[:5]
        assert not _verify_all(filer, model)
        assert model, "drill wrote nothing"
        revived.stop()
    finally:
        stop.set()
        filer.stop()
        for vs in servers[1:]:
            vs.stop()
        master.stop()


@gated
def test_chaos_maintenance_commands_under_load():
    """volume.balance/fsck/list running against the cluster while
    clients write/read/delete: invisible to clients."""
    import seaweedfs_tpu.shell  # noqa: F401
    from seaweedfs_tpu.shell.command_env import CommandEnv, run_command

    tmp = tempfile.mkdtemp(prefix="chaos_mt_")
    master, servers, _dirs, filer = _spawn_cluster(tmp,
                                                   replication="000")
    model, mlock = {}, threading.Lock()
    errors, stop, counter = [], threading.Event(), [0]
    threads = _client_pool(filer, model, mlock, errors, stop, counter,
                           deletes=True)

    def maintenance():
        rng = random.Random(9)
        while not stop.is_set():
            try:
                env = CommandEnv(master.url, out=io.StringIO())
                run_command(env, rng.choice(
                    ["volume.list", "volume.balance", "volume.fsck"]))
            except Exception as e:  # noqa: BLE001
                errors.append(f"maint: {repr(e)[:100]}")
            stop.wait(3.0)

    threads.append(threading.Thread(target=maintenance))
    for t in threads:
        t.start()
    try:
        time.sleep(40)
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors[:5]
        assert not _verify_all(filer, model)
        assert model
    finally:
        stop.set()
        filer.stop()
        for vs in servers:
            vs.stop()
        master.stop()


@gated
def test_chaos_ec_degraded_reads_through_holder_death():
    """Readers hammer an EC volume while its biggest shard holder dies
    and revives: zero mismatches (the id guard makes any misassembly
    an error, and errors must not happen either)."""
    import seaweedfs_tpu.shell  # noqa: F401
    from seaweedfs_tpu.shell.command_env import CommandEnv, run_command

    tmp = tempfile.mkdtemp(prefix="chaos_ec_")
    master, servers, dirs, filer = _spawn_cluster(tmp, n_vols=4,
                                                  replication="000")
    ports = [vs.port for vs in servers]
    rng = np.random.default_rng(0)
    payloads = {}
    a = op.assign(master.url, collection="ecc")
    vid = int(a["fid"].split(",")[0])
    for i in range(1, 25):
        fid = f"{vid},{i:x}00000001"
        data = rng.integers(0, 256, 120_000).astype(np.uint8).tobytes()
        op.upload(a["url"], fid, data, filename=f"f{i}")
        payloads[fid] = data
    env = CommandEnv(master.url, out=io.StringIO())
    run_command(env, f"ec.encode -volumeId {vid}")
    # all 14 shards registered at the master before readers start —
    # poll the lookup instead of sleeping across the pulse
    from conftest import wait_until
    from seaweedfs_tpu.ec import TOTAL_SHARDS
    from seaweedfs_tpu.server.http_util import get_json

    def _all_shards():
        out = get_json(f"http://{master.url}/cluster/ec_lookup"
                       f"?volumeId={vid}")
        return len(out.get("shards", {})) == TOTAL_SHARDS
    assert wait_until(_all_shards, timeout=15)

    errors, stop = [], threading.Event()

    def reader(tid):
        rngl = random.Random(tid)
        while not stop.is_set():
            fid, data = rngl.choice(sorted(payloads.items()))
            try:
                if op.read_file(master.url, fid) != data:
                    errors.append(f"MISMATCH {fid}")
            except Exception as e:  # noqa: BLE001
                errors.append(f"r{tid}: {repr(e)[:110]}")

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    try:
        time.sleep(6)
        counts = {}
        for vs in servers:
            ev = vs.store.find_ec_volume(vid)
            counts[vs.url] = len(ev.shard_ids()) if ev else 0
        victim = max(servers, key=lambda v: counts[v.url])
        victim._stop.set()
        victim.server.stop()
        time.sleep(12)
        vi = servers.index(victim)
        revived = VolumeServer(port=ports[vi], directories=[dirs[vi]],
                               master_url=master.url, pulse_seconds=1,
                               max_volume_counts=[20],
                               ec_backend="numpy").start()
        time.sleep(8)
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors[:5]
        revived.stop()
    finally:
        stop.set()
        filer.stop()
        for i, vs in enumerate(servers):
            if vs.url != victim.url:
                vs.stop()
        master.stop()
