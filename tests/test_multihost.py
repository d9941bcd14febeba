"""Multi-host DCN tier (SURVEY §5.8): two real OS processes, each
owning 4 virtual CPU devices, join one 8-device mesh via
jax.distributed and run the full sharded EC step, with the process
boundary (and therefore the cross-host collective paths) actually
exercised."""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from seaweedfs_tpu.parallel import init_distributed, multihost_ec_step
coord, nproc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
init_distributed(coord, nproc, pid)
out = multihost_ec_step(k=10, m=4, n_per_device=256)
print("MULTIHOST_RESULT " + json.dumps(out), flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.skipif(os.environ.get("SW_MULTIHOST_TESTS", "1") == "0",
                    reason="disabled by SW_MULTIHOST_TESTS=0")
def test_two_process_mesh_runs_ec_step(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    # children force CPU + 4 virtual devices via _CHILD before any jax
    # import; scrub settings that would fight that
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, coord, "2", str(pid)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, \
            f"process {pid} failed:\n{out[-2000:]}"
    results = []
    for out in outs:
        line = [l for l in out.splitlines()
                if l.startswith("MULTIHOST_RESULT ")]
        assert line, out[-1000:]
        results.append(json.loads(line[0].split(" ", 1)[1]))
    for pid, r in enumerate(results):
        assert r["ok"] and r["process_index"] == pid
        assert r["process_count"] == 2
        assert r["global_devices"] == 8 and r["local_devices"] == 4
        assert r["mesh_shape"] == {"data": 4, "shard": 2}
        # every process verified a non-empty slice of the outputs
        assert r["parity_shards_checked"] > 0
        assert r["rebuilt_shards_checked"] > 0
