"""chip_smoke.py: its last line, its phases on the CPU, and its refusal
to pass off the chip.

The script is the driver's chip check; PR 21 was thrown away because the
last stdout line was not exactly the contract's object. So that line is
built by one function with a test of its own (a), the phases are driven
in-process at 8 MiB where every byte-compare must pass (b), and the
script as a whole, run the way the driver runs it but with
JAX_PLATFORMS=cpu, must exit non-zero without ever claiming ok (c).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("ok,device", [
    (True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}),
    (True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
            "extra": "dropped"}),
    (False, {"platform": "cpu", "kind": "cpu", "count": 8}),
])
def test_last_line_is_exactly_the_contract(ok, device):
    line = chip_smoke.last_line(ok, device)
    assert "\n" not in line
    got = json.loads(line)
    assert set(got) == {"ok", "device"}
    assert set(got["device"]) == {"platform", "kind", "count"}
    assert got["ok"] is ok
    assert got["device"] == {k: device[k]
                             for k in ("platform", "kind", "count")}
    assert isinstance(got["device"]["count"], int)


def test_phases_pass_every_byte_compare_on_cpu(tmp_path, monkeypatch):
    """upload -> ec.encode -> NumpyCodec compare + GET all -> lose 4 ->
    degraded GETs -> ec.rebuild -> compare, in this process at 8 MiB.
    Any mismatch raises SmokeFailure out of run_cluster_phases."""
    # the master's repair loop would heal the loss before ec.rebuild
    monkeypatch.setenv("SW_REPAIR_INTERVAL_S", "0")
    lines = []
    run = chip_smoke.run_cluster_phases(
        str(tmp_path), "tpu", 8 << 20, seed=1, needle_bytes=256 << 10,
        emit=lines.append)
    phases = {rec["phase"]: rec for rec in lines}
    assert list(phases) == ["upload", "ec.encode", "compare",
                            "degraded_reads", "ec.rebuild"]
    assert phases["upload"]["payload_bytes"] >= 8 << 20
    assert phases["compare"]["shards_equal_numpy"] == 14
    assert phases["compare"]["needle_reads"]["needles"] == \
        phases["upload"]["needles"]
    assert len(phases["degraded_reads"]["lost_shards"]) == 4
    assert phases["degraded_reads"]["engine"]["reads"] > 0
    assert phases["ec.rebuild"]["rebuilt"] == \
        phases["degraded_reads"]["lost_shards"]
    assert len(set(run["shard_shas"])) == 14
    assert run["shard_shas"] == run["volume"]["ref_shas"]
    assert run["encode_backend"] == run["rebuild_backend"] == "tpu"
    for rec in lines:
        json.dumps(rec)  # every earlier line is one JSON object
    # and off the chip the proof refuses: wrong platform, CPU program
    bad = chip_smoke.chip_proof(
        {"platform": "cpu", "kind": "cpu", "count": 1}, 1, [run])
    assert any("not 'tpu'" in b for b in bad)
    assert any("rs_pallas._fused_fn" in b for b in bad)


def test_reference_striping_is_independent_of_the_encoder(tmp_path):
    """The smoke's plain reference agrees with ec/encoder.py's
    write_ec_files + NumpyCodec on a ragged .dat (tail row padded)."""
    import numpy as np
    from seaweedfs_tpu.ec import to_ext, write_ec_files
    from seaweedfs_tpu.ops.codec import NumpyCodec
    base = str(tmp_path / "7")
    raw = np.random.default_rng(2).integers(
        0, 256, (23 << 20) + 12345, dtype=np.uint8)
    raw.tofile(base + ".dat")
    write_ec_files(base, codec=NumpyCodec(10, 4), pipelined=False)
    want = [chip_smoke.sha256_file(base + to_ext(i)) for i in range(14)]
    assert chip_smoke.reference_shard_shas(base + ".dat") == want


def test_script_off_the_chip_exits_nonzero_and_never_says_ok(tmp_path):
    """The driver's command, in a checkout of committed sources only
    (the script deletes and rebuilds native binaries, which must not
    happen under the other xdist workers' feet)."""
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), checkout)
    shutil.copytree(
        os.path.join(REPO, "seaweedfs_tpu"), checkout / "seaweedfs_tpu",
        ignore=shutil.ignore_patterns("__pycache__", "*.so", "loadgen"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "SW_LOCK_DEBUG",
                        "SW_LOCK_GRAPH_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--size-mib", "8",
         "--needle-kib", "256", "--workdir", str(tmp_path / "work")],
        cwd=checkout, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode != 0, out.stderr[-3000:]
    assert '"ok": true' not in out.stdout
    assert out.stdout.endswith("\n") and not out.stdout.endswith("\n\n")
    lines = out.stdout.splitlines()
    for line in lines:
        assert isinstance(json.loads(line), dict)
    last = json.loads(lines[-1])
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": last["device"]["count"]}}
    # it failed for the right reason — every phase passed first
    phases = [json.loads(line).get("phase") for line in lines[:-1]]
    assert phases[-1] == "failed"
    assert "ec.rebuild" in phases and "device_proof" in phases
    failures = json.loads(lines[-2])["failures"]
    assert any("not 'tpu'" in f for f in failures)
    assert not any("SmokeFailure" in f for f in failures)
    # native libraries were built there, from the copied sources
    assert (checkout / "seaweedfs_tpu/ops/native/libseaweed_ec.so").exists()


def test_script_alone_fails_without_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it must fail, and print no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
