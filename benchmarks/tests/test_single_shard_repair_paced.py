"""CPU rehearsals of the paced single-shard-repair cell, run by hand:

    python -m pytest benchmarks/tests/test_single_shard_repair_paced.py -q

None of this is a chip run and no number it sees is a device number: each
rehearsal is `run.py --rehearse --volume-mib 64` in a process of its own.
At the configuration's 256 MiB/s a 64 MiB volume's repair (37 MiB from the
other holders, 0.25 s on the CPU) never meets its budget: the refill keeps
up. That run holds the cell's files together and ends `correct: true`.
What the budget does is rehearsed on a copy of the benchmark's files whose
configuration states 8 MiB/s and nothing else changed (`scaled`, below):
a sixteenth of the bytes at a thirty-second of the rate, a repair of 4-5 s
that is the budget's from end to end, as the chip's 2 s are. There the
four new metrics print in a traced rehearsal, `ignore_the_budget` comes
out not correct by `paced_rate_share_at_most` and `force_full_gather` by
the two route checks. Every list is asked for membership, never for last
place, and `process_stall_share` for `>= 0` (a window without a stall).
"""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from test_benchmark import (BENCH, ROOT, bench_json, last_line,  # noqa: E402
                            rehearse)
from test_single_shard_repair import checks_of, listed, phase  # noqa: E402

CELL = "f4-warm-rs10-4-4srv-paced-1chip.single-shard-repair-paced"
CONFIG = "f4-warm-rs10-4-4srv-paced-1chip"
NEW = {"paced_share", "paced_rate_share", "repair_remote_share",
       "pace_wait_ms"}
DEVICE_TRACE = {"repair_kernel_roofline_share"}
SIZE = ("--rehearse", "--volume-mib", "64")
SCALED_MIBPS = 8


@pytest.fixture(scope="module")
def scaled(tmp_path_factory) -> str:
    """A checkout of the benchmark whose paced configuration states
    8 MiB/s: BENCHMARK.json and benchmarks/ copied, the program linked."""
    root = str(tmp_path_factory.mktemp("scaled"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(ROOT, "seaweedfs_tpu"),
               os.path.join(root, "seaweedfs_tpu"))
    path = os.path.join(root, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        config = json.load(f)
    config["env"]["SW_COMPACTION_MBPS"] = str(SCALED_MIBPS)
    config["pull_budget"]["mib_per_second"] = SCALED_MIBPS
    config["pull_budget"]["bytes_per_second"] = SCALED_MIBPS << 20
    with open(path, "w") as f:
        json.dump(config, f)
    return root


def test_the_new_cell_is_listed_where_it_reports():
    bench = bench_json()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert entry["traffic"] == "single-shard-repair-paced"
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in by_name["rebuild_mbps"]["workloads"]
    assert CELL not in by_name["encode_mbps"]["workloads"]
    assert NEW | DEVICE_TRACE | {
        "rebuild_gather_share", "repair_bytes_share", "repair_fetch_ms",
        "repair_relayout_ms", "rebuild_assemble_ms", "fetch_max_ms",
        "process_stall_share"} == listed(CELL)
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "rebuild_mbps", m["name"]
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    assert int(config["env"]["SW_COMPACTION_MBPS"]) << 20 == \
        config["pull_budget"]["bytes_per_second"] == 256 << 20
    assert config["volume_servers"] == 4 and config["layout"] == "flat"
    with open(os.path.join(BENCH, "traffic",
                           "single-shard-repair-paced.json")) as f:
        mix = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "single-shard-repair.json")) as f:
        plain = json.load(f)
    assert mix["lost_order"] == plain["lost_order"]
    assert mix["needles"] == plain["needles"]
    assert mix["routes"]["flat"] == plain["routes"]["flat"]
    assert {"ignore_the_budget", "force_full_gather"} <= \
        set(mix["controls"])


def test_the_cell_at_its_own_rate_ends_correct():
    rc, lines, err = rehearse(CELL, "--seconds", "6", *SIZE,
                              rehearse_flag=False)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"rebuild_mbps", "setup_s"}
    checks = checks_of(lines)
    assert all(c["ok"] for c in checks.values())
    assert checks["paced_rate_share_at_most"]["limit"] == 1.03
    assert checks["repairs_faster_than_their_bytes_allow"]["limit"] == 0
    assert phase(lines, "verify")["route"] == "trace"
    assert phase(lines, "paced")["rate_bytes_per_s"] == 256 << 20


def test_under_a_budget_that_binds_the_new_metrics_print(scaled):
    rc, lines, err = rehearse(CELL, "--seconds", "12", *SIZE, root=scaled,
                              trace=1, rehearse_flag=False)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True and last["failed"] == 0
    metrics = {name: m["value"] for name, m in last["metrics"].items()}
    # every listed metric but the one a device trace alone can give
    assert set(metrics) >= listed(CELL) - DEVICE_TRACE
    assert metrics["process_stall_share"] >= 0
    assert all(metrics[name] > 0 for name in metrics
               if name != "process_stall_share")
    # the budget's from end to end: most of a repair waits for it, and
    # what crossed it is most of what it would have let through
    assert 50 < metrics["paced_share"] <= 100
    assert 80 < metrics["paced_rate_share"] <= 100
    assert 70 < metrics["repair_remote_share"] < 85     # ~10 of 13
    assert 62.5 <= metrics["repair_bytes_share"] <= 70.0
    checks = checks_of(lines)
    assert all(c["ok"] for c in checks.values())
    assert 0.8 < checks["paced_rate_share_at_most"]["value"] <= 1.03
    paced = phase(lines, "paced")
    assert paced["rate_bytes_per_s"] == SCALED_MIBPS << 20
    # (a 7 MiB shard is four stripes, all inside one pull window: the
    # least wall the second check allows is nothing here, 1.3 s of a
    # 1 GiB volume's 2.1)
    assert paced["repairs"] and all(
        r["wall_s"] >= r["least_s"] and r["paced_wall_s"] > 0 and
        r["wall_s"] >= (r["remote_bytes"] - 0.1 * (SCALED_MIBPS << 20))
        / (SCALED_MIBPS << 20) for r in paced["repairs"])
    done = json.loads(next(ln for ln in lines
                           if '"phase": "window_done"' in ln))
    assert done["counters"]["telemetry.throttle.bytes"] >= \
        sum(r["remote_bytes"] for r in paced["repairs"])
    assert done["counters"]["telemetry.throttle.wait_us"] > 0
    assert done["spans"]["ec.rebuild.pace"][0] > 0


@pytest.mark.parametrize("control,failing", [
    # every charge returns at once: the holders sent faster than the rate
    ("ignore_the_budget", ["paced_rate_share_at_most",
                           "repairs_faster_than_their_bytes_allow"]),
    # the full gather is off the route, and pulls k whole shards
    ("force_full_gather", ["repairs_off_the_configured_route",
                           "repair_bytes_share_at_most"]),
])
def test_control_comes_out_not_correct(scaled, control, failing):
    with open(os.path.join(BENCH, "traffic",
                           "single-shard-repair-paced.json")) as f:
        assert control in json.load(f)["controls"]
    rc, lines, err = rehearse(CELL, "--seconds", "8", "--control", control,
                              *SIZE, root=scaled, rehearse_flag=False)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is False and last["control"] == control
    checks = checks_of(lines)
    # the check named for it first; the shards are still the reference's
    failed = [name for name, c in checks.items() if not c["ok"]]
    assert failed and failed[0] == failing[0] and set(failed) <= \
        set(failing)
    assert all(checks[name]["value"] > checks[name]["limit"]
               for name in failed)


def test_the_parent_program_is_refused_at_once(tmp_path):
    """A program whose telemetry has no `throttle` cannot be measured in
    this cell: the kind says so at import, exit 1, before a server is
    built (the driver lays these files over the parent's checkout)."""
    import subprocess
    import textwrap
    script = tmp_path / "old_program.py"
    script.write_text(textwrap.dedent(f"""
        import runpy, sys
        sys.path.insert(0, {ROOT!r})
        from seaweedfs_tpu.ops import telemetry
        sound = telemetry.DispatchStats.snapshot
        def old(self):
            snap = sound(self)
            snap.pop("throttle")
            return snap
        telemetry.DispatchStats.snapshot = old
        sys.argv = ["run.py", "--workload", {CELL!r}, "--seed", "1",
                    "--seconds", "1", "--trace", "0", "--rehearse"]
        runpy.run_path({os.path.join(BENCH, "run.py")!r},
                       run_name="__main__")
        """))
    done = subprocess.run([sys.executable, str(script)], cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 1
    assert "no budget for what a rebuild pulls" in done.stderr
    assert done.stdout == ""
