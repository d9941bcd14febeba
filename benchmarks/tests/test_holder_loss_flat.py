"""CPU rehearsals of the RS(6,3) holder-loss cell, run by hand:

    python -m pytest benchmarks/tests/test_holder_loss_flat.py -q

None of this is a chip run and no number it sees is a device number: each
rehearsal is `run.py --rehearse` at a 32 MiB volume in a process of its
own (~2 min in all). What they hold: `correct` true with every check of
the cell printed beside its limit; a traced rehearsal's result line
CONTAINS the cell's listed metrics that have something to read off the
chip, `geometry_dispatch_share` at 100; the sets are lost in the order the
traffic file names, whatever the seed; each of the mix's three controls
comes out not correct by its own check alone; and a program that codes
every volume 10 + 4 is refused at once, in one line.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from test_benchmark import (BENCH, ROOT, bench_json, last_line,  # noqa: E402
                            rehearse)
from test_single_shard_repair import checks_of, listed, phase  # noqa: E402

CELL = "warm-rs6-3-3srv-1chip.holder-loss-flat"
SEAL = "f4-warm-rs10-4-1chip.seal-rebuild"
SETS = [[0, 3, 6], [1, 4, 7], [2, 5, 8]]
DEVICE_TRACE = {"kernel_roofline_share", "device_idle_share.seal",
                "idle_unattributed_share"}
CHECKS = {"shards_differing_from_reference": 0,
          "rebuilt_shards_differing_from_encoded": 0,
          "commands_that_raised": 0,
          "shards_not_on_disk_when_command_returned": 0,
          "holders_above_m_shards": 0, "rebuilds_off_the_full_gather": 0,
          "commands_off_the_configured_geometry": 0,
          "gathered_shards_at_most": 6.1, "compiles_in_window": 0}
WINDOW = ("--seconds", "6")


def mix() -> dict:
    with open(os.path.join(BENCH, "traffic", "holder-loss-flat.json")) as f:
        return json.load(f)


def lost_in_window(lines: list) -> list:
    """The sets the window's rebuilds lost, in order (the first cycle of
    the verify line is the warm-up's)."""
    return [lost for cycle in phase(lines, "verify")["lost"][1:]
            for lost in cycle]


def test_the_cell_is_listed_where_it_reports():
    bench = bench_json()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "holder-loss-flat"
    assert bench["workloads"][-1] is entry
    assert bench["configs"][-1]["name"] == entry["config"]
    for name in ("encode_mbps", "rebuild_mbps"):
        metric = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert metric["workloads"][-1] == CELL
    # the one-chip seal cell's metrics (the same flat stream, the same
    # full gather) and the one this cell brings
    assert listed(CELL) == listed(SEAL) | {"geometry_dispatch_share"}
    assert bench["per_layer"][-1]["name"] == "geometry_dispatch_share"
    assert bench["per_layer"][-1]["workloads"] == [CELL]
    traffic = mix()
    assert traffic["holder_sets"] == SETS and \
        traffic["losses_per_seal"] in (2, 3)
    assert traffic["gathered_shards_at_most"] == 6.1
    assert traffic["controls"] == ["corrupt_encode_matrix",
                                   "corrupt_rebuild_decode",
                                   "late_shard_after_rebuild"]
    with open(os.path.join(BENCH, "configs", entry["config"] + ".json")) as f:
        config = json.load(f)
    assert (config["data_shards"], config["parity_shards"],
            config["volume_servers"], config["layout"]) == (6, 3, 3, "flat")
    assert config["dispatch_bytes"] == 6 * config["slab_bytes_per_shard"]
    assert list(config["holder_sets"].values()) == SETS
    with open(os.path.join(BENCH, "configs",
                           "f4-warm-rs10-4-1chip.json")) as f:
        sibling = json.load(f)
    # its two settings and nothing else: the geometry is no knob
    assert config["env"] == sibling["env"]
    assert config["kernel"] == sibling["kernel"]
    assert set(config["reduced"]) == set(sibling["reduced"]) == set(
        bench["configs"][-1]["reduced"])


def test_cell_rehearsal_traced():
    rc, lines, err = rehearse(CELL, *WINDOW, trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 3       # an encode and its two rebuilds
    assert set(last["metrics"]) >= listed(CELL) - DEVICE_TRACE
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["metrics"]["geometry_dispatch_share"]["value"] == 100.0
    checks = checks_of(lines)
    assert all(c["ok"] for c in checks.values())
    # every check printed beside its limit, in the result line too
    assert {n: c["limit"] for n, c in checks.items()} == CHECKS
    assert {n: c["limit"] for n, c in last["checks"].items()} == CHECKS
    assert checks["gathered_shards_at_most"]["value"] == 6.0
    verify = phase(lines, "verify")
    assert verify["reference"].endswith("lib.reference")
    assert verify["geometry"] == "6,3"
    assert verify["lost"][0] == [SETS[0]]       # the warm-up loses A
    # the operand each node replied with, which the kernel's roofline
    # share is counted from: (3, 6), encode and decode alike
    ops = phase(lines, "roofline")["ops"]
    assert [o["operand"] for o in ops] == [[3, 6]] * 3
    assert [o["work"]["column_terms"] for o in ops] == [18] * 3
    encodes = [json.loads(ln) for ln in lines
               if '"phase": "ec.encode"' in ln]
    rebuilds = [json.loads(ln) for ln in lines
                if '"phase": "ec.rebuild"' in ln]
    assert all(e["counters"]["telemetry.geometry_dispatches.6+3"] ==
               e["counters"]["telemetry.dispatches"] for e in encodes)
    assert all(r["node"]["/admin/ec/rebuild"]["repair_mode"] == "full" and
               r["node"]["/admin/ec/rebuild"]["repair_fallback"] is None and
               r["node"]["/admin/ec/rebuild"]["operand"] == [3, 6]
               for r in rebuilds)
    done = phase(lines, "window_done")
    assert not [name for name in done["counters"]
                if name.startswith("telemetry.geometry_dispatches.")
                and name != "telemetry.geometry_dispatches.6+3"]


@pytest.mark.parametrize("seed", ["2147483659", "5"])
def test_the_order_of_lost_sets_does_not_hang_on_the_seed(seed):
    rc, lines, err = rehearse(CELL, *WINDOW, "--seed", seed)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"encode_mbps", "rebuild_mbps", "setup_s"}
    assert phase(lines, "verify")["lost"][0] == [SETS[0]]
    lost = lost_in_window(lines)
    assert len(lost) >= 3
    assert lost == [SETS[n % 3] for n in range(len(lost))]


@pytest.mark.parametrize("control,failing", [
    ("corrupt_encode_matrix", "shards_differing_from_reference"),
    ("corrupt_rebuild_decode", "rebuilt_shards_differing_from_encoded"),
    ("late_shard_after_rebuild", "shards_not_on_disk_when_command_returned"),
])
def test_control_comes_out_not_correct(control, failing):
    assert control in mix()["controls"]
    rc, lines, err = rehearse(CELL, *WINDOW, "--control", control)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is False and last["control"] == control
    checks = checks_of(lines)
    # its own check alone: a wrong coefficient of the coding matrix
    # still decodes itself, a decode off by one coefficient leaves the
    # encode the reference's, a late shard is the right shard
    assert [name for name, c in checks.items() if not c["ok"]] == [failing]
    assert checks[failing]["value"] > checks[failing]["limit"]


def test_a_program_that_codes_every_volume_10_4_is_refused_at_once(
        tmp_path):
    """What the driver does with the parent: this PR's benchmark files
    over a program whose ops/telemetry has no `geometry_dispatches`. It
    must exit 1 with one line, soon; never hang, never run 10 + 4 under
    an RS(6,3) name."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(ROOT, "seaweedfs_tpu"),
                    os.path.join(root, "seaweedfs_tpu"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.so"))
    path = os.path.join(root, "seaweedfs_tpu", "ops", "telemetry.py")
    with open(path) as f:
        source = f.read()
    assert 'snap["geometry_dispatches"]' in source
    with open(path, "w") as f:      # the parent's snapshot has no such key
        f.write(source.replace('snap["geometry_dispatches"]',
                               'snap["_not_reported"]'))
    t0 = time.perf_counter()
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "7", "--seconds", "2", "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60)
    assert done.returncode == 1 and done.stdout == ""
    assert time.perf_counter() - t0 < 5
    line, = [ln for ln in done.stderr.splitlines() if ln.strip()]
    assert "seal_holder_loss_flat.py" in line and \
        "geometry_dispatches" in line and "10 + 4" in line
