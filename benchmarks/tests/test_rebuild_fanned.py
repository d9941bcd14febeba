"""CPU rehearsals of the fanned-rebuild cell, run by hand:

    python -m pytest benchmarks/tests/test_rebuild_fanned.py -q

None of this is a chip run and no number it sees is a device number: each
rehearsal is `run.py --rehearse` at a 32 MiB volume over four host
devices in a process of its own (~2 min in all). What they hold: the cell
is a member of every list it reports under (and of none it does not:
`encode_mbps`); `correct` true with every check printed beside its limit;
a traced rehearsal's result line CONTAINS the cell's listed metrics that
have something to read off the chip, the lanes engaged; the servers are
lost in the order the traffic file names, whatever the seed; each of the
mix's three controls comes out not correct; and a program without
`-ec.backend tpu-own` is refused at once, in one line.
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

CELL = "f4-warm-rs10-4-4srv-4chip-fanned.rebuild-fanned-disk-loss"
CONFIG = "f4-warm-rs10-4-4srv-4chip-fanned"
SHARED = {"rebuild_gather_share", "rebuild_fetch_ms", "rebuild_assemble_ms",
          "fetch_max_ms", "process_stall_share"}
NEW = {"rebuild_volumes_inflight", "busiest_chip_dispatch_share",
       "rebuild_offtarget_share", "rebuild_deliver_share",
       "rebuild_kernel_roofline_share", "rebuild_outside_stream_share"}
DEVICE_TRACE = {"rebuild_kernel_roofline_share"}
CHECKS = {"shards_differing_from_reference": 0,
          "rebuilt_shards_differing_from_encoded": 0,
          "commands_that_raised": 0,
          "shards_not_on_disk_when_command_returned": 0,
          "rebuilt_shards_off_the_emptied_server": 0,
          "holders_above_m_shards": 0, "rebuilds_off_the_full_gather": 0,
          "gathered_shards_at_most": 10.1,
          "chips_that_dispatched_at_least": 4, "compiles_in_window": 0}
WINDOW = ("--seconds", "6")


def fanned(*extra, trace=0):
    return rehearse(CELL, *extra, devices=4, trace=trace)


def test_the_cell_is_a_member_of_every_list_it_reports_under():
    bench = bench_json()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 4 and entry["config"] == CONFIG
    assert entry["traffic"] == "rebuild-fanned-disk-loss"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 <= \
        len(bench["workloads"])
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in by_name["rebuild_mbps"]["workloads"]
    assert CELL not in by_name["encode_mbps"]["workloads"]
    assert "workloads" not in by_name["setup_s"]
    assert listed(CELL) == SHARED | NEW
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "rebuild_mbps"
        if CELL in m.get("workloads", []):
            assert m["moves"] == "rebuild_mbps"
            with open(os.path.join(BENCH, "layer_metrics",
                                   m["name"] + ".json")) as f:
                spec = json.load(f)
            assert os.path.isfile(os.path.join(
                BENCH, "readers", spec["reader"] + ".py"))
    with open(os.path.join(BENCH, "traffic", entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "rebuild_fanned"
    assert traffic["lost_servers"] == [0, 1, 2, 3]
    assert traffic["gathered_shards_at_most"] == 10.1
    assert traffic["controls"] == ["corrupt_rebuild_decode",
                                   "late_shard_after_rebuild",
                                   "deliver_to_wrong_node"]
    listed_config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, listed_config["file"])) as f:
        config = json.load(f)
    assert config["source"] == listed_config["source"]
    assert (config["data_shards"], config["parity_shards"], config["layout"],
            config["volume_servers"], config["chips"], config["volumes"],
            config["volume_mib"], config["ec_backend"]) == (
        10, 4, "flat", 4, 4, 4, 512, "tpu-own")
    assert set(config["reduced"]) == set(listed_config["reduced"])
    with open(os.path.join(BENCH, "configs",
                           "f4-warm-rs10-4-1chip.json")) as f:
        sibling = json.load(f)
    # block sizes, env and kernel entry as the one-chip flat cell's
    for key in ("large_block_bytes", "small_block_bytes",
                "slab_bytes_per_shard", "dispatch_bytes", "env",
                "pulse_seconds", "max_volumes"):
        assert config[key] == sibling[key], key
    assert config["kernel"]["entry"] == sibling["kernel"]["entry"]
    assert config["kernel"]["trace_pattern"] == \
        sibling["kernel"]["trace_pattern"]


def test_cell_rehearsal_traced():
    rc, lines, err = fanned(*WINDOW, trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 2
    assert set(last["metrics"]) >= listed(CELL) - DEVICE_TRACE
    assert "encode_mbps" not in last["metrics"]
    values = {n: m["value"] for n, m in last["metrics"].items()}
    assert all(v > 0 for n, v in values.items()
               if n != "process_stall_share")
    assert values["process_stall_share"] >= 0
    assert 2.5 <= values["rebuild_volumes_inflight"] <= 4.0
    assert 25.0 <= values["busiest_chip_dispatch_share"] <= 40.0
    assert 50.0 <= values["rebuild_offtarget_share"] <= 90.0
    assert 0 < values["rebuild_deliver_share"] < 100
    assert 0 < values["rebuild_outside_stream_share"] < 100
    checks = checks_of(lines)
    assert all(c["ok"] for c in checks.values())
    # every check printed beside its limit, in the result line too
    assert {n: c["limit"] for n, c in checks.items()} == CHECKS
    assert {n: c["limit"] for n, c in last["checks"].items()} == CHECKS
    assert checks["gathered_shards_at_most"]["value"] == 10.0
    upload = phase(lines, "upload")
    assert upload["volumes"] == 4 and len(upload["chips"]) == 4
    sealed = phase(lines, "sealed")
    assert sealed["in_flight"]["volumes"] == 4
    assert len(sealed["in_flight"]["dispatches_by_chip"]) == 4
    verify = phase(lines, "verify")
    assert verify["reference"].endswith("lib.reference")
    # a round of the four losses, again until one compiles nothing:
    # through ec.rebuild, nothing dispatched by hand
    warm = phase(lines, "warm_up")
    assert warm["losses"] == 4 * warm["rounds"] and 2 <= warm["rounds"] <= 4
    assert warm["programs_compiled"] >= 4     # a chip one at least
    assert warm["compiled_last_round"] == 0
    assert verify["compared"] is True
    assert verify["warm_up_commands"] == warm["losses"]
    assert verify["lost"] == [n % 4 for n in range(verify["commands"])]
    assert all(sizes in ([3, 4], [3], [4])
               for sizes in verify["lost_shards"])
    assert all(1 <= n <= 7 for n in verify["delivered"])
    # a command a loss, every volume of the collection in it, no encode
    timed = [json.loads(ln) for ln in lines if '"phase": "ec.' in ln
             and json.loads(ln)["timed"]]
    assert {t["phase"] for t in timed} == {"ec.rebuild"}
    fans = [json.loads(ln) for ln in lines if '"phase": "fanned"' in ln]
    assert all(f["volumes"] == 4 for f in fans)
    done = phase(lines, "window_done")
    assert done["spans"]["ec.rebuild.collection"][0] == verify["commands"]
    assert done["spans"]["ec.rebuild"][0] == 4 * verify["commands"]
    assert done["spans"]["ec.rebuild.deliver"][0] > 0
    assert "ec.encode" not in done["spans"]


@pytest.mark.parametrize("seed", ["2147483659", "5"])
def test_the_order_of_lost_servers_does_not_hang_on_the_seed(seed):
    rc, lines, err = fanned(*WINDOW, "--seed", seed)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"rebuild_mbps", "setup_s"}
    verify = phase(lines, "verify")
    assert verify["lost"][:2] == [0, 1]


@pytest.mark.parametrize("control,own_check", [
    ("corrupt_rebuild_decode", "rebuilt_shards_differing_from_encoded"),
    ("late_shard_after_rebuild",
     "shards_not_on_disk_when_command_returned"),
    ("deliver_to_wrong_node", "rebuilt_shards_off_the_emptied_server"),
])
def test_a_control_ends_not_correct(control, own_check):
    rc, lines, err = fanned(*WINDOW, "--control", control)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is False and last["control"] == control
    assert checks_of(lines)[own_check]["ok"] is False


def test_a_program_without_a_chip_a_server_is_refused_at_once(tmp_path):
    """The driver lays the benchmark's files over the parent checkout:
    there the kind has to end the run at once, non-zero, in one line."""
    root = str(tmp_path / "older")
    os.makedirs(root)
    for name in ("benchmarks", "seaweedfs_tpu"):
        shutil.copytree(os.path.join(ROOT, name), os.path.join(root, name),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, "seaweedfs_tpu", "ops", "telemetry.py")
    with open(path) as f:
        text = f.read()
    assert '"rebuild_delivered_bytes", ' in text
    with open(path, "w") as f:
        f.write(text.replace('"rebuild_delivered_bytes", ', ""))
    t0 = time.perf_counter()
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "2", "--rehearse"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 1 and time.perf_counter() - t0 < 30
    assert done.stdout == ""
    assert "rebuild_fanned.py" in done.stderr and \
        "tpu-own" in done.stderr
