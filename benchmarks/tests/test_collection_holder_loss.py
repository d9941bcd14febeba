"""CPU rehearsals of the sealed-collection cell, run by hand:

    python -m pytest benchmarks/tests/test_collection_holder_loss.py -q

None of this is a chip run and no number it sees is a device number: each
rehearsal is `run.py --rehearse` in a process of its own, 32 volumes of
32 MiB (8,192 needles each) for the whole cell and of 8 MiB for the rest
(~4 min in all). What they hold: `correct` true with every check of the
cell printed beside its limit; a traced rehearsal's result line CONTAINS
the cell's listed metrics that have something to read off the chip; each
command covers every volume of its collection and the encode every live
needle; the servers are lost in the order the traffic file names,
whatever the seed; each of the mix's four controls comes out not correct
by its own check alone, `keep_tombstones_in_ecx` by the index's; the cell
is a member of every list it reports under; and a program that accounts
for no index build is refused at once, in one line.
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

CELL = "hot-sealed-4k-32vol-4srv-1chip.collection-holder-loss"
CONFIG = "hot-sealed-4k-32vol-4srv-1chip"
HOLDER_LOSS = "f4-warm-piggyback-4srv-1chip.holder-loss"
VOLUMES = 32
DEVICE_TRACE = {"kernel_roofline_share", "device_idle_share.seal",
                "idle_unattributed_share"}
NEW = {"encode_index_share", "index_us_per_needle", "encode_fixed_share",
       "rebuild_fixed_share"}
CHECKS = {"shards_differing_from_reference": 0,
          "rebuilt_shards_differing_from_encoded": 0,
          "commands_that_raised": 0,
          "shards_not_on_disk_when_command_returned": 0,
          "holders_above_m_shards": 0, "rebuilds_off_the_full_gather": 0,
          "gathered_shards_at_most": 10.1,
          "ecx_files_differing_from_reference": 0, "compiles_in_window": 0}


def small(*extra: str, mib: int = 8, **kw):
    """A rehearsal at `mib` MiB a volume (the helper's own is 32)."""
    return rehearse(CELL, *extra, "--rehearse", "--volume-mib", str(mib),
                    rehearse_flag=False, **kw)


def mix() -> dict:
    with open(os.path.join(BENCH, "traffic",
                           "collection-holder-loss.json")) as f:
        return json.load(f)


def commands(lines: list, op: str, timed: bool) -> list:
    return [c for c in map(json.loads, lines)
            if c.get("phase") == "collection" and c["op"] == op
            and c["timed"] is timed]


def test_the_cell_is_a_member_of_every_list_it_reports_under():
    bench = bench_json()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert entry["traffic"] == "collection-holder-loss"
    config_entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    for name in ("encode_mbps", "rebuild_mbps"):
        metric = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert CELL in metric["workloads"]
    # what the four-server holder-loss cell reports of the flat stream
    # (not the piggyback layout's own), the dense operand's roofline
    # share, and the four this cell brings
    piggyback = {"pb_relayout_share", "pb_decode_relayout_share",
                 "kernel_terms_roofline"}
    assert listed(CELL) == (listed(HOLDER_LOSS) - piggyback) | \
        {"kernel_roofline_share"} | NEW
    for name in NEW:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
    traffic = mix()
    assert traffic["kind"] == "seal_collection"
    assert traffic["lost_servers"] == [0, 1, 2, 3]
    assert traffic["losses_per_seal"] == 2 and traffic["delete_every"] == 16
    assert traffic["needles"] == {"sizes": "ragged", "bytes": 4096,
                                  "ragged": 0.0625}
    assert traffic["gathered_shards_at_most"] == 10.1
    assert "keep_tombstones_in_ecx" in traffic["controls"]
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    assert (config["data_shards"], config["parity_shards"],
            config["volume_servers"], config["layout"]) == (10, 4, 4, "flat")
    # BASELINE config 3, uncut: 32 volumes, 1M needles of 4 KB
    assert config["volumes"] == VOLUMES
    assert config["volumes"] * config["needles_per_volume"] == 1_000_000
    assert config["needle_bytes"] == traffic["needles"]["bytes"]
    assert config["needles_per_volume"] * config["needle_bytes"] <= \
        config["volume_mib"] << 20
    with open(os.path.join(BENCH, "configs",
                           "f4-warm-rs10-4-1chip.json")) as f:
        sibling = json.load(f)
    # its two settings and nothing else, the flat cells' kernel
    assert config["env"] == sibling["env"]
    assert config["kernel"] == sibling["kernel"]
    assert set(config["reduced"]) == set(config_entry["reduced"]) == \
        {"dat_in_page_cache", "hosts", "processes"}


def test_cell_rehearsal_traced():
    rc, lines, err = rehearse(CELL, "--seconds", "45", trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 3       # an encode and its two rebuilds
    assert set(last["metrics"]) >= listed(CELL) - DEVICE_TRACE
    assert all(m["value"] > 0 for name, m in last["metrics"].items()
               if name != "process_stall_share")
    assert 0 < last["metrics"]["encode_index_share"]["value"] < 100
    assert 0 < last["metrics"]["encode_fixed_share"]["value"] < 100
    assert 0 < last["metrics"]["rebuild_fixed_share"]["value"] < 100
    checks = checks_of(lines)
    assert all(c["ok"] for c in checks.values())
    # every check printed beside its limit, in the result line too
    assert {n: c["limit"] for n, c in checks.items()} == CHECKS
    assert {n: c["limit"] for n, c in last["checks"].items()} == CHECKS
    assert checks["gathered_shards_at_most"]["value"] == 10.0
    upload = phase(lines, "upload")
    assert upload["needles"] == 8192 and upload["deleted"] == 512
    assert upload["idx_records"] == 8192 + 512
    assert upload["ecx_entries"] == 8192 - 512
    verify = phase(lines, "verify")
    assert verify["reference"] == ["lib.reference", "lib.reference_index"]
    # the warm-up: four volumes, each server lost once; then collections
    # of 32, the first of which loses servers 0 and 1
    assert verify["volumes"][0] == 4 and verify["lost"][0] == [0, 1, 2, 3]
    assert set(verify["volumes"][1:]) == {VOLUMES}
    assert verify["lost"][1] == [0, 1]
    # a server holds three or four shards of a volume, both in a warm-up
    assert {n for r in verify["lost_shards"][0] for n in r} == {3, 4}
    # one index a holder a volume, after every command
    assert verify["ecx_files"] >= 5 * 4 * 4 + 3 * 4 * VOLUMES
    # every timed command covers the collection, the encode every live
    # needle of it; the warm-up compiled both decode operands
    encodes = commands(lines, "ec.encode", True)
    assert encodes and all(
        c["volumes"] == VOLUMES and c["operand_rows"] == [4] and
        c["index_entries"] == VOLUMES * upload["ecx_entries"] and
        c["index_us"] > 0 for c in encodes)
    rebuilds = commands(lines, "ec.rebuild", True)
    assert len(rebuilds) >= 2 and all(
        c["volumes"] == VOLUMES and c["index_entries"] == 0 and
        set(c["operand_rows"]) <= {3, 4} for c in rebuilds)
    assert {n for c in commands(lines, "ec.rebuild", False)
            for n in c["operand_rows"]} == {3, 4}
    # the operand the roofline share is counted from: the mean rows of a
    # command's volumes, which counts its columns exactly
    ops = phase(lines, "roofline")["ops"]
    assert [o["op"] for o in ops] == ["ec.encode", "ec.rebuild",
                                      "ec.rebuild"]
    assert ops[0]["operand"] == [4.0, 10]
    assert all(3.0 <= o["operand"][0] <= 4.0 and o["operand"][1] == 10
               for o in ops[1:])
    for name in ("encode_index", "encode_fixed", "rebuild_fixed"):
        assert phase(lines, name)["parts"]


@pytest.mark.parametrize("seed", ["2147483659", "5"])
def test_the_order_of_lost_servers_does_not_hang_on_the_seed(seed):
    rc, lines, err = small("--seconds", "12", "--seed", seed)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"encode_mbps", "rebuild_mbps", "setup_s"}
    verify = phase(lines, "verify")
    assert verify["lost"][0] == [0, 1, 2, 3]
    lost = [server for cycle in verify["lost"][1:] for server in cycle]
    assert len(lost) >= 2
    assert lost == [n % 4 for n in range(len(lost))]


@pytest.mark.parametrize("control,failing", [
    ("corrupt_encode_matrix", "shards_differing_from_reference"),
    ("corrupt_rebuild_decode", "rebuilt_shards_differing_from_encoded"),
    ("late_shard_after_rebuild", "shards_not_on_disk_when_command_returned"),
    ("keep_tombstones_in_ecx", "ecx_files_differing_from_reference"),
])
def test_control_comes_out_not_correct(control, failing):
    assert control in mix()["controls"]
    rc, lines, err = small("--control", control)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is False and last["control"] == control
    checks = checks_of(lines)
    # its own check alone: a wrong coefficient still decodes itself, a
    # decode off by one coefficient leaves the encode the reference's, a
    # late shard is the right shard, and an index that keeps its deleted
    # needles sits beside shards that are what they were
    assert [name for name, c in checks.items() if not c["ok"]] == [failing]
    assert checks[failing]["value"] > checks[failing]["limit"]


def test_a_program_without_the_index_account_is_refused_at_once(tmp_path):
    """What the driver does with the parent: this PR's benchmark files
    over a program whose ops/telemetry has no `index_entries`. It must
    exit 1 with one line, soon; never hang."""
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
    assert '"index_entries", "index_us",' in source
    with open(path, "w") as f:      # the parent's fields have no such two
        f.write(source.replace('"index_entries", "index_us",', "")
                .replace("self.index_entries += entries", "pass")
                .replace("self.index_us += int(wall_s * 1e6)", "pass"))
    t0 = time.perf_counter()
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "7", "--seconds", "2", "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60)
    assert done.returncode == 1 and done.stdout == ""
    assert time.perf_counter() - t0 < 5
    line, = [ln for ln in done.stderr.splitlines() if ln.strip()]
    assert "seal_collection.py" in line and "index_entries" in line
