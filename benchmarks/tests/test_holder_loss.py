"""CPU rehearsals of the holder-loss cell, run by hand:

    python -m pytest benchmarks/tests/test_holder_loss.py -q

None of this is a chip run and no number it sees is a device number: each
rehearsal is `run.py --rehearse` at a 32 MiB volume in a process of its
own (~2 min in all). What they hold: `correct` true with the cell's three
own checks printed beside their limits; a traced rehearsal's result line
CONTAINS the cell's listed metrics that have something to read off the
chip; the sets are lost in the order the traffic file names, whatever the
seed; and each of the mix's three controls comes out not correct by its
own check alone.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from test_benchmark import BENCH, bench_json, last_line, rehearse  # noqa: E402
from test_single_shard_repair import checks_of, listed, phase  # noqa: E402

CELL = "f4-warm-piggyback-4srv-1chip.holder-loss"
SIBLING = "f4-warm-piggyback-1chip.single-shard-repair"
SETS = [[0, 4, 8, 12], [1, 5, 9, 13], [2, 6, 10], [3, 7, 11]]
DEVICE_TRACE = {"kernel_terms_roofline", "device_idle_share.seal",
                "idle_unattributed_share"}
# the coupled encode of 32 MiB takes 2-4 s where the CPU stands in for the
# kernel, a holder's rebuild 2 s: room for a cycle and a half
WINDOW = ("--seconds", "12")


def mix() -> dict:
    with open(os.path.join(BENCH, "traffic", "holder-loss.json")) as f:
        return json.load(f)


def lost_in_window(lines: list) -> list:
    """The sets the window's rebuilds lost, in order (the first cycle of
    the verify line is the warm-up's)."""
    return [lost for cycle in phase(lines, "verify")["lost"][1:]
            for lost in cycle]


def test_the_cell_is_listed_where_it_reports():
    bench = bench_json()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "holder-loss"
    for name in ("encode_mbps", "rebuild_mbps"):
        metric = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert metric["workloads"][-1] == CELL
    # its sibling's metrics but the single-shard routes', plus the full
    # range's fetch span and the decode's own re-layout share
    assert listed(CELL) == (listed(SIBLING) - {
        "repair_bytes_share", "repair_fetch_ms", "repair_relayout_ms"}) | {
        "rebuild_fetch_ms", "pb_decode_relayout_share"}
    traffic = mix()
    assert traffic["holder_sets"] == SETS and \
        traffic["losses_per_seal"] in (2, 3)
    with open(os.path.join(BENCH, "configs",
                           "f4-warm-piggyback-4srv-1chip.json")) as f:
        config = json.load(f)
    assert config["volume_servers"] == 4 and \
        list(config["holder_sets"].values()) == SETS
    with open(os.path.join(BENCH, "configs",
                           "f4-warm-piggyback-1chip.json")) as f:
        parent = json.load(f)
    same = set(parent) - {"source", "deployment", "volume_servers",
                          "guarantees", "guarantees_not_checked", "reduced",
                          "assumed", "piggyback"}
    assert all(config[key] == parent[key] for key in same)
    assert set(config["reduced"]) == set(parent["reduced"])


def test_cell_rehearsal_traced():
    rc, lines, err = rehearse(CELL, *WINDOW, trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 3       # an encode and its two rebuilds
    assert set(last["metrics"]) >= listed(CELL) - DEVICE_TRACE
    assert all(m["value"] > 0 for m in last["metrics"].values())
    checks = checks_of(lines)
    assert all(c["ok"] for c in checks.values())
    for name in ("holders_above_m_shards", "rebuilds_off_the_full_decode"):
        assert checks[name]["value"] == checks[name]["limit"] == 0
    assert checks["gathered_shards_at_most"]["value"] == 10.0
    assert checks["gathered_shards_at_most"]["limit"] == \
        mix()["gathered_shards_at_most"]
    assert checks["compiles_in_window"]["value"] == 0
    verify = phase(lines, "verify")
    assert verify["reference"].endswith("reference_piggyback")
    # the warm-up loses one set of each size
    assert verify["lost"][0] == [SETS[0], SETS[2]]
    assert phase(lines, "warm_plans")["plans"] == 4
    # the roofline count is made from the equation; the operand the node
    # replied with is printed beside it
    ops = phase(lines, "roofline")["ops"]
    assert [o["operand"] for o in ops] == [[128, 320]] * 3
    assert [o["work"]["column_terms"] for o in ops] == [60] * 3
    rebuilds = [json.loads(ln) for ln in lines
                if '"phase": "ec.rebuild"' in ln]
    assert all(r["node"]["/admin/ec/rebuild"]["repair_mode"] == "full" and
               r["node"]["/admin/ec/rebuild"]["repair_fallback"] is None
               for r in rebuilds)
    # no rebuild of the window built its plan: set-up had all four
    assert all(r["node"]["/admin/ec/rebuild"]["phases"]["plan"] < 0.01
               for r in rebuilds if r["timed"])
    relayout = phase(lines, "pb_decode_relayout")["parts"]
    assert relayout["ec.rebuild.pb_split"]["count"] == \
        relayout["ec.rebuild.pb_merge"]["count"] > 0


@pytest.mark.parametrize("seed", ["2147483659", "5"])
def test_the_order_of_lost_sets_does_not_hang_on_the_seed(seed):
    rc, lines, err = rehearse(CELL, *WINDOW, "--seed", seed)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"encode_mbps", "rebuild_mbps", "setup_s"}
    lost = lost_in_window(lines)
    assert len(lost) >= 3
    assert lost == [SETS[n % 4] for n in range(len(lost))]


@pytest.mark.parametrize("control,failing", [
    ("corrupt_piggyback_theta", "shards_differing_from_reference"),
    ("late_shard_after_rebuild", "shards_not_on_disk_when_command_returned"),
    ("corrupt_coupled_decode", "rebuilt_shards_differing_from_encoded"),
])
def test_control_comes_out_not_correct(control, failing):
    assert control in mix()["controls"]
    rc, lines, err = rehearse(CELL, *WINDOW, "--control", control)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is False and last["control"] == control
    checks = checks_of(lines)
    # its own check alone: another theta still rebuilds itself, a decode
    # off by one coefficient leaves the encode the reference's, a late
    # shard is the right shard
    assert [name for name, c in checks.items() if not c["ok"]] == [failing]
    assert checks[failing]["value"] > checks[failing]["limit"]
