"""CPU rehearsals of the two single-shard-repair cells, run by hand:

    python -m pytest benchmarks/tests/test_single_shard_repair.py -q

None of this is a chip run and no number it sees is a device number: each
rehearsal is `run.py --rehearse` at a 32 MiB volume in a process of its
own. What they hold: `correct` true with the two route checks printed
beside their limits; a traced rehearsal's result line CONTAINS the cell's
listed metrics that have something to read off the chip; the reference is
the one the configuration's layout names; lost shards are taken in the
order the traffic file names, whatever the seed, and no window repair
searches a plan; and the three controls
of the mix come out not correct with the named checks over their limits.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from test_benchmark import BENCH, bench_json, last_line, rehearse  # noqa: E402

PB = "f4-warm-piggyback-1chip.single-shard-repair"
FLAT = "f4-warm-rs10-4-1chip.single-shard-repair"
# read from a device trace: nothing to read where no device ran
DEVICE_TRACE = {"kernel_terms_roofline", "device_idle_share.seal",
                "idle_unattributed_share"}


# long enough for a whole cycle where the CPU stands in for the kernel: the
# coupled encode of 32 MiB takes 2-4 s there (a later --seconds wins)
WINDOW = ("--seconds", "8")
with open(os.path.join(BENCH, "traffic", "single-shard-repair.json")) as _f:
    ORDER = json.load(_f)["lost_order"]


def listed(cell: str, source=None) -> set:
    return {m["name"] for m in bench_json()["per_layer"]
            if cell in m.get("workloads", [])
            and (source is None or m["source"] == source)}


def checks_of(lines: list) -> dict:
    return {c["check"]: c for c in map(json.loads, lines) if "check" in c}


def phase(lines: list, name: str) -> dict:
    return next(json.loads(ln) for ln in lines
                if f'"phase": "{name}"' in ln)


def lost_in_window(lines: list) -> list:
    """The shards the window's repairs lost, in order (the first cycle of
    the verify line is the warm-up's)."""
    return [sid for cycle in phase(lines, "verify")["lost"][1:]
            for sid in cycle]


def test_the_new_cells_are_listed_where_they_report():
    bench = bench_json()
    for name in ("encode_mbps", "rebuild_mbps"):
        metric = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert {PB, FLAT} <= set(metric["workloads"])
    assert listed(PB) - listed(FLAT) == {"pb_relayout_share"}
    assert {"repair_bytes_share", "repair_fetch_ms", "repair_relayout_ms",
            "kernel_terms_roofline", "rebuild_gather_share"} <= listed(FLAT)
    # that one counts the dense operand a node dispatched: zeros and
    # padding here, so the cells report the share counted in terms
    assert "kernel_roofline_share" not in listed(PB) | listed(FLAT)
    # their spans are other routes' here: a full range is never fetched
    assert "rebuild_fetch_ms" not in listed(PB) | listed(FLAT)
    assert "mesh_sharded_share" not in listed(PB) | listed(FLAT)
    with open(os.path.join(BENCH, "traffic",
                           "single-shard-repair.json")) as f:
        mix = json.load(f)
    assert "lose" not in mix
    assert sorted(mix["lost_order"]) == list(range(10))
    assert 1 <= mix["repairs_per_seal"] <= 4
    for cell in (PB, FLAT):
        entry = next(w for w in bench["workloads"] if w["name"] == cell)
        assert entry["chips"] == 1
        assert entry["traffic"] == "single-shard-repair"


@pytest.mark.parametrize("cell,layout,route,limit,operands,terms", [
    (PB, "piggyback", "piggyback", 0.56, ([128, 320], [32, 176]), (60, 30)),
    (FLAT, "flat", "trace", 0.71, ([4, 10], [8, 56]), (40, None)),
])
def test_cell_rehearsal_traced(cell, layout, route, limit, operands, terms):
    rc, lines, err = rehearse(cell, *WINDOW, trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 3       # an encode and its two repairs
    # every listed metric but those a device trace alone can give
    assert set(last["metrics"]) >= listed(cell) - DEVICE_TRACE
    assert all(m["value"] > 0 for m in last["metrics"].values())
    checks = checks_of(lines)
    assert all(c["ok"] for c in checks.values())
    assert checks["repairs_off_the_configured_route"]["limit"] == 0
    assert checks["repair_bytes_share_at_most"]["limit"] == limit
    assert 0.5 < checks["repair_bytes_share_at_most"]["value"] <= limit
    assert checks["compiles_in_window"]["value"] == 0
    verify = phase(lines, "verify")
    assert verify["route"] == route and verify["repairs"] >= 3
    assert verify["reference"].endswith(
        "reference_piggyback" if layout == "piggyback" else "reference")
    assert all(len(set(lost)) == len(lost) and max(lost, default=0) < 10
               for lost in verify["lost"])
    assert lost_in_window(lines) == [ORDER[n % 10] for n in range(
        len(lost_in_window(lines)))]
    # the roofline count is made from the operation's equation; the
    # operand the node replied with is printed beside it
    ops = phase(lines, "roofline")["ops"]
    assert [o["operand"] for o in ops if o["op"] == "ec.encode"] == \
        [operands[0]]
    assert {tuple(o["operand"]) for o in ops if o["op"] == "ec.rebuild"} \
        == {tuple(operands[1])}
    assert [o["work"]["column_terms"] for o in ops
            if o["op"] == "ec.encode"] == [terms[0]]
    for o in (o for o in ops if o["op"] == "ec.rebuild"):
        assert o["work"]["column_terms"] == (
            terms[1] or 8 * (o["work"]["column_bytes"] - 8))
        assert terms[1] or 50 <= o["work"]["column_bytes"] - 8 <= 56
    # no repair of the window searched its plan: set-up had them planned
    repairs = [json.loads(ln) for ln in lines if '"phase": "ec.rebuild"' in ln]
    assert all(r["node"]["/admin/ec/rebuild"]["phases"]["plan"] < 0.05
               for r in repairs if r["timed"])
    assert ('"phase": "warm_plans"' in "".join(lines)) == (route == "trace")
    assert phase(lines, "memory")["max_rss_bytes"] > 0
    share = last["metrics"]["repair_bytes_share"]["value"]
    assert share == pytest.approx(55.0) if layout == "piggyback" \
        else 62.5 <= share <= 70.0


@pytest.mark.parametrize("cell,seed", [(PB, "2147483659"), (FLAT, "5"),
                                       (FLAT, "2147483659")])
def test_cell_rehearsal_untraced(cell, seed):
    """Whatever the seed, the warm-up repairs the order's first shard and
    the window's n-th repair its n-th: two rehearsals on different seeds
    emit the same `lost` sequence."""
    rc, lines, err = rehearse(cell, *WINDOW, "--seed", seed)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"encode_mbps", "rebuild_mbps", "setup_s"}
    assert phase(lines, "verify")["lost"][0] == ORDER[:1]
    lost = lost_in_window(lines)
    assert len(lost) >= 3
    assert lost == [ORDER[n % 10] for n in range(len(lost))]
    host = phase(lines, "host")
    assert host["ops"]["ec.rebuild"]["count"] == len(lost)


@pytest.mark.parametrize("cell,control,failing", [
    # the full gather is off the route and, with no byte account in its
    # reply, counted at the k whole shards it pulls
    (PB, "force_full_gather", ["repairs_off_the_configured_route",
                               "repair_bytes_share_at_most"]),
    (FLAT, "force_full_gather", ["repairs_off_the_configured_route",
                                 "repair_bytes_share_at_most"]),
    (PB, "corrupt_piggyback_theta", ["shards_differing_from_reference"]),
    (PB, "gather_every_range_twice", ["repair_bytes_share_at_most"]),
    (FLAT, "gather_every_range_twice", ["repair_bytes_share_at_most"]),
])
def test_control_comes_out_not_correct(cell, control, failing):
    with open(os.path.join(BENCH, "traffic",
                           "single-shard-repair.json")) as f:
        assert control in json.load(f)["controls"]
    rc, lines, err = rehearse(cell, *WINDOW, "--control", control)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is False and last["control"] == control
    checks = checks_of(lines)
    # only the named checks: the shards are still the reference's (full
    # gather, a doubled gather) or still repair themselves (another theta)
    assert [name for name, c in checks.items() if not c["ok"]] == failing
    assert all(checks[name]["value"] > checks[name]["limit"]
               for name in failing)
    if control == "gather_every_range_twice":
        share = checks["repair_bytes_share_at_most"]["value"]
        assert share == pytest.approx(1.10) if cell == PB \
            else 1.25 <= share <= 1.40
