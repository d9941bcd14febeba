"""CPU rehearsals of the seven per-layer metrics that read what a thread
waits for (PR 40: the holder's run split at its system calls, the probe of
the interpreter lock, the longest interval of a stage a reply), run by
hand like the files beside this one:

    python -m pytest benchmarks/tests/test_wait_metrics.py -q

None of this is a chip run and no number it sees is a device number.
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "readers"))

from test_benchmark import last_line, rehearse  # noqa: E402

NEW = ["holder_recv_share", "holder_write_share", "holder_cpu_share",
       "interp_lock_wait_ms", "process_stall_share", "d2h_max_ms",
       "fetch_max_ms"]
SEAL = "f4-warm-rs10-4-1chip.seal-rebuild"


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_seven_are_appended_after_the_accepted_ones_for_six_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(NEW[0]):][:7] == NEW
    assert names.index(NEW[0]) > names.index("spread_inflight")
    cells = [w["name"] for w in bench["workloads"]][:6]
    moved = {"process_stall_share": "rebuild_mbps",
             "fetch_max_ms": "rebuild_mbps"}
    for metric in bench["per_layer"]:
        if metric["name"] not in NEW:
            continue
        assert metric["workloads"][:6] == cells
        assert metric["source"] == "program_counter"
        assert metric["layer"] == "volume server EC stream"
        assert metric["moves"] == moved.get(metric["name"], "encode_mbps")
        spec = spec_of(metric["name"])
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        assert spec["what"]


def test_a_traced_rehearsal_of_the_seal_cell_prints_the_seven():
    rc, lines, err = rehearse(SEAL, trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True, err[-3000:]
    metrics = last["metrics"]
    assert set(NEW) <= set(metrics)
    assert metrics["holder_recv_share"]["value"] \
        + metrics["holder_write_share"]["value"] <= 100.0
    assert 0 < metrics["holder_cpu_share"]["value"] <= 100.0
    assert 0 <= metrics["interp_lock_wait_ms"]["value"] < 50.0
    assert 0 <= metrics["process_stall_share"]["value"] < 100.0
    assert metrics["d2h_max_ms"]["value"] >= metrics["d2h_ms"]["value"]
    assert metrics["fetch_max_ms"]["value"] >= \
        metrics["rebuild_fetch_ms"]["value"] * 0.999
    rows = [json.loads(ln) for ln in lines]
    held = [r for r in rows if r.get("phase") == "stage_max"]
    assert [r["key"] for r in held] == [["stage_max_s", "d2h+mxu"],
                                        ["stage_max_s", "gather"]]
    assert held[1]["op"] == "ec.rebuild"
    assert all(0 <= r["op_index"] < r["ops"] and r["wall_s"] > 0
               for r in held)
    window = next(r for r in rows if r.get("phase") == "window_done")
    for field in ("holder_runs", "holder_bytes", "holder_us",
                  "holder_recv_us", "holder_write_us", "holder_cpu_us",
                  "lock_probe_samples", "lock_probe_elapsed_us",
                  "lock_probe_late_us"):
        assert window["counters"]["telemetry." + field] > 0, field


def test_an_untraced_rehearsal_carries_the_counters_and_no_new_metric():
    rc, lines, err = rehearse(SEAL)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert set(last["metrics"]) == {"encode_mbps", "rebuild_mbps", "setup_s"}
    window = next(r for r in map(json.loads, lines)
                  if r.get("phase") == "window_done")
    assert window["counters"]["telemetry.holder_runs"] > 0
    assert window["counters"]["telemetry.lock_probe_samples"] > 0


def fake_run(ops):
    lines = []
    return types.SimpleNamespace(ops=ops, emit=lines.append, lines=lines)


def op(kind, wall, route, stats, error=None):
    return {"op": kind, "wall_s": wall, "error": error,
            "replies": {route: stats}}


def test_reply_max_takes_the_largest_and_names_who_held_it():
    import reply_max
    gen, reb = "/admin/ec/generate", "/admin/ec/rebuild"
    run = fake_run([
        op("ec.encode", 0.5, gen, {"stage_max_s": {"d2h+mxu": 0.031}}),
        op("ec.rebuild", 0.4, reb, {"stage_max_s": {"d2h+mxu": 0.047,
                                                    "gather": 0.09}}),
        op("ec.rebuild", 9.9, reb, {"stage_max_s": {"gather": 5.0}},
           error="HttpError: gone"),
        op("ec.encode", 0.6, gen, {"stage_max_s": {"d2h+mxu": 0.040}})])
    spec = spec_of("d2h_max_ms")
    assert spec["reader"] == "reply_max"
    assert reply_max.read(spec["args"], run, None) == pytest.approx(47.0)
    assert run.lines == [{
        "phase": "stage_max", "key": ["stage_max_s", "d2h+mxu"],
        "value": pytest.approx(47.0), "op_index": 1, "op": "ec.rebuild",
        "route": reb, "wall_s": 0.4, "ops": 4}]
    # one route: the rebuilds alone; a failed operation is left out
    assert reply_max.read(spec_of("fetch_max_ms")["args"], run, None) \
        == pytest.approx(90.0)
    assert reply_max.read({**spec["args"], "routes": [gen]}, run, None) \
        == pytest.approx(40.0)
    assert run.lines[-1]["op_index"] == 3


def test_reply_max_finds_nothing_in_replies_without_the_field():
    import reply_max
    gen, reb = "/admin/ec/generate", "/admin/ec/rebuild"
    run = fake_run([op("ec.encode", 0.5, gen, {"phases": {"gather": 0.1}}),
                    op("ec.rebuild", 0.4, reb, {"stage_max_s": "n/a"}),
                    op("ec.rebuild", 0.4, "/admin/ec/copy", None)])
    for name in ("d2h_max_ms", "fetch_max_ms"):
        assert reply_max.read(spec_of(name)["args"], run, None) is None
    assert reply_max.read(spec_of("d2h_max_ms")["args"], fake_run([]),
                          None) is None
    assert run.lines == []


def test_counter_metrics_find_nothing_on_a_tree_without_the_counters():
    import counter_delta
    run = types.SimpleNamespace(counters={"telemetry.dispatches": 26})
    for name in NEW[:5]:
        assert counter_delta.read(spec_of(name)["args"], run, None) is None
    # a window with no stall reads 0, not nothing
    run.counters = {"telemetry.lock_probe_elapsed_us": 45_000_000,
                    "telemetry.lock_probe_samples": 8000,
                    "telemetry.lock_probe_late_us": 4_000_000}
    assert counter_delta.read(spec_of("process_stall_share")["args"], run,
                              None) == 0.0
    assert counter_delta.read(spec_of("interp_lock_wait_ms")["args"], run,
                              None) == pytest.approx(0.5)
