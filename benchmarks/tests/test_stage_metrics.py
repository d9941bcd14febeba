"""CPU rehearsals of the six per-layer metrics that read the program's
stage spans (PR 25), run by hand like the file beside this one:

    python -m pytest benchmarks/tests/test_stage_metrics.py -q

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

from lib import stage_overlap, trace_reduce  # noqa: E402

from test_benchmark import last_line, rehearse  # noqa: E402

NEW = {"encode_read_mbps", "encode_read_cpu_share", "d2h_ms",
       "encode_outside_node_s", "rebuild_fetch_ms",
       "idle_unattributed_share"}
MARKS = {"command_marks": "ec."}    # as the metric's json gives them
CELLS = ["f4-warm-rs10-4-1chip.seal-rebuild",
         "f4-warm-rs10-4-mesh4.seal-rebuild"]


def fake_run(spans=None, counters=None):
    lines = []
    run = types.SimpleNamespace(spans=spans or {}, counters=counters or {},
                                emit=lines.append, lines=lines)
    return run


# -- the metrics are data: an entry, a file, a reader -----------------------

def test_the_six_metrics_are_entries_appended_for_both_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # unpinned (PR 37): later PRs append metrics and cells, so neither the
    # count of entries nor the six's place at the end is held any more
    assert NEW <= set(by_name)
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW} | {"shell orchestration"}
    for metric in (by_name[name] for name in NEW):
        assert metric["workloads"][:2] == CELLS
        assert metric["layer"] in layers
        with open(os.path.join(BENCH, "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))


# -- span sums and counters ---------------------------------------------------

def test_span_terms_divides_sums_of_spans_and_counters():
    import span_terms
    run = fake_run(
        spans={"ec.encode.read": [26, 5.0], "ec.encode": [2, 7.5],
               "POST /admin/ec/generate": [2, 6.0],
               "POST /admin/ec/mount": [8, 0.4]},
        counters={"telemetry.read_bytes": 2_000_000_000})
    # 2 GB in 5 s of reader spans
    assert span_terms.read(
        {"numerator": ["counter:telemetry.read_bytes"],
         "denominator": ["span_s:ec.encode.read"], "scale": 1e-6},
        run, None) == pytest.approx(400.0)
    # (7.5 - 6.0) s over two encodes, and the parts as a line of their own
    got = span_terms.read(
        {"numerator": ["span_s:ec.encode", "-span_s:POST /admin/ec/generate"],
         "denominator": ["span_n:ec.encode"],
         "emit": ["POST /admin/ec/mount", "POST /admin/delete_volume"],
         "emit_as": "encode_outside_node"}, run, None)
    assert got == pytest.approx(0.75)
    assert run.lines == [{"phase": "encode_outside_node", "units": 2.0,
                          "parts": {
        "POST /admin/ec/mount": {"count": 8, "seconds": 0.4,
                                 "per_unit": pytest.approx(0.2)},
        "POST /admin/delete_volume": {"count": 0, "seconds": 0.0,
                                      "per_unit": 0.0}}}]


def test_span_terms_finds_nothing_on_a_program_without_the_spans():
    """The parent commit has no `ec.encode.read` span and no `read_bytes`
    counter: the reader returns None and raises nothing."""
    import counter_delta
    import span
    import span_terms
    run = fake_run(spans={"ec.encode": [2, 7.5]},
                   counters={"telemetry.dispatches": 52})
    assert span_terms.read(
        {"numerator": ["counter:telemetry.read_bytes"],
         "denominator": ["span_s:ec.encode.read"]}, run, None) is None
    assert counter_delta.read(
        {"numerator": ["telemetry.read_cpu_us"],
         "denominator": ["telemetry.read_busy_us"]}, run, None) is None
    assert span.read({"span": "ec.d2h"}, run, None) is None
    assert span.read({"span": "ec.rebuild.fetch.remote"}, run, None) is None
    with pytest.raises(ValueError):
        span_terms.read({"numerator": ["bytes:x"],
                         "denominator": ["span_n:ec.encode"]}, run, None)


# -- idle intervals x stage intervals -----------------------------------------

def hand_made():
    """One device, a 1000 ns window, busy 100-200 and 600-700: idle 800.
    Marks: ec.encode 0-500, check 500-550, ec.rebuild 550-1000."""
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:window", 0.0, 1000.0], ["bench:ec.encode", 0.0, 500.0],
            ["bench:check", 500.0, 50.0],
            ["bench:ec.rebuild", 550.0, 450.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%sw_rs_fused.1", 100.0, 100.0],
                ["%sw_rs_fused.1", 600.0, 100.0]]},
            {"name": "XLA Modules", "events": [["jit", 0.0, 1000.0]]}]}]
    stages = [
        # reader thread: two slabs, the second runs into the kernel
        ["sw:ec.encode.read", 0.0, 80.0], ["sw:ec.encode.read", 90.0, 60.0],
        # a drain thread, side by side with the reader
        ["sw:ec.d2h", 120.0, 200.0],
        # two fetches that overlap each other on two pull threads
        ["sw:ec.rebuild.fetch.remote", 560.0, 100.0],
        ["sw:ec.rebuild.fetch.remote", 580.0, 300.0],
        ["sw:ec.rebuild.write", 900.0, 50.0]]
    return planes, stages


def test_idle_time_is_attributed_to_the_stages_open_on_any_thread():
    planes, stages = hand_made()
    got = stage_overlap.attribute(planes, stages, "ec.")
    assert got["idle_s"] == pytest.approx(800e-9)
    # idle 0-100 200-600 700-1000; covered by a stage: 0-80 90-100
    # 200-320 560-600 700-880 900-950 -> 80+10+120+40+180+50 = 480
    assert got["unattributed_s"] == pytest.approx(320e-9)
    by = got["by_stage"]
    assert by["ec.encode.read"] == pytest.approx(90e-9)   # 0-80, 90-100
    assert by["ec.d2h"] == pytest.approx(120e-9)          # 200-320
    # the two fetches are one union 560-880, idle in 560-600 and 700-880
    assert by["ec.rebuild.fetch.remote"] == pytest.approx(220e-9)
    assert by["ec.rebuild.write"] == pytest.approx(50e-9)
    enc, reb = got["by_mark"]["ec.encode"], got["by_mark"]["ec.rebuild"]
    assert enc["idle_s"] == pytest.approx(400e-9)         # 0-100, 200-500
    assert enc["unattributed_s"] == pytest.approx(190e-9)
    assert enc["most"] == "ec.d2h"
    assert reb["idle_s"] == pytest.approx(350e-9)      # 550-600, 700-1000
    assert reb["most"] == "ec.rebuild.fetch.remote"
    assert got["by_mark"]["check"] == {
        "idle_s": pytest.approx(50e-9),
        "unattributed_s": pytest.approx(50e-9), "stages": {}, "most": None}
    assert reb["unattributed_s"] == pytest.approx(80e-9)
    # the metric's own account: inside the two commands, not the check
    assert got["in_commands"] == {"idle_s": pytest.approx(750e-9),
                                  "unattributed_s": pytest.approx(270e-9)}
    # both accounts as shares: the metric's value and the whole window's
    assert got["share"] == {
        "in_commands": pytest.approx(100 * 270 / 750),
        "whole_window": pytest.approx(100 * 320 / 800)}
    assert got["stages"] == 6
    assert got["stages_outside_command_marks"] == 0
    # the marks are the caller's to name: another mix, another prefix
    other = stage_overlap.attribute(planes, stages, "check")
    assert other["in_commands"]["idle_s"] == pytest.approx(50e-9)
    assert other["share"]["in_commands"] == pytest.approx(100.0)


def test_a_stage_outside_its_command_is_counted():
    planes, stages = hand_made()
    stages.append(["sw:ec.spread.send", 490.0, 30.0])   # runs into `check`
    got = stage_overlap.attribute(planes, stages, "ec.")
    assert got["stages_outside_command_marks"] == 1


def test_two_devices_are_averaged_and_no_device_is_none():
    planes, stages = hand_made()
    planes.append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.1", 0.0, 1000.0]]}]})
    got = stage_overlap.attribute(planes, stages, "ec.")
    assert got["idle_s"] == pytest.approx(400e-9)       # (800 + 0) / 2
    assert got["unattributed_s"] == pytest.approx(160e-9)
    assert stage_overlap.attribute(planes[:1], stages, "ec.") is None


def test_the_trace_reader_finds_nothing_without_stages_or_devices(tmp_path):
    import trace_stages
    run = fake_run()
    run.args = types.SimpleNamespace(out=str(tmp_path))
    run.workdir = str(tmp_path)
    assert trace_stages.read(MARKS, run, None) is None
    assert trace_stages.read(MARKS, run, {"devices": 0}) is None
    # devices in the reduced trace, but no trace directory to load
    assert trace_stages.read(MARKS, run, {"devices": 1}) is None
    # the recorded PR 24 trace: a program that mirrors no stage
    recorded = os.path.join(HERE, "recorded_trace.xplane.pb")
    assert stage_overlap.load_host(recorded) == []
    where = tmp_path / "trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    os.link(recorded, where / "r.xplane.pb")
    assert trace_stages.read(MARKS, run, {"devices": 1}) is None
    assert run.lines == []


# -- the add-a-metric rehearsal: all six at 32 MiB ------------------------------

@pytest.mark.parametrize("workload,devices", [(CELLS[0], 1), (CELLS[1], 4)])
def test_rehearsal_prints_the_new_metrics(workload, devices, tmp_path):
    """A whole traced run off the chip. Five of the six print in its result
    line beside the older ones; `idle_unattributed_share` needs device
    events, which a CPU run has none of, so it is read here from the real
    host events of that run (the `sw:` stages, the benchmark's marks)
    against a hand-made device line."""
    out = str(tmp_path / "kept")
    rc, lines, err = rehearse(workload, "--out", out, devices=devices,
                              trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True
    printed = set(last["metrics"])
    assert NEW - {"idle_unattributed_share"} <= printed
    assert {"encode_gather_share", "rebuild_gather_share",
            "encode_dispatch_ms"} <= printed
    values = {k: v["value"] for k, v in last["metrics"].items()}
    assert all(values[name] > 0 for name in NEW & printed)
    assert values["encode_read_cpu_share"] <= 100.0
    parts = next(json.loads(ln) for ln in lines
                 if '"encode_outside_node"' in ln)
    assert parts["units"] >= 1
    assert parts["parts"]["POST /admin/volume/readonly"]["count"] >= 1
    done = next(json.loads(ln) for ln in lines if '"window_done"' in ln)
    spans, counters = done["spans"], done["counters"]
    # one account: the reader's spans fit inside the stream spans
    assert spans["ec.encode.read"][1] <= spans["ec.encode.stream"][1]
    assert counters["telemetry.read_busy_us"] == pytest.approx(
        1e6 * spans["ec.encode.read"][1], rel=1e-3)
    assert "spread.run" not in spans

    xplane = trace_reduce.find_xplane(os.path.join(out, "trace"))
    stages = stage_overlap.load_host(xplane)
    names = {name for name, _, _ in stages}
    assert {"sw:ec.encode.read", "sw:ec.h2d", "sw:ec.d2h",
            "sw:ec.encode.write", "sw:ec.spread.send",
            "sw:ec.rebuild.fetch.remote", "sw:ec.rebuild.write"} <= names
    planes = trace_reduce.load(xplane)
    window = next(e for p in planes for ln in p["lines"]
                  for e in ln["events"] if e[0] == "bench:window")
    planes.append({"name": "/device:TPU:0", "lines": [{
        "name": "XLA Ops",
        "events": [["%sw_rs_fused.1", window[1] + 1000.0, 1000.0]]}]})
    got = stage_overlap.attribute(planes, stages, "ec.")
    # the stages and the benchmark's marks share the profiler's clock:
    # every stage of the traced cycle lies inside its command's mark
    assert got["stages_outside_command_marks"] == 0
    assert 0 <= got["unattributed_s"] < got["idle_s"]
    assert 0 <= got["in_commands"]["unattributed_s"] <= \
        got["unattributed_s"]
    assert got["in_commands"]["idle_s"] < got["idle_s"]
    assert got["by_mark"]["ec.encode"]["most"] is not None
    assert got["by_mark"]["ec.rebuild"]["most"] is not None
    assert {"ec.encode", "ec.rebuild"} <= set(got["by_mark"])
