"""CPU rehearsals of `spread_run_mib` (PR 41: the mean bytes of a run of
the spread, which a lane's window — counted in bytes since — caps), run by
hand like the files beside this one:

    python -m pytest benchmarks/tests/test_run_metric.py -q

None of this is a chip run and no number it sees is a device number.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "readers"))

from test_benchmark import last_line, rehearse  # noqa: E402
from test_wait_metrics import fake_run, op, spec_of  # noqa: E402

NAME = "spread_run_mib"
GEN = "/admin/ec/generate"


def test_the_metric_is_the_last_entry_and_lists_the_six_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric = bench["per_layer"][-1]
    assert metric == {
        "name": NAME, "unit": "MiB", "better": "higher",
        "source": "program_counter", "layer": "volume server EC stream",
        "moves": "encode_mbps",
        "workloads": [w["name"] for w in bench["workloads"]][:6]}
    spread = next(m for m in bench["per_layer"]
                  if m["name"] == "spread_mbps")
    assert metric["workloads"] == spread["workloads"]
    spec = spec_of(NAME)
    assert spec["reader"] == spec_of("spread_mbps")["reader"] \
        == "reply_stats"
    assert spec["args"]["numerator"] == ["reply:spread_bytes"]
    assert spec["args"]["denominator"] == ["reply:spread_sends"]
    assert spec["args"]["scale"] == 2.0 ** -20 and spec["what"]


def test_it_is_bytes_over_runs_and_nothing_without_the_runs():
    import reply_stats
    args = spec_of(NAME)["args"]
    run = fake_run([
        op("ec.encode", 0.5, GEN, {"spread_bytes": 1503238560,
                                   "spread_sends": 57}),
        op("ec.encode", 0.5, GEN, {"spread_bytes": 1503238560,
                                   "spread_sends": 61}),
        op("ec.encode", 9.9, GEN, {"spread_bytes": 1, "spread_sends": 1},
           error="HttpError: gone"),
        op("ec.rebuild", 0.4, "/admin/ec/rebuild", {"gather_bytes": 7})])
    assert reply_stats.read(args, run, None) == pytest.approx(
        2 * 1503238560 / 118 / 2 ** 20)
    # a tree whose reply has no spread_sends: nothing to divide by, the
    # metric is left out (a missing NUMERATOR reads 0, as spread_inflight
    # does on a tree without spread_send_s)
    old = fake_run([op("ec.encode", 0.5, GEN,
                       {"spread_bytes": 1503238560, "spread_busy_s": 0.4})])
    assert reply_stats.read(args, old, None) is None
    assert reply_stats.read(spec_of("spread_inflight")["args"], old,
                            None) == 0.0
    assert reply_stats.read(args, fake_run([]), None) is None


@pytest.mark.parametrize("workload,devices", [
    ("f4-warm-rs10-4-1chip.seal-rebuild", 1),
    ("f4-warm-rs10-4-mesh4.seal-rebuild", 4)])
def test_a_traced_rehearsal_reads_it(workload, devices):
    """One chip's rows are slab wide, the mesh's (CPU devices here) a
    quarter of that: both read a mean run, no longer than a shard."""
    rc, lines, err = rehearse(workload, devices=devices, trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True, err[-3000:]
    metrics = last["metrics"]
    assert {NAME, "spread_mbps", "spread_inflight"} <= set(metrics)
    assert metrics[NAME]["unit"] == "MiB"
    shard_mib = 32 / 10
    assert 0 < metrics[NAME]["value"] <= shard_mib * 1.01
