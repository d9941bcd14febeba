"""CPU rehearsals of the benchmark, run by hand:

    python -m pytest benchmarks/tests -q

None of this is a chip run and no number it sees is a device number. Each
rehearsal is a process of its own (`run.py --rehearse`, JAX_PLATFORMS=cpu)
at a 32 MiB volume; the whole file takes about two minutes.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import datagen, reference, roofline, trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LAST_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench_json(root=ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(workload: str, *extra: str, root: str = ROOT, devices: int = 1,
             trace: int = 0, rehearse_flag: bool = True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, os.path.join(root, "benchmarks", "run.py"),
           "--workload", workload, "--seed", "2147483659", "--seconds", "2",
           "--trace", str(trace), *extra]
    if rehearse_flag:
        cmd += ["--rehearse", "--volume-mib", "32"]
    done = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    return done.returncode, lines, done.stderr


def last_line(lines: list) -> dict:
    last = json.loads(lines[-1])
    assert LAST_KEYS <= set(last), last
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(last["device"])
    assert last["rehearsal_not_a_chip_run"] is True
    return last


# -- what BENCHMARK.json names exists, and is named as the contract allows --

def test_benchmark_json_names_files_that_exist():
    bench = bench_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for config in bench["configs"]:
        assert NAME.match(config["name"])
        assert os.path.isfile(os.path.join(ROOT, config["file"]))
        with open(os.path.join(ROOT, config["file"])) as f:
            body = json.load(f)
        for key in config["reduced"]:
            assert NAME.match(key) and key in body["reduced"], key
        assert 1 <= len(config["source"]) <= 200
    configs = {c["name"] for c in bench["configs"]}
    cells = set()
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["config"] in configs and cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200, (cell["name"], len(cell["why"]))
        traffic = os.path.join(BENCH, "traffic", cell["traffic"] + ".json")
        with open(traffic) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(BENCH, "kinds", kind + ".py"))
        cells.add(cell["name"])
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= \
        max(1, len(cells) // 2)
    end = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in end
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert set(metric.get("workloads", [])) <= cells
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in end
        with open(os.path.join(BENCH, "layer_metrics",
                               metric["name"] + ".json")) as f:
            reader = json.load(f)["reader"]
        assert os.path.isfile(os.path.join(BENCH, "readers", reader + ".py"))
    for folder, _dirs, files in os.walk(BENCH):
        for name in files:
            if "__pycache__" not in folder:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


# -- the arithmetic of the yardstick ---------------------------------------

def test_roofline_count_against_hand_worked_numbers():
    assert roofline.column_bytes(4, 10) == 14
    assert roofline.column_ops(4, 10) == 5120        # (32 x 80) x 2
    assert roofline.column_ops(1, 10) == 1280
    peak = roofline.peaks("TPU v5 lite")
    # one column: 14 B / 819 GB/s = 17.1 ps against 5120 / 393 T = 13.0 ps
    one = roofline.least_seconds(1, 4, 10, peak)
    assert one["bound"] == "hbm"
    assert one["hbm_seconds"] == pytest.approx(17.09e-12, rel=1e-3)
    assert one["int8_seconds"] == pytest.approx(13.03e-12, rel=1e-3)
    # an 8 MiB-wide dispatch: no less than ~143 us
    wide = roofline.least_seconds(8 << 20, 4, 10, peak)
    assert wide["seconds"] == pytest.approx(143.4e-6, rel=1e-3)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_sizes_are_the_same_set_for_every_seed():
    spec = {"sizes": "ragged", "bytes": 1048576, "ragged": 0.0625}
    a = datagen.needle_sizes(spec, 64 << 20, 1, 0)
    b = datagen.needle_sizes(spec, 64 << 20, 2 ** 31 + 11, 0)
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.sum() >= 64 << 20 and len(a) == 64
    assert a.min() == 1048576 - 65536 and a.max() == 1048576 + 65536


def test_the_seal_mix_names_its_losses():
    with open(os.path.join(BENCH, "traffic", "seal-rebuild.json")) as f:
        mix = json.load(f)
    assert "lose" not in mix and len(mix["lose_sets"]) == 6
    # BASELINE config 2's "drop 4 shards": 2 data + 2 parity each
    assert all(lost == sorted(set(lost)) and len(lost) == 4 and
               lost[1] < 10 <= lost[2] < 14 for lost in mix["lose_sets"])
    # no code of the benchmark draws a loss from the seed any more
    for folder in ("kinds", "lib"):
        for name in os.listdir(os.path.join(BENCH, folder)):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    assert "def lost_shards" not in f.read(), name


@pytest.mark.parametrize("seed", ["2147483659", "5"])
def test_the_lost_sets_do_not_hang_on_the_seed(seed):
    """Two rehearsals on different seeds emit the same `lost` sequence:
    the warm-up takes the file's first set, the window's n-th rebuild
    its n-th, round and round."""
    with open(os.path.join(BENCH, "traffic", "seal-rebuild.json")) as f:
        sets = json.load(f)["lose_sets"]
    rc, lines, err = rehearse("f4-warm-rs10-4-1chip.seal-rebuild",
                              "--seed", seed, "--seconds", "4")
    assert rc == 0, err[-3000:]
    assert last_line(lines)["correct"] is True
    lost = next(json.loads(ln) for ln in lines
                if '"phase": "verify"' in ln)["lost"]
    # the warm-up's and two of the window's at the least, on a slow host
    assert len(lost) >= 3 and lost[0] == sets[0]
    assert lost[1:] == [sets[n % 6] for n in range(len(lost) - 1)]
    rebuilds = [json.loads(ln) for ln in lines
                if '"phase": "ec.rebuild"' in ln]
    assert len(rebuilds) == len(lost)


def test_shard_bytes_follow_the_striping_rule():
    assert reference.shard_bytes(1, 10) == 1 << 20
    assert reference.shard_bytes(10 << 20, 10) == 1 << 20
    assert reference.shard_bytes((10 << 20) + 1, 10) == 2 << 20
    # more than k x 1 GiB: one large-block row, the rest in small blocks
    assert reference.shard_bytes((10 << 30) + 1, 10) == (1 << 30) + (1 << 20)
    assert reference.shard_bytes(10 << 30, 10) == 1024 << 20


def test_reference_is_systematic_and_the_control_differs(tmp_path):
    matrix = reference.coding_matrix(10, 4)
    assert (matrix[:10] == np.eye(10, dtype="uint8")).all()
    dat = tmp_path / "v.dat"
    dat.write_bytes(os.urandom(3 * (10 << 20) // 2 + 12345))
    sound = reference.shard_shas(str(dat), 10, 4)
    assert len(set(sound)) == 14
    broken = matrix.copy()
    broken[10, 0] ^= 1
    control = reference.shard_shas(str(dat), 10, 4, matrix=broken)
    assert control[:10] == sound[:10] and control[10] != sound[10]
    assert control[11:] == sound[11:]


def test_trace_reduce_on_the_recorded_trace():
    """`recorded_trace.xplane.pb` is the profiler's own file from a chip run
    of this PR (TPU v5 lite x1): the first cycle of the one-chip seal-rebuild
    cell, one ec.encode + loss + ec.rebuild of a 1 GiB volume. The numbers
    were worked out from its events by hand: 26 kernel events of 0.9306 to
    0.9309 ms that do not overlap, inside a 5.89 s `bench:window`."""
    planes = trace_reduce.load(os.path.join(HERE, "recorded_trace.xplane.pb"))
    with open(os.path.join(BENCH, "configs",
                           "f4-warm-rs10-4-1chip.json")) as f:
        pattern = json.load(f)["kernel"]["trace_pattern"]
    got = trace_reduce.reduce(planes, pattern)
    assert got["devices"] == 1 and got["kernel_events"] == 26
    assert got["window_s"] == pytest.approx(5.89057015, rel=1e-9)
    assert got["busy_s"] == pytest.approx(0.024199367, rel=1e-9)
    assert got["kernel_s"] == pytest.approx(0.024199367, rel=1e-9)
    assert [name for name, _ in got["idle_gaps"]][:2] == \
        ["ec.encode", "ec.rebuild"]
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-9)
    assert len(got["device_ops"]) == 1
    assert len(got["device_ops"][0][0]) <= trace_reduce.NAME_CHARS

    # the two traced commands each dispatched 1,080,033,280 payload bytes
    # at k = 10: 216,006,656 columns x 14 B / 819 GB/s = 3.6924 ms, which
    # is 15.26 % of the 24.199 ms the kernel events took
    class FakeRun:
        device = {"kind": "TPU v5 lite"}
        ops = [{"op": op, "traced": True, "rows": 4, "k": 10,
                "counters": {"telemetry.device_bytes": 1080033280}}
               for op in ("ec.encode", "ec.rebuild")]

    sys.path.insert(0, os.path.join(BENCH, "readers"))
    import trace_kernel
    share = trace_kernel.read({"ops": ["ec.encode", "ec.rebuild"]},
                              FakeRun, got)
    assert share == pytest.approx(15.258, rel=1e-3)
    import trace_idle
    assert trace_idle.read({}, FakeRun, got) == pytest.approx(99.589, rel=1e-4)
    assert trace_kernel.read({"ops": []}, FakeRun, got) is None


def test_trace_reduce_unions_overlaps_and_names_gaps():
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench:window", 0.0, 1000.0], ["bench:ec.encode", 0.0, 400.0],
            ["bench:check", 400.0, 600.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 100.0, 100.0], ["kern.2", 150.0, 100.0],
                ["kern.2", 600.0, 100.0]]},
            {"name": "Steps", "events": [["0", 0.0, 1000.0]]}]}]
    got = trace_reduce.reduce(planes, r"^kern")
    assert got["devices"] == 1 and got["kernel_events"] == 2
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx(250e-9)      # 100-250 and 600-700
    assert got["kernel_s"] == pytest.approx(200e-9)
    gaps = dict(got["idle_gaps"])
    assert gaps["ec.encode"] == pytest.approx(450e-9)  # 0-100 and 250-600
    assert gaps["check"] == pytest.approx(300e-9)      # 700-1000


# -- each cell end to end, off the chip and labelled so --------------------

@pytest.mark.parametrize("workload,devices,trace,expect", [
    ("f4-warm-rs10-4-1chip.seal-rebuild", 1, 0,
     {"encode_mbps", "rebuild_mbps", "setup_s"}),
    ("f4-warm-rs10-4-1chip.seal-rebuild", 1, 1,
     {"encode_gather_share", "rebuild_gather_share", "encode_dispatch_ms"}),
    ("f4-warm-rs10-4-mesh4.seal-rebuild", 4, 1,
     {"encode_gather_share", "rebuild_gather_share", "encode_dispatch_ms",
      "mesh_sharded_share"}),
])
def test_cell_rehearsal(workload, devices, trace, expect):
    rc, lines, err = rehearse(workload, devices=devices, trace=trace)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    # device-trace metrics find nothing to read off the chip and are left out
    # (`>=`: a later PR's metric is one more name here, not a failure)
    assert set(last["metrics"]) >= expect
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["device"]["count"] == devices
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    checks = [json.loads(ln) for ln in lines[:-1] if '"check"' in ln]
    assert checks and all({"value", "limit"} <= set(c) for c in checks)
    # each number compared beside its limit: the result line's last key
    # and the last lines on standard error
    assert list(last)[-1] == "checks"
    assert last["checks"] == {c["check"]: {"value": c["value"],
                                           "limit": c["limit"]}
                              for c in checks}
    assert [ln.split(":")[0] for ln in err.splitlines()[-len(checks):]] == \
        ["check " + c["check"] for c in checks]
    # what the host did beside the run: an info line, never a metric
    host = next(json.loads(ln) for ln in lines if '"phase": "host"' in ln)
    assert set(host["ops"]) == {"ec.encode", "ec.rebuild"}
    for op in host["ops"].values():
        assert op["count"] >= 1
        assert 0 < op["median_wall_s"] <= op["max_wall_s"]
    assert {"ru_minflt", "ru_nivcsw"} <= set(host["window"])
    assert not {"host", "ops", "window"} & set(last["metrics"])


# -- the comparison fails when the program is wrong -------------------------

@pytest.mark.parametrize("control,failing,sound", [
    ("corrupt_encode_matrix", "shards_differing_from_reference",
     "rebuilt_shards_differing_from_encoded"),
    ("corrupt_rebuild_decode", "rebuilt_shards_differing_from_encoded",
     "shards_differing_from_reference"),
    ("late_shard_after_encode", "shards_not_on_disk_when_command_returned",
     "commands_that_raised"),
    ("late_shard_after_rebuild", "shards_not_on_disk_when_command_returned",
     "shards_differing_from_reference"),
])
def test_control_comes_out_not_correct(control, failing, sound):
    """The whole of a run but the look for a chip, with the program broken
    underneath: one coefficient of the coding matrix, one of the rebuild's
    decode plan alone (the encoded shards stay sound), or a shard that
    lands after its command has returned."""
    with open(os.path.join(BENCH, "traffic", "seal-rebuild.json")) as f:
        assert control in json.load(f)["controls"]
    rc, lines, err = rehearse("f4-warm-rs10-4-1chip.seal-rebuild",
                              "--control", control)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is False and last["failed"] > 0
    checks = {c["check"]: c for c in map(json.loads, lines) if "check" in c}
    assert checks[failing]["value"] > 0 and not checks[failing]["ok"]
    assert checks[sound]["value"] == 0 and checks[sound]["ok"]


def test_the_mesh_rebuild_control_fails_too():
    rc, lines, err = rehearse("f4-warm-rs10-4-mesh4.seal-rebuild",
                              "--control", "corrupt_rebuild_decode",
                              devices=4)
    assert rc == 0, err[-3000:]
    assert last_line(lines)["correct"] is False
    checks = {c["check"]: c for c in map(json.loads, lines) if "check" in c}
    assert checks["rebuilt_shards_differing_from_encoded"]["value"] > 0
    assert checks["shards_differing_from_reference"]["value"] == 0


def test_shards_short_on_disk(tmp_path):
    from lib.cluster import Cluster
    fake = Cluster.__new__(Cluster)
    fake.dirs, fake.collection = [str(tmp_path)], "bench"
    for sid, size in ((0, 8), (1, 8), (2, 5)):
        (tmp_path / f"bench_7.ec{sid:02d}").write_bytes(b"x" * size)
    (tmp_path / "bench_7.ecx").write_bytes(b"index")
    assert fake.shards_short_on_disk(7, [0, 1], 8) == []
    assert fake.shards_short_on_disk(7, [0, 1, 2, 3], 8) == [2, 3]


# -- no chip, no program: no result ------------------------------------------

def test_off_the_chip_there_is_no_result():
    rc, lines, _err = rehearse("f4-warm-rs10-4-1chip.seal-rebuild",
                               rehearse_flag=False)
    assert rc != 0 and lines == []


def copy_benchmark(tmp_path, with_program: bool) -> str:
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_program:
        os.symlink(os.path.join(ROOT, "seaweedfs_tpu"),
                   os.path.join(root, "seaweedfs_tpu"))
    return root


def test_without_the_program_there_is_no_result(tmp_path):
    root = copy_benchmark(tmp_path, with_program=False)
    rc, lines, _err = rehearse("f4-warm-rs10-4-1chip.seal-rebuild", root=root)
    assert rc != 0 and lines == []


# -- a later PR adds a cell by adding files and one entry each ---------------

def test_a_cell_and_a_metric_are_added_by_adding_files(tmp_path):
    root = copy_benchmark(tmp_path, with_program=True)
    bench_dir = os.path.join(root, "benchmarks")
    with open(os.path.join(bench_dir, "traffic", "seal-rebuild.json")) as f:
        mix = json.load(f)
    mix.update(lose_sets=[[3], [11]])
    with open(os.path.join(bench_dir, "traffic", "one-lost.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench_dir, "layer_metrics",
                           "stripe_gather_ms.json"), "w") as f:
        json.dump({"reader": "span", "args": {
            "span": "gather.stripe", "scale": 1000}}, f)
    bench = bench_json(root)
    cell = "f4-warm-rs10-4-1chip.one-lost"
    bench["workloads"].append({
        "name": cell, "config": "f4-warm-rs10-4-1chip",
        "traffic": "one-lost", "chips": 1, "why": "throw-away"})
    for metric in bench["end_to_end"]:
        if metric["name"] in ("encode_mbps", "rebuild_mbps"):
            metric["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "stripe_gather_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "volume server EC stream",
        "moves": "rebuild_mbps", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, lines, err = rehearse(cell, root=root, trace=1)
    assert rc == 0, err[-3000:]
    last = last_line(lines)
    assert last["correct"] is True
    assert last["metrics"]["stripe_gather_ms"]["value"] > 0
    # a metric that lists its cells is not read in a cell it does not list
    assert "encode_gather_share" not in last["metrics"]
    upload = next(json.loads(ln) for ln in lines if '"upload"' in ln)
    assert upload["lose_sets"] == [[3], [11]]
