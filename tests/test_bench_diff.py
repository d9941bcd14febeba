"""tools/bench_diff.py: regression detection between bench records.

Exercised on two small synthetic records in the driver's wrapper shape
(a throughput, a latency, a failure count and a nested leaf each; the
newer one also carries a drill the older one lacks, with a rebuild
throughput that fell off a cliff) plus fixtures for threshold/exit-code
behavior.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_diff  # noqa: E402


@pytest.fixture
def records(tmp_path):
    """(older, newer) record paths in the driver's {n, rc, parsed}
    wrapper. The older run has no cluster_rebuild drill; the newer one
    recorded 2 MB/s there."""
    older = {"n": 4, "rc": 0, "parsed": {
        "metric": "ec_encode_rs10_4_mbps", "value": 410, "unit": "MB/s",
        "encode_s": 10.2, "read_errors": 0,
        "data_plane": {"read_rps": 9800, "p99_ms": 1.9}}}
    newer = {"n": 5, "rc": 0, "parsed": {
        "metric": "ec_encode_rs10_4_mbps", "value": 395, "unit": "MB/s",
        "encode_s": 10.9, "read_errors": 0,
        "data_plane": {"read_rps": 10100, "p99_ms": 2.0},
        "cluster_rebuild": {"rebuild_mbps_volume_bytes": 2,
                            "rebuild_s": 31.0, "recompiles": 0}}}
    paths = []
    for name, rec in (("older.json", older), ("newer.json", newer)):
        p = tmp_path / name
        p.write_text(json.dumps(rec))
        paths.append(str(p))
    return paths


class TestDirection:
    def test_throughput_metrics_higher_is_better(self):
        for m in ("cluster_rebuild.rebuild_mbps_volume_bytes",
                  "bench.write_rps", "matmul.value",
                  "degraded_read.speedup"):
            assert bench_diff.direction(m) is True

    def test_latency_and_failure_metrics_lower_is_better(self):
        for m in ("cluster_rebuild.rebuild_s", "plane.p99_ms",
                  "cluster_rebuild.recompiles", "read.errors"):
            assert bench_diff.direction(m) is False

    def test_unclassified_metrics_never_flagged(self):
        assert bench_diff.direction("bench.shard_count") is None
        d = bench_diff.diff_records({"shard_count": 10},
                                    {"shard_count": 1}, 0.2)
        assert d["regressions"] == []
        assert [u["metric"] for u in d["unclassified"]] == \
            ["shard_count"]


class TestFlatten:
    def test_nested_numeric_leaves_dotted(self):
        flat = bench_diff.flatten(
            {"a": {"b_s": 1.5, "skip": "text", "flag": True,
                   "arr": [1, 2]}, "top_rps": 3})
        assert flat == {"a.b_s": 1.5, "top_rps": 3}

    def test_driver_wrapper_unwrapped(self, tmp_path):
        p = tmp_path / "rec.json"
        p.write_text(json.dumps(
            {"n": 5, "rc": 0, "parsed": {"x_rps": 7}}))
        assert bench_diff.load_record(str(p)) == {"x_rps": 7}


class TestDiffRecords:
    def test_regression_beyond_threshold_flagged_worst_first(self):
        d = bench_diff.diff_records(
            {"a_mbps": 100, "b_mbps": 100, "c_s": 1.0},
            {"a_mbps": 50, "b_mbps": 79, "c_s": 1.1}, 0.2)
        metrics = [r["metric"] for r in d["regressions"]]
        assert metrics == ["a_mbps", "b_mbps"]  # -50% before -21%
        assert d["regressions"][0]["delta_frac"] == pytest.approx(-0.5)

    def test_within_threshold_not_flagged(self):
        d = bench_diff.diff_records({"a_mbps": 100}, {"a_mbps": 85},
                                    0.2)
        assert d["regressions"] == []

    def test_improvements_and_added_removed(self):
        d = bench_diff.diff_records({"a_mbps": 100, "gone_s": 1.0},
                                    {"a_mbps": 200, "new_rps": 5}, 0.2)
        assert [i["metric"] for i in d["improvements"]] == ["a_mbps"]
        assert d["added"] == ["new_rps"]
        assert d["removed"] == ["gone_s"]

    def test_lower_is_better_regression(self):
        d = bench_diff.diff_records({"p99_ms": 10}, {"p99_ms": 30},
                                    0.2)
        assert [r["metric"] for r in d["regressions"]] == ["p99_ms"]


class TestWholeRecords:
    def test_disjoint_drill_sets_run_clean(self, records, capsys):
        """The older record predates the cluster-rebuild drill, so the
        newer one's cliff surfaces as ADDED metrics, not a regression —
        the differ must not crash on records with disjoint drill sets."""
        rc = bench_diff.main(records)
        assert rc == 0
        out = capsys.readouterr().out
        assert "cluster_rebuild" in out  # listed under added

    @staticmethod
    def _with_healthy_rebuild(older_path, tmp_path):
        """Graft a healthy 72 MB/s rebuild figure onto the older
        record — the newer one's 2 MB/s must then be flagged."""
        with open(older_path) as f:
            old = json.load(f)
        old["parsed"]["cluster_rebuild"] = {
            "rebuild_mbps_volume_bytes": 72}
        p = tmp_path / "older_healthy.json"
        p.write_text(json.dumps(old))
        return str(p)

    def test_rebuild_cliff_flagged(self, records, tmp_path, capsys):
        healthy = self._with_healthy_rebuild(records[0], tmp_path)
        rc = bench_diff.main([healthy, records[1]])
        assert rc == 1
        out = capsys.readouterr().out
        assert "cluster_rebuild.rebuild_mbps_volume_bytes" in out
        assert "-97" in out  # 72 -> 2 is a -97.2% cliff

    def test_json_output_machine_readable(self, records, tmp_path,
                                          capsys):
        healthy = self._with_healthy_rebuild(records[0], tmp_path)
        rc = bench_diff.main([healthy, records[1], "--json"])
        assert rc == 1
        d = json.loads(capsys.readouterr().out)
        cliff = next(
            r for r in d["regressions"]
            if r["metric"] == "cluster_rebuild.rebuild_mbps_volume_bytes")
        assert cliff["old"] == 72
        assert cliff["new"] == 2
        assert cliff["delta_frac"] == pytest.approx(-70 / 72,
                                                    abs=1e-4)

    def test_threshold_knob(self, records, tmp_path, capsys):
        """At an absurd threshold not even the cliff regresses."""
        healthy = self._with_healthy_rebuild(records[0], tmp_path)
        rc = bench_diff.main([healthy, records[1],
                              "--threshold", "10.0"])
        assert rc == 0
        capsys.readouterr()


class TestExitCodes:
    def test_unreadable_input_rc2(self, tmp_path, capsys):
        rc = bench_diff.main([str(tmp_path / "missing.json"),
                              str(tmp_path / "also_missing.json")])
        assert rc == 2
        capsys.readouterr()

    def test_malformed_json_rc2(self, tmp_path, capsys):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        q = tmp_path / "ok.json"
        q.write_text("{}")
        assert bench_diff.main([str(p), str(q)]) == 2
        capsys.readouterr()
