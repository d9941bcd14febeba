"""Metrics, leveled logging, scaffold (reference weed/stats/metrics.go,
weed/glog, weed/command/scaffold.go)."""

import io
import json

import pytest

from seaweedfs_tpu.stats.metrics import (Counter, Gauge, Histogram,
                                         Registry)
from seaweedfs_tpu.util import glog


class TestMetrics:
    def test_counter(self):
        r = Registry()
        c = r.counter("x_total", "help here", labels=("op",))
        c.inc("read")
        c.inc("read")
        c.inc("write", amount=3)
        text = r.render()
        assert '# TYPE x_total counter' in text
        assert 'x_total{op="read"} 2' in text
        assert 'x_total{op="write"} 3' in text

    def test_gauge(self):
        r = Registry()
        g = r.gauge("vols", labels=("collection", "type"))
        g.set(5, "", "normal")
        g.set(14, "pics", "ec")
        text = r.render()
        assert 'vols{collection="",type="normal"} 5' in text
        assert 'vols{collection="pics",type="ec"} 14' in text

    def test_histogram_buckets(self):
        r = Registry()
        h = r.histogram("lat_seconds", labels=("op",),
                        buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v, "get")
        text = r.render()
        assert 'lat_seconds_bucket{op="get",le="0.01"} 1' in text
        assert 'lat_seconds_bucket{op="get",le="0.1"} 2' in text
        assert 'lat_seconds_bucket{op="get",le="1"} 3' in text
        assert 'lat_seconds_bucket{op="get",le="+Inf"} 4' in text
        assert 'lat_seconds_count{op="get"} 4' in text
        assert 'lat_seconds_sum{op="get"} 5.555' in text

    def test_histogram_le_inclusive(self):
        """A value landing exactly on a bucket bound counts in THAT
        bucket — Prometheus 'le' is inclusive."""
        r = Registry()
        h = r.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
        h.observe(0.01)
        h.observe(0.1)
        text = r.render()
        assert 'lat_seconds_bucket{le="0.01"} 1' in text
        assert 'lat_seconds_bucket{le="0.1"} 2' in text
        assert 'lat_seconds_bucket{le="1"} 2' in text

    def test_label_escaping(self):
        """Backslash, double quote, and newline in label values must be
        escaped per the exposition text format."""
        r = Registry()
        c = r.counter("x_total", labels=("op",))
        c.inc('a"b\\c\nd')
        text = r.render()
        assert 'x_total{op="a\\"b\\\\c\\nd"} 1' in text
        from seaweedfs_tpu.stats.metrics import _escape_label_value
        assert _escape_label_value('\\"\n') == '\\\\\\"\\n'

    def test_registry_render_golden(self):
        r = Registry()
        c = r.counter("req_total", "Requests.", labels=("op",))
        c.inc("get", amount=2)
        g = r.gauge("temp", "Temperature.")
        g.set(36.5)
        h = r.histogram("lat_seconds", "Latency.", buckets=(0.5, 2.0))
        h.observe(0.25)
        h.observe(5.0)
        assert r.render() == (
            "# HELP req_total Requests.\n"
            "# TYPE req_total counter\n"
            'req_total{op="get"} 2\n'
            "# HELP temp Temperature.\n"
            "# TYPE temp gauge\n"
            "temp 36.5\n"
            "# HELP lat_seconds Latency.\n"
            "# TYPE lat_seconds histogram\n"
            'lat_seconds_bucket{le="0.5"} 1\n'
            'lat_seconds_bucket{le="2"} 1\n'
            'lat_seconds_bucket{le="+Inf"} 2\n'
            "lat_seconds_sum 5.25\n"
            "lat_seconds_count 2\n")

    def test_push_loop_survives_failing_gateway(self):
        """The push loop must outlive a gateway that answers 500s (and
        one that isn't listening at all), and stop via its stop_event."""
        import threading
        import time
        from http.server import BaseHTTPRequestHandler, HTTPServer

        from seaweedfs_tpu.stats.metrics import start_push_loop

        hits = []

        class FailingGateway(BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length",
                                                     0)))
                hits.append(self.path)
                self.send_error(500, "gateway on fire")

            def log_message(self, fmt, *args):
                pass

        gw = HTTPServer(("127.0.0.1", 0), FailingGateway)
        threading.Thread(target=gw.serve_forever, daemon=True).start()
        r = Registry()
        r.counter("x_total").inc()
        t = start_push_loop(r, f"http://127.0.0.1:{gw.server_port}",
                            "job1", interval_s=0.05)
        try:
            deadline = time.time() + 10
            while len(hits) < 2 and time.time() < deadline:
                time.sleep(0.02)
            assert len(hits) >= 2, "loop died on the first 500"
            assert t.is_alive()
            assert hits[0] == "/metrics/job/job1"
        finally:
            t.stop_event.set()
            gw.shutdown()
        t.join(5)
        assert not t.is_alive(), "stop_event did not stop the loop"

    def test_check_metrics_lint(self):
        """tools/analyze.py's metrics sub-checker validates every
        registry (tier-1)."""
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools"))
        import analyze
        assert analyze.run_metrics_checks() == []

    def test_servers_expose_metrics(self, tmp_path):
        from seaweedfs_tpu.server.http_util import http_call
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        master = MasterServer(port=0, pulse_seconds=1).start()
        vs = VolumeServer(port=0, directories=[str(tmp_path)],
                          master_url=master.url, pulse_seconds=1,
                          max_volume_counts=[5],
                          ec_backend="numpy").start()
        try:
            from seaweedfs_tpu.client import operation as op
            op.upload_data(master.url, b"metric-me", filename="m.bin")
            mtext = http_call("GET",
                              f"http://{master.url}/metrics").decode()
            assert "SeaweedFS_master_request_total" in mtext
            assert "SeaweedFS_master_request_seconds_bucket" in mtext
            vtext = http_call("GET", f"http://{vs.url}/metrics").decode()
            assert "SeaweedFS_volumeServer_request_total" in vtext
            assert "SeaweedFS_volumeServer_request_seconds_bucket" \
                in vtext
            assert "SeaweedFS_volumeServer_volumes" in vtext
            # EC phase histogram family + mirrored device telemetry
            assert "SeaweedFS_volumeServer_ec_phase_seconds" in vtext
            assert 'SeaweedFS_volumeServer_ec_device_telemetry_total' \
                '{kind="dispatches"}' in vtext
        finally:
            vs.stop()
            master.stop()


class TestGlog:
    def setup_method(self):
        self.buf = io.StringIO()
        glog.set_stream(self.buf)
        glog.set_verbosity(0)
        glog.set_vmodule("")

    def teardown_method(self):
        import sys
        glog.set_stream(sys.stderr)

    def test_severities_and_format(self):
        glog.infof("hello %s", "world")
        glog.warningf("warn")
        glog.errorf("bad: %d", 7)
        lines = self.buf.getvalue().splitlines()
        assert lines[0].startswith("I") and "hello world" in lines[0]
        assert "test_stats.py:" in lines[0]
        assert lines[1].startswith("W")
        assert lines[2].startswith("E") and "bad: 7" in lines[2]

    def test_verbosity_gate(self):
        glog.V(2).infof("hidden")
        assert self.buf.getvalue() == ""
        glog.set_verbosity(2)
        glog.V(2).infof("visible")
        assert "visible" in self.buf.getvalue()

    def test_vmodule_override(self):
        glog.set_vmodule("test_stats=3")
        glog.V(3).infof("module-level")
        assert "module-level" in self.buf.getvalue()


class TestScaffold:
    def test_all_configs_print(self):
        from seaweedfs_tpu.command.scaffold import SCAFFOLDS, \
            print_scaffold
        from seaweedfs_tpu.util.config import _toml_module
        tomllib = _toml_module()
        for name in SCAFFOLDS:
            text = print_scaffold(name)
            if name == "master":        # TOML scaffold (reference master.toml)
                tomllib.loads(text)
                continue
            payload = "\n".join(l for l in text.splitlines()
                                if not l.strip().startswith("//"))
            json.loads(payload)     # the non-comment part is valid JSON

    def test_unknown_raises(self):
        from seaweedfs_tpu.command.scaffold import print_scaffold
        with pytest.raises(SystemExit):
            print_scaffold("nope")
