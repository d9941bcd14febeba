"""Round-3 auxiliary subsystems: write throttler, config tiers, TLS,
master maintenance cron, status UIs (SURVEY §5.6)."""

import os
import subprocess
import time

import numpy as np
import pytest

from conftest import wait_until
from seaweedfs_tpu.server.http_util import (HttpServer, Request, Router,
                                            configure_tls, get_json,
                                            http_call, reset_tls)
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.util.config import config_get, load_config
from seaweedfs_tpu.util.throttler import WriteThrottler


# -- throttler ---------------------------------------------------------------

def test_throttler_limits_rate():
    t = WriteThrottler(bytes_per_second=1 << 20)  # 1 MB/s
    start = time.monotonic()
    for _ in range(6):
        t.maybe_slowdown(256 << 10)  # 1.5MB total
    elapsed = time.monotonic() - start
    assert elapsed >= 0.8  # ~1.4s of debt after the first window

    free = WriteThrottler(0)
    start = time.monotonic()
    for _ in range(100):
        free.maybe_slowdown(10 << 20)
    assert time.monotonic() - start < 0.1  # unthrottled = no sleeps


def test_throttled_compaction(tmp_path):
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume
    v = Volume(str(tmp_path), "", 1, create=True)
    rng = np.random.default_rng(0)
    for i in range(1, 9):
        v.write_needle(Needle(id=i, cookie=1, data=rng.integers(
            0, 256, 128 << 10).astype(np.uint8).tobytes()))
    t0 = time.monotonic()
    v.compact(bytes_per_second=1 << 20)  # ~1MB of live data at 1MB/s
    throttled = time.monotonic() - t0
    v.commit_compact()
    assert throttled >= 0.5
    for i in range(1, 9):
        assert v.read_needle(Needle(id=i, cookie=1)).size > 0
    v.close()


# -- config tiers ------------------------------------------------------------

def test_config_search_path_and_env_override(tmp_path):
    (tmp_path / "security.toml").write_text(
        '[jwt.signing]\nkey = "from-file"\n[https]\ncert = "/c.pem"\n')
    cfg = load_config("security", dirs=[str(tmp_path)], env={})
    assert config_get(cfg, "jwt.signing.key") == "from-file"
    assert config_get(cfg, "https.cert") == "/c.pem"
    # WEED_* env overrides the file (reference scaffold.go env tiers)
    cfg = load_config("security", dirs=[str(tmp_path)],
                      env={"WEED_JWT_SIGNING_KEY": "from-env"})
    assert config_get(cfg, "jwt.signing.key") == "from-env"
    # underscore/dot tolerance
    assert config_get(cfg, "jwt_signing_key") == "from-env"
    # no file at all: pure-env configs still work
    cfg = load_config("nope", dirs=[str(tmp_path)],
                      env={"WEED_HTTPS_CA": "/ca.pem"})
    assert config_get(cfg, "https.ca") == "/ca.pem"


# -- TLS ---------------------------------------------------------------------

def _make_cert(tmp_path):
    cert, key = str(tmp_path / "c.pem"), str(tmp_path / "k.pem")
    out = subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "1", "-subj",
         "/CN=127.0.0.1"], capture_output=True)
    if out.returncode != 0:
        pytest.skip(f"openssl unavailable: {out.stderr[:100]}")
    return cert, key


def test_tls_end_to_end(tmp_path):
    cert, key = _make_cert(tmp_path)
    router = Router()
    router.add("GET", "/ping", lambda req: {"pong": True})
    try:
        configure_tls(cert, key)
        srv = HttpServer(0, router, "127.0.0.1")
        srv.start()
        # plain-looking URL transparently upgrades to https and verifies
        out = get_json(f"http://127.0.0.1:{srv.port}/ping")
        assert out == {"pong": True}
        srv.stop()
    finally:
        reset_tls()
    # after reset, plaintext servers work again
    srv2 = HttpServer(0, router, "127.0.0.1")
    srv2.start()
    assert get_json(f"http://127.0.0.1:{srv2.port}/ping") == {"pong": True}
    srv2.stop()


# -- maintenance cron --------------------------------------------------------

def test_master_maintenance_scripts_run():
    from seaweedfs_tpu.shell.command_env import command

    runs = []

    @command("test.maintenance.probe", "test-only")
    def probe(env, args):  # noqa: ARG001
        runs.append(time.time())

    master = MasterServer(port=0, maintenance_scripts=
                          "test.maintenance.probe",
                          maintenance_interval=0.2).start()
    try:
        assert wait_until(lambda: runs, timeout=5), \
            "maintenance script never ran"
        assert master._maintenance_runs >= 1
    finally:
        master.stop()


def test_master_toml_fills_flag_defaults(tmp_path, monkeypatch):
    """master.toml (reference scaffold MASTER_TOML_EXAMPLE) provides
    maintenance scripts / interval, sequencer choice, growth counts and
    the maintenance shell's filer; explicit flags always win."""
    import argparse

    from seaweedfs_tpu.command.cli import _apply_master_config
    from seaweedfs_tpu.command.scaffold import print_scaffold

    # the scaffold's own output must parse through the loader
    (tmp_path / "master.toml").write_text(print_scaffold("master"))
    monkeypatch.chdir(tmp_path)
    args = argparse.Namespace(maintenanceScripts="",
                              maintenanceIntervalSeconds=17 * 60,
                              sequencer="auto",
                              sequencerEtcd="127.0.0.1:2379")
    kw = _apply_master_config(args)
    assert args.maintenanceScripts == \
        "ec.rebuild;volume.balance;volume.vacuum -garbageThreshold 0.3"
    assert args.maintenanceIntervalSeconds == 17 * 60
    assert args.sequencer == "auto"  # scaffold says memory
    assert kw["growth_counts"] == {1: 7, 2: 6, 3: 3, "other": 1}
    assert kw["maintenance_filer_url"] == "localhost:8888"

    # a config with explicit overrides + etcd sequencer urls
    (tmp_path / "master.toml").write_text(
        '[master.maintenance]\nscripts = "volume.vacuum"\n'
        'sleep_minutes = 2\n'
        '[master.sequencer]\ntype = "etcd"\n'
        'sequencer_etcd_urls = "http://etcd-a:2390,http://etcd-b:2390"\n'
        '[master.volume_growth]\ncopy_1 = 2\ncopy_other = 5\n')
    args = argparse.Namespace(maintenanceScripts="",
                              maintenanceIntervalSeconds=17 * 60,
                              sequencer="auto",
                              sequencerEtcd="127.0.0.1:2379")
    kw = _apply_master_config(args)
    assert args.maintenanceIntervalSeconds == 120
    assert args.sequencer == "etcd"
    assert args.sequencerEtcd == "etcd-a:2390"
    assert kw["growth_counts"] == {1: 2, "other": 5}

    # flags beat config
    args = argparse.Namespace(maintenanceScripts="volume.list",
                              maintenanceIntervalSeconds=60.0,
                              sequencer="etcd",
                              sequencerEtcd="me:2379")
    _apply_master_config(args)
    assert args.maintenanceScripts == "volume.list"
    assert args.maintenanceIntervalSeconds == 60.0
    assert args.sequencerEtcd == "me:2379"

    # growth counts reach volume growth decisions
    m = MasterServer(port=0, growth_counts={1: 2, "other": 5})
    try:
        assert m.growth_counts[1] == 2
    finally:
        m.stop()


# -- status UIs --------------------------------------------------------------

def test_filer_browser_page(tmp_path):
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.http_util import post_multipart
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=1,
                      ec_backend="numpy").start()
    filer = FilerServer(port=0, master_url=master.url).start()
    try:
        post_multipart(f"http://{filer.url}/docs/<i>.txt", "x",
                       b"escaped-name")
        page = http_call("GET", f"http://{filer.url}/docs/",
                         headers={"Accept": "text/html"}).decode()
        assert "<h1>Filer /docs" in page
        assert "&lt;i&gt;.txt" in page and "<i>.txt" not in page  # XSS
        # API clients still get JSON
        js = http_call("GET", f"http://{filer.url}/docs/").decode()
        assert js.startswith("{")
    finally:
        filer.stop()
        vs.stop()
        master.stop()


def test_status_pages_render(tmp_path):
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=1,
                      ec_backend="numpy").start()
    try:
        from seaweedfs_tpu.client import operation as op
        a = op.assign(master.url)
        op.upload(a["url"], a["fid"], b"ui-bytes" * 10, filename="u.bin")
        page = http_call("GET", f"http://{master.url}/").decode()
        assert "Volume servers" in page and vs.url in page
        vpage = http_call("GET", f"http://{vs.url}/ui").decode()
        assert "Volumes" in vpage and "rw" in vpage
    finally:
        vs.stop()
        master.stop()


def test_sampling_profiler_collapsed_stacks(tmp_path):
    """The all-thread sampler must attribute time to a busy worker
    thread's frames in folded-stack format."""
    import threading
    import time as _time

    from seaweedfs_tpu.util.profiling import SamplingProfiler

    stop = threading.Event()

    def busy_worker_fn():
        while not stop.is_set():
            sum(i * i for i in range(2000))

    t = threading.Thread(target=busy_worker_fn, name="busy")
    out = tmp_path / "prof.folded"
    prof = SamplingProfiler(str(out), interval=0.002).start()
    t.start()
    _time.sleep(0.4)
    stop.set()
    t.join()
    prof.stop()
    text = out.read_text()
    assert "busy_worker_fn" in text
    # folded format: "frame;frame;... count"
    for line in text.splitlines():
        stack, _, count = line.rpartition(" ")
        assert stack and count.isdigit()


def test_tls_redirect_rewrites_scheme(tmp_path):
    """A 301 whose Location is plain http (the volume read-redirect
    shape) must be refetched over TLS when the cluster runs TLS — the
    pooled client re-applies the scheme rewrite on redirect targets."""
    cert, key = _make_cert(tmp_path)
    router = Router()
    hits = []

    def redirecting(req):
        hits.append("redirector")
        from seaweedfs_tpu.server.http_util import Response
        return Response(b"", 301,
                        headers={"Location":
                                 f"http://127.0.0.1:{target.port}/data"})

    def data(req):
        hits.append("target")
        return {"ok": True}

    router.add("GET", "/hop", redirecting)
    t_router = Router()
    t_router.add("GET", "/data", data)
    try:
        configure_tls(cert, key)
        target = HttpServer(0, t_router, "127.0.0.1")
        target.start()
        srv = HttpServer(0, router, "127.0.0.1")
        srv.start()
        out = get_json(f"http://127.0.0.1:{srv.port}/hop")
        assert out == {"ok": True}
        assert hits == ["redirector", "target"]
        srv.stop()
        target.stop()
    finally:
        reset_tls()


def test_server_stop_severs_keepalive_without_fd_close_race():
    """stop() must sever established keep-alive connections (a stopped
    server stops serving) via shutdown — the owning handler thread
    closes the fd, so a concurrent in-process client can never inherit
    a reused fd mid-response."""
    import http.client
    import time as _time

    router = Router()
    router.add("GET", "/ping", lambda req: {"pong": True})
    srv = HttpServer(0, router, "127.0.0.1")
    srv.start()
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
    conn.request("GET", "/ping")
    assert conn.getresponse().read() == b'{"pong": true}'
    srv.stop()
    # the established keep-alive connection is dead now
    with pytest.raises((ConnectionError, http.client.HTTPException,
                        OSError)):
        conn.request("GET", "/ping")
        conn.getresponse().read()
    conn.close()
    # handler threads owned the close: tracked set drains
    assert wait_until(lambda: not srv.httpd._client_socks, timeout=5)


def test_master_whitelist_and_metrics_broadcast(tmp_path):
    """Master -whiteList guards the user-facing API but not cluster
    channels (reference guard.WhiteList on master_server.go:112-123);
    -metrics.address rides heartbeat responses and starts the volume
    server's push loop (reference master_grpc_server.go:75-77 +
    LoopPushingMetric)."""
    import threading
    import pytest
    from seaweedfs_tpu.server.http_util import (HttpError, HttpServer,
                                                Router, get_json)
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    # a tiny in-process push-gateway
    pushes = []
    got_push = threading.Event()
    router = Router()

    def catch(req):
        pushes.append(req.path)
        got_push.set()
        return {}
    router.set_fallback(catch)
    gw = HttpServer(0, router, "127.0.0.1").start()

    master = MasterServer(port=0, pulse_seconds=1,
                          whitelist=["10.9.9.9"],   # excludes 127.0.0.1
                          metrics_address=f"127.0.0.1:{gw.port}",
                          metrics_interval=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[3], ec_backend="numpy").start()
    try:
        # user-facing API is refused for non-whitelisted clients...
        with pytest.raises(HttpError) as ei:
            get_json(f"http://{master.url}/dir/assign")
        assert ei.value.status == 403
        # ...but the heartbeat channel stayed open (the vs registered)
        assert master.topology.find_node(vs.url) is not None
        # and the metrics push loop fired against the gateway
        assert got_push.wait(10), "no metrics push arrived"
        assert any("volume_" in p for p in pushes)
    finally:
        vs.stop()
        master.stop()
        gw.stop()
