"""No fallback hides the device: backend selection, /admin/devices and
the native library build say what they did or fail.

- `-ec.backend tpu|mesh` computes off the TPU only where JAX_PLATFORMS
  names cpu (this suite does, in conftest.py); anywhere else it raises.
- `auto` asks JAX once and lets a backend-init error through.
- GET /admin/devices never boots a backend in a process that has not
  touched JAX (the chip belongs to one process).
- ops/rs_native builds libseaweed_ec.so from its source when missing or
  stale, and reports a failed build.
"""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from seaweedfs_tpu.ops import codec as ops_codec
from seaweedfs_tpu.ops import rs_native
from seaweedfs_tpu.ops.codec import get_codec
from seaweedfs_tpu.util import glog, jax_platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = np.random.default_rng(3).integers(0, 256, (10, 4096), dtype=np.uint8)


@pytest.fixture
def glog_text(monkeypatch):
    buf = io.StringIO()
    monkeypatch.setattr(glog, "_stream", buf)
    return buf


@pytest.mark.parametrize("backend", ["tpu", "mesh"])
def test_device_backend_off_tpu_needs_explicit_cpu(backend, monkeypatch):
    """JAX computes on the CPU here; without JAX_PLATFORMS=cpu saying so
    on purpose that is an error at the codec's first device touch."""
    codec = get_codec(10, 4, backend)
    want = get_codec(10, 4, "numpy").encode(DATA)
    assert np.array_equal(codec.encode(DATA), want)  # explicit cpu: runs
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        get_codec(10, 4, backend).encode(DATA)


def test_cpu_request_must_be_explicit(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu,tpu")
    assert jax_platform.cpu_explicitly_requested()
    for not_cpu in ("tpu", "tpu,cpu", ""):
        monkeypatch.setenv("JAX_PLATFORMS", not_cpu)
        assert not jax_platform.cpu_explicitly_requested()
    with pytest.raises(RuntimeError, match="-ec.backend mesh"):
        jax_platform.require_tpu("mesh", platform="cpu")
    assert jax_platform.require_tpu("mesh", platform="tpu") == "tpu"


def test_auto_propagates_backend_init_error(monkeypatch):
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(ops_codec, "_AUTO_CHOICE", None)
    monkeypatch.setattr(jax_platform, "default_platform", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        get_codec(10, 4, "auto")
    assert ops_codec._AUTO_CHOICE is None  # a failure is not cached


def test_auto_says_what_it_chose(monkeypatch, glog_text):
    monkeypatch.setattr(ops_codec, "_AUTO_CHOICE", None)
    codec = get_codec(10, 4, "auto")
    assert codec.backend == "native"  # JAX is on the cpu; .so builds
    get_codec(10, 4, "auto")
    err = glog_text.getvalue()
    assert err.count("-ec.backend auto -> native") == 1
    assert "not a TPU" in err


def test_compile_cache_placement(monkeypatch, tmp_path):
    """Placeable from outside; otherwise <checkout>/.jax_cache, from the
    package's own path; nothing cached where the cpu was asked for."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax_platform.compile_cache_dir() == \
        os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax_platform.compile_cache_dir() == str(tmp_path)
    assert jax_platform.configure_compile_cache() is None  # cpu suite


_CACHE_PROBE = """
import json, sys
from seaweedfs_tpu.util import glog, jax_platform
import jax
before = jax.config.jax_compilation_cache_dir
got = jax_platform.configure_compile_cache()
print(json.dumps({"before": before, "returned": got,
                  "config": jax.config.jax_compilation_cache_dir,
                  "min_s": jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir_set_in_code_only_without_env(from_env, tmp_path):
    """In a process nobody told to use the cpu. Only jax.config is
    touched: no backend is initialised, so no TPU is needed."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["min_s"] == 0.0
    if from_env:
        # JAX read the variable itself; the helper set nothing
        assert got["before"] == got["config"] == str(tmp_path)
        assert got["returned"] == str(tmp_path)
    else:
        assert got["before"] is None
        assert got["config"] == got["returned"] == \
            os.path.join(REPO, ".jax_cache")


_ADMIN_DEVICES_PROBE = """
import json, sys, tempfile
{preamble}
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.server.http_util import get_json, http_call
from seaweedfs_tpu.util.jax_platform import backend_initialized
m = MasterServer(port=0, pulse_seconds=1).start()
vs = VolumeServer(port=0, directories=[tempfile.mkdtemp()],
                  master_url=m.url, pulse_seconds=1,
                  ec_backend="numpy").start()
try:
    snap = get_json(f"http://{{vs.url}}/admin/devices")
    scrape = http_call("GET", f"http://{{vs.url}}/metrics")  # raises on >= 400
finally:
    vs.stop()
    m.stop()
print(json.dumps({{"inventory": snap["inventory"],
                  "scrape_has_families": b"ec_xla_" in scrape,
                  "jax_imported": "jax" in sys.modules,
                  "backend_initialized": backend_initialized()}}))
"""


@pytest.mark.parametrize("jax_imported", [False, True])
def test_admin_devices_does_not_boot_a_backend(jax_imported):
    """Neither in a process that never imported jax, nor in one that
    imported it and has not touched a device yet: the status question
    and the scrape must not be the calls that reach for the chip."""
    env = dict(os.environ, PYTHONPATH=REPO)
    probe = _ADMIN_DEVICES_PROBE.format(
        preamble="import jax" if jax_imported else "")
    out = subprocess.run([sys.executable, "-c", probe],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["inventory"]["initialized"] is False
    assert got["inventory"]["devices"] == []
    assert got["scrape_has_families"] is True
    assert got["jax_imported"] is jax_imported
    assert got["backend_initialized"] is False


def test_device_inventory_reports_a_backend_fault(monkeypatch):
    """On the status path a broken backend is an `error` in the payload
    (a scrape stays a 200); only the codec path raises it."""
    import jax

    from seaweedfs_tpu.ops import device_stats
    jax.devices()

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", broken)
    inv = device_stats.device_inventory()
    assert inv["initialized"] is False
    assert "Unable to initialize backend" in inv["error"]


@pytest.fixture
def private_native(monkeypatch, tmp_path):
    """rs_native pointed at a library path of its own, unloaded."""
    lib = tmp_path / "libseaweed_ec.so"
    monkeypatch.setattr(rs_native, "_LIB_PATH", str(lib))
    monkeypatch.setattr(rs_native, "_lib", None)
    monkeypatch.setattr(rs_native, "_load_failed", False)
    return lib


def test_native_load_builds_missing_library(private_native):
    assert not private_native.exists()
    assert rs_native._load() is not None
    assert private_native.exists()
    got = rs_native.NativeCodec(10, 4).encode(DATA)
    assert np.array_equal(got, get_codec(10, 4, "numpy").encode(DATA))
    assert not list(private_native.parent.glob("*.tmp"))


def test_native_load_rebuilds_stale_library(private_native, monkeypatch):
    private_native.write_bytes(b"not a shared object")
    old = os.path.getmtime(rs_native._SRC_PATH) - 100
    os.utime(private_native, (old, old))
    assert rs_native._load() is not None
    assert os.path.getmtime(private_native) > old


def test_native_build_failure_is_reported(private_native, monkeypatch,
                                          tmp_path, glog_text):
    bad = tmp_path / "broken.cc"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(rs_native, "_SRC_PATH", str(bad))
    assert rs_native._load() is None
    err = glog_text.getvalue()
    assert "native EC library unavailable" in err
    assert "error" in err.lower()  # the compiler's own words
    with pytest.raises(RuntimeError, match="could not be built"):
        rs_native.NativeCodec(10, 4)
