"""util/malloc_policy: the three mallopt calls a volume server makes, and
the arena cap the package sets when it is imported."""

import platform

import numpy as np
import pytest

from seaweedfs_tpu.util import malloc_policy


@pytest.mark.skipif(platform.libc_ver()[0] != "glibc",
                    reason="mallopt's parameters are glibc's")
def test_keep_freed_memory_is_accepted_and_idempotent():
    assert malloc_policy.keep_freed_memory() is True
    assert malloc_policy.keep_freed_memory() is True
    # blocks of the EC stream's sizes still come and go
    for n in (8 << 20, 32 << 20, 80 << 20):
        a = np.empty(n, dtype=np.uint8)
        a[::4096] = 7
        assert int(a[4096]) == 7
        del a


def test_without_glibc_it_reports_false(monkeypatch):
    class NoMallopt:
        def __getattr__(self, name):
            raise AttributeError(name)

    monkeypatch.setattr(malloc_policy.ctypes, "CDLL", lambda name: NoMallopt())
    assert malloc_policy.keep_freed_memory() is False
    assert malloc_policy.one_arena() is False


@pytest.mark.skipif(platform.libc_ver()[0] != "glibc",
                    reason="mallopt's parameters are glibc's")
def test_one_arena_is_accepted_and_threads_still_allocate():
    import threading
    assert malloc_policy.one_arena() is True
    sums = []

    def work():
        a = np.full(46 << 20, 3, dtype=np.uint8)
        sums.append(int(a[::1 << 20].sum()))

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sums == [46 * 3] * 4


def test_glibcs_own_variable_wins(monkeypatch):
    """An operator who set MALLOC_ARENA_MAX keeps that setting: the
    package makes no mallopt call over it."""
    calls = []
    monkeypatch.setattr(malloc_policy, "_mallopt",
                        lambda: lambda p, v: calls.append((p, v)) or 1)
    monkeypatch.setenv("MALLOC_ARENA_MAX", "104")
    assert malloc_policy.one_arena() is False and calls == []
    monkeypatch.delenv("MALLOC_ARENA_MAX")
    assert malloc_policy.one_arena() is True and calls == [(-8, 1)]


def test_importing_the_package_caps_the_arenas():
    """In a process of its own: the cap has to be set before the first
    thread, which only the package's import can promise."""
    import subprocess
    import sys
    code = (
        "import ctypes\n"
        "calls = []\n"
        "from seaweedfs_tpu.util import malloc_policy\n"
        "sound = malloc_policy._mallopt\n"
        "import importlib, seaweedfs_tpu\n"
        "malloc_policy._mallopt = lambda: (lambda p, v: calls.append((p, v)) or 1)\n"
        "importlib.reload(seaweedfs_tpu)\n"
        "print(calls)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[(-8, 1)]"


def test_volume_server_start_sets_the_policy(tmp_path, monkeypatch):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    calls = []
    monkeypatch.setattr(malloc_policy, "keep_freed_memory",
                        lambda: calls.append(1) or True)
    master = MasterServer(port=0).start()
    try:
        vs = VolumeServer(port=0, directories=[str(tmp_path)],
                          master_url=master.url).start()
        vs.stop()
    finally:
        master.stop()
    assert calls == [1]
