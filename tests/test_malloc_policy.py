"""util/malloc_policy: the three mallopt calls a volume server makes."""

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
