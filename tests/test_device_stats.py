"""Device-runtime observability plane (ops/device_stats).

Covers the ISSUE-18 contract: explicit compile/execute separation,
the recompile sentinel latching on deliberately-broken width bucketing
(while the properly bucketed path stays at zero), the clock-free
guarantee of a warm dispatch,
const-cache and jit-factory accounting, the ec_xla_* /
ec_const_cache_* metrics mirror, GET /admin/devices, shell
cluster.devices, and the cluster aggregation roundtrip.
"""

import io
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from seaweedfs_tpu.ops import device_stats  # noqa: E402
from seaweedfs_tpu.ops.device_stats import (  # noqa: E402
    DeviceStats, canonical_width, wrap)


def _jit_scale():
    """A tiny jitted (const, data) -> data kernel shaped like every EC
    entry point: last arg's trailing axis is the width."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda c, d: (d.astype(jnp.int32) * c).astype(d.dtype))


def _data(width):
    return np.ones((4, width), dtype=np.uint8)


def _const():
    return np.int32(3)


class TestCanonicalWidth:
    def test_bucketed_widths_are_fixed_points(self):
        from seaweedfs_tpu.ops.rs_tpu import width_bucket
        for n in (1, 7, 511, 512, 513, 4000, 1 << 20):
            b = width_bucket(n, 32 << 20)
            assert canonical_width(b) == b

    def test_exact_widths_fold_into_one_bucket(self):
        assert canonical_width(600) == canonical_width(700) == 1024
        assert canonical_width(512) == 512
        assert canonical_width(1) == 512


class TestCompileExecuteSplit:
    def test_one_compile_many_dispatches(self):
        stats = DeviceStats()
        fn = wrap(_jit_scale(), "t.split", stats=stats)
        out = np.asarray(fn(_const(), _data(512)))
        assert (out == 3).all()
        for _ in range(4):
            fn(_const(), _data(512))
        snap = stats.snapshot()
        assert snap["compiles"] == {"t.split": 1}
        assert snap["dispatches"] == {"t.split": 5}
        assert snap["compile_seconds"]["t.split"] > 0.0
        assert snap["recompiles"] == {}
        assert snap["sentinel"] is False

    def test_distinct_buckets_compile_separately_without_latching(self):
        stats = DeviceStats()
        fn = wrap(_jit_scale(), "t.buckets", stats=stats)
        # the properly bucketed path: every dispatch width is already a
        # bucket (512, 1024), each compiles once, zero recompiles
        for width in (512, 1024, 512, 1024):
            fn(_const(), _data(width))
        snap = stats.snapshot()
        assert snap["compiles"]["t.buckets"] == 2
        assert snap["recompiles"] == {}
        assert snap["sentinel"] is False

    def test_delta_reports_movement_only(self):
        stats = device_stats.DEVICE_STATS
        fn = wrap(_jit_scale(), "t.delta")
        before = stats.snapshot()
        fn(_const(), _data(512))
        fn(_const(), _data(512))
        moved = device_stats.delta(before)
        assert moved["compiles"]["t.delta"] == 1
        assert moved["dispatches"]["t.delta"] == 2
        assert moved["compiles_total"] >= 1
        assert moved["recompiles_total"] == 0


class TestAProgramOfOneChip:
    """`-ec.backend tpu-own`: the same jitted program as each chip's
    own, its executables and its counts apart (PR 45)."""

    def test_a_sibling_a_chip_compiles_once_each_and_never_latches(self):
        import jax
        stats = DeviceStats()
        fn = wrap(_jit_scale(), "t.own", stats=stats)
        devices = jax.local_devices()[:3]
        for index, dev in enumerate(devices):
            on = fn.on_device(index)
            assert on is fn.on_device(index) and on.device == index
            assert on.raw_jit is fn.raw_jit and on.entry == "t.own"
            for _ in range(index + 1):
                out = on(jax.device_put(_const(), dev),
                         jax.device_put(_data(512), dev))
            assert out.devices() == {dev}
            assert (np.asarray(out) == 3).all()
        snap = stats.snapshot()
        # a chip's first use of a (entry, bucket) is a compile, not a
        # recompile of the chip before it
        assert snap["compiles"] == {"t.own": 3}
        assert snap["recompiles"] == {} and snap["sentinel"] is False
        assert snap["dispatches"] == {"t.own": 6, "dev0": 1, "dev1": 2,
                                      "dev2": 3}

    def test_the_same_chip_twice_is_still_a_recompile(self):
        stats = DeviceStats()
        stats.note_compile("t.own", ("sig", 512), 0.1, chip="dev1")
        stats.note_compile("t.own", ("sig", 512), 0.1, chip="dev2")
        assert stats.sentinel is False
        stats.note_compile("t.own", ("sig", 512), 0.1, chip="dev1")
        assert stats.sentinel is True and stats.recompiles == {"t.own": 1}
        assert stats.offenders[0].startswith("t.own:('dev1'")

    def test_a_program_of_no_chip_counts_under_no_chip(self):
        stats = DeviceStats()
        fn = wrap(_jit_scale(), "t.anywhere", stats=stats)
        assert fn.device is None
        fn(_const(), _data(512))
        assert stats.snapshot()["dispatches"] == {"t.anywhere": 1}


class TestRecompileSentinel:
    def test_shape_churn_latches_while_bucketed_stays_zero(self):
        stats = DeviceStats()
        # deliberately broken bucketing: exact payload widths jitted
        # as-is. 600 and 700 both belong to the 1024 bucket, so the
        # second compile is a recompile and the sentinel latches.
        churn = wrap(_jit_scale(), "t.churn", stats=stats)
        churn(_const(), _data(600))
        assert stats.snapshot()["sentinel"] is False
        churn(_const(), _data(700))
        snap = stats.snapshot()
        assert snap["sentinel"] is True
        assert snap["recompiles"] == {"t.churn": 1}
        assert snap["compiles"]["t.churn"] == 2
        assert any("t.churn" in off for off in snap["offenders"])
        # the bucketed path through the SAME stats instance stays clean
        good = wrap(_jit_scale(), "t.good", stats=stats)
        good(_const(), _data(512))
        good(_const(), _data(1024))
        snap = stats.snapshot()
        assert "t.good" not in snap["recompiles"]
        assert snap["recompiles"] == {"t.churn": 1}

    def test_global_sentinel_default_unlatched(self):
        # the process-global instance must not have latched during the
        # suite's real EC traffic — that would mean production
        # bucketing is broken
        assert device_stats.DEVICE_STATS.snapshot()["sentinel"] is False


class TestDispatchClock:
    def test_timing_off_path_is_clock_free(self, monkeypatch):
        """After warmup, a dispatch performs ZERO perf_counter reads —
        the same discipline SW_PLANE_STATS=0 gives the native plane.
        (Device time is read from a profiler trace, not from a
        host-timed block_until_ready.)"""
        stats = DeviceStats()
        fn = wrap(_jit_scale(), "t.off", stats=stats)
        fn(_const(), _data(512))  # warmup: the COMPILE may read clocks

        calls = {"n": 0}
        real = device_stats._perf_counter

        def probe():
            calls["n"] += 1
            return real()

        monkeypatch.setattr(device_stats, "_perf_counter", probe)
        for _ in range(16):
            fn(_const(), _data(512))
        assert calls["n"] == 0, \
            "dispatch hot path read the clock"
        assert stats.snapshot()["dispatches"]["t.off"] == 17
        # a new width compiles, and the compile is timed: the SAME
        # probe fires — proving it would have seen any read above
        fn(_const(), _data(1024))
        assert calls["n"] >= 2


class TestConstCacheAccounting:
    def test_hit_miss_eviction_and_occupancy(self):
        from seaweedfs_tpu.ops.codec import _ConstCache
        stats = device_stats.DEVICE_STATS
        before = stats.snapshot()["const_cache"]
        cache = _ConstCache(maxsize=2)
        arr = np.zeros(16, dtype=np.uint8)
        cache.get("a", lambda: arr)
        cache.get("a", lambda: arr)          # hit
        cache.get("b", lambda: arr)
        cache.get("c", lambda: arr)          # evicts "a"
        now = stats.snapshot()["const_cache"]
        assert now["hits"] - before["hits"] == 1
        assert now["misses"] - before["misses"] == 3
        assert now["evictions"] - before["evictions"] == 1
        occ = cache.occupancy()
        assert occ["entries"] == 2
        assert occ["bytes"] == 32
        # the instance is registered: global occupancy includes it
        total = stats.const_cache_occupancy()
        assert total["entries"] >= 2


class TestJitFactoryRegistry:
    def test_rs_tpu_factories_registered_with_knob_maxsize(self):
        from seaweedfs_tpu.ops import rs_tpu  # noqa: F401
        from seaweedfs_tpu.util import config
        snap = device_stats.jit_factory_snapshot()
        assert "rs_tpu._packed_fn" in snap
        info = snap["rs_tpu._packed_fn"]
        assert info["maxsize"] == config.env_int("SW_EC_JIT_CACHE_SIZE")
        assert set(info) == {"hits", "misses", "maxsize", "currsize",
                             "evictions"}

    def test_evictions_derived_from_cache_info(self):
        import functools
        calls = []

        @functools.lru_cache(maxsize=2)
        def factory(n):
            calls.append(n)
            return n

        device_stats.register_jit_factory("t.factory", factory)
        try:
            for n in (1, 2, 3, 1):  # 3 evicts 1, the late 1 re-misses
                factory(n)
            info = device_stats.jit_factory_snapshot()["t.factory"]
            assert info["misses"] == 4
            assert info["currsize"] == 2
            assert info["evictions"] == 2
        finally:
            device_stats._JIT_FACTORIES.pop("t.factory", None)


class TestInventoryAndMetricsMirror:
    def test_inventory_reports_cpu_mesh(self):
        import jax
        jax.devices()   # the inventory never boots a backend itself
        inv = device_stats.device_inventory()
        assert inv["initialized"] is True
        assert inv["platform"] == "cpu"
        assert sum(inv["device_kinds"].values()) == len(inv["devices"])

    def test_admin_snapshot_shape(self):
        snap = device_stats.admin_snapshot()
        assert set(snap) == {"stats", "jit_factories", "inventory"}
        assert "sentinel" in snap["stats"]

    def test_observe_device_stats_renders_families(self):
        from seaweedfs_tpu.stats.metrics import (VOLUME_SERVER_GATHER,
                                                 observe_device_stats)
        stats = DeviceStats()
        fn = wrap(_jit_scale(), "t.mirror", stats=stats)
        fn(_const(), _data(512))
        observe_device_stats(stats.snapshot(),
                             device_stats.jit_factory_snapshot(),
                             device_stats.device_inventory())
        text = VOLUME_SERVER_GATHER.render()
        assert ('SeaweedFS_volumeServer_ec_xla_compiles_total'
                '{entry="t.mirror"} 1') in text
        assert ('SeaweedFS_volumeServer_ec_xla_dispatches_total'
                '{entry="t.mirror"} 1') in text
        assert ("SeaweedFS_volumeServer_ec_xla_recompile_sentinel 0"
                in text)
        assert "SeaweedFS_volumeServer_ec_const_cache_entries" in text
        assert 'factory="rs_tpu._packed_fn"' in text

    def test_sentinel_gauge_mirrors_latch(self):
        from seaweedfs_tpu.stats.metrics import (VOLUME_SERVER_GATHER,
                                                 observe_device_stats)
        stats = DeviceStats()
        fn = wrap(_jit_scale(), "t.latch", stats=stats)
        fn(_const(), _data(600))
        fn(_const(), _data(700))
        observe_device_stats(stats.snapshot())
        text = VOLUME_SERVER_GATHER.render()
        assert ("SeaweedFS_volumeServer_ec_xla_recompile_sentinel 1"
                in text)
        assert ('SeaweedFS_volumeServer_ec_xla_recompiles_total'
                '{entry="t.latch"} 1') in text
        # restore the unlatched gauge for later renders
        observe_device_stats(DeviceStats().snapshot())


@pytest.fixture
def small_cluster(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v0")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[4], ec_backend="numpy").start()
    try:
        yield master, vs
    finally:
        vs.stop()
        master.stop()


class TestServingSurfaces:
    def test_admin_devices_endpoint(self, small_cluster):
        from seaweedfs_tpu.server.http_util import get_json
        master, vs = small_cluster
        snap = get_json(f"http://{vs.url}/admin/devices")
        assert snap["inventory"]["platform"] == "cpu"
        assert "compiles" in snap["stats"]
        assert snap["stats"]["sentinel"] is False
        assert isinstance(snap["jit_factories"], dict)

    def test_metrics_scrape_carries_ec_xla_families(self, small_cluster):
        from seaweedfs_tpu.server.http_util import http_call
        master, vs = small_cluster
        text = http_call("GET", f"http://{vs.url}/metrics").decode()
        assert "SeaweedFS_volumeServer_ec_xla_recompile_sentinel" in text
        assert ("SeaweedFS_volumeServer_ec_const_cache_events_total"
                in text)

    def test_cluster_metrics_aggregates_device_plane(self,
                                                     small_cluster):
        from conftest import wait_until
        from seaweedfs_tpu.server.http_util import http_call
        master, vs = small_cluster

        def merged():
            text = http_call(
                "GET",
                f"http://{master.url}/cluster/metrics?refresh=1"
            ).decode()
            return text if "ec_xla_recompile_sentinel" in text else None

        text = wait_until(merged, timeout=15)
        assert text, "device families never reached /cluster/metrics"
        # gauges keep the node label through aggregation
        assert f'node="{vs.url}"' in text

    def test_shell_cluster_devices(self, small_cluster):
        import seaweedfs_tpu.shell  # noqa: F401
        from conftest import wait_until
        from seaweedfs_tpu.shell.command_env import (CommandEnv,
                                                     run_command)
        master, vs = small_cluster
        env = CommandEnv(master.url, out=io.StringIO())
        assert wait_until(lambda: len(env.cluster_nodes()) == 1,
                          timeout=15)
        run_command(env, "cluster.devices")
        out = env.out.getvalue()
        assert "cluster.devices: 1 nodes" in out
        assert "platform=cpu" in out
        assert "recompiles=0" in out
        assert "SENTINEL" not in out


class TestAggregatorRoundtrip:
    def test_device_families_sum_across_nodes(self):
        from seaweedfs_tpu.stats.aggregate import ClusterMetricsAggregator
        from seaweedfs_tpu.stats.metrics import (parse_prometheus_text,
                                                 render_families)
        fam = ("# TYPE SeaweedFS_volumeServer_ec_xla_compiles_total "
               "counter\n")
        texts = {
            "n1:1": fam + ('SeaweedFS_volumeServer_ec_xla_compiles_'
                           'total{entry="mesh_codec._fn"} 2\n'),
            "n2:2": fam + ('SeaweedFS_volumeServer_ec_xla_compiles_'
                           'total{entry="mesh_codec._fn"} 3\n'),
        }
        agg = ClusterMetricsAggregator(lambda: list(texts),
                                       interval_s=60,
                                       fetch=lambda url: texts[url])
        assert agg.scrape_once() == 2
        out = agg.render()
        assert ('SeaweedFS_volumeServer_ec_xla_compiles_total'
                '{entry="mesh_codec._fn"} 5') in out
        # the merged text round-trips through the parser unchanged
        assert render_families(parse_prometheus_text(out)) == out
