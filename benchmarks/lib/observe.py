"""Snapshots of the program's own counters, flattened to one level."""


def counters_now(cluster=None) -> dict:
    """A flat snapshot of the program's counters (monotonic)."""
    from seaweedfs_tpu.ops import device_stats, telemetry
    flat = {}
    tel = telemetry.STATS.snapshot()
    for key, value in tel.items():
        if isinstance(value, dict):
            for dev, n in value.items():
                flat[f"telemetry.{key}.{dev}"] = n
        else:
            flat[f"telemetry.{key}"] = value
    dev = device_stats.DEVICE_STATS.snapshot()
    for field in ("dispatches", "compiles", "recompiles", "compile_seconds"):
        for entry, n in dev[field].items():
            flat[f"jit.{field}.{entry}"] = n
        flat[f"jit.{field}"] = sum(dev[field].values())
    if cluster is not None:
        for vs in cluster.servers:
            for key, value in vs.degraded.snapshot().items():
                if isinstance(value, (int, float)):
                    flat[f"degraded.{key}"] = \
                        flat.get(f"degraded.{key}", 0) + value
    return flat


def counters_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}
