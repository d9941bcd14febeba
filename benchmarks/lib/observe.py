"""Snapshots of the program's own counters, flattened to one level, and
of what the host did beside a window."""

import resource
import statistics

VMSTAT = ("pgpgout", "nr_dirtied", "nr_written")


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


def host_now() -> dict:
    """The host's own counters (monotonic): the pages it wrote back and
    dirtied, all processes' (`/proc/vmstat`), the ticks the hypervisor
    kept from this machine (`/proc/stat` steal), and this process's
    minor faults (memory it had not touched) and involuntary context
    switches. A counter the host does not offer is left out."""
    now = {}
    try:
        with open("/proc/vmstat") as f:
            for line in f:
                name, _, value = line.partition(" ")
                if name in VMSTAT:
                    now[name] = int(value)
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        if cpu[0] == "cpu" and len(cpu) > 8:
            now["cpu_steal_ticks"] = int(cpu[8])
    except (OSError, ValueError):
        pass
    usage = resource.getrusage(resource.RUSAGE_SELF)
    now["ru_minflt"], now["ru_nivcsw"] = usage.ru_minflt, usage.ru_nivcsw
    return now


def host_line(ops: list, before: dict, after: dict) -> dict:
    """What tells a slow run's cause apart, beside every run and never a
    metric: by kind of timed command the count, the median and the
    largest wall (one stalled command shows in the largest alone; every
    command slower alike moves the median), and the host's counters over
    the window (write-back that ran in this window and not in that)."""
    walls = {}
    for record in ops:
        walls.setdefault(record["op"], []).append(record["wall_s"])
    return {"phase": "host",
            "ops": {op: {"count": len(w), "median_wall_s":
                         statistics.median(w), "max_wall_s": max(w)}
                    for op, w in sorted(walls.items())},
            "window": {k: after[k] - before[k] for k in sorted(after)
                       if k in before}}
