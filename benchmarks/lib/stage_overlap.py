"""Where the device's idle time falls among the program's own stages.

The program mirrors each stage of the EC stream into the profiler's trace
as a host event `sw:<span name>` on the thread that did the work
(seaweedfs_tpu/util/tracing.Stage), beside the benchmark's own `bench:`
marks. `load_host` reads those events from an `.xplane.pb`; `attribute`
works on plain lists alone, so it is tested on hand-made ones. Per device
the idle intervals are the traced window less the union of its operations;
an idle instant is attributed when any `sw:` stage is open on any thread.
The benchmark's marks split the account: `in_commands` is the part inside
the marks of the timed commands (the caller names their prefix: a metric's
`args`), where the program is at work; between them the harness is, and no
stage of the program can be open. The whole window's account (`idle_s`,
`unattributed_s`) stands beside it, and `share` gives both as percentages.
"""

import re

from lib import trace_reduce

STAGE_PREFIX = "sw:"


def load_host(path: str, device_plane: str = trace_reduce.DEVICE_PLANE) -> list:
    """[[name, start_ns, dur_ns]] of every `sw:` event of the host planes."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        if re.search(device_plane, plane.name):
            continue
        for line in plane.lines:
            events += [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events
                       if e.name.startswith(STAGE_PREFIX)]
    return events


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(a: list, b: list) -> float:
    """Summed length of the intersection of two sorted disjoint unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def attribute(planes: list, stages: list, command_marks: str,
              device_plane: str = trace_reduce.DEVICE_PLANE,
              ops_line: str = trace_reduce.OPS_LINE) -> dict:
    """`planes` as `trace_reduce.load` gives them (device operations and
    the `bench:` marks), `stages` as `load_host` does; `command_marks` is
    the prefix of the marks that time the program's commands (`ec.` for
    `bench:ec.encode`, `bench:ec.rebuild`). Seconds are means over the
    devices. `by_mark` splits the idle time by the benchmark's
    marks (`ec.encode`, `ec.rebuild`, ...): under each, the idle seconds
    that overlap each stage name (threads run side by side, so they may sum
    past the idle time) and the stage with the most. None: no device
    events or no traced window."""
    marks = [e for p in planes if not re.search(device_plane, p["name"])
             for ln in p["lines"] for e in ln["events"]
             if e[0].startswith(trace_reduce.MARK_PREFIX)]
    windows = [e for e in marks if e[0] == trace_reduce.WINDOW_MARK]
    devices = [[(s, s + d) for ln in p["lines"] if ln["name"] == ops_line
                for _, s, d in ln["events"]]
               for p in planes if re.search(device_plane, p["name"])]
    if not windows or not any(devices):
        return None
    w0 = min(e[1] for e in windows)
    w1 = max(e[1] + e[2] for e in windows)
    by_name = {}
    for name, s, d in stages:
        by_name.setdefault(name[len(STAGE_PREFIX):], []).append((s, s + d))
    by_name = {name: trace_reduce._union(_clip(ivs, w0, w1))
               for name, ivs in by_name.items()}
    any_stage = trace_reduce._union(
        [tuple(iv) for ivs in by_name.values() for iv in ivs])
    regions = {}
    for name, s, d in marks:
        if name != trace_reduce.WINDOW_MARK:
            regions.setdefault(name[len(trace_reduce.MARK_PREFIX):],
                               []).append((s, s + d))
    commands = trace_reduce._union(
        [iv for name, ivs in regions.items()
         if name.startswith(command_marks) for iv in ivs])
    outside = sum(1 for _, s, d in stages
                  if _overlap([(s, s + d)], commands) < d - 1.0)
    n = len(devices)
    out = {"idle_s": 0.0, "unattributed_s": 0.0, "by_stage": {},
           "by_mark": {}, "stages": len(stages),
           "stages_outside_command_marks": outside}
    for ops in devices:
        busy = trace_reduce._union(_clip(ops, w0, w1))
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle_ns = sum(b - a for a, b in idle)
        out["idle_s"] += idle_ns / 1e9 / n
        out["unattributed_s"] += \
            (idle_ns - _overlap(idle, any_stage)) / 1e9 / n
        for name, ivs in by_name.items():
            out["by_stage"][name] = out["by_stage"].get(name, 0.0) + \
                _overlap(idle, ivs) / 1e9 / n
        for mark, region in regions.items():
            part = trace_reduce._union(
                [iv for lo, hi in region for iv in _clip(idle, lo, hi)])
            got = out["by_mark"].setdefault(mark, {
                "idle_s": 0.0, "unattributed_s": 0.0, "stages": {}})
            part_ns = sum(b - a for a, b in part)
            got["idle_s"] += part_ns / 1e9 / n
            got["unattributed_s"] += \
                (part_ns - _overlap(part, any_stage)) / 1e9 / n
            for name, ivs in by_name.items():
                secs = _overlap(part, ivs) / 1e9 / n
                if secs > 0:
                    got["stages"][name] = got["stages"].get(name, 0.0) + secs
    for got in out["by_mark"].values():
        got["most"] = max(got["stages"], key=got["stages"].get) \
            if got["stages"] else None
    # inside the timed commands alone: between them the harness works
    # (waits for the master, hashes shard files), not the program
    inside = [got for mark, got in out["by_mark"].items()
              if mark.startswith(command_marks)]
    out["in_commands"] = {key: sum(got[key] for got in inside)
                          for key in ("idle_s", "unattributed_s")}
    out["share"] = {
        where: 100.0 * acct["unattributed_s"] / acct["idle_s"]
        for where, acct in (("in_commands", out["in_commands"]),
                            ("whole_window", out))
        if acct["idle_s"] > 0}
    return out
