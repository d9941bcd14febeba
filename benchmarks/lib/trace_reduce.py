"""From a profiler trace to the few numbers the benchmark reports.

`load` reads an `.xplane.pb` with nothing but JAX (`ProfileData`) into
plain lists; `reduce` works on those lists alone, so it is tested on a
recorded trace kept beside the tests. Per device: the union of the
intervals in which an operation ran (busy), the summed time of the
configuration's kernel events, the operations that took most time, and the
longest idle gaps named by the benchmark's own annotation that was open on
the host when the gap began.
"""

import glob
import os
import re

DEVICE_PLANE = r"^/device:TPU:\d+"
OPS_LINE = "XLA Ops"
MARK_PREFIX = "bench:"
WINDOW_MARK = "bench:window"
NAME_CHARS = 160      # an XLA op's name is its whole HLO line


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, device_plane: str = DEVICE_PLANE) -> list:
    """[{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}]
    of the device planes, and of host lines only the benchmark's marks."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = re.search(device_plane, plane.name) is not None
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(MARK_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: list) -> list:
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _mark_at(marks: list, t: float) -> str:
    """The innermost benchmark annotation open at time t."""
    best = None
    for name, start, dur in marks:
        if start <= t < start + dur and name != WINDOW_MARK:
            if best is None or start >= best[1]:
                best = (name, start)
    return best[0][len(MARK_PREFIX):] if best else "unmarked"


def reduce(planes: list, kernel_pattern: str, top: int = 10,
           device_plane: str = DEVICE_PLANE, ops_line: str = OPS_LINE) -> dict:
    """See the module's docstring. Times in seconds. `window_s` is the
    length of the `bench:window` annotation (the traced part of the run)
    or, without one, the span of the device events."""
    marks = [e for p in planes if not re.search(device_plane, p["name"])
             for ln in p["lines"] for e in ln["events"]
             if e[0].startswith(MARK_PREFIX)]
    windows = [e for e in marks if e[0] == WINDOW_MARK]
    devices = []
    for plane in planes:
        if not re.search(device_plane, plane["name"]):
            continue
        events = [e for ln in plane["lines"] if ln["name"] == ops_line
                  for e in ln["events"]]
        devices.append({"name": plane["name"], "events": events})
    every = [e for d in devices for e in d["events"]]
    if windows:
        w0 = min(e[1] for e in windows)
        w1 = max(e[1] + e[2] for e in windows)
    elif every:
        w0 = min(e[1] for e in every)
        w1 = max(e[1] + e[2] for e in every)
    else:
        return {"devices": 0, "window_s": 0.0, "busy_s": 0.0,
                "kernel_s": 0.0, "kernel_events": 0, "per_device": [],
                "device_ops": [], "idle_gaps": []}
    kernel = re.compile(kernel_pattern)
    op_seconds, gap_seconds, per_device = {}, {}, []
    kernel_s, kernel_events = 0.0, 0
    for dev in devices:
        inside = [(max(s, w0), min(s + d, w1)) for _, s, d in dev["events"]
                  if s + d > w0 and s < w1]
        busy = _union(inside)
        busy_ns = sum(e - s for s, e in busy)
        k_ns = 0.0
        for name, s, d in dev["events"]:
            if s + d <= w0 or s >= w1:
                continue
            short = name[:NAME_CHARS]
            op_seconds[short] = op_seconds.get(short, 0.0) + d / 1e9
            if kernel.search(name):
                k_ns += d
                kernel_events += 1
        kernel_s += k_ns / 1e9
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                what = _mark_at(marks, a)
                gap_seconds[what] = gap_seconds.get(what, 0.0) + (b - a) / 1e9
        per_device.append({"device": dev["name"], "busy_s": busy_ns / 1e9,
                           "kernel_s": k_ns / 1e9})
    n = max(len(devices), 1)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"devices": len(devices), "window_s": (w1 - w0) / 1e9,
            "busy_s": sum(d["busy_s"] for d in per_device) / n,
            "kernel_s": kernel_s, "kernel_events": kernel_events,
            "per_device": per_device,
            "device_ops": [[k, v] for k, v in rank(op_seconds)],
            "idle_gaps": [[k, v / n] for k, v in rank(gap_seconds)]}


def summary(path: str, limit: int = 12) -> list:
    """Every plane and line of a trace with its busiest event names: what
    to read by hand before trusting a pattern."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if not line.name:
                continue
            total, count = {}, 0
            for e in line.events:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns / 1e9
                count += 1
            names = sorted(((n[:NAME_CHARS], t) for n, t in total.items()),
                           key=lambda kv: -kv[1])[:limit]
            out.append({"plane": plane.name, "line": line.name,
                        "events": count, "top": names})
    return out
