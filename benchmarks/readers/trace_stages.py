"""The share of the device's idle time inside the traced cycle's timed
commands (the marks `bench:<args.command_marks>*`) during which no stage of
the program was open on any thread (`sw:` host events,
lib/stage_overlap.py). It loads the host events itself from the trace the
harness wrote, and prints beside its value one line
`{"phase": "idle_by_stage", ...}`: the idle seconds under each stage name,
over the whole traced window (the harness's own work between the commands
included) and split by the benchmark's marks, with `share`: the value
itself (`in_commands`) and the same share over the whole window. No
device events, no trace, or a program that mirrors no stage (the trace
holds no `sw:` event): None."""

import os

from lib import stage_overlap, trace_reduce


def read(args: dict, run, trace):
    if not trace or not trace["devices"]:
        return None
    log_dir = os.path.join(run.args.out or run.workdir, "trace")
    try:
        xplane = trace_reduce.find_xplane(log_dir)
    except FileNotFoundError:
        return None
    stages = stage_overlap.load_host(xplane)
    if not stages:
        return None
    got = stage_overlap.attribute(trace_reduce.load(xplane), stages,
                                  args["command_marks"])
    if got is None or "in_commands" not in got["share"]:
        return None
    run.emit({"phase": "idle_by_stage", **got})
    return got["share"]["in_commands"]
