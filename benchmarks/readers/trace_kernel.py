"""A kernel's share of its roofline over the traced part of the window:
the least time the chip could take for the columns the traced operations
dispatched (lib/roofline.py, peaks.json) over the summed device time of
the configuration's kernel events, all devices together. No kernel event
or no traced operation: None."""

from lib import roofline


def read(args: dict, run, trace):
    if not trace or not trace["kernel_events"] or trace["kernel_s"] <= 0:
        return None
    peak = roofline.peaks(run.device["kind"])
    least = 0.0
    for record in run.ops:
        if record["traced"] and record["op"] in args["ops"]:
            columns = record["counters"].get("telemetry.device_bytes", 0) \
                // record["k"]
            least += roofline.least_seconds(
                columns, record["rows"], record["k"], peak)["seconds"]
    if least <= 0:
        return None
    return 100.0 * least / trace["kernel_s"]
