"""A kernel's share of its roofline over the traced part of the window,
with the work counted from each operation's equation and not from the
operand the program dispatched (lib/roofline_terms.py says why): the least
time the chip could take for the traced operations' `work`, which their
kind attached from the configuration, over the summed device time of the
configuration's kernel events. No kernel event, or no traced operation
with a `work`: None.

Beside the value it prints one line, `kernel_roofline`: which limit bounds
each operation, and `dispatched_operand_fill_pct`, the same ratio counted
as lib/roofline.py counts the dense operand the node replied with - how
full the matrix unit ran on the matrix it was given, zeros and padding
included. That one is not a share of the algorithm's roofline."""

from lib import roofline, roofline_terms


def read(args: dict, run, trace):
    if not trace or not trace["kernel_events"] or trace["kernel_s"] <= 0:
        return None
    peak = roofline.peaks(run.device["kind"])
    least = dispatched = 0.0
    ops = []
    for record in run.ops:
        work = record.get("work")
        if not (record["traced"] and record["op"] in args["ops"] and work):
            continue
        need = roofline_terms.least_seconds(work, peak)
        least += need["seconds"]
        columns = record["counters"].get("telemetry.device_bytes", 0) \
            // record["k"]
        dispatched += roofline.least_seconds(
            columns, record["rows"], record["k"], peak)["seconds"]
        ops.append({"op": record["op"], **work, **need,
                    "operand": [record["rows"], record["k"]]})
    if least <= 0:
        return None
    run.emit({"phase": "kernel_roofline", "kernel_s": trace["kernel_s"],
              "least_s": least, "ops": ops,
              "dispatched_operand_fill_pct":
                  100.0 * dispatched / trace["kernel_s"]})
    return 100.0 * least / trace["kernel_s"]
