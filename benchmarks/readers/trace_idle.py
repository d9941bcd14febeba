"""The device's idle share of the traced window: 1 - (union of the
intervals in which an operation ran on the device) / window, averaged
over the devices used. No device events in the trace: None."""


def read(args: dict, run, trace):
    if not trace or not trace["devices"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
