"""The mean duration of the program's spans of one name that finished
inside the window (util/tracing finish hook). No such span: None."""


def read(args: dict, run, trace):
    count, seconds = run.spans.get(args["span"], (0, 0.0))
    if not count:
        return None
    return float(args.get("scale", 1)) * seconds / count
