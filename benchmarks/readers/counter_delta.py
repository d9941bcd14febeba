"""A ratio of counter movements over the whole window (the program's
telemetry, jit accounting and degraded-read engines, summed over the
servers). A denominator that did not move: None."""


def read(args: dict, run, trace):
    num = sum(run.counters.get(name, 0) for name in args["numerator"])
    den = sum(run.counters.get(name, 0) for name in args["denominator"])
    if den <= 0:
        return None
    return float(args.get("scale", 1)) * num / den
