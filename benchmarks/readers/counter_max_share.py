"""The largest of a family of counters over the family's sum, over the
whole window: the counters whose names start with `prefix` (one a chip,
one a holder, ...). A family that did not move, or that the program does
not count: None."""


def read(args: dict, run, trace):
    moved = [n for name, n in run.counters.items()
             if name.startswith(args["prefix"]) and n > 0]
    if not moved:
        return None
    return float(args.get("scale", 1)) * max(moved) / sum(moved)
