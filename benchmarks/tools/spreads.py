#!/usr/bin/env python3
"""The spreads of one cell's sets, from what `chip_sets.py` kept:

    python3 benchmarks/tools/spreads.py chiprun_out/sets/a.jsonl \\
        chiprun_out/sets/b.jsonl

Every (file, `setN`) pair is one set of same-code runs. For each metric
and set: the median, the distance between the quartiles
(`statistics.quantiles(values, n=4)`) as a share of it, and the statistic
the driver words in its refusals ("the spread is 46.87 and 41.64 MB/s ...
A spread leaves out the run farthest from its median where that narrows
it"): the range of the set with that run left out, in the metric's unit
and as a share; then the mean of the sets' trimmed shares, which a bound
has to be at least twice of, and the bound that 2.5 times it gives. Below,
run by run, the `host` line beside the metrics: the median and the
largest wall of each kind of command and what the host wrote and
faulted in over the window, so that a run that reads low can be told
apart (one stalled command, write-back, every command slower alike).
Runs on no chip and touches no JAX.
"""

import json
import math
import statistics
import sys


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed_range(values: list) -> float:
    """The range of a set, leaving out the run farthest from the median
    where that narrows it (it always does, or leaves it as it is)."""
    if len(values) < 3:
        return max(values) - min(values)
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return max(rest) - min(rest)


def main(paths: list) -> int:
    sets = {}
    for path in paths:
        with open(path) as f:
            for row in map(json.loads, f):
                if row["kind"].startswith("set") and row["last"]:
                    sets.setdefault((path, row["kind"]), []).append(row)
    metrics = sorted({m for rows in sets.values() for r in rows
                      for m in r["last"]["metrics"]})
    for metric in metrics:
        shares = []
        for (path, kind), rows in sets.items():
            values = [r["last"]["metrics"][metric]["value"] for r in rows]
            if len(values) < 2:
                continue
            mid, rng = statistics.median(values), trimmed_range(values)
            shares.append(rng / mid)
            print(f"{metric:>13} {path.rsplit('/', 1)[-1]}:{kind} n="
                  f"{len(values)} median {mid:.2f} quartile spread "
                  f"{100 * spread(values):.2f} % trimmed range {rng:.2f} "
                  f"= {100 * rng / mid:.2f} %  "
                  f"{[round(v, 1) for v in values]}")
        if shares:
            mean = statistics.mean(shares)
            print(f"{metric:>13} mean trimmed share {100 * mean:.2f} %, "
                  f"widest {100 * max(shares):.2f} %; 2.5 x the mean, "
                  f"rounded up: {math.ceil(250 * mean) / 100:.2f}\n")
    for (path, kind), rows in sets.items():
        for r in rows:
            host = r.get("host") or {"ops": {}, "window": {}}
            ops = " ".join(
                f"{op}: n {o['count']} med {o['median_wall_s']:.3f} "
                f"max {o['max_wall_s']:.3f}"
                for op, o in host["ops"].items())
            values = {m: round(v["value"], 1)
                      for m, v in r["last"]["metrics"].items()}
            print(f"{path.rsplit('/', 1)[-1]}:{kind} seed {r['seed']} "
                  f"correct {r['last']['correct']} {values} | {ops} | "
                  f"{host['window']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
