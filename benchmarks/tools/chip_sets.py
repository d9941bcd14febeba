#!/usr/bin/env python3
"""The builder's measurement of one cell on the chip, all in one call:

    chiprun --timeout 3000 -- python3 benchmarks/tools/chip_sets.py \\
        --workload <cell> --tag c1 --seeds 11,12,13,14,15,16 --sets 2 \\
        --traced 21 --traced-short 22,23 --short 31,32,33 --control 41

`--sets` full-length runs over the same seeds (`run_seconds` of
BENCHMARK.json, --trace 0), then traced runs, short runs on further seeds
(`correct` on a dozen seeds) and each of the traffic mix's controls, or
those `--controls` names (every one has to end `correct: false`). This
process never touches JAX: each run is a child that holds the chip alone.
Every run's stdout is kept under chiprun_out/sets/<tag>/, its last line in
chiprun_out/sets/<tag>.jsonl, and the spreads the bounds are set from are
printed at the end, for each metric and set as `spreads.py` beside this
file prints them (it reads the .jsonl again, so sets of several calls can
be put side by side later).
"""

import argparse
import json
import os
import subprocess
import sys
import time

import spreads

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", default="")
    ap.add_argument("--traced-short", default="",
                    help="traced runs of --short-seconds: the trace covers "
                         "the window's first cycle whatever its length")
    ap.add_argument("--short", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--controls", default="",
                    help="comma-separated; default: all the mix names")
    ap.add_argument("--short-seconds", type=int, default=10)
    ap.add_argument("--root", default=ROOT,
                    help="the checkout to run (an unpacked git archive)")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        controls = [c for c in args.controls.split(",") if c] or \
            json.load(f)["controls"]
    out_dir = os.path.join(ROOT, "chiprun_out", "sets", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    log = open(out_dir + ".jsonl", "a")
    plan = [("set%d" % n, seed, bench["run_seconds"], 0, [])
            for n in range(args.sets) for seed in seeds(args.seeds)]
    plan += [("traced", seed, bench["run_seconds"], 1,
              ["--out", os.path.join(out_dir, f"trace-{seed}")])
             for seed in seeds(args.traced)]
    plan += [("traced", seed, args.short_seconds, 1,
              ["--out", os.path.join(out_dir, f"trace-{seed}")])
             for seed in seeds(args.traced_short)]
    plan += [("short", seed, args.short_seconds, 0, [])
             for seed in seeds(args.short)]
    plan += [("control", seed, args.short_seconds, 0, ["--control", control])
             for control in controls for seed in seeds(args.control)]
    rows = []
    for kind, seed, seconds, trace, extra in plan:
        cmd = bench["command"] + ["--workload", args.workload, "--seed",
                                  str(seed), "--seconds", str(seconds),
                                  "--trace", str(trace)] + extra
        t0 = time.time()
        name = os.path.join(out_dir, "-".join(
            [kind] + extra[1:] * (kind == "control") + [str(seed)]))
        with open(name + ".out", "w") as so, open(name + ".err", "w") as se:
            rc = subprocess.run(cmd, cwd=args.root, stdout=so,
                                stderr=se).returncode
        with open(name + ".out") as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        last = json.loads(lines[-1]) if rc == 0 and lines else None
        row = {"kind": kind, "seed": seed, "seconds": seconds, "rc": rc,
               "control": extra[1] if kind == "control" else None,
               "checks": [json.loads(ln) for ln in lines
                          if ln.startswith('{') and '"check"' in ln],
               "host": next((json.loads(ln) for ln in lines
                             if '"phase": "host"' in ln), None),
               "took_s": round(time.time() - t0, 1), "last": last}
        rows.append(row)
        log.write(json.dumps(row) + "\n")
        log.flush()
        print(json.dumps({k: row[k] for k in ("kind", "seed", "rc",
                                              "took_s")}),
              None if last is None else (last["correct"], {
                  k: round(v["value"], 3)
                  for k, v in last["metrics"].items()}), flush=True)
    log.close()
    spreads.main([out_dir + ".jsonl"])
    bad = [r for r in rows if r["kind"] != "control" and
           not (r["last"] and r["last"]["correct"])]
    passed_control = [r for r in rows if r["kind"] == "control" and
                      not (r["last"] and r["last"]["correct"] is False)]
    print("runs not correct:", [(r["kind"], r["seed"]) for r in bad])
    print("controls that did not fail:",
          [(r["kind"], r["seed"]) for r in passed_control])
    return 1 if bad or passed_control else 0


if __name__ == "__main__":
    sys.exit(main())
