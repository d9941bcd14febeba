#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by the names in
`BENCHMARK.json`: the configuration (`configs/<config>.json`), the traffic
mix (`traffic/<traffic>.json`, whose `kind` names the loop in
`kinds/<kind>.py`) and each per-layer metric (`layer_metrics/<name>.json`,
whose `reader` names `readers/<reader>.py`). This file holds no table of
names.

The process holds the chip: master, volume servers, clients and the shell
commands all live here. Earlier stdout lines are one JSON object each;
the last line is the contract's result. Off the TPU, or with fewer chips
than the cell asks, it exits non-zero and prints no result. `--rehearse`
is the CPU rehearsal (JAX_PLATFORMS=cpu first): it says so on every line,
and a number it prints is never a device number.
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_metrics(metrics: list, workload: str, reported=None) -> list:
    """The metrics this cell reports: those that list it, and those that
    list no cells (per-layer ones: if they move a metric it reports)."""
    return [m for m in metrics
            if (workload in m["workloads"] if "workloads" in m
                else reported is None or m["moves"] in reported)]


class Tracer:
    """`jax.profiler` around the part of the window a kind chooses."""

    def __init__(self, enabled: bool, log_dir: str):
        self.enabled = enabled
        self.log_dir = log_dir
        self.active = False
        self._window = None

    def start(self):
        if not self.enabled or self.active:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench:window")
        self._window.__enter__()
        self.active = True

    def stop(self):
        if not self.active:
            return
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def mark(self, name: str):
        """A host annotation in the trace's own clock; idle gaps of the
        device are named by the mark that was open when they began."""
        import jax
        return jax.profiler.TraceAnnotation("bench:" + name)


class Run:
    """What a kind and the readers get: the cell's data, the seed, the
    clock's budget, and places to put what they observe."""

    def __init__(self, args, bench, workload, config, traffic, emit):
        self.args = args
        self.bench = bench
        self.workload = workload
        self.config = config
        self.traffic = traffic
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.emit = emit
        self.workdir = None
        self.tracer = None
        self.device = None
        self.cluster = None
        # observations: timed operations, span totals, window counters
        self.ops = []
        self.spans = {}
        self.counters = {}
        self.checks = []
        self.attempted = 0
        self.failed = 0

    def check(self, name: str, value, limit, ok: bool):
        """One number compared beside its limit; printed in every run."""
        self.checks.append({"check": name, "value": value, "limit": limit,
                            "ok": bool(ok)})
        self.emit(self.checks[-1])


def device_info() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks)) if peaks else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: allow a platform other than the "
                         "TPU and --volume-mib; labelled on every line")
    ap.add_argument("--volume-mib", type=int, default=0,
                    help="rehearsal only: a smaller sealed volume")
    ap.add_argument("--control", default="",
                    help="break the program as lib/controls.py names it: "
                         "the run then has to end `correct: false`")
    ap.add_argument("--out", default="",
                    help="keep the trace and its reduction in this "
                         "directory (inside the checkout)")
    args = ap.parse_args(argv)

    # This process owns the real stdout: fd 1 is pointed at stderr so no
    # print(), child or native library can write between the JSON lines.
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def emit(obj: dict):
        obj = {"t": round(time.perf_counter() - T_START, 3), **obj}
        if args.rehearse:
            obj = {"rehearsal_not_a_chip_run": True, **obj}
        out.write(json.dumps(obj) + "\n")
        out.flush()

    bench = load_json(ROOT, "BENCHMARK.json")
    workload = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
    if workload is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    entry = next(c for c in bench["configs"]
                 if c["name"] == workload["config"])
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", workload["traffic"] + ".json")
    kind = load_module("kinds", traffic["kind"])
    if args.volume_mib:
        if not args.rehearse:
            print("--volume-mib is for --rehearse only", file=sys.stderr)
            return 2
        config["volume_mib"] = args.volume_mib

    from lib import cluster as cl
    from lib import observe, trace_reduce
    cl.apply_env(config["env"])
    if args.control:
        from lib import controls
        controls.CONTROLS[args.control]()
    cl.build_native()
    device = device_info()
    on_chip = device["platform"] == "tpu" and \
        device["count"] >= int(workload["chips"])
    if not on_chip and not args.rehearse:
        print(f"cell {workload['name']} needs {workload['chips']} TPU "
              f"chip(s); JAX offers {device}", file=sys.stderr)
        return 3

    run = Run(args, bench, workload, config, traffic, emit)
    run.device = device
    run.workdir = tempfile.mkdtemp(prefix="swbench_")
    try:
        run.cluster = cl.Cluster(run.workdir, config)
        last = measure(run, kind, observe, trace_reduce)
    finally:
        if run.cluster is not None:
            run.cluster.stop()
        shutil.rmtree(run.workdir, ignore_errors=True)
    if args.rehearse:
        last["rehearsal_not_a_chip_run"] = True
    if args.control:
        last["control"] = args.control
    # each number compared beside its limit: the last key of the result
    # line and the last lines on standard error
    last["checks"] = {c["check"]: {"value": c["value"], "limit": c["limit"]}
                      for c in run.checks}
    for c in run.checks:
        print(f"check {c['check']}: {c['value']} (limit {c['limit']})"
              f"{'' if c['ok'] else '  NOT MET'}", file=sys.stderr)
    sys.stderr.flush()
    out.write(json.dumps(last) + "\n")
    out.flush()
    return 0


def measure(run, kind, observe, trace_reduce) -> dict:
    """Set-up, the window, the checks and the reduction; returns the
    result line. Raises where the harness itself cannot go on."""
    args, bench, config, emit = run.args, run.bench, run.config, run.emit
    workload, device = run.workload, run.device
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    trace_dir = os.path.join(args.out or run.workdir, "trace")
    run.tracer = Tracer(bool(args.trace), trace_dir)
    emit({"phase": "start", "workload": workload["name"], "seed": run.seed,
          "seconds": run.seconds, "trace": args.trace, "device": device,
          "control": args.control or None,
          "env": {n: os.environ.get(n) for n in (
              "JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}})

    from seaweedfs_tpu.util import tracing

    def on_span(span: dict):
        agg = run.spans.setdefault(span["name"], [0, 0.0])
        agg[0] += 1
        agg[1] += span.get("duration_s") or 0.0

    state = kind.prepare(run)
    setup_s = time.perf_counter() - T_START
    before = observe.counters_now(run.cluster)
    emit({"phase": "setup_done", "setup_s": setup_s,
          "compiles": {k: v for k, v in before.items()
                       if k.startswith("jit.compile")}})
    tracing.add_finish_hook(on_span)
    host_before = observe.host_now()
    t0 = time.perf_counter()
    try:
        kind.window(run, state)
    finally:
        run.tracer.stop()
        tracing.remove_finish_hook(on_span)
    window_s = time.perf_counter() - t0
    emit(observe.host_line(run.ops, host_before, observe.host_now()))
    run.counters = observe.counters_delta(
        before, observe.counters_now(run.cluster))
    emit({"phase": "window_done", "window_s": window_s,
          "counters": run.counters, "spans": dict(sorted(
              run.spans.items()))})
    kind.verify(run, state)
    # the served path ran on the configuration's kernel, and nothing
    # compiled once the window had opened
    on_kernel = run.counters.get(
        "jit.dispatches." + config["kernel"]["entry"], 0)
    if device["platform"] == "tpu":     # off it, another program runs
        run.check("kernel_entry_dispatches_at_least", on_kernel, 1,
                  on_kernel >= 1)
    compiled = run.counters.get("jit.compiles", 0) + \
        run.counters.get("jit.recompiles", 0)
    run.check("compiles_in_window", compiled, 0, compiled == 0)
    results = kind.end_to_end(run, state, window_s)
    results["setup_s"] = setup_s
    peak = memory_peak_bytes()
    run.cluster.stop()      # before the reduction: nothing of it runs on

    trace = None
    if args.trace:
        xplane = trace_reduce.find_xplane(trace_dir)
        trace = trace_reduce.reduce(trace_reduce.load(xplane),
                                    config["kernel"]["trace_pattern"])
        emit({"phase": "trace", **trace})
        if args.out:
            with open(os.path.join(args.out, "trace_summary.json"), "w") as f:
                json.dump(trace_reduce.summary(xplane), f, indent=1)

    end_to_end = cell_metrics(bench["end_to_end"], workload["name"])
    values = {}
    if args.trace:
        for metric in cell_metrics(bench["per_layer"], workload["name"],
                                   {m["name"] for m in end_to_end}):
            spec = load_json(HERE, "layer_metrics", metric["name"] + ".json")
            reader = load_module("readers", spec["reader"])
            values[metric["name"]] = (
                reader.read(spec.get("args", {}), run, trace), metric)
    else:
        values = {m["name"]: (results.get(m["name"]), m) for m in end_to_end}
    # a reader that found nothing to read returned None: left out
    metrics = {name: {"value": value, "unit": metric["unit"]}
               for name, (value, metric) in values.items()
               if value is not None}
    correct = all(c["ok"] for c in run.checks) and run.failed == 0 \
        and run.attempted > 0
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": peak}
    last = {"correct": correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device_out}
    if trace is not None:
        device_out["busy_s"] = trace["busy_s"]
        device_out["window_s"] = trace["window_s"]
        last["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    return last


if __name__ == "__main__":
    sys.exit(main())
