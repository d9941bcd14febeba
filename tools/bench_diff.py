#!/usr/bin/env python
"""Diff two bench.py records and flag per-metric regressions.

bench.py writes one BENCH_r<NN>.json per full run; comparing runs used
to mean eyeballing nested dicts, which is how a mesh-rebuild cliff
(rebuild_mbps_volume_bytes 72 -> 2) once sat unnoticed inside an
otherwise-green record. This tool flattens both records to
dotted numeric metrics, classifies each metric's good direction from
its name, and flags any move beyond --threshold (default 20%) in the
bad direction:

    python tools/bench_diff.py older.json newer.json
    python tools/bench_diff.py old.json new.json --json   # CI mode

Exit status: 0 clean, 1 when regressions were flagged, 2 on usage /
unreadable input. `--json` emits one machine-readable object with
`regressions`, `improvements`, `added`, `removed`, and `unclassified`
so a CI step can gate on `regressions == []` without parsing text.

Records may be either the driver's `{n, cmd, rc, tail, parsed}` wrapper
(the `parsed` headline is diffed) or a bare headline dict, so the tool
also works on `bench.py --json` output piped to a file.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

# Name-suffix direction classification. A metric whose trailing name
# segment matches neither list is structural/informational (shard
# counts, file sizes, unix stamps) and is reported under
# `unclassified`, never flagged.
HIGHER_IS_BETTER = (
    "mbps", "rps", "value", "vs_baseline", "speedup", "ratio",
    "overlap_frac", "busy_frac", "hit_ratio", "width_devices",
    "speedup_vs_python_warm",
)
LOWER_IS_BETTER = (
    "_s", "_ms", "_us", "seconds", "errors", "failures", "recompiles",
    "retries", "fallbacks", "redirects", "bytes_frac", "lost",
    "bytes_per_read",
)


def direction(metric: str) -> Optional[bool]:
    """True = higher is better, False = lower, None = unclassified.
    The LAST dotted segment carries the unit token — not necessarily
    at the end (`rebuild_mbps_volume_bytes` qualifies its unit), so
    single-word entries match as underscore-delimited tokens anywhere
    in the leaf while compound entries match as suffixes. Throughput
    wins over latency when both appear; identity fields fall through
    to None."""
    leaf = metric.rsplit(".", 1)[-1]
    tokens = leaf.split("_")
    for suf in HIGHER_IS_BETTER:
        if "_" in suf:
            if leaf == suf or leaf.endswith("_" + suf):
                return True
        elif suf in tokens:
            return True
    for suf in LOWER_IS_BETTER:
        word = suf.lstrip("_")
        if "_" in word:
            if leaf == word or leaf.endswith("_" + word):
                return False
        elif word in tokens:
            return False
    return None


def load_record(path: str) -> Dict:
    with open(path, encoding="utf-8") as f:
        obj = json.load(f)
    if isinstance(obj, dict) and isinstance(obj.get("parsed"), dict):
        return obj["parsed"]
    if isinstance(obj, dict):
        return obj
    raise ValueError(f"{path}: not a BENCH record (expected an object)")


def flatten(obj, prefix: str = "") -> Dict[str, float]:
    """Numeric leaves as dotted metrics; bools and strings are config
    echo, lists (retry logs, per-device maps keyed by index) are
    skipped — a diff over them is noise, not a regression signal."""
    out: Dict[str, float] = {}
    if not isinstance(obj, dict):
        return out
    for key, val in obj.items():
        name = f"{prefix}.{key}" if prefix else str(key)
        if isinstance(val, bool):
            continue
        if isinstance(val, (int, float)):
            out[name] = float(val)
        elif isinstance(val, dict):
            out.update(flatten(val, name))
    return out


def diff_records(old: Dict, new: Dict,
                 threshold: float) -> Dict[str, List]:
    old_flat, new_flat = flatten(old), flatten(new)
    regressions, improvements, unclassified = [], [], []
    for metric in sorted(set(old_flat) & set(new_flat)):
        ov, nv = old_flat[metric], new_flat[metric]
        if ov == nv:
            continue
        base = max(abs(ov), 1e-12)
        delta_frac = (nv - ov) / base
        entry = {"metric": metric, "old": ov, "new": nv,
                 "delta_frac": round(delta_frac, 4)}
        better = direction(metric)
        if better is None:
            unclassified.append(entry)
            continue
        worse_frac = -delta_frac if better else delta_frac
        if worse_frac > threshold:
            regressions.append(entry)
        elif worse_frac < -threshold:
            improvements.append(entry)
    # Sort worst-first: the biggest cliff leads the report.
    regressions.sort(key=lambda e: -abs(e["delta_frac"]))
    improvements.sort(key=lambda e: -abs(e["delta_frac"]))
    return {
        "threshold": threshold,
        "regressions": regressions,
        "improvements": improvements,
        "unclassified": unclassified,
        "added": sorted(set(new_flat) - set(old_flat)),
        "removed": sorted(set(old_flat) - set(new_flat)),
    }


def render_text(report: Dict, old_path: str, new_path: str) -> str:
    lines = [f"bench_diff: {old_path} -> {new_path} "
             f"(threshold {report['threshold']:.0%})"]
    for entry in report["regressions"]:
        lines.append(
            f"  REGRESSION {entry['metric']}: {entry['old']:g} -> "
            f"{entry['new']:g} ({entry['delta_frac']:+.1%})")
    for entry in report["improvements"]:
        lines.append(
            f"  improved   {entry['metric']}: {entry['old']:g} -> "
            f"{entry['new']:g} ({entry['delta_frac']:+.1%})")
    if report["removed"]:
        lines.append("  removed: " + ", ".join(report["removed"]))
    if report["added"]:
        lines.append("  added:   " + ", ".join(report["added"]))
    if not report["regressions"]:
        lines.append("  no regressions flagged")
    return "\n".join(lines)


def main(argv: List[str] = None) -> int:
    parser = argparse.ArgumentParser(
        description="Diff two bench.py records; exit 1 on any "
                    "per-metric regression beyond the threshold.")
    parser.add_argument("old", help="baseline BENCH record")
    parser.add_argument("new", help="candidate BENCH record")
    parser.add_argument("--threshold", type=float, default=0.2,
                        help="regression fraction to flag "
                             "(default 0.2 = 20%%)")
    parser.add_argument("--json", action="store_true",
                        help="emit the machine-readable report")
    args = parser.parse_args(argv)
    if args.threshold < 0:
        parser.error("--threshold must be >= 0")
    try:
        old = load_record(args.old)
        new = load_record(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    report = diff_records(old, new, args.threshold)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render_text(report, args.old, args.new))
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
