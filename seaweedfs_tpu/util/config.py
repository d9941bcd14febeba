"""Config-file loading with search path + env override tiers.

Reference weed/util/config.go: viper loads <name>.toml from ".",
"$HOME/.seaweedfs", "/etc/seaweedfs", and every key is overridable via
WEED_<SECTION>_<KEY> environment variables
(reference command/scaffold.go:15-25). Here: <name>.toml (stdlib
tomllib) or <name>.json from the same three-tier search path, flattened
to dotted keys, then WEED_* env vars override — e.g.

    WEED_JWT_SIGNING_KEY=secret    ->  cfg["jwt.signing.key"]

(env words map to dotted segments, lowercase, like viper's replacer).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

SEARCH_DIRS = [".", os.path.expanduser("~/.seaweedfs_tpu"),
               "/etc/seaweedfs_tpu"]
ENV_PREFIX = "WEED_"

# -- SW_* env-knob registry --------------------------------------------------
#
# Every SW_* tunable the codebase reads is declared here ONCE — name,
# type, default, one-line doc — and read through the typed accessors
# below (env_str/env_int/env_float/env_bool/env_is_set). tools/analyze.py
# enforces the contract as a tier-1 lint: a raw os.environ/os.getenv read
# of an SW_* name anywhere else is a violation, a registered knob nobody
# reads is a violation, and the README env table is generated from this
# registry (a stale committed table is a violation too).

KNOB_KINDS = ("str", "int", "float", "bool")


class EnvKnob:
    __slots__ = ("name", "kind", "default", "doc")

    def __init__(self, name: str, kind: str, default, doc: str):
        self.name = name
        self.kind = kind
        self.default = default
        self.doc = doc

    def default_repr(self) -> str:
        if self.default is None:
            return "(unset)"
        if self.kind == "bool":
            return "1" if self.default else "0"
        return str(self.default)


KNOBS: Dict[str, EnvKnob] = {}


def _knob(name: str, kind: str, default, doc: str) -> str:
    if not name.startswith("SW_"):
        raise ValueError(f"env knob {name!r} must start with SW_")
    if kind not in KNOB_KINDS:
        raise ValueError(f"env knob {name}: bad kind {kind!r}")
    if not doc or "\n" in doc:
        raise ValueError(f"env knob {name}: doc must be one line")
    if name in KNOBS:
        raise ValueError(f"env knob {name} registered twice")
    KNOBS[name] = EnvKnob(name, kind, default, doc)
    return name


# server / transport
_knob("SW_PULSE_S", "float", 5.0,
      "Default heartbeat/prune pulse seconds for servers constructed "
      "without an explicit pulse_seconds.")
_knob("SW_COMPACTION_MBPS", "int", 0,
      "Default -compactionMBps (MiB/s, 0 = unthrottled) for volume "
      "servers constructed without an explicit compaction_mbps: "
      "vacuum's copy and every byte the server pulls in the background "
      "(rebuild gathers, volume.copy, ec.copy).")
_knob("SW_HTTP_POLL_S", "float", 0.5,
      "HTTP accept-loop poll interval; server shutdown latency is "
      "bounded by it.")
_knob("SW_FILER_TICK_S", "float", 1.0,
      "Filer background deletion/notification loop tick seconds.")
_knob("SW_HTTP_POOL_MAX_IDLE_S", "float", 60.0,
      "Idle age after which pooled keep-alive connections are evicted.")
_knob("SW_HTTP_PLANE_LIB", "str", None,
      "Override path to the native HTTP plane shared library (e.g. an "
      "ASAN build); must exist when set.")
_knob("SW_RETRY_BACKOFF_SCALE", "float", 1.0,
      "Multiplier on internal retry-backoff sleeps (uploads, streams, "
      "vid-map refresh, notification queues); 0 retries immediately.")
_knob("SW_CLUSTER_SCRAPE_S", "float", 15.0,
      "Master metrics-scrape sweep interval for /cluster/metrics.")
_knob("SW_REPAIR_INTERVAL_S", "float", 5.0,
      "Master repair-queue drain tick seconds; <= 0 disables the loop.")
_knob("SW_REPAIR_AT_RISK_SCORE", "float", 0.4,
      "Holder health score below which an advisory at_risk_holder "
      "incident is queued.")

# EC data path
_knob("SW_EC_SMALL_DISPATCH_BYTES", "int", 256 << 10,
      "Width below which device codecs answer reconstruct() on the "
      "host instead of dispatching.")
_knob("SW_EC_SMALL_DISPATCH_AUTO", "bool", False,
      "Let the tuner's fitted host/device crossover supersede "
      "SW_EC_SMALL_DISPATCH_BYTES live.")
_knob("SW_EC_MESH_SHARD_MIN_BYTES", "int", 1 << 20,
      "Slab payload bytes (k * width) below which the mesh backend "
      "dispatches on one device instead of sharding the width axis.")
_knob("SW_EC_MESH_WIDTH_DEVICES", "int", 0,
      "Cap on devices the mesh codec puts on its width axis; 0 uses "
      "every visible device.")
_knob("SW_EC_GATHER_WINDOW", "int", 4,
      "Bounded in-flight stripe prefetch window for streaming gathers.")
_knob("SW_EC_HEDGE_MS", "float", 0.0,
      "Hedge a duplicate survivor range read after this many ms; 0 "
      "disables hedging.")
_knob("SW_EC_SPREAD_WINDOW", "int", 4,
      "Bounded per-lane send-queue window for streaming encode "
      "spread: stripes of the stream's slab width, counted in bytes.")
_knob("SW_EC_REPAIR_MODE", "str", "auto",
      "Single-shard rebuild mode: auto (layout-routed: piggyback on "
      "coupled layouts, else trace, with fallback), trace, piggyback, "
      "or full.")
_knob("SW_EC_LAYOUT", "str", "flat",
      "On-disk EC layout for NEW volumes: flat (plain RS) or piggyback "
      "(coupled sub-chunk parities; single-data-shard repair downloads "
      "(k+1)/2k of k*shard). Existing volumes keep their layout.")
_knob("SW_EC_PLAN_CACHE_SIZE", "int", 128,
      "LRU bound on each derived-plan cache (repair/piggyback plans); "
      "read live, so operators can resize without a restart.")
_knob("SW_EC_PIGGYBACK_PAIRS", "int", 5,
      "Cap on coupled data-shard pairs (alpha = 2^pairs sub-chunks); "
      "shards beyond the paired prefix repair via the flat paths.")
_knob("SW_EC_DEGRADED_CACHE_BYTES", "int", 64 << 20,
      "Byte budget of the reconstructed-slab LRU; 0 disables caching.")
_knob("SW_EC_DEGRADED_SLAB_BYTES", "int", 128 << 10,
      "Reconstructed-slab granularity of the degraded-read engine.")
_knob("SW_EC_DEGRADED_BATCH_MS", "float", 2.0,
      "Degraded-read leader coalescing window in milliseconds.")
_knob("SW_EC_DEGRADED_READ_TIMEOUT_S", "float", 10.0,
      "Per-holder budget for degraded-read survivor fetches.")
_knob("SW_EC_DEGRADED_READAHEAD_SLABS", "int", 1,
      "Neighbor slabs reconstructed per degraded batch beyond the "
      "requested range; 0 disables.")
_knob("SW_EC_DEGRADED_MODE", "str", "batch",
      "Degraded-read serving mode: batch (engine) or naive (per-read "
      "exactly-k fallback).")
_knob("SW_EC_SCRUB_RATE_MBPS", "float", 8.0,
      "Gather-bandwidth ceiling for a scrub pass; 0 disables pacing.")
_knob("SW_EC_SCRUB_IDLE_S", "float", 300.0,
      "Sleep between background scrub passes; <= 0 disables the loop "
      "(manual POST /admin/ec/scrub still works).")
_knob("SW_EC_SCRUB_SLAB_BYTES", "int", 1 << 20,
      "Scrub verification slab size in bytes.")
_knob("SW_TIER_ENABLE", "bool", False,
      "Master-leased background tierer: demote sealed replicated "
      "volumes to erasure-coded warm storage while they keep serving "
      "reads.")
_knob("SW_TIER_INTERVAL_S", "float", 60.0,
      "Sleep between tierer scans for demotion candidates; <= 0 "
      "disables the loop even with SW_TIER_ENABLE on.")
_knob("SW_TIER_AGE_S", "float", 3600.0,
      "Seconds a sealed volume must go unmodified before it is a "
      "demotion candidate (the f4 age threshold).")
_knob("SW_TIER_CONCURRENCY", "int", 1,
      "Volume demotions the tierer runs at once.")
_knob("SW_TIER_RATE_MBPS", "float", 8.0,
      "Encode+spread bandwidth ceiling per demotion so foreground "
      "traffic keeps its tail; 0 disables pacing.")
_knob("SW_TIER_FULL_FRAC", "float", 0.95,
      "Fraction of the volume size limit at which a still-writable "
      "volume counts as sealed for demotion purposes.")
_knob("SW_EC_HEALTH_REF_MS", "float", 50.0,
      "Holder fetch latency that scores 0.5 on the health board.")
_knob("SW_EC_HEALTH_ROUTING", "bool", False,
      "Consult holder health scores when routing gathers and choosing "
      "rebuild survivors.")
_knob("SW_EC_JIT_CACHE_SIZE", "int", 64,
      "lru_cache maxsize for the jitted EC kernel factories; an evicted "
      "entry recompiles on next use (visible in ec_xla_jit_cache_total).")

# debug / tooling
_knob("SW_PROFILE_MAX_S", "float", 30.0,
      "Ceiling on POST /admin/profile?seconds=N sampling windows.")
_knob("SW_PLANE_STATS", "bool", True,
      "Native-plane telemetry (counters, latency histogram, slow ring); "
      "0 removes even the clock reads from the fast path.")
_knob("SW_PLANE_SLOW_US", "int", 10000,
      "Native-plane requests at or above this many microseconds enter "
      "the slow-request ring (GET /admin/plane/slow).")
_knob("SW_PLANE_CACHE_BYTES", "int", 32 << 20,
      "Byte budget of the native plane's reconstructed-slab cache; 0 "
      "disables the in-plane degraded fast path (lost-shard reads "
      "redirect to Python as before).")
_knob("SW_PLANE_FSYNC_MODE", "str", "off",
      "Write-durability mode for appends (plane AND Python fallback): "
      "off acks from the page cache, group amortizes one fdatasync per "
      "commit window over every rider before acking the batch, always "
      "fdatasyncs per append (the baseline group is measured against).")
_knob("SW_PLANE_FSYNC_BATCH_US", "int", 2000,
      "Group-commit window in microseconds: riders accumulate this "
      "long (or until SW_PLANE_FSYNC_MAX_PENDING) before the one "
      "covering fdatasync; p99 write latency absorbs at most one "
      "window.")
_knob("SW_PLANE_FSYNC_MAX_PENDING", "int", 512,
      "Riders that force a group commit before the window closes "
      "(bounds the pending-ack queue and the data at risk per batch).")
_knob("SW_LOCK_DEBUG", "bool", False,
      "Record the cross-thread lock-acquisition graph (util/locks.py) "
      "for deadlock detection; auto-on under pytest.")
_knob("SW_LOCK_GRAPH_DIR", "str", None,
      "Directory where instrumented processes dump their lock graph at "
      "exit for cross-process cycle checks.")

_UNSET = object()
_TRUTHY = ("1", "true", "yes", "on")


def _lookup(name: str, kind: str, fallback):
    knob = KNOBS.get(name)
    if knob is None:
        raise KeyError(
            f"env knob {name} is not registered in util/config.py — "
            f"declare it with _knob() (tools/analyze.py enforces this)")
    if knob.kind != kind:
        raise TypeError(
            f"env knob {name} is registered as {knob.kind}, read as "
            f"{kind}")
    raw = os.environ.get(name)
    default = knob.default if fallback is _UNSET else fallback
    return raw, default


def env_str(name: str, fallback=_UNSET) -> Optional[str]:
    raw, default = _lookup(name, "str", fallback)
    return raw if raw is not None else default


def env_int(name: str, fallback=_UNSET) -> Optional[int]:
    raw, default = _lookup(name, "int", fallback)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        return default


def env_float(name: str, fallback=_UNSET) -> Optional[float]:
    raw, default = _lookup(name, "float", fallback)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def env_bool(name: str, fallback=_UNSET) -> bool:
    raw, default = _lookup(name, "bool", fallback)
    if raw is None:
        return bool(default)
    return raw.strip().lower() in _TRUTHY


def retry_backoff_s(seconds: float) -> float:
    """Internal retry-backoff sleeps route through here so one knob
    (SW_RETRY_BACKOFF_SCALE) can compress them — the tier-1 conftest
    zeroes it; a congested deployment can stretch it."""
    return max(0.0, seconds * env_float("SW_RETRY_BACKOFF_SCALE"))


def env_is_set(name: str) -> bool:
    """Whether the (registered) knob is explicitly set in the
    environment — for override-must-fail-loudly semantics."""
    _lookup(name, KNOBS[name].kind if name in KNOBS else "str", _UNSET)
    return name in os.environ


def env_table() -> str:
    """The README env-knob table, generated from the registry (one
    source of truth; tools/analyze.py fails when the committed copy is
    stale)."""
    rows = ["| Variable | Type | Default | Description |",
            "| --- | --- | --- | --- |"]
    for name in sorted(KNOBS):
        k = KNOBS[name]
        rows.append(
            f"| `{k.name}` | {k.kind} | `{k.default_repr()}` | "
            f"{k.doc} |")
    return "\n".join(rows)


def _flatten(d: dict, prefix: str = "") -> Dict[str, object]:
    out: Dict[str, object] = {}
    for k, v in d.items():
        key = f"{prefix}{k}".lower()
        if isinstance(v, dict):
            out.update(_flatten(v, key + "."))
        else:
            out[key] = v
    return out


def find_config_file(name: str,
                     dirs: Optional[List[str]] = None) -> Optional[str]:
    for d in dirs or SEARCH_DIRS:
        for ext in (".toml", ".json"):
            p = os.path.join(d, name + ext)
            if os.path.isfile(p):
                return p
    return None


def _toml_module():
    """stdlib tomllib is 3.11+; on 3.10 fall back to a tomli copy —
    standalone if installed, else the one pip/setuptools vendor (same
    package tomllib was adopted from, identical load() API)."""
    try:
        import tomllib
        return tomllib
    except ImportError:
        pass
    try:
        import tomli
        return tomli
    except ImportError:
        from pip._vendor import tomli
        return tomli


def load_config(name: str, dirs: Optional[List[str]] = None,
                env: Optional[dict] = None) -> Dict[str, object]:
    """Flattened dotted-key config for <name>, {} when no file exists;
    WEED_* env vars always apply on top (a config can be pure env)."""
    cfg: Dict[str, object] = {}
    path = find_config_file(name, dirs)
    if path is not None:
        if path.endswith(".toml"):
            tomllib = _toml_module()
            with open(path, "rb") as f:
                cfg = _flatten(tomllib.load(f))
        else:
            with open(path) as f:
                cfg = _flatten(json.load(f))
    environ = os.environ if env is None else env
    for k, v in environ.items():
        if k.startswith(ENV_PREFIX):
            dotted = k[len(ENV_PREFIX):].lower().replace("_", ".")
            cfg[dotted] = v
    return cfg


def config_get(cfg: Dict[str, object], key: str, default=None):
    """Dotted lookup with underscore tolerance (env vars can't carry
    dots, so WEED_SECURITY_JWT_KEY and [security] jwt_key in TOML must
    land on the same value)."""
    key = key.lower()
    if key in cfg:
        return cfg[key]
    alt = key.replace("_", ".")
    return cfg.get(alt, default)
