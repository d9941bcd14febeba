#!/usr/bin/env python
"""Static + dynamic analysis suite for the seaweedfs_tpu codebase.

The Go reference leans on ``go vet``/``-race``; this is the Python
equivalent, run as a tier-1 test (tests/test_analyze.py) and usable
standalone:

    python tools/analyze.py --all           # every checker, exit != 0 on findings
    python tools/analyze.py --changed       # fast mode: files changed vs HEAD
    python tools/analyze.py --env-table     # print the README env table
    python tools/analyze.py --env-table --apply   # rewrite it in README.md
    python tools/analyze.py --lock-report [DIR]   # cycles in recorded lock graphs

Static checkers (AST, no imports of the checked modules needed):

- env-knobs: every ``SW_*`` environment read must go through the
  central registry (seaweedfs_tpu/util/config.py).  Flags raw
  ``os.environ``/``os.getenv`` reads, unregistered knob names, accessor
  kind mismatches (``env_int`` on a float knob), registered knobs that
  nothing reads, and a stale README env table.
- lock-discipline: blocking operations (sleep, http_util network
  calls, file opens, subprocess waits, device dispatch) lexically
  inside a ``with <lock>:`` body.  Deliberate cases are allowlisted
  below WITH a one-line justification that the lint echoes.
- backend-isolation: ``import jax`` only in ops/ and parallel/.
  Everything else must stay importable on a box with no JAX at all.
- thread-hygiene: every thread is named (the lock-graph and the fleet
  traces key on thread names), non-daemon threads must be joined
  somewhere in their file, and no bare ``except:``.

The metrics lint is the ``metrics`` sub-checker (``run_metrics_checks``).
The dynamic half lives in seaweedfs_tpu/util/locks.py: under
SW_LOCK_DEBUG=1 every factory lock records the cross-thread
acquisition graph; ``--lock-report`` (and the conftest session hook)
fail on any cycle not covered by LOCK_ORDER_ALLOWED.
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# ---------------------------------------------------------------------------
# scopes + allowlists
# ---------------------------------------------------------------------------

PACKAGE = "seaweedfs_tpu"
# files whose SW_* handling IS the registry / its debug plumbing
ENV_EXEMPT_FILES = {
    "seaweedfs_tpu/util/config.py",
}
# jax imports allowed wholesale in these trees
JAX_ALLOWED_DIRS = ("seaweedfs_tpu/ops/", "seaweedfs_tpu/parallel/")

# (checker-scoped) allowlists.  Every entry carries a one-line
# justification that the lint echoes, so a reader of the CI log sees
# WHY the exception exists without chasing the file.
JAX_IMPORT_ALLOWED = {
    "seaweedfs_tpu/util/jax_platform.py":
        "start-up rules for the device modules: compile-cache config "
        "and the tpu/mesh platform check, imported lazily",
    "seaweedfs_tpu/util/profiling.py":
        "jax.profiler trace capture is this module's entire purpose; "
        "import is lazy inside the capture call",
}

# (path, category) -> justification.  Categories: sleep, net, file,
# subprocess, device, wait.
LOCK_BLOCKING_ALLOWED: Dict[Tuple[str, str], str] = {
    ("seaweedfs_tpu/storage/volume.py", "file"):
        "volume.lock exists to serialize needle file I/O; the append "
        "offset and the write must be one atomic step",
    ("seaweedfs_tpu/storage/volume_tier.py", "file"):
        "tier pull swaps the live .dat under volume.lock; readers "
        "must never see the half-swapped file",
}

# Deliberately ordered same-or-cross-class lock nestings observed in the
# dynamic graph.  (holder, acquired) -> justification.  Keep EMPTY until
# a real, argued-for ordering shows up: every entry here is an edge the
# cycle detector ignores.
LOCK_ORDER_ALLOWED: Dict[Tuple[str, str], str] = {}
LOCK_ORDER_ALLOWED_EDGES = set(LOCK_ORDER_ALLOWED)

# (path, knob) -> justification for raw env access the registry lint
# would otherwise flag.
ENV_RAW_ALLOWED: Dict[Tuple[str, str], str] = {}

# path -> justification for bare jax.jit( calls outside the
# device_stats.wrap instrumentation.  Every production jit entry point
# must route through ops/device_stats.wrap so compile/recompile
# accounting sees it; only code whose POINT is raw compile behavior
# belongs here.
JIT_ALLOWED: Dict[str, str] = {
    "tools/kernel_exp.py":
        "offline single-kernel MXU experiment: chained-slope timing "
        "needs the raw executable, instrumentation would skew the "
        "slope it measures",
}

README_BEGIN = "<!-- env-table:begin (generated by tools/analyze.py --env-table) -->"
README_END = "<!-- env-table:end -->"

ACCESSOR_KINDS = {
    "env_str": "str", "env_int": "int",
    "env_float": "float", "env_bool": "bool",
}

HTTP_BLOCKING_FUNCS = {
    "get_json", "post_json", "http_call", "http_download",
    "http_get_with_headers", "post_chunked", "post_multipart",
    "post_multipart_file", "urlopen",
}
SUBPROCESS_BLOCKING = {"check_call", "check_output", "run", "communicate"}
DEVICE_BLOCKING = {"block_until_ready", "device_put"}


def rel(path: str) -> str:
    return os.path.relpath(os.path.abspath(path), REPO_ROOT)


# ---------------------------------------------------------------------------
# per-file AST checkers
# ---------------------------------------------------------------------------

def _name_of(node: ast.AST) -> str:
    """Trailing identifier of a Name/Attribute chain ('' otherwise)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted text of a Name/Attribute chain."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_environ(node: ast.AST) -> bool:
    d = _dotted(node)
    return d.endswith("environ")


def _str_const(node: ast.AST,
               consts: Dict[str, str]) -> Optional[str]:
    """Literal string value of ``node`` — directly, or via a
    module-level ``NAME = "SW_..."`` constant."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def _module_str_consts(tree: ast.Module) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            out[node.targets[0].id] = node.value.value
    return out


def _looks_like_lock(expr: ast.AST) -> Optional[str]:
    name = _name_of(expr)
    low = name.lower()
    if "lock" in low or low in ("_mu", "mutex"):
        return name
    return None


class _FileReport:
    def __init__(self, path: str):
        self.path = path
        self.problems: List[str] = []
        self.allowed: List[str] = []
        # allowlist entries that actually fired, for staleness checks:
        # ("env"|"lock"|"jax", key-into-that-dict)
        self.allowed_keys: set = set()
        # knob name -> accessor kind ("" for env_is_set)
        self.knob_reads: List[Tuple[str, str, int]] = []

    def problem(self, line: int, checker: str, msg: str):
        self.problems.append(f"{self.path}:{line}: {checker}: {msg}")

    def note_allowed(self, line: int, checker: str, msg: str, why: str):
        self.allowed.append(
            f"{self.path}:{line}: {checker}: allowed: {msg} — {why}")


def _check_env_reads(tree: ast.Module, rep: _FileReport,
                     consts: Dict[str, str]):
    """Raw SW_* environment access + registry accessor calls.

    The registry itself (ENV_EXEMPT_FILES) is excused from the
    raw-access rules but its literal accessor calls still count as
    knob reads (retry_backoff_s reads SW_RETRY_BACKOFF_SCALE there)."""
    exempt = rep.path in ENV_EXEMPT_FILES

    def raw_read(line: int, knob: str, how: str):
        if exempt:
            return
        why = ENV_RAW_ALLOWED.get((rep.path, knob))
        if why is not None:
            rep.allowed_keys.add(("env", (rep.path, knob)))
            rep.note_allowed(line, "env-knobs",
                             f"raw {how} of {knob}", why)
        else:
            rep.problem(
                line, "env-knobs",
                f"raw {how} of {knob}: route it through "
                f"util/config.py (env_str/env_int/env_float/env_bool)")

    for node in ast.walk(tree):
        # os.environ.get("SW_X", ...) / os.getenv("SW_X")
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "get" \
                    and _is_environ(fn.value) and node.args:
                v = _str_const(node.args[0], consts)
                if v and v.startswith("SW_"):
                    raw_read(node.lineno, v, "os.environ.get")
            elif _name_of(fn) == "getenv" and node.args:
                v = _str_const(node.args[0], consts)
                if v and v.startswith("SW_"):
                    raw_read(node.lineno, v, "os.getenv")
            elif _name_of(fn) in ACCESSOR_KINDS or \
                    _name_of(fn) == "env_is_set":
                acc = _name_of(fn)
                if node.args:
                    v = _str_const(node.args[0], consts)
                    if v is None:
                        if not exempt:
                            rep.problem(
                                node.lineno, "env-knobs",
                                f"{acc}() with a non-literal knob "
                                f"name: the registry lint cannot "
                                f"see it")
                    else:
                        rep.knob_reads.append((v, acc, node.lineno))
        # os.environ["SW_X"] reads (writes/dels are legal: steering
        # subprocesses through inheritance)
        elif isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and _is_environ(node.value):
            v = _str_const(node.slice, consts)
            if v and v.startswith("SW_"):
                raw_read(node.lineno, v, "os.environ[] read")
        # "SW_X" in os.environ
        elif isinstance(node, ast.Compare) and node.ops and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
                _is_environ(node.comparators[0]):
            v = _str_const(node.left, consts)
            if v and v.startswith("SW_"):
                raw_read(node.lineno, v,
                         "membership test (use config.env_is_set)")


def _check_lock_discipline(tree: ast.Module, rep: _FileReport):
    """Blocking calls lexically inside ``with <lock>:`` bodies."""
    if not rep.path.startswith(PACKAGE + "/"):
        return

    def classify(call: ast.Call) -> Optional[Tuple[str, str]]:
        fn = call.func
        name = _name_of(fn)
        if name == "sleep":
            return "sleep", "time.sleep"
        if name in HTTP_BLOCKING_FUNCS:
            return "net", f"network call {name}()"
        if name == "open" and isinstance(fn, ast.Name):
            return "file", "open()"
        if name in SUBPROCESS_BLOCKING and isinstance(fn, ast.Attribute) \
                and _name_of(fn.value) in ("subprocess", "proc", "p"):
            return "subprocess", f"subprocess {name}()"
        if name in DEVICE_BLOCKING:
            return "device", f"device dispatch {name}()"
        if name == "wait" and isinstance(fn, ast.Attribute):
            recv = _name_of(fn.value).lower()
            if "stop" in recv or "event" in recv or "ready" in recv:
                return "wait", f"{_dotted(fn)}() event wait"
        if name == "join" and isinstance(fn, ast.Attribute):
            recv = _name_of(fn.value).lower()
            if "thread" in recv:
                return "wait", f"thread {_dotted(fn)}()"
        return None

    def scan_body(body: List[ast.stmt], lock_name: str):
        stack: List[ast.AST] = list(body)
        while stack:
            node = stack.pop()
            # a nested def/lambda runs later, outside the lock — do
            # not descend into it
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef, ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(node))
            if isinstance(node, ast.Call):
                hit = classify(node)
                if hit is not None:
                    category, desc = hit
                    why = LOCK_BLOCKING_ALLOWED.get((rep.path, category))
                    msg = (f"{desc} while holding {lock_name!r}")
                    if why is not None:
                        rep.allowed_keys.add(
                            ("lock", (rep.path, category)))
                        rep.note_allowed(node.lineno, "lock-discipline",
                                         msg, why)
                    else:
                        rep.problem(
                            node.lineno, "lock-discipline",
                            f"{msg}: move it outside the critical "
                            f"section or allowlist it in "
                            f"tools/analyze.py with a justification")

    # new locks must come from the factory or the dynamic recorder
    # is blind to them
    if rep.path != "seaweedfs_tpu/util/locks.py":
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("Lock", "RLock") and \
                    _name_of(node.func.value) == "threading":
                rep.problem(
                    node.lineno, "lock-discipline",
                    f"bare threading.{node.func.attr}(): use "
                    f"util.locks.make_{'r' if node.func.attr == 'RLock' else ''}lock"
                    f"('<class name>') so the lock-order recorder "
                    f"sees it")

    class W(ast.NodeVisitor):
        def visit_With(self, node: ast.With):
            for item in node.items:
                lock = _looks_like_lock(item.context_expr)
                if lock:
                    scan_body(node.body, lock)
                    break
            self.generic_visit(node)

        # nested defs inside a with-body were skipped by scan_body but
        # must still be visited for their OWN with-statements
        def visit_FunctionDef(self, node):  # noqa: N802
            self.generic_visit(node)

    W().visit(tree)


# partitioning primitives: modules whose import marks a file as doing
# SPMD sharding, which stays confined to ops/ and parallel/ even for
# files allowlisted for generic jax imports (a profiling shim may
# import jax; it may NOT start partitioning programs)
SHARDING_MODULES = ("jax.sharding", "jax.experimental.pjit",
                    "jax.experimental.shard_map")
SHARDING_NAMES = {"sharding", "pjit", "shard_map"}


def _check_backend_isolation(tree: ast.Module, rep: _FileReport):
    if not rep.path.startswith(PACKAGE + "/"):
        return
    if any(rep.path.startswith(d) for d in JAX_ALLOWED_DIRS):
        return

    def flag(line: int, what: str):
        why = JAX_IMPORT_ALLOWED.get(rep.path)
        if why is not None:
            rep.allowed_keys.add(("jax", rep.path))
            rep.note_allowed(line, "backend-isolation", what, why)
        else:
            rep.problem(
                line, "backend-isolation",
                f"{what}: jax may only be imported under ops/ and "
                f"parallel/ — gate device code behind those modules")

    def flag_sharding(line: int, what: str):
        # no allowlist escape: partitioning primitives are confined
        # unconditionally
        rep.problem(
            line, "backend-isolation",
            f"{what}: partitioning primitives (jax.sharding / pjit / "
            f"shard_map) stay confined to ops/ and parallel/ — even "
            f"modules allowlisted for generic jax imports may not shard")

    def is_sharding_module(mod: str) -> bool:
        return any(mod == m or mod.startswith(m + ".")
                   for m in SHARDING_MODULES)

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if is_sharding_module(alias.name):
                    flag_sharding(node.lineno, f"import {alias.name}")
                    continue
                root = alias.name.split(".")[0]
                if root in ("jax", "jaxlib"):
                    flag(node.lineno, f"import {alias.name}")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            root = mod.split(".")[0]
            if root not in ("jax", "jaxlib"):
                continue
            pulled = {a.name for a in node.names}
            if is_sharding_module(mod) or \
                    (mod in ("jax", "jax.experimental") and
                     pulled & SHARDING_NAMES):
                flag_sharding(node.lineno,
                              f"from {mod} import "
                              f"{', '.join(sorted(pulled))}")
            else:
                flag(node.lineno, f"from {mod} import ...")


def _check_thread_hygiene(tree: ast.Module, rep: _FileReport, src: str):
    if not rep.path.startswith(PACKAGE + "/"):
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            rep.problem(node.lineno, "thread-hygiene",
                        "bare 'except:': name the exception "
                        "(at minimum 'except BaseException:')")
        if isinstance(node, ast.Call) and \
                _name_of(node.func) == "Thread":
            kw = {k.arg: k.value for k in node.keywords}
            if "name" not in kw and "target" in kw:
                rep.problem(
                    node.lineno, "thread-hygiene",
                    "unnamed thread: pass name= (lock-graph edges and "
                    "trace spans key on thread names)")
            daemon = kw.get("daemon")
            is_daemon = isinstance(daemon, ast.Constant) and \
                daemon.value is True
            if "target" in kw and not is_daemon and ".join(" not in src:
                rep.problem(
                    node.lineno, "thread-hygiene",
                    "non-daemon thread with no .join() anywhere in "
                    "this file: it can outlive shutdown")


def _check_jit_discipline(tree: ast.Module, rep: _FileReport):
    """Bare ``jax.jit(`` calls outside ``device_stats.wrap(...)``.

    An uninstrumented jit is invisible to the compile/recompile plane:
    its warmup wall folds into the first dispatch and a broken width
    bucket never trips the sentinel. A jit call is fine when it is a
    direct argument of a wrap(...) call (the idiom every entry point
    uses); anything else needs a justified JIT_ALLOWED entry."""
    if rep.path == "seaweedfs_tpu/ops/device_stats.py":
        return  # the wrapper implementation itself
    wrapped_nodes = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _name_of(node.func) == "wrap":
            for arg in node.args:
                for sub in ast.walk(arg):
                    wrapped_nodes.add(id(sub))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if not (d == "jax.jit" or d.endswith(".jax.jit")
                or (d == "jit" and _name_of(node.func) == "jit")):
            continue
        if id(node) in wrapped_nodes:
            continue
        why = JIT_ALLOWED.get(rep.path)
        if why is not None:
            rep.allowed_keys.add(("jit", rep.path))
            rep.note_allowed(node.lineno, "jit-discipline",
                             "bare jax.jit( call", why)
        else:
            rep.problem(
                node.lineno, "jit-discipline",
                "bare jax.jit( call: route it through "
                "ops/device_stats.wrap(jax.jit(...), \"<entry>\") so "
                "compile/recompile accounting sees it, or add a "
                "justified JIT_ALLOWED entry")


def analyze_source(src: str, path: str) -> _FileReport:
    """Run every per-file static checker over one source blob.

    ``path`` is repo-relative and decides which checkers apply (tests
    feed synthetic paths like 'seaweedfs_tpu/fake.py').  Returns the
    report: .problems, .allowed (echoed justifications), .knob_reads.
    """
    rep = _FileReport(path)
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        rep.problem(e.lineno or 0, "parse", f"syntax error: {e.msg}")
        return rep
    consts = _module_str_consts(tree)
    _check_env_reads(tree, rep, consts)
    _check_lock_discipline(tree, rep)
    _check_backend_isolation(tree, rep)
    _check_thread_hygiene(tree, rep, src)
    _check_jit_discipline(tree, rep)
    return rep


# ---------------------------------------------------------------------------
# cross-file checks
# ---------------------------------------------------------------------------

def iter_scope_files() -> List[str]:
    out = []
    for base, _dirs, names in os.walk(os.path.join(REPO_ROOT, PACKAGE)):
        for n in sorted(names):
            if n.endswith(".py"):
                out.append(rel(os.path.join(base, n)))
    for n in sorted(os.listdir(os.path.join(REPO_ROOT, "tools"))):
        if n.endswith(".py"):
            out.append(f"tools/{n}")
    return out


def check_registry_coverage(all_reads: List[Tuple[str, str, int, str]]
                            ) -> List[str]:
    """Unregistered reads, kind mismatches, registered-but-unread."""
    from seaweedfs_tpu.util import config

    problems = []
    read_names = set()
    for name, accessor, line, path in all_reads:
        knob = config.KNOBS.get(name)
        if knob is None:
            problems.append(
                f"{path}:{line}: env-knobs: {accessor}({name!r}) but "
                f"{name} is not registered in util/config.py KNOBS")
            continue
        read_names.add(name)
        want = ACCESSOR_KINDS.get(accessor)
        if want is not None and knob.kind != want:
            problems.append(
                f"{path}:{line}: env-knobs: {accessor}({name!r}) but "
                f"{name} is registered as kind {knob.kind!r}")
    for name in sorted(set(config.KNOBS) - read_names):
        problems.append(
            f"seaweedfs_tpu/util/config.py: env-knobs: {name} is "
            f"registered but nothing reads it — drop the registration "
            f"or wire the knob up")
    return problems


def check_readme_table() -> List[str]:
    from seaweedfs_tpu.util import config

    readme = os.path.join(REPO_ROOT, "README.md")
    with open(readme, encoding="utf-8") as f:
        text = f.read()
    if README_BEGIN not in text or README_END not in text:
        return [f"README.md: env-knobs: missing the generated env "
                f"table markers ({README_BEGIN!r} ... {README_END!r}); "
                f"run tools/analyze.py --env-table --apply"]
    current = text.split(README_BEGIN, 1)[1].split(README_END, 1)[0]
    want = "\n" + config.env_table() + "\n"
    if current != want:
        return ["README.md: env-knobs: env table is stale — run "
                "tools/analyze.py --env-table --apply"]
    return []


def apply_readme_table() -> None:
    from seaweedfs_tpu.util import config

    readme = os.path.join(REPO_ROOT, "README.md")
    with open(readme, encoding="utf-8") as f:
        text = f.read()
    if README_BEGIN not in text or README_END not in text:
        raise SystemExit(
            f"README.md has no {README_BEGIN!r} ... {README_END!r} "
            f"markers to rewrite between")
    head = text.split(README_BEGIN, 1)[0]
    tail = text.split(README_END, 1)[1]
    with open(readme, "w", encoding="utf-8") as f:
        f.write(head + README_BEGIN + "\n" + config.env_table() +
                "\n" + README_END + tail)


# ---------------------------------------------------------------------------
# metrics sub-checker
# ---------------------------------------------------------------------------

# https://prometheus.io/docs/concepts/data_model/
METRIC_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' -?[0-9.eE+-]+(e[+-]?[0-9]+)?$|'
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \+?-?Inf$|'
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? NaN$')
# OpenMetrics-style exemplar tail appended to a sample line:
# ` # {trace_id="..."} <value> <timestamp>`
EXEMPLAR_RE = re.compile(
    r'^ # \{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\}'
    r' -?[0-9.eE+-]+ [0-9.eE+-]+$')
RESERVED_SUFFIXES = ("_bucket", "_sum", "_count")

# families the observability contract depends on: dashboards/bench
# assertions reference them by name, so renaming or dropping one must
# fail the lint, not silently flatline a panel
REQUIRED_FAMILIES = {
    "master": (
        "SeaweedFS_master_cluster_scrape_total",
        "SeaweedFS_master_cluster_scrape_seconds",
        "SeaweedFS_master_cluster_node_up",
        "SeaweedFS_master_cluster_scraped_nodes",
        "SeaweedFS_master_repair_queue_incidents_total",
        "SeaweedFS_master_repair_queue_open",
        "SeaweedFS_master_repair_queue_unattributed",
        "SeaweedFS_master_repair_queue_ttr_seconds",
        "SeaweedFS_master_tier_demotions_total",
        "SeaweedFS_master_tier_demotion_seconds_total",
        "SeaweedFS_master_tier_demoted_bytes_total",
        "SeaweedFS_master_tier_mbps",
        "SeaweedFS_master_tier_volumes",
    ),
    "volume": (
        "SeaweedFS_volumeServer_ec_holder_health",
        "SeaweedFS_volumeServer_ec_holder_latency_ewma_ms",
        "SeaweedFS_volumeServer_ec_holder_events_total",
        "SeaweedFS_volumeServer_ec_phase_seconds_total",
        "SeaweedFS_volumeServer_ec_gather_total",
        "SeaweedFS_volumeServer_ec_gather_seconds_total",
        "SeaweedFS_volumeServer_ec_gather_mbps",
        "SeaweedFS_volumeServer_ec_overlap_frac",
        "SeaweedFS_volumeServer_ec_mesh_dispatches_total",
        "SeaweedFS_volumeServer_ec_mesh_dispatch_width_devices",
        "SeaweedFS_volumeServer_ec_mesh_device_bytes_total",
        "SeaweedFS_volumeServer_ec_mesh_device_byte_share",
        "SeaweedFS_volumeServer_http_pool_churn_total",
        "SeaweedFS_volumeServer_ec_spread_total",
        "SeaweedFS_volumeServer_ec_spread_seconds_total",
        "SeaweedFS_volumeServer_ec_spread_mbps",
        "SeaweedFS_volumeServer_ec_encode_overlap_frac",
        "SeaweedFS_volumeServer_ec_repair_total",
        "SeaweedFS_volumeServer_ec_repair_seconds_total",
        "SeaweedFS_volumeServer_ec_repair_bytes_frac",
        "SeaweedFS_volumeServer_ec_repair_symbol_bits_total",
        "SeaweedFS_volumeServer_ec_piggyback_total",
        "SeaweedFS_volumeServer_ec_piggyback_bytes_frac",
        "SeaweedFS_volumeServer_ec_plan_cache_events_total",
        "SeaweedFS_volumeServer_ec_plan_cache_entries",
        "SeaweedFS_volumeServer_ec_degraded_total",
        "SeaweedFS_volumeServer_ec_degraded_read_seconds",
        "SeaweedFS_volumeServer_ec_degraded_batch_width",
        "SeaweedFS_volumeServer_ec_degraded_cache_hit_ratio",
        "SeaweedFS_volumeServer_ec_degraded_readahead_hit_ratio",
        "SeaweedFS_volumeServer_ec_transport_total",
        "SeaweedFS_volumeServer_ec_transport_seconds_total",
        "SeaweedFS_volumeServer_ec_transport_window_stripes",
        "SeaweedFS_volumeServer_ec_transport_peak_buffer_bytes",
        "SeaweedFS_volumeServer_ec_scrub_total",
        "SeaweedFS_volumeServer_ec_scrub_mbps",
        "SeaweedFS_volumeServer_ec_scrub_last_pass_unixtime",
        "SeaweedFS_volumeServer_plane_request_total",
        "SeaweedFS_volumeServer_plane_bytes_total",
        "SeaweedFS_volumeServer_plane_events_total",
        "SeaweedFS_volumeServer_plane_request_seconds",
        "SeaweedFS_volumeServer_plane_slow_ring_depth",
        "SeaweedFS_volumeServer_plane_build_failed",
        "SeaweedFS_volumeServer_plane_degraded_total",
        "SeaweedFS_volumeServer_plane_cache_events_total",
        "SeaweedFS_volumeServer_plane_cache_put_bytes_total",
        "SeaweedFS_volumeServer_plane_cache_entries",
        "SeaweedFS_volumeServer_plane_cache_bytes",
        "SeaweedFS_volumeServer_plane_cache_max_bytes",
        "SeaweedFS_volumeServer_plane_fsync_batches_total",
        "SeaweedFS_volumeServer_plane_fsync_riders_total",
        "SeaweedFS_volumeServer_plane_fsync_failures_total",
        "SeaweedFS_volumeServer_plane_fsync_seconds",
        "SeaweedFS_volumeServer_plane_fsync_pending",
        "SeaweedFS_volumeServer_ec_xla_compiles_total",
        "SeaweedFS_volumeServer_ec_xla_compile_seconds_total",
        "SeaweedFS_volumeServer_ec_xla_recompiles_total",
        "SeaweedFS_volumeServer_ec_xla_recompile_sentinel",
        "SeaweedFS_volumeServer_ec_xla_dispatches_total",
        "SeaweedFS_volumeServer_ec_xla_jit_cache_total",
        "SeaweedFS_volumeServer_ec_xla_jit_cache_entries",
        "SeaweedFS_volumeServer_ec_xla_device_memory_bytes",
        "SeaweedFS_volumeServer_ec_const_cache_events_total",
        "SeaweedFS_volumeServer_ec_const_cache_entries",
        "SeaweedFS_volumeServer_ec_const_cache_bytes",
    ),
}

EC_ROUTE_RE = re.compile(
    r'router\.add\(\s*"(?:GET|POST|\*)"\s*,\s*\n?\s*"(/admin/ec/[^"]+)"')


def check_route_coverage(repo_root: str) -> list:
    """Every EC admin route / coverage token must appear in tests."""
    vs_py = os.path.join(repo_root, "seaweedfs_tpu", "server",
                         "volume_server.py")
    with open(vs_py, encoding="utf-8") as f:
        routes = EC_ROUTE_RE.findall(f.read())
    if not routes:
        return [f"route-coverage: no /admin/ec/ routes found in {vs_py}"]
    tests_dir = os.path.join(repo_root, "tests")
    corpus = []
    for name in sorted(os.listdir(tests_dir)):
        if name.endswith(".py"):
            with open(os.path.join(tests_dir, name),
                      encoding="utf-8") as f:
                corpus.append(f.read())
    blob = "\n".join(corpus)
    problems = [f"route-coverage: {route} is registered in "
                f"volume_server.py but no test references it"
                for route in routes if route not in blob]
    # the repair-read and plane-read routes carry mini-protocols
    # (ranged projected/plane reads, 416 beyond-shard, 400 bad
    # masks/geometry/range, 404 wrong shard)
    for proto_route in ("/admin/ec/shard_repair_read",
                        "/admin/ec/shard_plane_read"):
        if proto_route not in routes or proto_route not in blob:
            continue
        route_files = [c for c in corpus if proto_route in c]
        if not any("offset=" in c for c in route_files):
            problems.append(
                f"route-coverage: no test exercises {proto_route} "
                f"with a ranged (offset=) request")
        for status in ("416", "404", "400"):
            if not any(status in c for c in route_files):
                problems.append(
                    f"route-coverage: no test covering {proto_route} "
                    f"asserts a {status} error response")
    degraded_py = os.path.join(repo_root, "seaweedfs_tpu", "ec",
                               "degraded.py")
    if os.path.exists(degraded_py):
        for token, what in (
                ("DegradedReadEngine", "the engine"),
                ("_reconstruct_shard_range", "the serving fallthrough"),
                ("ec_degraded_", "the ec_degraded_* metric families")):
            if token not in blob:
                problems.append(
                    f"degraded-coverage: no test under tests/ "
                    f"references {token} ({what})")
    scrub_py = os.path.join(repo_root, "seaweedfs_tpu", "ec", "scrub.py")
    if os.path.exists(scrub_py):
        for token, what in (
                ("ScrubEngine", "the scrub engine"),
                ("ec_scrub_", "the ec_scrub_* metric families"),
                ("RepairQueue", "the master repair queue"),
                ("repair_queue_", "the repair_queue_* metric families")):
            if token not in blob:
                problems.append(
                    f"scrub-coverage: no test under tests/ "
                    f"references {token} ({what})")
    master_py = os.path.join(repo_root, "seaweedfs_tpu", "server",
                             "master.py")
    with open(master_py, encoding="utf-8") as f:
        master_src = f.read()
    with open(vs_py, encoding="utf-8") as f:
        volume_src = f.read()
    filer_py = os.path.join(repo_root, "seaweedfs_tpu", "server",
                            "filer_server.py")
    with open(filer_py, encoding="utf-8") as f:
        filer_src = f.read()
    for route, src, src_name in (
            ("/cluster/metrics", master_src, "master.py"),
            ("/cluster/health", master_src, "master.py"),
            ("/cluster/repairs", master_src, "master.py"),
            ("/cluster/tiering", master_src, "master.py"),
            ("/admin/traces/export", master_src, "master.py"),
            ("/admin/plane/slow", volume_src, "volume_server.py"),
            ("/admin/plane/cache", volume_src, "volume_server.py"),
            ("/admin/plane/durability", volume_src, "volume_server.py"),
            ("/admin/devices", volume_src, "volume_server.py"),
            ("/admin/profile", master_src, "master.py"),
            ("/admin/profile", volume_src, "volume_server.py"),
            ("/admin/profile", filer_src, "filer_server.py")):
        if f'"{route}"' not in src:
            problems.append(
                f"route-coverage: {route} is not registered in "
                f"{src_name}")
        elif route not in blob:
            problems.append(
                f"route-coverage: {route} is registered in {src_name} "
                f"but no test references it")
    return problems


PLANE_ABI_SYMBOL_RE = re.compile(
    r'^[A-Za-z_][A-Za-z0-9_* ]*?\b(swhp_[a-z0-9_]+)\s*\(', re.M)


def check_plane_abi_coverage(repo_root: str) -> list:
    """Every symbol the native plane exports must have a ctypes binding
    in native_plane.py AND be named by at least one test. An exported-
    but-unbound symbol is dead ABI surface; an untested one can change
    signature silently and only crash at runtime in production."""
    cc = os.path.join(repo_root, "seaweedfs_tpu", "server", "native",
                      "http_plane.cc")
    with open(cc, encoding="utf-8") as f:
        src = f.read()
    marker = 'extern "C" {'
    if marker not in src:
        return [f"plane-abi: no extern \"C\" block in {cc}"]
    exported = sorted(set(
        PLANE_ABI_SYMBOL_RE.findall(src[src.index(marker):])))
    if not exported:
        return [f"plane-abi: no swhp_* exports found in {cc}"]
    np_py = os.path.join(repo_root, "seaweedfs_tpu", "server",
                         "native_plane.py")
    with open(np_py, encoding="utf-8") as f:
        bindings = f.read()
    tests_dir = os.path.join(repo_root, "tests")
    blob = []
    for name in sorted(os.listdir(tests_dir)):
        if name.endswith(".py"):
            with open(os.path.join(tests_dir, name),
                      encoding="utf-8") as f:
                blob.append(f.read())
    test_blob = "\n".join(blob)
    problems = []
    for sym in exported:
        if f".{sym}" not in bindings:
            problems.append(
                f"plane-abi: exported symbol {sym} has no ctypes "
                f"binding in native_plane.py")
        if sym not in test_blob:
            problems.append(
                f"plane-abi: exported symbol {sym} is named by no test "
                f"under tests/")
    return problems


def check_required(role: str, registry) -> list:
    names = {m.name for m in registry._metrics}
    return [f"{role}: required metric family missing: {want}"
            for want in REQUIRED_FAMILIES.get(role, ())
            if want not in names]


def check_registry(role: str, registry) -> list:
    problems = []
    seen = {}
    for m in registry._metrics:
        where = f"{role}:{m.name}"
        if not METRIC_NAME_RE.match(m.name):
            problems.append(f"{where}: invalid metric name")
        if m.name.startswith("__"):
            problems.append(f"{where}: reserved __ metric prefix")
        if m.kind == "counter" and not m.name.endswith("_total"):
            problems.append(f"{where}: counter must end in _total")
        if m.kind == "histogram" and \
                m.name.endswith(RESERVED_SUFFIXES):
            problems.append(
                f"{where}: histogram base name ends in a reserved "
                f"series suffix")
        prev = seen.get(m.name)
        if prev is not None and prev != (m.kind, m.label_names):
            problems.append(
                f"{where}: duplicate registration with different "
                f"kind/labels {prev} vs {(m.kind, m.label_names)}")
        seen[m.name] = (m.kind, m.label_names)
        for ln in m.label_names:
            if not LABEL_NAME_RE.match(ln):
                problems.append(f"{where}: invalid label name {ln!r}")
            if ln.startswith("__"):
                problems.append(f"{where}: reserved __ label {ln!r}")
            if m.kind == "histogram" and ln == "le":
                problems.append(
                    f"{where}: 'le' is reserved for histogram buckets")
    return problems


def check_render(role: str, registry) -> list:
    problems = []
    for i, line in enumerate(registry.render().splitlines()):
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        sample = line
        cut = line.find(" # {")
        if cut != -1:
            sample, tail = line[:cut], line[cut:]
            if not EXEMPLAR_RE.match(tail):
                problems.append(
                    f"{role} render line {i + 1}: malformed exemplar "
                    f"tail: {tail!r}")
        if not SAMPLE_RE.match(sample):
            problems.append(
                f"{role} render line {i + 1}: unparseable exposition "
                f"text: {line!r}")
    return problems


def run_metrics_checks() -> List[str]:
    from seaweedfs_tpu.stats import metrics

    registries = {
        "master": metrics.MASTER_GATHER,
        "volume": metrics.VOLUME_SERVER_GATHER,
        "filer": metrics.FILER_GATHER,
    }
    problems = []
    for role, reg in registries.items():
        problems += check_registry(role, reg)
        problems += check_render(role, reg)
        problems += check_required(role, reg)
    problems += check_route_coverage(REPO_ROOT)
    problems += check_plane_abi_coverage(REPO_ROOT)
    return [f"metrics: {p}" for p in problems]


# ---------------------------------------------------------------------------
# dynamic half: lock-order report
# ---------------------------------------------------------------------------

def lock_report(graph_dir: Optional[str]) -> Tuple[List[str], int]:
    """Cycles in the merged recorded lock graphs; returns (problems,
    edge_count)."""
    from seaweedfs_tpu.util import config, locks

    edges = locks.load_graph_dir(
        graph_dir or config.env_str("SW_LOCK_GRAPH_DIR") or "")
    rec = locks.LockGraphRecorder()
    cycles = rec.cycles(extra_edges=edges,
                        allowed=LOCK_ORDER_ALLOWED_EDGES)
    problems = []
    for cyc in cycles:
        problems.append(
            "lock-order: potential ABBA deadlock: " +
            " -> ".join(list(cyc) + [cyc[0]]))
    for (a, b), why in sorted(LOCK_ORDER_ALLOWED.items()):
        print(f"lock-order: allowed edge {a} -> {b} — {why}")
    return problems, len(edges)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def changed_files() -> List[str]:
    try:
        out = subprocess.check_output(
            ["git", "diff", "--name-only", "HEAD"], cwd=REPO_ROOT,
            text=True)
        untracked = subprocess.check_output(
            ["git", "ls-files", "--others", "--exclude-standard"],
            cwd=REPO_ROOT, text=True)
    except (OSError, subprocess.CalledProcessError):
        return iter_scope_files()
    scope = set(iter_scope_files())
    picked = [p for p in (out + untracked).splitlines() if p in scope]
    return picked


def run_static(files: List[str], full_scope: bool = True
               ) -> Tuple[List[str], List[str]]:
    problems: List[str] = []
    allowed: List[str] = []
    fired: set = set()
    all_reads: List[Tuple[str, str, int, str]] = []
    for path in files:
        full = os.path.join(REPO_ROOT, path)
        if not os.path.exists(full):
            continue
        with open(full, encoding="utf-8") as f:
            src = f.read()
        rep = analyze_source(src, path)
        problems += rep.problems
        allowed += rep.allowed
        fired |= rep.allowed_keys
        all_reads += [(name, acc, line, path)
                      for (name, acc, line) in rep.knob_reads]
    if full_scope:
        # cross-file checks only make sense over the whole scope
        problems += check_registry_coverage(all_reads)
        for key in sorted(ENV_RAW_ALLOWED):
            if ("env", key) not in fired:
                problems.append(
                    f"tools/analyze.py: allowlist: stale ENV_RAW_ALLOWED "
                    f"entry {key!r} — nothing matches it any more")
        for key in sorted(LOCK_BLOCKING_ALLOWED):
            if ("lock", key) not in fired:
                problems.append(
                    f"tools/analyze.py: allowlist: stale "
                    f"LOCK_BLOCKING_ALLOWED entry {key!r} — nothing "
                    f"matches it any more")
        for key in sorted(JAX_IMPORT_ALLOWED):
            if ("jax", key) not in fired:
                problems.append(
                    f"tools/analyze.py: allowlist: stale "
                    f"JAX_IMPORT_ALLOWED entry {key!r} — nothing "
                    f"matches it any more")
        for key in sorted(JIT_ALLOWED):
            if ("jit", key) not in fired:
                problems.append(
                    f"tools/analyze.py: allowlist: stale JIT_ALLOWED "
                    f"entry {key!r} — nothing matches it any more")
    return problems, allowed


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--all", action="store_true",
                    help="every checker over the whole scope (default)")
    ap.add_argument("--changed", action="store_true",
                    help="fast mode: only files changed vs HEAD")
    ap.add_argument("--env-table", action="store_true",
                    help="print the registered-knob markdown table")
    ap.add_argument("--apply", action="store_true",
                    help="with --env-table: rewrite README.md in place")
    ap.add_argument("--lock-report", nargs="?", const="", default=None,
                    metavar="DIR",
                    help="report cycles in recorded lock graphs "
                         "(DIR defaults to $SW_LOCK_GRAPH_DIR)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress allowlist echo lines")
    args = ap.parse_args(argv)

    if args.env_table:
        from seaweedfs_tpu.util import config
        if args.apply:
            apply_readme_table()
            print("README.md env table rewritten")
        else:
            print(config.env_table())
        return 0

    if args.lock_report is not None:
        problems, n_edges = lock_report(args.lock_report or None)
        for p in problems:
            print(p, file=sys.stderr)
        print(f"lock-report: {n_edges} recorded edges, "
              f"{len(problems)} cycle(s)")
        return 1 if problems else 0

    if args.changed and not args.all:
        files = changed_files()
        problems, allowed = run_static(files, full_scope=False)
    else:
        files = iter_scope_files()
        problems, allowed = run_static(files)
        problems += check_readme_table()
        problems += run_metrics_checks()

    if not args.quiet:
        for a in allowed:
            print(a)
    for p in problems:
        print(p, file=sys.stderr)
    n = len(files)
    if problems:
        print(f"analyze: {len(problems)} problem(s) across {n} files",
              file=sys.stderr)
        return 1
    print(f"analyze: {n} files clean "
          f"({len(allowed)} allowlisted exceptions)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
