"""CommandEnv — shared state for shell commands (reference
weed/shell/commands.go CommandEnv + MasterClient)."""

from __future__ import annotations

import shlex
from typing import Callable, Dict, List

from ..server.http_util import HttpError, get_json, http_call, post_json

COMMANDS: Dict[str, Callable] = {}
HELP: Dict[str, str] = {}


def command(name: str, help_text: str = ""):
    def deco(fn):
        COMMANDS[name] = fn
        HELP[name] = help_text or (fn.__doc__ or "").strip()
        return fn
    return deco


class CommandEnv:
    def __init__(self, master_url: str, out=None, filer_url: str = ""):
        self.master_url = master_url
        self.filer_url = filer_url
        self.cwd = "/"          # fs.* commands' working directory
        # admin operations move whole volumes (encode/copy/rebuild of
        # tens of GB): a short client deadline would orphan a
        # still-running server-side op, so the cap is generous — the
        # reference's gRPC admin streams carry no deadline at all.
        # Batch drivers (bench) lower it to keep their runs bounded.
        self.admin_timeout = 3600.0
        import sys
        self.out = out or sys.stdout

    def filer(self):
        """FilerClient for fs.* commands (requires shell -filer)."""
        if not self.filer_url:
            raise HttpError(400, "no filer configured: start the shell "
                                 "with -filer <host:port>")
        from ..filer.filer_client import FilerClient
        return FilerClient(self.filer_url)

    def resolve(self, path: str) -> str:
        """Absolute path for an fs.* operand, relative to fs.cd's cwd."""
        import posixpath
        if not path:
            return self.cwd
        if not path.startswith("/"):
            path = posixpath.join(self.cwd, path)
        return posixpath.normpath(path)

    def write(self, *args):
        # one call a line: the volumes of a collection command write
        # from their own threads, and print() hands a stream the text
        # and the newline apart
        self.out.write(" ".join(map(str, args)) + "\n")

    # -- cluster state helpers --------------------------------------------
    def master_get(self, path: str) -> dict:
        return get_json(f"http://{self.master_url}{path}")

    def master_post(self, path: str) -> dict:
        return post_json(f"http://{self.master_url}{path}")

    def node_post(self, node: str, path: str,
                  timeout: "float | None" = None,
                  body: dict = None) -> dict:
        if timeout is None:
            timeout = self.admin_timeout
        return post_json(f"http://{node}{path}", body, timeout=timeout)

    def node_get(self, node: str, path: str) -> dict:
        return get_json(f"http://{node}{path}")

    def cluster_nodes(self) -> List[dict]:
        return self.master_get("/cluster/status").get("nodes", [])

    def all_volumes(self) -> Dict[str, List[dict]]:
        return self.master_get("/cluster/volumes").get("volumes", {})

    def ec_volumes(self) -> Dict[str, dict]:
        return self.master_get("/cluster/ec_status").get("volumes", {})


def split_script(script: str) -> List[str]:
    """Split a ';'-separated command script into lines, ignoring
    semicolons inside single/double quotes — shared by `shell -c` and
    the master's maintenance cron."""
    parts, cur, quote = [], [], None
    for ch in script:
        if quote:
            cur.append(ch)
            if ch == quote:
                quote = None
        elif ch in "\"'":
            quote = ch
            cur.append(ch)
        elif ch == ";":
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def run_command(env: CommandEnv, line: str) -> bool:
    """Execute one shell line. Returns False on 'exit'."""
    line = line.strip()
    if not line or line.startswith("#"):
        return True
    if line in ("exit", "quit"):
        return False
    try:
        parts = shlex.split(line)
    except ValueError as e:
        # unbalanced quotes must not kill the REPL/script
        env.write(f"error: {e}")
        return True
    name, args = parts[0], parts[1:]
    if name == "help":
        if args and args[0] in HELP:
            env.write(f"{args[0]}: {HELP[args[0]]}")
        else:
            for cmd in sorted(COMMANDS):
                env.write(f"  {cmd:28s} {HELP.get(cmd, '').splitlines()[0] if HELP.get(cmd) else ''}")
        return True
    fn = COMMANDS.get(name)
    if fn is None:
        env.write(f"unknown command {name!r}; try 'help'")
        return True
    try:
        fn(env, args)
    except HttpError as e:
        env.write(f"error: {e.status} {e.message or e}")
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:  # noqa: BLE001 — a REPL must survive any
        env.write(f"error: {type(e).__name__}: {e}")  # command failure
    return True


def parse_flags2(args: List[str], bool_flags=()):
    """Like parse_flags but keeps positional operands and never lets a
    known boolean flag swallow the operand after it.
    '-l /dir' with bool_flags={'l'} -> ({'l': 'true'}, ['/dir'])."""
    flags: Dict[str, str] = {}
    ops: List[str] = []
    i = 0
    while i < len(args):
        a = args[i]
        if a.startswith("-"):
            key = a.lstrip("-")
            if "=" in key:
                k, v = key.split("=", 1)
                flags[k] = v
            elif key in bool_flags:
                flags[key] = "true"
            elif i + 1 < len(args) and not args[i + 1].startswith("-"):
                flags[key] = args[i + 1]
                i += 1
            else:
                flags[key] = "true"
        else:
            ops.append(a)
        i += 1
    return flags, ops


def parse_flags(args: List[str]) -> Dict[str, str]:
    """'-volumeId 3 -collection x -force' -> {volumeId: 3, ...}."""
    out: Dict[str, str] = {}
    i = 0
    while i < len(args):
        a = args[i]
        if a.startswith("-"):
            key = a.lstrip("-")
            if "=" in key:
                k, v = key.split("=", 1)
                out[k] = v
            elif i + 1 < len(args) and not args[i + 1].startswith("-"):
                out[key] = args[i + 1]
                i += 1
            else:
                out[key] = "true"
        i += 1
    return out
