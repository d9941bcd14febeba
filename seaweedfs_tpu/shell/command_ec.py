"""ec.* shell commands — the north-star orchestration.

Reference weed/shell/command_ec_encode.go / _rebuild.go / _decode.go /
_balance.go: freeze -> generate -> spread -> mount -> drop originals;
rebuild lost shards on the freest node; decode back to normal volumes;
balance shards across nodes.
"""

from __future__ import annotations

from typing import Dict, List

from ..ec.constants import DATA_SHARDS, MAX_SHARDS, PARITY_SHARDS
from ..server.http_util import HttpError
from ..util.locks import make_lock
from .command_env import CommandEnv, command, parse_flags


def _free_nodes(env: CommandEnv) -> List[dict]:
    return sorted(env.cluster_nodes(), key=lambda n: -n.get("free", 0))


def pick_rebuilder(nodes: List[dict], shards: Dict[int, List[str]]) -> str:
    """The node that rebuilds a volume's lost shards: among the nodes
    with a free slot, the one that holds fewest of THIS volume's shards,
    then the freest (reference command_ec_rebuild.go picks by free slots
    alone and leaves the stacking to ec.balance). The free count is the
    whole server's: after a holder's loss a command rebuilds volume
    after volume onto the emptied server, which fills as it goes, and
    where the freest node then is one that already holds m of a volume's
    shards the rebuilt ones stacked on it put it above m — a second
    loss the volume does not survive. ``nodes`` as the master lists
    them (`/cluster/status`), ``shards`` the volume's survivors by
    holder (`/cluster/ec_status`)."""
    held: Dict[str, int] = {}
    for urls in shards.values():
        for url in urls:
            held[url] = held.get(url, 0) + 1
    with_room = [n for n in nodes if n.get("free", 0) > 0] or nodes
    return min(with_room, key=lambda n: (held.get(n["url"], 0),
                                         -n.get("free", 0)))["url"]


def chips_of(nodes: List[dict]) -> Dict[str, str]:
    """url -> the chip that node's codecs compute on, as the master
    passes on what the node's heartbeat names (`-ec.backend tpu-own`:
    ``device.chip``, a name no other chip has). A node that names none
    is taken to share one with every other such node: how many chips a
    command may use is read from the cluster, never typed, and where
    nothing is known it is one."""
    return {n["url"]: (n.get("device") or {}).get("chip", "")
            for n in nodes}


def lanes_of(lane_of: Dict[str, str]) -> int:
    """How many volumes a collection command keeps in flight: the
    distinct lanes its nodes lie on (`run_in_lanes`)."""
    return len(set(lane_of.values()))


def run_in_lanes(jobs: list, lane_of: Dict[str, str], place, run):
    """A collection's volumes, ONE in flight per lane, the jobs taken in
    order. What a lane is the caller says: ``lane_of`` maps a node's url
    to the lane a job placed on that node occupies — the chip the node
    computes on for `ec.rebuild` (`chips_of`), the node itself for
    `ec.encode -collection` (a volume is coded on the server its `.dat`
    lies on, and what is scarce there is that server's). ``place(job,
    busy)`` is asked, with the volumes in flight by lane, where a job
    would run now: it returns (node url, whatever ``run`` needs beside)
    or None while the job has to wait; the first job of the order that
    has a place starts, on a thread of its own, and ``run(job,
    placement)`` does the work. Where there is one lane (a single
    server, a collection of one volume, a rebuild on a cluster that
    names one chip) nothing is placed and no thread is started:
    ``run(job, None)`` in order on the caller's thread, which is what
    the commands did before there were lanes. A job that raises stops
    new ones from starting; those in flight finish, and the first
    error in job order is raised."""
    import threading
    if lanes_of(lane_of) <= 1:
        for job in jobs:
            run(job, None)
        return
    busy = dict.fromkeys(lane_of.values(), 0)
    freed = threading.Condition()
    errors: Dict[int, BaseException] = {}
    threads = []
    pending = list(enumerate(jobs))

    def work(n, job, placement):
        try:
            run(job, placement)
        except BaseException as e:  # noqa: BLE001 - raised by the caller
            errors[n] = e
        finally:
            with freed:
                busy[lane_of[placement[0]]] -= 1
                freed.notify()

    while pending and not errors:
        with freed:
            snapshot = dict(busy)
        start = None
        # only this thread adds to `busy`: a job placed by the snapshot
        # finds its lane no busier when it starts
        for n, job in pending:
            placement = place(job, snapshot)
            if placement is not None:
                start = (n, job, placement)
                break
        with freed:
            if start is None:
                if busy == snapshot:
                    freed.wait()
                continue
            busy[lane_of[start[2][0]]] += 1
        pending.remove(start[:2])
        t = threading.Thread(target=work, args=start, daemon=True,
                             name=f"ec-volume-{start[0]}")
        threads.append(t)
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[min(errors)]


def _volume_replicas(env: CommandEnv, vid: int) -> List[dict]:
    return env.all_volumes().get(str(vid), [])


def _geometry_of(info: dict) -> tuple:
    """(k, m) of an EC volume as the master reports it (from its
    holders' heartbeats); the default where it names none."""
    return (int(info.get("data_shards") or DATA_SHARDS),
            int(info.get("parity_shards") or PARITY_SHARDS))


def balanced_ec_distribution(nodes: List[dict],
                             geometry: tuple = (DATA_SHARDS,
                                                PARITY_SHARDS)
                             ) -> List[str]:
    """Assign the k + m shards of one volume (14 by default) round-robin
    by free slots (reference balancedEcDistribution
    command_ec_encode.go:237-253)."""
    if not nodes:
        raise ValueError("no volume servers")
    k, m = geometry
    # plain round-robin over servers that still have free EC slots (one
    # volume slot = k shard slots: 10 by default)
    picked: Dict[str, int] = {n["url"]: 0 for n in nodes}
    free_slots = {n["url"]: max(n.get("free", 0), 0) * k for n in nodes}
    urls = [n["url"] for n in nodes]
    out: List[str] = []
    i = 0
    spins = 0
    while len(out) < k + m:
        url = urls[i % len(urls)]
        i += 1
        if free_slots[url] - picked[url] >= 1:
            out.append(url)
            picked[url] += 1
            spins = 0
        else:
            spins += 1
            if spins > len(urls):
                raise ValueError("not enough free EC slots in the cluster")
    return out


def collect_volume_ids_for_ec_encode(env: CommandEnv, collection: str,
                                     full_percent: float = 0.95,
                                     quiet_seconds: float = 3600,
                                     size_limit: int = None
                                     ) -> Dict[int, tuple]:
    """Quiet & nearly-full volumes by volume id: (size, the url of the
    server that holds it, the urls of all its replicas' holders)
    (reference collectVolumeIdsForEcEncode
    command_ec_encode.go:255-287)."""
    import time
    if size_limit is None:
        status = env.master_get("/dir/status")
        size_limit = status.get("volumeSizeLimit") \
            or 30 * 1024 * 1024 * 1024
    now = time.time()
    out = {}
    for vid_s, replicas in env.all_volumes().items():
        vi = replicas[0]
        if vi.get("collection", "") != collection:
            continue
        if vi.get("size", 0) < full_percent * size_limit:
            continue
        modified = vi.get("modified_at", 0)
        if modified and now - modified < quiet_seconds:
            continue
        out[int(vid_s)] = (int(vi.get("size", 0)), vi.get("url"),
                           [r.get("url") for r in replicas])
    return out


def plan_encode_placements(nodes: List[dict], jobs: list,
                           geometry: tuple) -> tuple:
    """vid -> (assignment, spares) of every volume of an `ec.encode
    -collection` whose volumes run in lanes, decided before the first
    starts, by one thread, in job order: the shards of volume n go
    where they would have gone had the volumes been coded one after the
    other. ``nodes`` is the master's list as the command begins
    (`/cluster/status`), ``jobs`` the (vid, replica urls) in job order.
    A volume coded one after the other reads the master's free counts
    when it starts, and those hold every earlier volume whole: its
    shards mounted (a shard takes 1/k of a slot) and its replicas
    dropped (a slot back each). With several in flight the master is
    between two states, so the counts are carried here instead, by the
    master's own arithmetic (topology/node.DataNode.free_space: the
    slots less the SUM of the volumes' shard shares, in the order the
    volumes were coded), which keeps ties as the master would. Where a
    volume finds no room the plan ends before it, as the serial order
    would have coded the volumes before it and raised: returns (the
    plan, that error or None)."""
    k = geometry[0]
    slots = {n["url"]: n.get("free", 0) for n in nodes}
    coded: Dict[str, List[float]] = {n["url"]: [] for n in nodes}
    out = {}
    for vid, replicas in jobs:
        view = sorted(({**n, "free": slots[n["url"]] - sum(coded[n["url"]])}
                       for n in nodes), key=lambda n: -n["free"])
        try:
            assignment = balanced_ec_distribution(view, geometry)
        except ValueError as e:
            return out, e
        out[vid] = (assignment, [n["url"] for n in view
                                 if n["url"] not in assignment])
        for url in set(assignment):
            coded[url].append(assignment.count(url) / k)
        for url in replicas:
            if url in slots:
                slots[url] += 1
    return out, None


@command("ec.encode",
         "-volumeId <id> | -collection <name> [-fullPercent 0.95] "
         "[-geometry <data>,<parity>] : erasure-code volumes and spread "
         "their shards across the cluster, each shard's ranges pushed to "
         "its holder while later slabs encode; a collection's volumes run "
         "one at a time per source server (the server a volume's .dat "
         "lies on), so a collection on four servers has four in flight "
         "(geometry = the RS code of "
         "the new EC volume, e.g. 6,3 for nine shards; 10,4 and 14 "
         "shards without the flag; it is stamped into the volume's .vif "
         "and every later command reads it from there)")
def ec_encode(env: CommandEnv, args: List[str]):
    import time
    from ..ops import telemetry
    from ..util import tracing
    flags = parse_flags(args)
    geometry = None
    if "geometry" in flags:
        from ..ec.layout import parse_geometry
        geometry = parse_geometry(flags["geometry"])
    if "volumeId" in flags:
        do_ec_encode(env, int(flags["volumeId"]), geometry=geometry)
        return
    if "collection" not in flags:
        env.write("usage: ec.encode -volumeId <id> | -collection <name>")
        return
    found = collect_volume_ids_for_ec_encode(
        env, flags["collection"], float(flags.get("fullPercent", 0.95)),
        quiet_seconds=float(flags.get("quietFor", 3600)))
    # a volume is coded on the server its .dat lies on: that server's
    # freeze, index, read and spread lanes are what a volume occupies,
    # so the command keeps one volume in flight per source server
    homes = {home: home for _, home, _ in found.values()}
    lanes = lanes_of(homes)
    jobs = sorted(found) if lanes > 1 else list(found)
    # the whole command under one span of its own trace; each volume's
    # ec.encode stays the root of its own and names this one
    whole = tracing.Span("ec.encode.collection", tags={
        "collection": flags["collection"], "volumes": 0, "bytes": 0,
        "lanes": lanes, "volumes_inflight_mean": 0.0})
    counted = make_lock("command_ec.collection_span")
    inflight_s = 0.0

    def encode(vid, placement):
        nonlocal inflight_s
        t0 = time.perf_counter()
        try:
            do_ec_encode(env, vid, geometry=geometry,
                         command=whole.trace_id,
                         placement=placement and placement[1:])
        finally:
            with counted:   # volumes in lanes finish on their threads
                inflight_s += time.perf_counter() - t0
        with counted:
            whole.tags["volumes"] += 1
            whole.tags["bytes"] += found[vid][0]

    # asked only where there are lanes: where every volume of the
    # command will put its shards, as the serial order would have
    planned, no_room = plan_encode_placements(
        env.cluster_nodes(), [(vid, found[vid][2]) for vid in jobs],
        geometry or (DATA_SHARDS, PARITY_SHARDS)) if lanes > 1 \
        else ({}, None)
    if no_room:
        jobs = jobs[:len(planned)]

    def home_if_free(vid, busy):
        home = found[vid][1]
        return None if busy[home] else (home, *planned[vid])

    t0 = time.perf_counter()
    try:
        run_in_lanes(jobs, homes, home_if_free, encode)
        if no_room:
            raise no_room
    finally:
        wall = time.perf_counter() - t0
        whole.tags["volumes_inflight_mean"] = \
            round(inflight_s / wall, 3) if wall > 0 else 0.0
        telemetry.STATS.add_collection_encode(inflight_s, wall)
        tracing.finish_span(whole)


def do_ec_encode(env: CommandEnv, vid: int,
                 timings: Dict = None, rate_mbps: float = 0.0,
                 geometry: tuple = None, command: str = None,
                 placement: tuple = None):
    """Freeze -> encode+spread -> mount -> drop originals.

    The shard assignment goes to the source, which pushes each shard's
    slab ranges to its holder WHILE later slabs encode — remote-bound
    shards never touch the source disk (`_encode_spread_streaming`).

    Any failure after the freeze unwinds: generated shard files (and
    ``.part`` stages) are deleted cluster-wide and each replica's
    readonly flag is restored to its own prior state — a failed encode
    must not leave the volume frozen with orphan shards.

    ``timings``, when given, records encode/spread busy seconds,
    ``overlap_frac``, and the spread counters for bench. ``rate_mbps``
    > 0 paces the spread (the tierer's background cap).
    ``geometry`` (k, m) is the new EC volume's RS
    code, passed on to the source's ``/admin/ec/generate``; None leaves
    the node at its default, 10 + 4. ``command`` is the trace id of the
    `ec.encode -collection` span this volume is one of, kept as a tag.
    ``placement`` is (assignment, spares) where the command runs its
    volumes in lanes and has decided every volume's holders before the
    first started (`plan_encode_placements`); None: the master is asked
    here, now, as it always was."""
    from ..util import tracing
    replicas = _volume_replicas(env, vid)
    if not replicas:
        env.write(f"volume {vid} not found")
        return
    collection = replicas[0].get("collection", "")
    source = replicas[0]["url"]
    root = tracing.start_span("ec.encode", volume=vid)
    if command:
        root.tags["command"] = command
    try:
        # 1. freeze every replica, recording each holder's OWN prior
        # state (not the master's heartbeat-delayed view) so a failure
        # thaws exactly what this command froze
        froze: List[str] = []
        with tracing.Stage("ec.encode.freeze", root):
            for r in replicas:
                out = env.node_post(
                    r["url"], f"/admin/volume/readonly?volume={vid}")
                if not (out or {}).get("was_readonly"):
                    froze.append(r["url"])
        assignment, spares = placement or (None, None)
        if assignment is None:
            assignment = balanced_ec_distribution(
                _free_nodes(env), geometry or (DATA_SHARDS, PARITY_SHARDS))
        try:
            # 2+3. encode + spread + mount
            _encode_spread_streaming(env, vid, collection, source,
                                     assignment, timings, rate_mbps,
                                     geometry, spares)
        except BaseException as e:
            _cleanup_partial_encode(env, vid, collection,
                                    set(assignment) | {source})
            for url in froze:
                try:
                    env.node_post(url,
                                  f"/admin/volume/readonly?volume={vid}"
                                  f"&readonly=false")
                except HttpError:
                    pass
            root.tags.setdefault("error", type(e).__name__)
            raise
        # 5. drop the original volume everywhere
        with tracing.Stage("ec.encode.drop", root):
            for r in replicas:
                env.node_post(r["url"],
                              f"/admin/delete_volume?volume={vid}")
        if timings is not None:
            timings["trace_id"] = root.trace_id
    finally:
        tracing.finish_span(root)
    env.write(f"volume {vid}: ec encoded, original removed")


def _cleanup_partial_encode(env: CommandEnv, vid: int, collection: str,
                            nodes):
    """Best-effort removal of every shard file and ``.part`` stage a
    failed encode may have left on any involved node (of whatever
    geometry: every shard id a volume can have)."""
    all_shards = ",".join(map(str, range(MAX_SHARDS)))
    for url in nodes:
        try:
            env.node_post(url, f"/admin/ec/delete_shards?volume={vid}"
                               f"&collection={collection}"
                               f"&shards={all_shards}")
        except HttpError:
            pass


def _encode_spread_streaming(env: CommandEnv, vid: int, collection: str,
                             source: str, assignment: List[str],
                             timings: Dict = None,
                             rate_mbps: float = 0.0,
                             geometry: tuple = None,
                             spares: List[str] = None):
    """One POST: the source encodes and pushes each shard's slab ranges
    to its assigned holder while later slabs encode. Afterwards only
    the KB-scale index sidecars (.ecx/.vif) are copied to remote
    holders, then every holder mounts its shards. ``spares`` (nodes a
    dead target's shards may move to) come with a planned placement;
    None: the master is asked."""
    import time as _time
    from ..util.fanout import fan_out_must_succeed
    if spares is None:
        spares = [n["url"] for n in _free_nodes(env)
                  if n["url"] not in assignment]
    t0 = _time.perf_counter()
    out = env.node_post(
        source, f"/admin/ec/generate?volume={vid}"
                f"&collection={collection}{_geometry_query(geometry)}",
        body={"assignment": {str(s): u
                             for s, u in enumerate(assignment)},
              "spares": spares,
              "rate_mbps": rate_mbps})
    wall = _time.perf_counter() - t0
    stats = out.get("stats") or {}
    # re-group by the FINAL placement: failover may have moved a dead
    # target's shards to a spare ('' = the source kept them)
    final = {int(s): (u or source)
             for s, u in (out.get("assignment") or {}).items()}
    if not final:
        final = dict(enumerate(assignment))
    by_node: Dict[str, List[int]] = {}
    for sid in sorted(final):
        by_node.setdefault(final[sid], []).append(sid)
    env.write(f"volume {vid}: streamed {len(final)} shards from "
              f"{source} (encode {stats.get('encode_busy_s', 0.0)}s ∥ "
              f"spread {stats.get('spread_busy_s', 0.0)}s, overlap "
              f"{stats.get('overlap_frac', 0.0)})")

    def mount(target):
        url, shards = target
        s = ",".join(map(str, shards))
        if url != source:
            # shard bytes are already there — pull only the sidecars
            env.node_post(url, f"/admin/ec/copy?volume={vid}"
                               f"&collection={collection}"
                               f"&source={source}&shards="
                               f"&copy_ecx=true")
        env.node_post(url, f"/admin/ec/mount?volume={vid}"
                           f"&collection={collection}&shards={s}")
        return s

    from ..util import tracing
    with tracing.Stage("ec.encode.mount", tracing.current_span()):
        for (url, _), s in zip(
                by_node.items(),
                fan_out_must_succeed(
                    mount, list(by_node.items()),
                    what=f"ec shard mount for volume {vid}",
                    dedicated=True)):
            env.write(f"volume {vid}: shards {s} -> {url}")
        if source not in by_node:
            # the source kept no shards: drop its now-orphan sidecars
            env.node_post(source, f"/admin/ec/delete_shards?volume={vid}"
                                  f"&collection={collection}&shards=")
    if timings is not None:
        timings["encode_wall_s"] = \
            timings.get("encode_wall_s", 0) + wall
        _merge_rebuild_stats(timings, out)


def _geometry_query(geometry) -> str:
    return f"&geometry={geometry[0]},{geometry[1]}" if geometry else ""


@command("ec.rebuild",
         "[-collection <name>] [-repair auto|trace|piggyback|full] : "
         "regenerate missing shards from ranged survivor reads "
         "overlapped with the decode (repair = "
         "single-shard strategy — trace ships projected sub-shard "
         "symbols from all survivors on flat volumes, piggyback ships "
         "half-shard planes on piggyback-layout volumes, full pulls k "
         "whole ranges, auto picks by the volume's layout)")
def ec_rebuild(env: CommandEnv, args: List[str]):
    from ..util import tracing
    flags = parse_flags(args)
    # the whole command under one span of its own trace; each volume's
    # ec.rebuild stays the root of its own and names this one
    whole = tracing.Span("ec.rebuild.collection", tags={
        "collection": flags.get("collection", ""), "volumes": 0,
        "bytes": 0})
    counted = make_lock("command_ec.collection_span")

    def rebuild(job, placement):
        vid, collection, shards, missing = job
        timings: Dict = {}
        do_ec_rebuild(env, vid, collection, shards, missing,
                      timings=timings, repair=flags.get("repair"),
                      command=whole.trace_id, placement=placement)
        with counted:       # volumes in lanes finish on their threads
            whole.tags["volumes"] += 1
            whole.tags["bytes"] += timings.get("rebuilt_bytes", 0)

    try:
        jobs = []
        for vid_s, info in env.ec_volumes().items():
            vid = int(vid_s)
            collection = info.get("collection", "")
            if "collection" in flags and collection != flags["collection"]:
                continue
            shards = {int(s): urls for s, urls in info["shards"].items()}
            k, m = _geometry_of(info)
            missing = [s for s in range(k + m) if s not in shards]
            if not missing:
                continue
            if len(shards) < k:
                env.write(f"volume {vid}: only {len(shards)} shards left, "
                          f"cannot rebuild")
                continue
            jobs.append((vid, collection, shards, missing))
        chips = chips_of(env.cluster_nodes()) if jobs else {}
        if lanes_of(chips) > 1:
            jobs.sort(key=lambda job: job[0])

        def where(job, busy):
            if all(busy.values()):
                return None     # no chip free: ask the master nothing
            return place_rebuild(env.cluster_nodes(), chips, busy,
                                 job[2], job[3])

        run_in_lanes(jobs, chips, where, rebuild)
    finally:
        tracing.finish_span(whole)


def place_rebuild(nodes: List[dict], chips: Dict[str, str],
                  busy: Dict[str, int], shards: Dict[int, List[str]],
                  missing: List[int]):
    """(computing node, target) of one volume's rebuild, or None while
    it has to wait. The target is placement's
    (`pick_rebuilder`: fewest of this volume's shards, then freest) and
    keeps the rebuilt shards; the node that gathers and decodes is the
    target where its chip has nothing in flight from this command, else
    a node on the chip with least in flight, of those the one that holds
    most of the volume's survivors (least to gather) — f4's rebuilder
    nodes, apart from its storage nodes. Only a loss of several shards
    (the flat full gather) is decoded off its target; a single shard's
    routes (trace, half-planes) read and write on the target, and wait
    for its chip."""
    target = pick_rebuilder(nodes, shards)
    if not busy[chips[target]]:
        return target, target
    if len(missing) < 2:
        return None
    held: Dict[str, int] = {}
    for urls in shards.values():
        for url in urls:
            held[url] = held.get(url, 0) + 1
    node = min((n["url"] for n in nodes if n["url"] in chips),
               key=lambda url: (busy[chips[url]], -held.get(url, 0)))
    return node, target


def _merge_rebuild_stats(timings: Dict, out: dict):
    """Fold the rebuilder's stats dict into the shell timings: numbers
    sum across volumes, dict-valued breakdowns (per-phase seconds,
    per-holder fetch/error counts) merge per key, a stage's longest
    interval stays the longest."""
    for key, val in (out.get("stats") or {}).items():
        if key == "phases" and isinstance(val, dict):
            agg = timings.setdefault("phases", {})
            for ph, secs in val.items():
                agg[ph] = round(agg.get(ph, 0.0) + secs, 6)
        elif key == "stage_max_s" and isinstance(val, dict):
            agg = timings.setdefault("stage_max_s", {})
            for stage, secs in val.items():
                agg[stage] = max(agg.get(stage, 0.0), secs)
        elif key in ("holder_fetches", "holder_errors") and \
                isinstance(val, dict):
            agg = timings.setdefault(key, {})
            for holder, n in val.items():
                agg[holder] = agg.get(holder, 0) + n
        elif key in ("k", "m", "shards"):
            timings[key] = val      # the volume's geometry: no sum
        elif isinstance(val, (int, float)):
            timings[key] = timings.get(key, 0) + val
        else:
            timings[key] = val


def do_ec_rebuild(env: CommandEnv, vid: int, collection: str,
                  shards: Dict[int, List[str]], missing: List[int],
                  timings: Dict[str, float] = None,
                  repair: str = None, command: str = None,
                  placement: tuple = None):
    """The survivor holder map goes to the rebuilder, which pulls slab
    ranges and decodes them overlapped — no whole-shard temp copies, no
    trailing delete_shards pass (`_rebuild_streaming`). `timings`, when
    given, records the phase walls plus the rebuilder's stats
    (gather/compute busy time, overlap_frac, dispatch telemetry) — the
    benchmark's overlap accounting.

    repair: "auto" (default; `SW_EC_REPAIR_MODE` overrides) lets the
    rebuilder pick the cheapest single-shard strategy for the volume's
    layout — trace repair (projected sub-shard symbols from all
    survivors) on flat volumes, plane repair (half-shard planes from
    k+1 helpers) on piggyback volumes. "trace"/"piggyback" force the
    matching strategy and error on the other layout; "full" forces the
    k-survivor gather on either. ``command`` is the
    trace id of the `ec.rebuild` command's span, kept as a tag.

    ``placement`` is (computing node, target) where the command runs
    its volumes in lanes (`place_rebuild`); None: the target is picked
    here and computes, as it always did. Where the two differ the
    computing node decodes and delivers the rebuilt shards to the
    target's disk, the target pulls the sidecars and mounts; if that
    fails the target is cleaned of every partial shard and the volume
    is rebuilt on the target itself. The span's ``device`` tag is the
    chip the node that decoded names in its reply."""
    from ..util import config as _config
    from ..util import tracing
    repair = (repair or _config.env_str("SW_EC_REPAIR_MODE") or
              "auto").lower()
    # shell-side trace root: every call below — survivor gathering, the
    # rebuild, mount — carries its traceparent: ONE trace per operation
    root = tracing.start_span("ec.rebuild", volume=vid, repair=repair)
    if command:
        root.tags["command"] = command
    try:
        node, rebuilder = placement or (None, None)
        if rebuilder is None:
            rebuilder = node = pick_rebuilder(env.cluster_nodes(), shards)
        root.tags["target"], root.tags["computed_on"] = rebuilder, node
        root.tags["device"] = ""    # the node that decodes names it
        if node != rebuilder:
            try:
                rebuilt = _rebuild_streaming(
                    env, vid, collection, shards, rebuilder, root,
                    timings, repair=repair, node=node)
            except HttpError as e:
                env.write(f"volume {vid}: rebuild on {node} for "
                          f"{rebuilder} failed ({e}); rebuilding "
                          f"on {rebuilder}")
                root.tags["fallback"] = "target"
                root.tags["computed_on"] = node = rebuilder
                _cleanup_partial_rebuild(env, vid, collection,
                                         rebuilder, missing)
        if node == rebuilder:
            rebuilt = _rebuild_streaming(env, vid, collection, shards,
                                         rebuilder, root, timings,
                                         repair=repair)
        if timings is not None:
            timings["trace_id"] = root.trace_id
    except BaseException as e:
        root.tags.setdefault("error", type(e).__name__)
        raise
    finally:
        tracing.finish_span(root)
    env.write(f"volume {vid}: rebuilt shards {rebuilt} on {rebuilder}")


def _cleanup_partial_rebuild(env: CommandEnv, vid: int, collection: str,
                             target: str, missing: List[int]):
    """What a delivery that died may have left on the target: the
    `.part` stages of the shards it was sending, and a shard it had
    already finalized. None of them is mounted (the mount comes after
    the last); all go, as `_cleanup_partial_encode` clears a failed
    spread, and the survivors the target holds stay."""
    try:
        env.node_post(target, f"/admin/ec/delete_shards?volume={vid}"
                              f"&collection={collection}"
                              f"&shards={','.join(map(str, missing))}")
    except HttpError:
        pass


def _rebuild_streaming(env: CommandEnv, vid: int, collection: str,
                       shards: Dict[int, List[str]], rebuilder: str,
                       root, timings: Dict = None,
                       repair: str = "auto",
                       node: str = None) -> List[int]:
    """One POST: the rebuilder pulls slab-aligned survivor ranges from
    the holder map and feeds them straight into the pipelined decode
    (or, single-shard loss with ``repair`` auto/trace/piggyback, pulls
    projected repair symbols or half-shard planes from the helpers the
    volume's layout prescribes). ``node``, where given and another
    server than ``rebuilder``, does the gather and the decode in its
    place and sends the rebuilt shards to the rebuilder's disk; the
    rebuilder then pulls the sidecars from a holder, as a target of the
    encode's spread does, and mounts."""
    import time as _time
    node = node or rebuilder
    sources = {str(sid): urls for sid, urls in shards.items()
               if node not in urls}
    body = {"sources": sources, "repair": repair}
    if node != rebuilder:
        body["target"] = rebuilder
    t0 = _time.perf_counter()
    out = env.node_post(
        node,
        f"/admin/ec/rebuild?volume={vid}&collection={collection}",
        body=body)
    t1 = _time.perf_counter()
    root.tags["device"] = out.get("device", "")
    rebuilt = out.get("rebuilt", [])
    if rebuilt and node != rebuilder and \
            not any(rebuilder in urls for urls in shards.values()):
        # the shard bytes are there; the index a mount needs is not
        holder = next(urls[0] for urls in shards.values() if urls)
        env.node_post(rebuilder, f"/admin/ec/copy?volume={vid}"
                                 f"&collection={collection}"
                                 f"&source={holder}&shards="
                                 f"&copy_ecx=true")
    if timings is not None:
        stats = out.get("stats") or {}
        # stream mode has no serialized gather wall: report the busy
        # times so gather_s + compute_s estimates the SERIALIZED cost
        # the overlap saved (wall_s carries the actual elapsed time)
        timings["gather_s"] = timings.get("gather_s", 0) + \
            stats.get("gather_busy_s", 0.0)
        timings["compute_s"] = timings.get("compute_s", 0) + \
            stats.get("compute_busy_s", 0.0)
        timings["wall_s"] = timings.get("wall_s", 0) + (t1 - t0)
        timings["gathered_shards"] = \
            timings.get("gathered_shards", 0) + \
            stats.get("gather_remote_shards", len(sources))
        _merge_rebuild_stats(timings, out)
    if rebuilt:
        from ..util import tracing
        t3 = _time.perf_counter()
        with tracing.Stage("ec.rebuild.mount", root):
            env.node_post(rebuilder,
                          f"/admin/ec/mount?volume={vid}"
                          f"&collection={collection}"
                          f"&shards={','.join(map(str, rebuilt))}")
        if timings is not None:
            timings["mount_s"] = timings.get("mount_s", 0) + \
                (_time.perf_counter() - t3)
    return rebuilt


@command("ec.decode",
         "-volumeId <id> | -collection <name> : decode EC back to volumes")
def ec_decode(env: CommandEnv, args: List[str]):
    flags = parse_flags(args)
    for vid_s, info in env.ec_volumes().items():
        vid = int(vid_s)
        collection = info.get("collection", "")
        if "volumeId" in flags and vid != int(flags["volumeId"]):
            continue
        if "collection" in flags and collection != flags["collection"]:
            continue
        shards = {int(s): urls for s, urls in info["shards"].items()}
        k, m = _geometry_of(info)
        data_shards = {s: u for s, u in shards.items() if s < k}
        if len(data_shards) < k:
            env.write(f"volume {vid}: missing data shards; run ec.rebuild "
                      f"first")
            continue
        # pick the node holding the most data shards as the decode target
        counts: Dict[str, int] = {}
        for sid, urls in data_shards.items():
            for u in urls:
                counts[u] = counts.get(u, 0) + 1
        target = max(counts, key=counts.get)
        held = {s for s, urls in shards.items() if target in urls}
        for sid, urls in data_shards.items():
            if sid in held:
                continue
            env.node_post(target,
                          f"/admin/ec/copy?volume={vid}"
                          f"&collection={collection}&source={urls[0]}"
                          f"&shards={sid}&copy_ecx=false")
        env.node_post(target, f"/admin/ec/mount?volume={vid}"
                              f"&collection={collection}"
                              f"&shards="
                              f"{','.join(str(s) for s in range(k))}")
        env.node_post(target, f"/admin/ec/to_volume?volume={vid}"
                              f"&collection={collection}")
        # remove EC shards cluster-wide
        all_shards = ",".join(map(str, range(k + m)))
        holders = {u for urls in shards.values() for u in urls} | {target}
        for u in holders:
            env.node_post(u, f"/admin/ec/delete_shards?volume={vid}"
                             f"&collection={collection}&shards={all_shards}")
        env.write(f"volume {vid}: decoded back to a normal volume on "
                  f"{target}")


def _move_shard(env: CommandEnv, vid: int, collection: str, sid: int,
                src: str, dst: str):
    env.node_post(dst, f"/admin/ec/copy?volume={vid}"
                       f"&collection={collection}&source={src}"
                       f"&shards={sid}")
    env.node_post(dst, f"/admin/ec/mount?volume={vid}"
                       f"&collection={collection}&shards={sid}")
    env.node_post(src, f"/admin/ec/delete_shards?volume={vid}"
                       f"&collection={collection}&shards={sid}")


def _balance_one_ec_volume(env: CommandEnv, vid: int, collection: str,
                           shards: Dict[int, List[str]],
                           node_rack: Dict[str, str]) -> int:
    """Rack-aware two-phase balance of one EC volume (reference
    command_ec_balance.go): first spread shards evenly across RACKS (a
    lost rack must never cost more than its fair share of shards), then
    even node counts within each rack. Returns moves made."""
    import math
    moves = 0
    racks = sorted(set(node_rack.values()))
    nodes_in_rack = {r: sorted(u for u, rr in node_rack.items()
                               if rr == r) for r in racks}

    # replicated shards count EVERY holder (a shard may briefly — or by
    # policy — live on several nodes); a move relocates one replica and
    # must never target a node already holding the shard
    def rack_counts() -> Dict[str, int]:
        c = {r: 0 for r in racks}
        for sid, urls in shards.items():
            for u in urls:
                r = node_rack.get(u)
                if r is not None:
                    c[r] += 1
        return c

    def node_counts(urls) -> Dict[str, int]:
        c = {u: 0 for u in urls}
        for sid, holders in shards.items():
            for h in holders:
                if h in c:
                    c[h] += 1
        return c

    def relocate(sid: int, src: str, dst: str):
        _move_shard(env, vid, collection, sid, src, dst)
        shards[sid] = [dst if u == src else u for u in shards[sid]]

    # phase 1: across racks
    if len(racks) > 1:
        ceil_per_rack = math.ceil(len(shards) / len(racks))
        while True:
            rc = rack_counts()
            hi = max(racks, key=lambda r: rc[r])
            lo = min(racks, key=lambda r: rc[r])
            if rc[hi] <= ceil_per_rack or rc[hi] - rc[lo] <= 1:
                break
            nc = node_counts(nodes_in_rack[lo])
            job = None
            for s in sorted(shards):
                src = next((u for u in shards[s]
                            if node_rack.get(u) == hi), None)
                if src is None:
                    continue
                # racks already holding ANOTHER replica of s (besides
                # the one being moved) are off limits — two replicas of
                # one shard in a rack is exactly the fault-domain
                # collapse this phase exists to prevent
                other_racks = {node_rack.get(u) for u in shards[s]
                               if u != src}
                if lo in other_racks:
                    continue
                dst = min((u for u in nodes_in_rack[lo]
                           if u not in shards[s]),
                          key=lambda u: nc[u], default=None)
                if dst is not None:
                    job = (s, src, dst)
                    break
            if job is None:
                break  # nothing movable without double-placing a shard
            relocate(*job)
            moves += 1

    # phase 2: within each rack
    for r in racks:
        urls = nodes_in_rack[r]
        if len(urls) < 2:
            continue
        while True:
            nc = node_counts(urls)
            hi = max(urls, key=lambda u: nc[u])
            lo = min(urls, key=lambda u: nc[u])
            if nc[hi] - nc[lo] <= 1:
                break
            sid = next((s for s in sorted(shards)
                        if hi in shards[s] and lo not in shards[s]),
                       None)
            if sid is None:
                break
            relocate(sid, hi, lo)
            moves += 1
    return moves


@command("ec.balance",
         "[-collection <name>] : spread EC shards evenly across racks, "
         "then across nodes within each rack")
def ec_balance(env: CommandEnv, args: List[str]):
    flags = parse_flags(args)
    cluster = env.cluster_nodes()
    if not cluster:
        env.write("no volume servers")
        return
    node_rack = {n["url"]: n.get("rack", "") or "DefaultRack"
                 for n in cluster}
    moves = 0
    for vid_s, info in env.ec_volumes().items():
        vid = int(vid_s)
        collection = info.get("collection", "")
        if "collection" in flags and collection != flags["collection"]:
            continue
        shards = {int(s): list(urls)
                  for s, urls in info["shards"].items()}
        moves += _balance_one_ec_volume(env, vid, collection, shards,
                                        node_rack)
    env.write(f"ec.balance: {moves} shard moves")


@command("volume.ec.degraded",
         ": per-server degraded-read engine status (reconstruct-on-read "
         "batching, slab cache, survivor traffic)")
def volume_ec_degraded(env: CommandEnv, args: List[str]):
    nodes = env.cluster_nodes()
    if not nodes:
        env.write("no volume servers")
        return
    for node in nodes:
        url = node["url"]
        try:
            snap = env.node_get(url, "/status").get("ec_degraded") or {}
        except HttpError as e:
            env.write(f"{url}  unreachable: {e}")
            continue
        reads = int(snap.get("reads", 0))
        batches = int(snap.get("batches", 0))
        coalesced = int(snap.get("batched_requests", 0))
        avg_w = coalesced / batches if batches else 0.0
        env.write(
            f"{url}  reads={reads} batches={batches} "
            f"width(avg/max)={avg_w:.1f}/{int(snap.get('max_batch_requests', 0))} "
            f"hit_ratio={snap.get('cache_hit_ratio', 0.0):.2f} "
            f"cache={int(snap.get('cache_bytes', 0)) >> 10}KB/"
            f"{int(snap.get('cache_entries', 0))} slabs "
            f"survivor={int(snap.get('survivor_bytes', 0)) >> 10}KB "
            f"(remote {int(snap.get('remote_bytes', 0)) >> 10}KB) "
            f"dispatch(host/dev)={int(snap.get('host_dispatches', 0))}/"
            f"{int(snap.get('device_dispatches', 0))} "
            f"p99={snap.get('p99_ms', 0.0):.1f}ms "
            f"errors={int(snap.get('errors', 0))}")


@command("volume.ec.scrub",
         "[-trigger] [-volumeId <id>]: per-server syndrome-scrub status "
         "(passes, bytes verified, corruption found); -trigger runs a "
         "synchronous pass on every server first")
def volume_ec_scrub(env: CommandEnv, args: List[str]):
    flags = parse_flags(args)
    nodes = env.cluster_nodes()
    if not nodes:
        env.write("no volume servers")
        return
    vid = flags.get("volumeId")
    for node in nodes:
        url = node["url"]
        try:
            if "trigger" in flags:
                q = f"?volume={int(vid)}" if vid else ""
                env.node_post(url, f"/admin/ec/scrub{q}")
            snap = env.node_get(url, "/admin/ec/scrub_status") or {}
        except HttpError as e:
            env.write(f"{url}  unreachable: {e}")
            continue
        env.write(
            f"{url}  passes={int(snap.get('passes', 0))} "
            f"volumes={int(snap.get('volumes_scrubbed', 0))} "
            f"slabs={int(snap.get('slabs', 0))} "
            f"verified={int(snap.get('bytes_verified', 0)) >> 20}MB "
            f"@{snap.get('last_pass_mbps', 0.0):.1f}MB/s "
            f"corrupt(slabs/cols)={int(snap.get('corrupt_slabs', 0))}/"
            f"{int(snap.get('corrupt_columns', 0))} "
            f"findings={int(snap.get('findings', 0))} "
            f"dispatch(host/dev)={int(snap.get('host_dispatches', 0))}/"
            f"{int(snap.get('device_dispatches', 0))} "
            f"skipped(owner/missing)="
            f"{int(snap.get('skipped_not_owner', 0))}/"
            f"{int(snap.get('skipped_missing', 0))} "
            f"errors={int(snap.get('errors', 0))}")
