"""A piggyback-coded warm tier on four holders that keeps losing one of
them, back to back and one volume at a time:

    ec.encode -volumeId v  ->  14 shards at the master, 4+4+3+3
    then `losses_per_seal` times:
        every shard of one holder set lost  ->  loss at the master
        ->  ec.rebuild -collection c  ->  14 shards

Only the shell commands are timed; waits, deletions and checks sit between
them. The loop is `seal_repair.py`'s (its `_timed`, `_landed` and
`end_to_end` are used as they are) but for what is lost and what a rebuild
is held to. A loss is a whole holder set, and which one is named by the
traffic file, never drawn from the seed: the n-th loss of the window takes
`holder_sets[n mod 4]`, so every run does the same work. `ec.rebuild`,
given no `-repair` flag, then has to take the full coupled decode as its
route: a reply that names another route, another set of lost shards or
another operand than (32 x lost, 320), or that carries a `repair_fallback`,
or a gather of more than k whole shards, makes the run not correct. After
each encode the master is asked that no holder has more than m shards, the
deployment's reason for four servers. Each rebuild record carries the
`work` the decode needs by the configuration's equation
(lib/roofline_terms_decode.py) beside the `operand` its node replied with.

A wrong shard file is counted once: a rebuilt shard that differs from the
one `ec.encode` wrote under `rebuilt_shards_differing_from_encoded`, and
under `shards_differing_from_reference` the encoded shards and the rebuilt
ones that agree with a wrong encoded one (the reference is an encoder; a
rebuilt shard equal to a sound encoded shard is the reference's).
"""

import os
import time

from kinds import seal_repair
from lib import cluster as cl
from lib import controls, datagen, reference, reference_piggyback
from lib import roofline_terms, roofline_terms_decode

REBUILD = seal_repair.NODE_ROUTE["ec.rebuild"]


def _refuse_a_program_without_the_coupled_decode():
    """The cell holds a rebuild to what a program older than it does not
    reply with: `lost` and the byte account of the full coupled decode,
    and no `repair_fallback` for a loss no single-shard route was meant
    for (the program that has them counts `coupled_decodes` in
    ops/telemetry). Such a program cannot be measured here; say so at
    once, before anything is started."""
    from seaweedfs_tpu.ops import telemetry
    if "coupled_decodes" not in telemetry.STATS.snapshot():
        raise SystemExit(
            "benchmarks/kinds/seal_holder_loss.py: this program's full "
            "coupled decode replies with neither the lost shards nor its "
            "byte account (ops/telemetry has no coupled_decodes): the "
            "holder-loss cell cannot be measured on it")


_refuse_a_program_without_the_coupled_decode()


# -- the control of this mix (seal_repair's import added its own) -----------

def corrupt_coupled_decode():
    """One coefficient of every full coupled decode plan the program
    builds is changed. ec.encode asks for none, so the encoded shards
    stay the reference's and only ec.rebuild goes wrong: breaks "a
    rebuilt shard is bit-identical to the encoded one".
    (`controls.corrupt_rebuild_decode` patches the flat codec's
    `decode_plan`, which this path never calls.)"""
    from seaweedfs_tpu.ops import codec
    sound = codec._build_piggyback_decode

    def broken(pplan, present):
        src, missing, coeffs = sound(pplan, present)
        coeffs = coeffs.copy()
        coeffs[0, 0] ^= 1
        return src, missing, coeffs

    codec._build_piggyback_decode = broken


controls.CONTROLS.update(corrupt_coupled_decode=corrupt_coupled_decode)


# -- the loop ---------------------------------------------------------------

def prepare(run) -> dict:
    config, traffic, cluster = run.config, run.traffic, run.cluster
    sets = [sorted(int(s) for s in held) for held in traffic["holder_sets"]]
    cl.check(sorted(s for held in sets for s in held) ==
             list(range(cluster.total)) and
             len(sets) == len(cluster.servers),
             f"holder_sets {sets} is no partition of the {cluster.total} "
             f"shards over {len(cluster.servers)} servers")
    state = {"cycles": [], "sets": sets, "losses": 0,
             "per_seal": int(traffic["losses_per_seal"]),
             "gather_limit": float(traffic["gathered_shards_at_most"])}
    sizes = datagen.needle_sizes(traffic["needles"],
                                 int(config["volume_mib"]) << 20,
                                 run.seed, 0)
    t0 = time.perf_counter()
    volume = cluster.upload_volume(run.seed, sizes)
    state["kept"] = cluster.keep_sealed(
        volume["vid"], os.path.join(run.workdir, "sealed"))
    state["dat_bytes"] = os.path.getsize(state["kept"] + ".dat")
    state["shard_bytes"] = reference.shard_bytes(state["dat_bytes"],
                                                 cluster.k)
    state["next_vid"] = volume["vid"] + 1
    run.emit({"phase": "upload", "needles": len(sizes),
              "payload_bytes": int(sizes.sum()),
              "dat_bytes": state["dat_bytes"], "holder_sets": sets,
              "losses_per_seal": state["per_seal"],
              "seconds": time.perf_counter() - t0})
    # warm-up: the same commands on the uploaded volume itself, losing
    # the first set of each size, so that the encode's shape and both
    # decode shapes are compiled (or found in the cache) and both plans
    # built before the window opens (compiles_in_window holds the
    # program to that)
    by_size = {}
    for n, held in enumerate(sets):
        by_size.setdefault(len(held), n)
    state["warm"] = _cycle(run, state, volume["vid"], timed=False,
                           deadline=None, losses=sorted(by_size.values()))
    _warm_decode_plans(run, sets)
    return state


def _warm_decode_plans(run, sets):
    """What a server that has been up for a while holds and a new process
    does not: the decode plan of each holder set lost alone (an inverse
    of the coupled system, 20-40 ms, kept for the life of the process in
    ops/codec's plan cache). The warm-up cycle built two of the four;
    the others are asked of the program's own planner here with what a
    store passes it, so that the window measures the server after that,
    as `seal_repair._warm_trace_plans` has it."""
    from seaweedfs_tpu.ops import codec as planner
    cluster = run.cluster
    codec = cluster.servers[0].store.codec
    t0 = time.perf_counter()
    for lost in sets:
        planner.piggyback_decode_plan(
            cluster.k, cluster.m,
            tuple(s not in lost for s in range(cluster.total)),
            matrix_kind=codec.matrix_kind, matrix=codec.matrix,
            pairs=int(run.config["piggyback"]["pairs"]))
    run.emit({"phase": "warm_plans", "plans": len(sets),
              "seconds": time.perf_counter() - t0})


def _cycle(run, state, vid: int, timed: bool, deadline, losses=None) -> dict:
    """One sealed volume: the encode, then its holder losses. The
    warm-up is told which sets to lose; a cycle of the window takes the
    next ones in order and stops losing once the time is up, so that the
    command in flight then is the last one."""
    cluster, config = run.cluster, run.config
    every = set(range(cluster.total))
    cycle = {"vid": vid, "encoded": None, "rebuilds": [], "error": None,
             "raised": False, "not_landed": 0, "above_m": 0}
    state["cycles"].append(cycle)
    enc = seal_repair._timed(run, "ec.encode", state["dat_bytes"], timed,
                             "-volumeId", str(vid))
    enc["work"] = roofline_terms.encode_work(config, state["shard_bytes"])
    if enc["error"]:
        cycle["error"], cycle["raised"] = enc["error"], True
        return cycle
    if not seal_repair._landed(run, state, cycle, "ec.encode", vid,
                               sorted(every)):
        return cycle
    with run.tracer.mark("check"):
        cluster.wait_shards(vid, every, f"14 shards of volume {vid}")
        held = {}
        for urls in cluster.ec_lookup(vid).values():
            for url in urls:
                held[url] = held.get(url, 0) + 1
        cycle["above_m"] = sum(n > cluster.m for n in held.values())
        files = cluster.shard_files(vid)
        cycle["encoded"] = reference.sha256_files(
            [files[s] for s in range(cluster.total)])
    for n in range(state["per_seal"] if losses is None else len(losses)):
        if deadline and time.perf_counter() >= deadline:
            break
        if losses is None:
            which = state["losses"] % len(state["sets"])
            state["losses"] += 1
        else:
            which = losses[n]
        lost = state["sets"][which]
        rebuild = {"set": which, "lost": lost, "shas": None, "reply": None}
        cycle["rebuilds"].append(rebuild)
        with run.tracer.mark("lose"):
            cluster.delete_shards(vid, lost)
        reb = seal_repair._timed(run, "ec.rebuild",
                                 state["shard_bytes"] * len(lost), timed,
                                 "-collection", cluster.collection)
        reb["work"] = roofline_terms_decode.coupled_decode_work(
            config, state["shard_bytes"], lost)
        if reb["error"]:
            cycle["error"], cycle["raised"] = reb["error"], True
            return cycle
        rebuild["reply"] = reb["replies"].get(REBUILD) or {}
        if not seal_repair._landed(run, state, cycle, "ec.rebuild", vid,
                                   lost):
            return cycle
        with run.tracer.mark("check"):
            cluster.wait_shards(vid, every,
                                f"14 shards of {vid} after rebuild")
            files = cluster.shard_files(vid)
            cl.check(set(files) == every, f"shard files of {vid} after "
                     f"rebuild: {sorted(files)}")
            rebuild["shas"] = reference.sha256_files(
                [files[s] for s in lost])
    with run.tracer.mark("drop"):
        # the volume is done: drop its shards so disk use stays bounded
        # and the next ec.rebuild finds nothing of it
        cluster.delete_shards(vid, sorted(every))
    return cycle


def window(run, state):
    cluster = run.cluster
    deadline = time.perf_counter() + run.seconds
    run.tracer.start()
    n = 0
    while time.perf_counter() < deadline:
        vid = state["next_vid"]
        state["next_vid"] += 1
        with run.tracer.mark("clone_and_mount"):
            cluster.clone_sealed(state["kept"], vid,
                                 n % len(cluster.servers))
        cycle = _cycle(run, state, vid, timed=True, deadline=deadline)
        run.tracer.stop()       # the trace covers the first whole cycle
        n += 1
        if cycle["error"]:
            break


def _off_the_full_decode(rebuild: dict, config: dict) -> bool:
    reply, lost = rebuild["reply"], rebuild["lost"]
    alpha = int(config["piggyback"]["alpha"])
    return reply.get("repair_mode") != "full" or \
        sorted(reply.get("lost") or []) != lost or \
        list(reply.get("operand") or []) != [
            alpha * len(lost), alpha * int(config["data_shards"])] or \
        bool(reply.get("repair_fallback"))


def verify(run, state):
    """Outside the timed ops: every encoded volume's 14 shards against
    the plain reference, every rebuilt shard against the encoded one (and
    so against the reference's), every rebuild's route, lost set, operand
    and gathered bytes, every encode's spread over the holders."""
    cluster = run.cluster
    t0 = time.perf_counter()
    want = reference_piggyback.shard_shas(state["kept"] + ".dat", cluster.k,
                                          cluster.m)
    differing = rebuilt_differing = raised = not_landed = 0
    above_m = off_route = 0
    gathered = []
    for cycle in state["cycles"]:
        # a command that raised or whose shards had not landed left
        # nothing to compare: its own check counts it, not these
        encoded = cycle["encoded"] is not None
        bad_enc = encoded and sum(
            got != ref for got, ref in zip(cycle["encoded"], want))
        done = [r for r in cycle["rebuilds"] if r["shas"] is not None]
        bad_reb = bad_ref = 0
        for r in done:
            for sid, sha in zip(r["lost"], r["shas"]):
                if sha != cycle["encoded"][sid]:
                    bad_reb += 1
                elif sha != want[sid]:
                    bad_ref += 1
        differing += bad_enc + bad_ref
        rebuilt_differing += bad_reb
        raised += cycle["raised"]
        not_landed += cycle["not_landed"]
        above_m += cycle["above_m"]
        replied = [r for r in cycle["rebuilds"] if r["reply"] is not None]
        off_route += sum(_off_the_full_decode(r, run.config)
                         for r in replied)
        # a reply with no byte account cannot say it gathered k shards
        # only: counted as every shard there is
        gathered += [r["reply"]["repair_bytes"] / state["shard_bytes"]
                     if r["reply"].get("repair_bytes")
                     else float(cluster.total) for r in replied]
        if cycle is not state["warm"]:
            started = len(cycle["rebuilds"]) if encoded else 0
            run.attempted += 1 + started
            run.failed += (bad_enc > 0 or not encoded) + \
                (started - len(done)) + sum(
                    any(sha != cycle["encoded"][sid] or sha != want[sid]
                        for sid, sha in zip(r["lost"], r["shas"]))
                    for r in done)
    run.check("shards_differing_from_reference", differing, 0,
              differing == 0)
    run.check("rebuilt_shards_differing_from_encoded", rebuilt_differing, 0,
              rebuilt_differing == 0)
    run.check("commands_that_raised", raised, 0, raised == 0)
    run.check("shards_not_on_disk_when_command_returned", not_landed, 0,
              not_landed == 0)
    run.check("holders_above_m_shards", above_m, 0, above_m == 0)
    run.check("rebuilds_off_the_full_decode", off_route, 0, off_route == 0)
    worst = max(gathered, default=0.0)
    run.check("gathered_shards_at_most", worst, state["gather_limit"],
              worst <= state["gather_limit"])
    run.emit({"phase": "verify", "cycles": len(state["cycles"]),
              "rebuilds": sum(len(c["rebuilds"]) for c in state["cycles"]),
              "lost": [[r["lost"] for r in c["rebuilds"]]
                       for c in state["cycles"]],
              "reference": reference_piggyback.__name__,
              "reference_s": time.perf_counter() - t0})


end_to_end = seal_repair.end_to_end
