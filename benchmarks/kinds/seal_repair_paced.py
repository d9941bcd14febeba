"""A warm tier whose volume servers pull in the background under a budget
(`weed volume -compactionMBps`), losing single shards back to back:

    ec.encode -volumeId v  ->  14 shards at the master, 4+4+3+3
    then `repairs_per_seal` times:
        one data shard lost  ->  loss at the master
        ->  ec.rebuild -collection c  ->  14 shards, no faster than the
            bytes it pulled from the other holders over the budget

The loop, the route checks and the reference are `seal_repair.py`'s, used
as they are: the same shards in the same named order, the layout's
single-shard route and its byte share, rebuilt shards bit-identical to the
reference's. What this kind adds is the budget, held with the plain
arithmetic of bytes over seconds and with nothing of the budget's own
counters: set-up reads the rate off every server and compares it with the
configuration's; during the window a finish hook of its own sums the
`bytes` tag of the holders' read handlers' spans (what the holders say they
sent: `run.py`'s hook keeps counts and durations only); afterwards

    paced_rate_share_at_most: those bytes over the summed walls of the
        timed ec.rebuild commands, as a share of the configured rate, is
        at most the traffic file's limit (one refill window of credit a
        repair over its wall);
    repairs_faster_than_their_bytes_allow: no timed repair's wall is
        under (its remote bytes by its node's reply - one refill window's
        credit - one pull window of stripes) / rate.

The cell's end-to-end metric is `rebuild_mbps` alone: three encodes a
window are no sample of a rate.
"""

from kinds import seal_repair
from lib import cluster as cl
from lib import controls

REBUILD = seal_repair.NODE_ROUTE["ec.rebuild"]
RATE_KNOB = "SW_COMPACTION_MBPS"
# the holders' ends of the three wire formats a rebuild's readers speak
READ_HANDLERS = ("GET /admin/ec/shard_read",
                 "POST /admin/ec/shard_repair_read",
                 "POST /admin/ec/shard_plane_read")


def _refuse_a_program_without_the_budget():
    """The cell holds every ec.rebuild to a budget that a program older
    than it does not have: there `-compactionMBps` reaches the vacuum
    alone and a rebuild pulls as fast as loopback goes (the program that
    charges its pulls counts `throttle` in ops/telemetry). Such a program
    cannot be measured here; say so at once, before anything is started."""
    from seaweedfs_tpu.ops import telemetry
    if "throttle" not in telemetry.STATS.snapshot():
        raise SystemExit(
            "benchmarks/kinds/seal_repair_paced.py: this program's volume "
            "servers have no budget for what a rebuild pulls "
            "(-compactionMBps throttles the vacuum alone, ops/telemetry "
            "has no throttle): the paced single-shard-repair cell cannot "
            "be measured on it")


_refuse_a_program_without_the_budget()


# -- the control of this mix (seal_repair's import added its own) -----------

def ignore_the_budget():
    """Every charge to a server's budget returns at once, as a rebuild
    that never heard of the flag: the shards come out the same by the same
    route, faster than the bytes the holders sent may cross the budget."""
    from seaweedfs_tpu.util import throttler
    throttler.ByteBudget.reserve = lambda self, n: 0.0


controls.CONTROLS.update(ignore_the_budget=ignore_the_budget)


# -- the loop is seal_repair's ----------------------------------------------

def prepare(run) -> dict:
    rate = int(run.config["env"][RATE_KNOB]) << 20
    cl.check(rate == int(run.config["pull_budget"]["bytes_per_second"]),
             f"the configuration's {RATE_KNOB} and its pull_budget differ")
    budgets = [vs.pull_budget for vs in run.cluster.servers]
    cl.check(all(b is not None and b.bps == rate for b in budgets),
             f"every volume server's budget should read {rate} B/s: "
             f"{[b and b.bps for b in budgets]}")
    state = seal_repair.prepare(run)
    state["rate"] = rate
    state["refill_bytes"] = rate * float(
        run.config["pull_budget"]["refill_window_s"])
    state["holders_sent"] = 0
    return state


def window(run, state):
    from seaweedfs_tpu.util import tracing

    def on_span(span: dict):
        if span["name"] in READ_HANDLERS:
            state["holders_sent"] += int(span["tags"].get("bytes") or 0)

    tracing.add_finish_hook(on_span)
    try:
        seal_repair.window(run, state)
    finally:
        tracing.remove_finish_hook(on_span)


def _pull_window_bytes(reply: dict) -> float:
    """The remote bytes of as many stripes as the gather keeps in flight
    (SW_EC_GATHER_WINDOW), by the node's own account of its stripes."""
    from seaweedfs_tpu.ec import transport
    stripes = reply.get("gather_stripes") or 1
    return reply.get("gather_remote_bytes", 0) / stripes * \
        min(transport.pull_window(), stripes)


def verify(run, state):
    seal_repair.verify(run, state)
    rate = state["rate"]
    repairs = [r for r in run.ops if r["op"] == "ec.rebuild"
               and not r["error"]]
    walls = sum(r["wall_s"] for r in repairs)
    share = state["holders_sent"] / walls / rate if walls else 0.0
    limit = float(run.traffic["paced_rate_share_at_most"])
    run.check("paced_rate_share_at_most", share, limit, share <= limit)
    too_fast, rows = 0, []
    for r in repairs:
        reply = r["replies"].get(REBUILD) or {}
        remote = reply.get("gather_remote_bytes", 0)
        least = (remote - state["refill_bytes"] -
                 _pull_window_bytes(reply)) / rate
        too_fast += r["wall_s"] < least
        rows.append({"wall_s": r["wall_s"], "remote_bytes": remote,
                     "least_s": least,
                     "paced_wall_s": reply.get("paced_wall_s")})
    run.check("repairs_faster_than_their_bytes_allow", too_fast, 0,
              too_fast == 0)
    run.emit({"phase": "paced", "rate_bytes_per_s": rate,
              "holders_sent_bytes": state["holders_sent"],
              "timed_repair_walls_s": walls, "repairs": rows})


def end_to_end(run, state, window_s: float) -> dict:
    out = seal_repair.end_to_end(run, state, window_s)
    out.pop("encode_mbps", None)
    return out
