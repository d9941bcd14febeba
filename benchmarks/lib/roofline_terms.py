"""What a coded operation needs of the chip, counted from its equation.

`lib/roofline.py` counts a dense (r, k) operand, which is the algorithm
itself for flat RS(k, m): every coefficient of its matrix is non-zero.
The operands of the piggyback layout and of the single-shard repairs are
not: the coupled encode's (128, 320) block matrix holds 15 non-zero
coefficients of 320 a row, the plane repair's (32, 176) at most 15 of 176,
and the trace combine is padded from its plan's 50-56 rows to 56. Zeros
and padding are what the program chose to dispatch, and by
`lib/roofline.py`'s own rule the program's cost does not enter: a share
counted from them cannot pass 100 % whatever the kernel does, and falls
when a sparser kernel gets faster.

So the work here is counted per *column of the operation's equation*, from
the configuration: the bytes that have to cross HBM (each input byte read
once, each output byte written once) and the GF(2^8) multiply-adds
("terms") the equation names. A term is what `lib/roofline.py` counts for
one coefficient: lifted to GF(2), 8 x 8 multiply-adds = 128 int8 ops.

    flat encode, a byte column of the stripe: k bytes in, m out, m*k terms
    coupled encode (the configuration's `piggyback.equation`), the same
        column: k in, m out; each parity byte is k flat terms and one
        gated partner term a pair (of a pair's two shards exactly one has
        its gate open on a given sub-chunk): m*(k + pairs) terms
    plane repair of a data shard, a byte column of its half-plane: the
        k - 1 other data shards and two parities in, the lost shard's two
        coupled bytes out; two parity equations, each less k - 1 flat and
        pairs - 1 gated terms, then a 2 x 2 solve: 2*(k + pairs) terms
    trace repair, eight bytes of the lost shard (one byte of each packed
        bit-plane): the plan's `total_bits` bytes in, 8 out; the combine
        is an (8, total_bits) {0,1} matrix, counted as `lib/roofline.py`
        counts any coefficient: 8 * total_bits terms (an upper count: a
        {0,1} coefficient needs an XOR, not a multiply)
"""

from lib import roofline

TERM_OPS = roofline.column_ops(1, 1)


def encode_work(config: dict, shard_bytes: int) -> dict:
    k, m = int(config["data_shards"]), int(config["parity_shards"])
    gated = int(config["piggyback"]["pairs"]) \
        if config["layout"] == "piggyback" else 0
    return {"columns": shard_bytes, "column_bytes": k + m,
            "column_terms": m * (k + gated)}


def repair_work(config: dict, shard_bytes: int, reply: dict):
    """One lost data shard by the layout's route; None where the reply
    names another route (the checks count that, not the roofline)."""
    k = int(config["data_shards"])
    if config["layout"] == "piggyback":
        if reply.get("repair_mode") != "piggyback":
            return None
        pairs = int(config["piggyback"]["pairs"])
        return {"columns": shard_bytes // 2, "column_bytes": (k + 1) + 2,
                "column_terms": 2 * (k + pairs)}
    bits = int(reply.get("repair_total_bits") or 0)
    if reply.get("repair_mode") != "trace" or not bits:
        return None
    return {"columns": -(-shard_bytes // 8), "column_bytes": bits + 8,
            "column_terms": 8 * bits}


def least_seconds(work: dict, peak: dict) -> dict:
    """The least time one chip could take for `work`, and which of the
    two limits bounds it."""
    by_bytes = work["columns"] * work["column_bytes"] / \
        peak["hbm_bytes_per_s"]
    by_ops = work["columns"] * work["column_terms"] * TERM_OPS / \
        peak["int8_ops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "int8",
            "hbm_seconds": by_bytes, "int8_seconds": by_ops}
