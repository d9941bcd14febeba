"""What the full coupled decode needs of the chip, counted from the
configuration's equation as `lib/roofline_terms.py` counts the coupled
encode (that file is the accepted benchmark's and is not edited; this one
stands beside it and returns the same shape of `work`, which
`readers/trace_kernel_terms.py` reads off a record).

The operation: the shards `lost` of a piggyback volume rebuilt from k whole
survivor shards. Per byte column of the stripe k bytes have to come in and
|lost| go out. A rebuilt byte, data or parity, is a GF(2^8) combination of
the k source bytes of its column and, of each coupled pair, one gated
partner term (of a pair's two shards exactly one has its gate open on a
given sub-chunk: the configuration's `piggyback.equation`) - the count the
encode's parity byte has, k + pairs terms, because decoding a linear code
of that shape is solving the same equations for other unknowns:

    |lost| * (k + pairs) terms a column

The program dispatches more than that: `ops/codec.piggyback_decode_plan`
inverts the coupled system once a loss pattern and the inverse fills in, so
its (32 * |lost|, 320) operand is dense where the encode's (128, 320) holds
15 non-zeros of 320 a row. That fill-in is the program's choice of operand
(a two-step decode - strip the known terms, then solve a small system -
would not pay it) and by `lib/roofline.py`'s own rule it does not enter;
only a lone lost parity shard dispatches exactly the counted terms (tied in
tests/test_piggyback_reference.py). How full the matrix unit ran on the
operand as dispatched is printed beside the share by the reader
(`dispatched_operand_fill_pct` of the `kernel_roofline` line), from the
`operand` the rebuilding node replied with.
"""


def coupled_decode_work(config: dict, shard_bytes: int, lost) -> dict:
    k = int(config["data_shards"])
    pairs = int(config["piggyback"]["pairs"])
    return {"columns": shard_bytes, "column_bytes": k + len(lost),
            "column_terms": len(lost) * (k + pairs)}
