"""What a GF(2^8) matrix dispatch needs of the chip, from its shapes.

One column of an (r, k) dispatch reads k bytes and writes r bytes of HBM
and, lifted to GF(2), is an (8r x 8k) {0,1} matrix times a vector:
8r * 8k multiply-adds = 2 * 64 * r * k int8 operations. Padding of the
(k, n) array to 16 rows in HBM tiling, bit-plane temporaries and bucket
padding are the program's cost, not the algorithm's: they do not enter.
"""

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}: add it with its source")
    return table[device_kind]


def column_bytes(r: int, k: int) -> int:
    return k + r


def column_ops(r: int, k: int) -> int:
    return 2 * (8 * r) * (8 * k)


def least_seconds(columns: int, r: int, k: int, peak: dict) -> dict:
    """The least time one chip could take for `columns` columns, and
    which of the two bounds it."""
    by_bytes = columns * column_bytes(r, k) / peak["hbm_bytes_per_s"]
    by_ops = columns * column_ops(r, k) / peak["int8_ops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "int8",
            "hbm_seconds": by_bytes, "int8_seconds": by_ops}
