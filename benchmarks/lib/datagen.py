"""Needle sizes of a traffic mix: the same set for every seed, in an
order drawn from the seed, so the seed changes the bytes and the order
and never the amount of work."""

import numpy as np


def _ragged(n: int, spec: dict) -> np.ndarray:
    base = int(spec["bytes"])
    spread = base * float(spec["ragged"])
    return np.rint(base + np.linspace(-spread, spread, n)).astype(np.int64)


SHAPES = {"ragged": _ragged}


def needle_sizes(spec: dict, total_bytes: int, seed: int,
                 stream: int) -> np.ndarray:
    """The fewest needles of the spec's shape whose payload reaches
    total_bytes, permuted by (seed, stream)."""
    shape = SHAPES[spec["sizes"]]
    lo, hi = 1, 2
    while shape(hi, spec).sum() < total_bytes:
        lo, hi = hi, hi * 2
    while lo < hi:
        mid = (lo + hi) // 2
        if shape(mid, spec).sum() < total_bytes:
            lo = mid + 1
        else:
            hi = mid
    sizes = shape(lo, spec)
    return sizes[np.random.default_rng([seed, 7, stream]).permutation(lo)]
