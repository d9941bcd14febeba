"""The piggybacked layout and the two single-shard repair routes against
a plain reference that shares nothing with the program
(`benchmarks/lib/reference_piggyback.py`: the layout's equation in its
sparse form; it never builds the program's block matrix `emat`), small
and on the CPU:

  (a) `write_ec_files(layout="piggyback")` through the numpy and the
      pipelined CPU backends gives the reference's 14 shards;
  (b) each of the ten data shards lost alone: the store's `auto` route
      picks `piggyback`, rebuilds it bit-identical, and gathers at most
      0.56 of k x shard;
  (c) the same on a flat volume: `trace`, at most 0.70;
  (d) the reference's parity XORed with the flat reference's is zero
      exactly on the sub-chunks whose gate is closed (one data shard
      non-zero at a time), and the sparse form agrees with the equation
      evaluated literally;
  (e) a corrupted theta is caught by (a).
"""

import hashlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from lib import reference, reference_piggyback  # noqa: E402

from seaweedfs_tpu.ec import to_ext, write_ec_files  # noqa: E402
from seaweedfs_tpu.ec.layout import write_layout_sidecars  # noqa: E402
from seaweedfs_tpu.ops import codec as ops_codec  # noqa: E402
from seaweedfs_tpu.ops import telemetry  # noqa: E402
from seaweedfs_tpu.ops.codec import NumpyCodec  # noqa: E402

K, M, PAIRS, ALPHA = 10, 4, 5, 32
# a few windows of 4 KiB (128-byte sub-chunks); one large-block row of 16
# windows, then small rows, the tail short of a row
LB, SB = 1 << 16, 1 << 12
DAT_BYTES = K * LB + 3 * K * SB + 12_345


def _codec(backend):
    if backend == "numpy":
        return NumpyCodec(K, M)
    from seaweedfs_tpu.ops.rs_tpu import TpuCodec
    return TpuCodec(K, M)


def _dat(dirpath, seed=27) -> str:
    base = os.path.join(str(dirpath), "1")
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, DAT_BYTES, dtype=np.uint8).tobytes())
    return base


def _shas(base) -> list:
    out = []
    for i in range(K + M):
        with open(base + to_ext(i), "rb") as f:
            out.append(hashlib.sha256(f.read()).hexdigest())
    return out


def _reference_shas(base, layout) -> list:
    ref = reference_piggyback if layout == "piggyback" else reference
    return ref.shard_shas(base + ".dat", K, M, large_block=LB,
                          small_block=SB)


def _encode(base, backend, layout):
    write_ec_files(base, codec=_codec(backend), large_block=LB,
                   small_block=SB, slab=2 * SB,
                   pipelined=(backend != "numpy"), layout=layout)


# -- (a) ---------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "tpu"])
def test_piggyback_encode_gives_the_references_shards(tmp_path, backend):
    base = _dat(tmp_path)
    want = _reference_shas(base, "piggyback")
    _encode(base, backend, "piggyback")
    assert _shas(base) == want
    # and the reference is not the flat one in disguise: data shards are
    # shared, every parity shard differs
    flat = _reference_shas(base, "flat")
    assert flat[:K] == want[:K]
    assert all(a != b for a, b in zip(flat[K:], want[K:]))


def test_encode_replies_with_the_operand_it_ran(tmp_path):
    base = _dat(tmp_path)
    assert write_ec_files(base, codec=NumpyCodec(K, M), large_block=LB,
                          small_block=SB, slab=2 * SB,
                          layout="piggyback") == (M * ALPHA, K * ALPHA)
    assert write_ec_files(base, codec=NumpyCodec(K, M), large_block=LB,
                          small_block=SB, slab=2 * SB) == (M, K)


# -- (b), (c) ----------------------------------------------------------------

@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    """One encoded volume a layout in a Store's directory, with the
    sidecars `ec.encode` leaves, and the reference's shas beside it."""
    from seaweedfs_tpu.storage.store import Store
    out = {}
    for layout in ("piggyback", "flat"):
        d = tmp_path_factory.mktemp(layout)
        base = _dat(d)
        _encode(base, "numpy", layout)
        want = _reference_shas(base, layout)
        os.remove(base + ".dat")
        open(base + ".ecx", "wb").close()
        write_layout_sidecars(base, layout, window=SB, pairs=PAIRS,
                              version=3, offset_width=4)
        out[layout] = (Store([str(d)], codec=NumpyCodec(K, M)), base, want)
    return out


@pytest.mark.parametrize("layout,route,limit", [
    ("piggyback", "piggyback", 0.56), ("flat", "trace", 0.70)])
@pytest.mark.parametrize("lost", range(K))
def test_a_lost_data_shard_takes_the_layouts_route(stores, layout, route,
                                                   limit, lost):
    store, base, want = stores[layout]
    os.remove(base + to_ext(lost))
    before = telemetry.STATS.snapshot()
    stats = {}
    rebuilt = store.rebuild_ec_shards_streaming(1, "", stats=stats)
    after = telemetry.STATS.snapshot()
    assert rebuilt == [lost]
    assert _shas(base) == want                  # bit-identical, all 14
    assert stats["repair_mode"] == route and "repair_fallback" not in stats
    share = stats["repair_bytes"] / stats["repair_baseline_bytes"]
    assert 0.5 < share <= limit, share
    assert stats["repair_baseline_bytes"] == \
        K * os.path.getsize(base + to_ext(lost))
    if route == "piggyback":
        assert stats["operand"] == [ALPHA, (K + 1) * ALPHA // 2]
        assert share == pytest.approx(0.55)
    else:
        # the symbol block is padded to the row bucket, on every codec,
        # so that the ten plans share one compiled program; the bytes
        # gathered and the bit count replied are the plan's own
        plan = ops_codec.repair_plan(
            K, M, lost, survivors=[i for i in range(K + M) if i != lost])
        assert stats["operand"] == [8, 56] and 50 <= plan.total_bits <= 56
        assert stats["repair_total_bits"] == plan.total_bits
        assert share == pytest.approx(plan.total_bits / 80)
    moved = {r: after["repair_route"][r] - before["repair_route"][r]
             for r in after["repair_route"]}
    assert moved == {"piggyback": 0, "trace": 0, "full": 0, route: 1}
    assert after["repair_fallbacks"] == before["repair_fallbacks"]


def test_two_lost_shards_take_the_full_decode_as_their_route(stores):
    """More than one lost shard was never the plane route's: the full
    coupled decode is the rebuild's own route, not a fallback."""
    store, base, want = stores["piggyback"]
    for sid in (2, 11):
        os.remove(base + to_ext(sid))
    before = telemetry.STATS.snapshot()
    stats = {}
    assert store.rebuild_ec_shards_streaming(1, "", stats=stats) == [2, 11]
    after = telemetry.STATS.snapshot()
    assert _shas(base) == want
    assert stats["repair_mode"] == "full" and stats["lost"] == [2, 11]
    assert "repair_fallback" not in stats
    assert stats["operand"] == [2 * ALPHA, K * ALPHA]
    assert stats["repair_bytes"] == stats["repair_baseline_bytes"] == \
        stats["survivor_bytes"] == K * os.path.getsize(base + to_ext(0))
    assert after["repair_route"]["full"] - before["repair_route"]["full"] == 1
    assert after["repair_fallbacks"] == before["repair_fallbacks"]
    assert after["coupled_decodes"] - before["coupled_decodes"] == 1


def test_the_device_trace_combine_has_one_shape(tmp_path):
    """The plans of RS(10,4) have 50 to 56 bits by lost shard; through a
    device codec every repair runs an (8, 56) operand, the same bytes."""
    from seaweedfs_tpu.storage.store import Store
    base = _dat(tmp_path)
    _encode(base, "numpy", "flat")
    want = _reference_shas(base, "flat")
    os.remove(base + ".dat")
    open(base + ".ecx", "wb").close()
    write_layout_sidecars(base, "flat", version=3, offset_width=4)
    store = Store([str(tmp_path)], codec=_codec("tpu"))
    bits = set()
    for lost in (2, 6, 8):      # 50, 56 and 53 bits
        bits.add(ops_codec.repair_plan(
            K, M, lost, survivors=[i for i in range(K + M)
                                   if i != lost]).total_bits)
        os.remove(base + to_ext(lost))
        stats = {}
        assert store.rebuild_ec_shards_streaming(1, "", stats=stats) == \
            [lost]
        assert stats["operand"] == [8, 56] and stats["repair_mode"] == "trace"
        assert _shas(base) == want
    assert bits == {50, 53, 56}


# -- (d) ---------------------------------------------------------------------

def _literal_parity(matrix, data, window, theta):
    """The equation as written, a sub-chunk at a time."""
    k, width = data.shape
    wsub = window // ALPHA
    mul = reference.MUL
    out = np.zeros((M, width), dtype=np.uint8)
    for j in range(M):
        for w in range(0, width, window):
            for z in range(ALPHA):
                acc = np.zeros(wsub, dtype=np.uint8)
                for i in range(k):
                    a = int(matrix[k + j, i])
                    s = data[i, w + z * wsub:w + (z + 1) * wsub]
                    acc ^= mul[a][s]
                    p, b = i >> 1, i & 1
                    if (z >> p) & 1 == b:
                        zp = z ^ (1 << p)
                        partner = data[i, w + zp * wsub:w + (zp + 1) * wsub]
                        acc ^= mul[int(mul[theta[j], a])][partner]
                out[j, w + z * wsub:w + (z + 1) * wsub] = acc
    return out


def test_sparse_form_is_the_equation_and_its_gate():
    rng = np.random.default_rng(5)
    window, wsub = 2 * ALPHA * 8, 16
    matrix = reference.coding_matrix(K, M)
    theta = reference_piggyback.thetas(M, 5)
    assert theta == [int(reference.EXP[((5 * M + j) * 11) % 255])
                     for j in range(M)] and len(set(theta)) == M
    data = rng.integers(0, 256, (K, 2 * window), dtype=np.uint8)
    got = reference_piggyback.encode_rows(matrix, data, window, PAIRS, theta)
    assert (got == _literal_parity(matrix, data, window, theta)).all()
    # one data shard non-zero at a time: the difference from the flat
    # parity lies on the sub-chunks whose gate that shard opens, and on
    # no other (random bytes: a zero sub-chunk there is a 2^-128 event)
    for i in range(K):
        alone = np.zeros_like(data)
        alone[i] = data[i]
        diff = reference_piggyback.encode_rows(
            matrix, alone, window, PAIRS, theta) ^ \
            reference.encode_rows(matrix, alone)
        touched = diff.reshape(M, -1, ALPHA, wsub).any(axis=3)
        for z in range(ALPHA):
            assert touched[:, :, z].all() == \
                reference_piggyback.gate_open(z, i), (i, z)
            assert touched[:, :, z].any() == \
                reference_piggyback.gate_open(z, i), (i, z)
    # with five pairs every sub-chunk has, of each pair, exactly one
    # shard's gate open: none is left as the flat code's
    assert all(sum(reference_piggyback.gate_open(z, i) for i in range(K))
               == PAIRS for z in range(ALPHA))


# -- (e) ---------------------------------------------------------------------

def test_a_corrupted_theta_is_caught(tmp_path, monkeypatch):
    base = _dat(tmp_path)
    want = _reference_shas(base, "piggyback")
    sound = ops_codec._pb_build

    def broken(k, m, matrix_kind, matrix, theta_seed, cap):
        return sound(k, m, matrix_kind, matrix, theta_seed + 7, cap)

    monkeypatch.setattr(ops_codec, "_pb_build", broken)
    monkeypatch.setattr(ops_codec, "_PIGGYBACK_PLAN_CACHE",
                        ops_codec._PlanLRU("piggyback"))
    _encode(base, "numpy", "piggyback")
    got = _shas(base)
    assert got[:K] == want[:K]
    assert all(a != b for a, b in zip(got[K:], want[K:]))


def test_the_benchmarks_roofline_counts_the_equations_terms():
    """benchmarks/lib/roofline_terms.py counts an operation's work from
    the configuration, in the terms of its equation: that count is the
    number of non-zero coefficients of the matrices the program builds
    (never more), and a small part of their dense size."""
    import json
    from lib import roofline, roofline_terms
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "f4-warm-piggyback-1chip.json")) as f:
        pb = json.load(f)
    with open(os.path.join(root, "benchmarks", "configs",
                           "f4-warm-rs10-4-1chip.json")) as f:
        flat = json.load(f)
    # the coupled encode: emat works on ALPHA stripe columns at once
    emat = ops_codec.piggyback_plan(K, M).emat
    enc = roofline_terms.encode_work(pb, 1 << 20)
    assert enc == {"columns": 1 << 20, "column_bytes": K + M,
                   "column_terms": M * (K + PAIRS)}
    assert np.count_nonzero(emat) == enc["column_terms"] * ALPHA
    assert roofline.column_ops(*emat.shape) == 5242880
    assert enc["column_terms"] * ALPHA * roofline_terms.TERM_OPS == 245760
    # the plane repair: its matrix works on ALPHA / 2 half-plane columns
    rep = roofline_terms.repair_work(pb, 1 << 20,
                                     {"repair_mode": "piggyback"})
    assert rep == {"columns": 1 << 19, "column_bytes": K + 3,
                   "column_terms": 2 * (K + PAIRS)}
    nnz = [np.count_nonzero(ops_codec.piggyback_repair_plan(
        K, M, lost).matrix) for lost in range(K)]
    assert max(nnz) == rep["column_terms"] * ALPHA // 2
    assert roofline_terms.repair_work(pb, 1 << 20,
                                      {"repair_mode": "full"}) is None
    # flat: the dense (m, k) operand is the algorithm itself
    assert roofline_terms.encode_work(flat, 1 << 20)["column_terms"] == \
        np.count_nonzero(NumpyCodec(K, M).matrix[K:])
    # the trace combine: the plan's bits, not the padded block's
    plan = ops_codec.repair_plan(K, M, 0)
    tr = roofline_terms.repair_work(
        flat, 1 << 20, {"repair_mode": "trace",
                        "repair_total_bits": plan.total_bits})
    assert tr == {"columns": 1 << 17, "column_bytes": plan.total_bits + 8,
                  "column_terms": 8 * plan.total_bits}
    assert np.count_nonzero(plan.combine) <= tr["column_terms"]
    peak = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    least = roofline_terms.least_seconds(enc, peak)
    assert least["bound"] == "int8"
    assert least["seconds"] == pytest.approx(
        (1 << 20) * 60 * 128 / 393e12)
    assert roofline_terms.least_seconds(rep, peak)["bound"] == "hbm"


HOLDER_SETS = ([0, 4, 8, 12], [1, 5, 9, 13], [2, 6, 10], [3, 7, 11])


@pytest.mark.parametrize("lost", HOLDER_SETS + ([12], [2, 7], [3, 11]),
                         ids=lambda lost: "-".join(map(str, lost)))
def test_the_decodes_roofline_counts_the_equations_terms(lost):
    """benchmarks/lib/roofline_terms_decode.py counts the full coupled
    decode from the configuration's equation: a rebuilt byte is as many
    terms as an encoded parity byte (k flat + one gated a pair). A lone
    lost parity shard dispatches exactly those; a lost data shard's rows
    fill in (the inverse is the program's choice of operand) and are
    never fewer."""
    import json
    from lib import roofline_terms, roofline_terms_decode
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "f4-warm-piggyback-4srv-1chip.json")) as f:
        config = json.load(f)
    assert [sorted(s) for s in config["holder_sets"].values()] == \
        [list(s) for s in HOLDER_SETS]
    work = roofline_terms_decode.coupled_decode_work(config, 1 << 20, lost)
    per_parity_byte = roofline_terms.encode_work(
        config, 1 << 20)["column_terms"] // M
    assert work == {"columns": 1 << 20, "column_bytes": K + len(lost),
                    "column_terms": len(lost) * per_parity_byte}
    assert per_parity_byte == K + PAIRS
    src, missing, coeffs = ops_codec.piggyback_decode_plan(
        K, M, tuple(i not in lost for i in range(K + M)))
    assert missing == list(lost) and len(src) == K
    assert coeffs.shape == (ALPHA * len(lost), ALPHA * K)
    nnz = int(np.count_nonzero(coeffs))
    if all(s >= K for s in lost):
        assert nnz == work["column_terms"] * ALPHA
    else:
        assert work["column_terms"] * ALPHA < nnz <= coeffs.size
    # the least time it gives is that of as many parity bytes encoded
    peak = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    least = roofline_terms.least_seconds(work, peak)
    assert least["int8_seconds"] == pytest.approx(
        (1 << 20) * work["column_terms"] * 128 / 393e12)
