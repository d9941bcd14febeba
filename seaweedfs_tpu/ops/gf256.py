"""GF(2^8) arithmetic over the polynomial x^8+x^4+x^3+x^2+1 (0x11D).

This is the field used by the reference's Reed-Solomon dependency
(klauspost/reedsolomon, imported at reference
weed/storage/erasure_coding/ec_encoder.go:8): generator element 2,
field polynomial 0x11D. Tables are built once at import with numpy.

Matrix builders:
  * vandermonde_systematic(k, total) — the reference dependency's default
    encoding matrix: a (total x k) Vandermonde matrix right-multiplied by the
    inverse of its top square, so the top k rows are the identity (systematic
    code: data shards are stored verbatim, parity rows below).
  * cauchy(k, total) — identity on top, parity rows m[r][c] = 1/(r ^ c);
    supports any geometry with k + m <= 256 (BASELINE config 4: RS(6,3),
    RS(20,4)).
"""

from __future__ import annotations

import numpy as np

FIELD_POLY = 0x11D
GENERATOR = 2


def _build_tables():
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= FIELD_POLY
    # duplicate so exp[(log a + log b)] needs no mod
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    log[0] = -1  # sentinel; never indexed on the hot path
    return exp, log


EXP_TABLE, LOG_TABLE = _build_tables()


def _build_mul_table():
    # 256x256 full multiplication table — the numpy codec's inner loop is a
    # single row-gather MUL_TABLE[coeff][data].
    a = np.arange(256, dtype=np.int32)
    la = LOG_TABLE[a][:, None]  # (256,1)
    lb = LOG_TABLE[a][None, :]  # (1,256)
    t = EXP_TABLE[(la + lb) % 255]
    t = t.astype(np.uint8)
    t[0, :] = 0
    t[:, 0] = 0
    return t


MUL_TABLE = _build_mul_table()
INV_TABLE = np.zeros(256, dtype=np.uint8)
INV_TABLE[1:] = EXP_TABLE[255 - LOG_TABLE[np.arange(1, 256)]]


def gf_mul(a: int, b: int) -> int:
    return int(MUL_TABLE[a, b])


def gf_div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF(2^8) division by zero")
    if a == 0:
        return 0
    return int(EXP_TABLE[(LOG_TABLE[a] - LOG_TABLE[b]) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of zero")
    return int(INV_TABLE[a])


def gf_pow(a: int, n: int) -> int:
    """a**n in GF(2^8). 0**0 == 1 (matches the reference dependency)."""
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP_TABLE[(LOG_TABLE[a] * n) % 255])


# ---------------------------------------------------------------------------
# Matrix algebra over GF(2^8) (small matrices: k+m <= 256)
# ---------------------------------------------------------------------------

def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(r x n) @ (n x c) over GF(2^8)."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    r, n = a.shape
    n2, c = b.shape
    assert n == n2
    out = np.zeros((r, c), dtype=np.uint8)
    for i in range(r):
        # gather-per-coefficient, XOR-accumulate
        acc = np.zeros(c, dtype=np.uint8)
        for j in range(n):
            acc ^= MUL_TABLE[a[i, j]][b[j]]
        out[i] = acc
    return out


def mat_inv(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inverse over GF(2^8). Raises ValueError if singular."""
    m = np.array(m, dtype=np.uint8)
    n = m.shape[0]
    assert m.shape == (n, n)
    aug = np.concatenate([m, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        # find pivot
        piv = -1
        for row in range(col, n):
            if aug[row, col] != 0:
                piv = row
                break
        if piv < 0:
            raise ValueError("singular matrix over GF(2^8)")
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        # scale pivot row to 1
        inv_p = INV_TABLE[aug[col, col]]
        aug[col] = MUL_TABLE[inv_p][aug[col]]
        # eliminate other rows
        for row in range(n):
            if row != col and aug[row, col] != 0:
                aug[row] ^= MUL_TABLE[aug[row, col]][aug[col]]
    return aug[:, n:].copy()


def vandermonde(rows: int, cols: int) -> np.ndarray:
    v = np.zeros((rows, cols), dtype=np.uint8)
    for r in range(rows):
        for c in range(cols):
            v[r, c] = gf_pow(r, c)
    return v


def vandermonde_systematic(data_shards: int, total_shards: int) -> np.ndarray:
    """The reference dependency's default encode matrix (systematic form)."""
    vm = vandermonde(total_shards, data_shards)
    top = vm[:data_shards, :]
    return mat_mul(vm, mat_inv(top))


def cauchy(data_shards: int, total_shards: int) -> np.ndarray:
    m = np.zeros((total_shards, data_shards), dtype=np.uint8)
    for i in range(data_shards):
        m[i, i] = 1
    for r in range(data_shards, total_shards):
        for c in range(data_shards):
            m[r, c] = INV_TABLE[r ^ c]
    return m


def build_matrix(data_shards: int, total_shards: int,
                 kind: str = "vandermonde") -> np.ndarray:
    if not (0 < data_shards < total_shards <= 256):
        raise ValueError(f"bad geometry k={data_shards} total={total_shards}")
    if kind == "vandermonde":
        return vandermonde_systematic(data_shards, total_shards)
    if kind == "cauchy":
        return cauchy(data_shards, total_shards)
    raise ValueError(f"unknown matrix kind {kind!r}")


# ---------------------------------------------------------------------------
# GF(2) bit-plane expansion — the bridge to the TPU kernel.
#
# Multiplication by a constant c in GF(2^8) is linear over GF(2)^8, so the
# whole (total x k) byte matrix lifts to a (8k x 8(total-k)) binary matrix and
# RS encoding becomes a {0,1} matmul followed by mod-2 — which is exactly an
# MXU-shaped op on TPU (see ops/rs_tpu.py).
# ---------------------------------------------------------------------------

def bit_matrix(coeff_rows: np.ndarray) -> np.ndarray:
    """Lift a (rows x cols) GF(2^8) coefficient matrix to GF(2).

    Returns B of shape (cols*8, rows*8), uint8 in {0,1}, such that for input
    bits x (n, cols*8) (bit l of input byte j at column j*8+l, LSB-first) the
    output bits are (x @ B) % 2 with output byte i's bit b at column i*8+b.
    """
    coeff_rows = np.asarray(coeff_rows, dtype=np.uint8)
    rows, cols = coeff_rows.shape
    b = np.zeros((cols * 8, rows * 8), dtype=np.uint8)
    for i in range(rows):
        for j in range(cols):
            c = int(coeff_rows[i, j])
            if c == 0:
                continue
            for l in range(8):
                prod = MUL_TABLE[c, 1 << l]  # c * x^l
                for k in range(8):
                    if (prod >> k) & 1:
                        b[j * 8 + l, i * 8 + k] = 1
    return b


def pack_bit_matrix(coeff_rows: np.ndarray) -> np.ndarray:
    """bit_matrix with the input-bit axis packed into uint32 words.

    Returns P of shape (ceil(cols*8/32), rows*8) uint32 where bit
    (j % 32) of P[j // 32, o] is bit_matrix[j, o]. With payload columns
    packed the same way (4 consecutive byte rows -> one uint32, byte j
    at bit offset 8*(j % 4)), output bit o of a column is
    parity(popcount(x & P[:, o])) — the AND/popcount form of the GF(2)
    matmul that CPU backends run ~2 orders of magnitude faster than the
    8x-lifted int8 dot (ops/rs_tpu.py chooses per platform).
    """
    bm = bit_matrix(coeff_rows)
    k8, r8 = bm.shape
    packed = np.zeros(((k8 + 31) // 32, r8), dtype=np.uint32)
    for j in range(k8):
        packed[j // 32] |= bm[j].astype(np.uint32) << np.uint32(j % 32)
    return packed


# ---------------------------------------------------------------------------
# Field trace and GF(2) linear algebra — the substrate of trace repair.
#
# Tr(x) = x + x^2 + ... + x^128 maps GF(2^8) onto GF(2), and
# Tr(a*x) is GF(2)-linear in x for any fixed a.  A lost RS symbol can
# therefore be rebuilt from *bits* Tr(mask * c_i) collected from the
# survivors instead of their full bytes (arxiv 2205.11015); the masks
# come from dual codewords, found below via gf_nullspace.
# ---------------------------------------------------------------------------

def _build_trace_table():
    x = np.arange(256, dtype=np.uint8)
    acc = x.copy()
    cur = x.copy()
    for _ in range(7):
        cur = MUL_TABLE[cur, cur]
        acc ^= cur
    assert set(np.unique(acc)) <= {0, 1}
    return acc


TRACE_TABLE = _build_trace_table()
# TRACE_MUL[a, b] = Tr(a*b) in {0,1} — the survivor-side projection is a
# single row-gather of this table followed by packbits.
TRACE_MUL = TRACE_TABLE[MUL_TABLE]


def gf_trace(a: int) -> int:
    return int(TRACE_TABLE[a])


def gf_nullspace(a: np.ndarray):
    """One nullspace vector of a (r x c, r < c) matrix over GF(2^8),
    or None if the map is injective. Used by ops/codec.repair_plan to
    produce dual codewords vanishing on a chosen position subset."""
    a = np.array(a, dtype=np.uint8)
    r, c = a.shape
    piv_of_col = {}
    row = 0
    for col in range(c):
        piv = None
        for rr in range(row, r):
            if a[rr, col]:
                piv = rr
                break
        if piv is None:
            continue
        if piv != row:
            a[[row, piv]] = a[[piv, row]]
        inv = INV_TABLE[a[row, col]]
        a[row] = MUL_TABLE[inv][a[row]]
        for rr in range(r):
            if rr != row and a[rr, col]:
                a[rr] ^= MUL_TABLE[a[rr, col]][a[row]]
        piv_of_col[col] = row
        row += 1
        if row == r:
            break
    free = [col for col in range(c) if col not in piv_of_col]
    if not free:
        return None
    f = free[0]
    x = np.zeros(c, dtype=np.uint8)
    x[f] = 1
    for col, rr in piv_of_col.items():
        x[col] = a[rr, f]  # char 2: -v == v
    return x


def gf2_reduce(vals: np.ndarray, basis) -> np.ndarray:
    """Reduce uint8 values by a reduced GF(2) basis of field elements
    (distinct leading bits, descending). Vectorized over vals."""
    v = vals.copy()
    for b in basis:
        lead = b.bit_length() - 1
        mask = ((v >> lead) & 1).astype(bool)
        v[mask] ^= b
    return v


def gf2_insert(basis: list, val: int) -> bool:
    """Insert val into a reduced GF(2) basis in place; True if the
    span grew."""
    for b in basis:
        lead = b.bit_length() - 1
        if (val >> lead) & 1:
            val ^= b
    if val:
        basis.append(int(val))
        basis.sort(reverse=True)
        return True
    return False


def gf2_decompose(val: int, basis) -> list:
    """Coordinates of val over a reduced GF(2) basis (same order as
    basis). Raises ValueError when val is outside the span."""
    coords = [0] * len(basis)
    for i, b in enumerate(basis):
        lead = b.bit_length() - 1
        if (val >> lead) & 1:
            val ^= b
            coords[i] = 1
    if val:
        raise ValueError("value outside GF(2) span")
    return coords


def gf2_mat_inv(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inverse of a {0,1} matrix over GF(2)."""
    m = np.array(m, dtype=np.uint8) & 1
    n = m.shape[0]
    assert m.shape == (n, n)
    aug = np.concatenate([m, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = -1
        for row in range(col, n):
            if aug[row, col]:
                piv = row
                break
        if piv < 0:
            raise ValueError("singular matrix over GF(2)")
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        for row in range(n):
            if row != col and aug[row, col]:
                aug[row] ^= aug[col]
    return aug[:, n:].copy()


def decode_coeff_rows(matrix: np.ndarray, k: int, survivor_rows,
                      missing_rows, inv: np.ndarray = None) -> np.ndarray:
    """Fused decode plan: (len(missing_rows), k) GF coefficients C such
    that missing = C @ stack(first k surviving shards).

    Data rows come from the inverse of the first-k-survivors submatrix,
    parity rows from matrix[row] @ that inverse — one derivation shared
    by ReedSolomonCodec.decode_plan and ec/encoder._rebuild_coeffs, so
    the two call sites cannot drift apart.
    """
    src = list(survivor_rows)[:k]
    if inv is None:
        inv = mat_inv(matrix[src, :])
    rows = []
    for r in missing_rows:
        if r < k:
            rows.append(inv[r])
        else:
            rows.append(mat_mul(matrix[r:r + 1, :], inv)[0])
    if not rows:
        return np.zeros((0, k), dtype=np.uint8)
    return np.stack(rows, axis=0)
