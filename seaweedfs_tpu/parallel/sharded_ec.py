"""Multi-chip erasure coding: SPMD GF(2) matmuls over a device mesh.

Two sharding strategies compose (the EC analogs of DP/TP — SURVEY §2.6):

  * encode — stripes are independent byte positions, so the payload axis n
    shards over 'data' (pure data parallel, zero communication), while the
    parity-output bit-rows shard over 'shard' (output/tensor parallel; the
    input is replicated across that axis by GSPMD). One jit, XLA inserts
    the layout.

  * rebuild — the contraction (input bit-rows of surviving shards) shards
    over 'shard': each device holds a slice of the surviving shards, computes
    its partial GF(2) products, and the XOR-reduction completes with a
    lax.psum over ICI followed by mod 2. This is the device-level analog of
    the reference's reconstruct-on-read gathering >=10 sibling shards over
    gRPC (reference store_ec.go:319-373).

All arithmetic is exact int32; results are bit-identical to the single-chip
and CPU backends.
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops import device_stats, gf256


def _pad_rows(mat: np.ndarray, mult: int) -> np.ndarray:
    rows = mat.shape[0]
    pad = (-rows) % mult
    if pad == 0:
        return mat
    return np.concatenate(
        [mat, np.zeros((pad, mat.shape[1]), dtype=mat.dtype)], axis=0)


def encode_in_specs(mesh, m: int):
    """The PartitionSpecs sharded_encode_fn declares for its inputs
    (bitmat, data). Multi-process callers must BUILD their global
    arrays with exactly these (jit refuses mismatched committed inputs
    across processes) — one definition, used by both sides."""
    from jax.sharding import PartitionSpec as P
    bm_cols = "shard" if (m * 8) % mesh.shape["shard"] == 0 else None
    return P(None, bm_cols), P(None, "data")


def rebuild_in_specs(mesh):
    """PartitionSpecs for sharded_rebuild_fn's (bitmat_dec, survivors)."""
    from jax.sharding import PartitionSpec as P
    return P("shard", None), P(None, "data")


def sharded_encode_fn(mesh, k: int, m: int, n: int):
    """Returns (jitted_fn, bitmat) for distributed encode.

    jitted_fn(bitmat (k*8, m*8) int8, data (k, n) uint8) -> parity (m, n),
    with n sharded over 'data' and the parity rows over 'shard'.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def fn(bitmat, data):
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = ((data[:, None, :] >> shifts[None, :, None]) & 1)
        x = bits.reshape(k * 8, n).astype(jnp.int8)
        y = jax.lax.dot_general(
            bitmat.T, x, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        ybits = (y & 1).astype(jnp.uint8).reshape(m, 8, n)
        weights = (jnp.uint8(1) << shifts)[None, :, None]
        return (ybits * weights).sum(axis=1, dtype=jnp.uint8)

    bitmat = gf256.bit_matrix(
        gf256.build_matrix(k, k + m)[k:]).astype(np.int8)
    # parity rows shard over 'shard' only when they divide evenly; otherwise
    # the output replicates across that axis (the matmul itself still
    # partitions over 'data')
    out_rows = "shard" if m % mesh.shape["shard"] == 0 else None
    bm_spec, data_spec = encode_in_specs(mesh, m)
    jfn = device_stats.wrap(
        jax.jit(
            fn,
            in_shardings=(NamedSharding(mesh, bm_spec),
                          NamedSharding(mesh, data_spec)),
            out_shardings=NamedSharding(mesh, P(out_rows, "data"))),
        "sharded_ec.encode_fn")
    return jfn, bitmat


def sharded_rebuild_fn(mesh, k: int, n_out_shards: int, n: int):
    """Returns jitted_fn for distributed reconstruct with explicit psum.

    jitted_fn(bitmat_dec (k*8p, out*8) int8 sharded over 'shard' on axis 0,
              survivors (k, n) uint8 sharded ('shard' on rows, 'data' on n))
      -> rebuilt (n_out_shards, n) uint8, n sharded over 'data'.

    k*8 is zero-padded so the contraction axis splits evenly over 'shard';
    zero rows contribute nothing to the XOR.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard_ax = mesh.shape["shard"]
    k8p = k * 8 + ((-k * 8) % shard_ax)
    out8 = n_out_shards * 8

    def local(bm_local, bits_local):
        # bm_local (k8p/s, out8), bits_local (k8p/s, n/d)
        y = jax.lax.dot_general(
            bm_local.T, bits_local,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = jax.lax.psum(y, "shard")
        shifts = jnp.arange(8, dtype=jnp.uint8)
        ybits = (y & 1).astype(jnp.uint8).reshape(n_out_shards, 8, -1)
        weights = (jnp.uint8(1) << shifts)[None, :, None]
        return (ybits * weights).sum(axis=1, dtype=jnp.uint8)

    smap = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("shard", None), P("shard", "data")),
        out_specs=P(None, "data"))

    def fn(bitmat_dec, survivors):
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = ((survivors[:, None, :] >> shifts[None, :, None]) & 1)
        x = bits.reshape(k * 8, n).astype(jnp.int8)
        x = jnp.pad(x, ((0, k8p - k * 8), (0, 0)))
        return smap(bitmat_dec, x)

    bm_spec, surv_spec = rebuild_in_specs(mesh)
    return device_stats.wrap(
        jax.jit(
            fn,
            in_shardings=(NamedSharding(mesh, bm_spec),
                          NamedSharding(mesh, surv_spec)),
            out_shardings=NamedSharding(mesh, P(None, "data"))),
        "sharded_ec.rebuild_fn")


def decode_bitmat(k: int, m: int, survivor_rows, missing_rows,
                  pad_to_mult: int = 1) -> np.ndarray:
    """GF(2) lift of the decode matrix restoring missing_rows from the first
    k survivor_rows, zero-padded on the contraction axis to pad_to_mult.
    The coefficient derivation is the shared fused decode plan
    (gf256.decode_coeff_rows — same rows ReedSolomonCodec.decode_plan
    and rebuild_ec_files dispatch in one matmul)."""
    matrix = gf256.build_matrix(k, k + m)
    coeffs = gf256.decode_coeff_rows(matrix, k, survivor_rows,
                                     missing_rows)  # (len(missing), k)
    bm = gf256.bit_matrix(coeffs).astype(np.int8)  # (k*8, len(missing)*8)
    return _pad_rows(bm, pad_to_mult)


def distributed_ec_step(mesh, k: int = 10, m: int = 4,
                        n_per_device: int = 2048):
    """One full distributed EC 'training step' for dry-runs: encode a
    sharded payload, drop m shards, rebuild them with the psum path, and
    return (parity, rebuilt, max_abs_diff_vs_encode).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    data_ax = mesh.shape["data"]
    shard_ax = mesh.shape["shard"]
    n = n_per_device * data_ax

    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (k, n), dtype=np.uint8)

    enc_fn, enc_bitmat = sharded_encode_fn(mesh, k, m, n)
    parity = enc_fn(jnp.asarray(enc_bitmat), jnp.asarray(data))

    # drop the last m data shards; reconstruct them from the first k
    # survivors (k-m data shards + m parity shards)
    survivors = list(range(k - m)) + list(range(k, k + m))
    missing = list(range(k - m, k))
    reb_fn = sharded_rebuild_fn(mesh, k, len(missing), n)
    bm_dec = decode_bitmat(k, m, survivors, missing, pad_to_mult=shard_ax)
    surv_data = np.concatenate(
        [data[: k - m], np.asarray(parity)], axis=0)  # (k, n)
    rebuilt = reb_fn(jnp.asarray(bm_dec), jnp.asarray(surv_data))

    diff = int(np.abs(np.asarray(rebuilt).astype(np.int32)
                      - data[k - m: k].astype(np.int32)).max())
    return np.asarray(parity), np.asarray(rebuilt), diff
