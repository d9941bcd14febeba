"""Throwaway experiment: is the fused kernel's int8 dot_general the
best MXU mapping, or does a bf16 x bf16 -> f32 variant (exact for 0/1
operands with row sums <= 2048) run faster on the live chip?

Chained-slope methodology: serially-dependent
iterations, scalar fetch, rotating buffers; slope over >=3 chain
lengths.
"""
import functools
import time

import numpy as np

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_pallas import fuse_bitmat, pick_tile

K, M = 10, 4


def make_fn(k, r, n, tile, dot_dtype):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(bitmat_ref, data_ref, out_ref):
        data = data_ref[...]
        x = jnp.concatenate(
            [((data & (1 << l)) != 0).astype(dot_dtype) for l in range(8)],
            axis=0)
        acc_t = jnp.int32 if dot_dtype == jnp.int8 else jnp.float32
        y = jax.lax.dot_general(
            bitmat_ref[...].astype(dot_dtype), x,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=acc_t)
        if acc_t == jnp.float32:
            y = y.astype(jnp.int32)
        acc = y[0:r, :] & 1
        for b in range(1, 8):
            acc = acc + (y[b * r:(b + 1) * r, :] & 1) * (1 << b)
        out_ref[...] = acc.astype(jnp.uint8)

    grid = (n + tile - 1) // tile
    fn = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((8 * r, 8 * k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.uint8),
        interpret=False,
    )
    return jax.jit(fn)


def make_fn_batched(k, r, n, tile, u, dot_dtype):
    """u-way M-fill batching: the (8r x 8k) operand fills only
    (8r/128)x(8k/128) of the 128x128 MXU. Stack u column-chunks'
    bit-planes along the contraction dim and use a block-diagonal
    (u*8r x u*8k) coefficient matrix: M goes 8r -> u*8r (128 at u=4
    for RS(10,4)), at the cost of u x zero-padding in K. Theoretical
    tile math says ~25% fewer tile-passes at u=4; this measures what
    the hardware actually does."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(bigmat_ref, data_ref, out_ref):
        planes = []
        for j in range(u):
            d = data_ref[:, j * tile:(j + 1) * tile]
            planes.append(jnp.concatenate(
                [((d & (1 << l)) != 0).astype(dot_dtype)
                 for l in range(8)], axis=0))
        x = jnp.concatenate(planes, axis=0)          # (u*8k, tile)
        acc_t = jnp.int32 if dot_dtype == jnp.int8 else jnp.float32
        y = jax.lax.dot_general(
            bigmat_ref[...].astype(dot_dtype), x,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=acc_t)            # (u*8r, tile)
        if acc_t == jnp.float32:
            y = y.astype(jnp.int32)
        for j in range(u):
            yj = y[j * 8 * r:(j + 1) * 8 * r, :]
            acc = yj[0:r, :] & 1
            for b in range(1, 8):
                acc = acc + (yj[b * r:(b + 1) * r, :] & 1) * (1 << b)
            out_ref[:, j * tile:(j + 1) * tile] = acc.astype(jnp.uint8)

    grid = (n + u * tile - 1) // (u * tile)
    fn = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((u * 8 * r, u * 8 * k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, u * tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, u * tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.uint8),
        interpret=False,
    )
    return jax.jit(fn)


def block_diag_bitmat(bm: np.ndarray, u: int) -> np.ndarray:
    rows, cols = bm.shape
    big = np.zeros((u * rows, u * cols), dtype=bm.dtype)
    for j in range(u):
        big[j * rows:(j + 1) * rows, j * cols:(j + 1) * cols] = bm
    return big


def chained_rate(fn, bitmat, slabs, lengths=(5, 15, 25), reps=3):
    import jax
    n = slabs[0].shape[1]

    @functools.partial(jax.jit, static_argnums=2)
    def chain(bm, x0, iters):
        import jax.numpy as jnp
        x = x0
        acc = jnp.zeros((), jnp.uint32)
        for _ in range(iters):
            y = fn(bm, x)
            acc = acc + y[0, 0].astype(jnp.uint32)
            # feed a transform of the output back so iterations are
            # serially dependent and nothing is value-cached
            x = x.at[0, 0].set(y[0, 0])
        return acc

    times = {}
    for it in lengths:
        best = float("inf")
        for rep in range(reps):
            x = slabs[rep % len(slabs)]
            chain(bitmat, x, it).block_until_ready()  # warm compile
            t0 = time.perf_counter()
            chain(bitmat, x, it).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        times[it] = best
    xs = np.array(sorted(times))
    ys = np.array([times[i] for i in xs])
    slope, icept = np.polyfit(xs, ys, 1)
    fit = slope * xs + icept
    ss_res = float(((ys - fit) ** 2).sum())
    ss_tot = float(((ys - ys.mean()) ** 2).sum())
    r2 = 1 - ss_res / ss_tot if ss_tot else 1.0
    payload = K * n  # bytes per iteration
    return payload / slope / 1e6, r2


def main():
    import jax
    import jax.numpy as jnp
    print("devices:", jax.devices())
    slab_mb = 8
    n = slab_mb << 20
    rng = np.random.default_rng(7)
    slabs = [jnp.asarray(rng.integers(0, 256, (K, n), dtype=np.uint8))
             for _ in range(3)]
    matrix = gf256.build_matrix(K, K + M, "vandermonde")
    bm_np = fuse_bitmat(matrix[K:])

    tile = pick_tile(K, M, n)
    print(f"tile={tile}")
    oracle = None
    for name, dtype in (("int8", jnp.int8), ("bf16", jnp.bfloat16),
                        ("f32", jnp.float32)):
        try:
            fn = make_fn(K, M, n, tile, dtype)
            bm = jnp.asarray(bm_np)
            out = np.asarray(jax.device_get(fn(bm, slabs[0])))
            if oracle is None:
                oracle = gf256.mat_mul(matrix[K:], np.asarray(slabs[0]))
            ok = np.array_equal(out, oracle)
            rate, r2 = chained_rate(fn, bm, slabs)
            print(f"{name}: {rate:,.0f} MB/s (r2 {r2:.4f}) exact={ok}")
        except Exception as e:  # noqa: BLE001 - experiment
            print(f"{name}: FAILED {type(e).__name__}: {e}")
    # M-fill batching: block-diagonal stacking to fill the 128-row MXU
    for u in (2, 4):
        for name, dtype in (("int8", jnp.int8), ("bf16", jnp.bfloat16)):
            try:
                bt = pick_tile(K, M, n) // u   # same VMEM data budget
                bt = max(256, (bt // 256) * 256)
                fnb = make_fn_batched(K, M, n, bt, u, dtype)
                bigbm = jnp.asarray(block_diag_bitmat(bm_np, u))
                out = np.asarray(jax.device_get(fnb(bigbm, slabs[0])))
                ok = np.array_equal(out, oracle)
                rate, r2 = chained_rate(fnb, bigbm, slabs)
                print(f"batched u={u} {name}: {rate:,.0f} MB/s "
                      f"(r2 {r2:.4f}) exact={ok}")
            except Exception as e:  # noqa: BLE001 - experiment
                print(f"batched u={u} {name}: FAILED "
                      f"{type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
