"""Multi-host device tier: one logical mesh over DCN-connected
processes (SURVEY §5.8).

The reference scales EC work across hosts by fanning shard jobs over
the cluster (reference weed/shell/command_ec_rebuild.go:57-240 — each
rebuild runs whole on one server). The TPU-native design instead forms
ONE `jax.sharding.Mesh` spanning every process's devices
(`jax.distributed.initialize`): intra-host axes ride ICI, cross-host
axes ride DCN, and the same `shard_map`/`psum` programs from
`sharded_ec.py` compile unchanged — XLA inserts the cross-host
collectives.

Wiring: `init_distributed()` before any other jax call (the CLI's
`-mesh.coordinator/-mesh.processes/-mesh.processId` volume flags call
it when set; tests drive it directly). Every process then sees the
GLOBAL device list and participates in every jit; inputs are built
per-process from local shards via `jax.make_array_from_callback`, and
results are checked against the process-local oracle shardwise —
no host ever materializes another host's bytes.

Validated by tests/test_multihost.py: 2 processes x 4 virtual CPU
devices each form an 8-device mesh and run the full encode + psum
rebuild step (`multihost_ec_step`), bit-checked per process against
the NumpyCodec oracle.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int,
                     local_device_ids: Optional[list] = None) -> None:
    """`jax.distributed.initialize` with the arguments the CLI flags
    carry. Must run before the first jax device query in the process;
    afterwards jax.devices() is the GLOBAL list and
    jax.local_devices() this host's slice."""
    import jax
    kwargs = {}
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id, **kwargs)


def _global(mesh, spec, value: np.ndarray):
    """A global Array with exactly `spec`, built from per-process
    local slices (every process holds the same host value, so each
    callback serves its addressable shards locally — no cross-host
    bytes move). Multi-process jit REQUIRES inputs to arrive already
    in the in_shardings layout."""
    import jax
    from jax.sharding import NamedSharding
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(value.shape, sharding,
                                        lambda idx: value[idx])


def multihost_ec_step(k: int = 10, m: int = 4,
                      n_per_device: int = 512) -> dict:
    """The distributed EC step (encode, drop m shards, psum-rebuild)
    on the GLOBAL mesh, inputs assembled per-process and outputs
    verified per-process against the CPU oracle. Returns a summary
    dict (identical on every process when everything agrees)."""
    import jax
    from ..ops.codec import NumpyCodec
    from .mesh import make_mesh
    from .sharded_ec import (decode_bitmat, sharded_encode_fn,
                             sharded_rebuild_fn)

    devices = jax.devices()
    mesh = make_mesh(devices=devices)
    n = n_per_device * mesh.shape["data"]

    # identical on every process: the logical payload
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (k, n), dtype=np.uint8)
    oracle = NumpyCodec(k, m).encode(data)

    from .sharded_ec import encode_in_specs, rebuild_in_specs

    enc_fn, bitmat = sharded_encode_fn(mesh, k, m, n)
    # the SAME spec objects the jit declares (one definition — a
    # drifted copy here would fail every multi-process run while
    # single-process tests kept passing)
    bm_spec, data_spec = encode_in_specs(mesh, m)
    parity = enc_fn(_global(mesh, bm_spec, bitmat.astype(np.int8)),
                    _global(mesh, data_spec, data))

    def check_local(global_arr, want: np.ndarray, label: str) -> int:
        """Compare only this process's addressable shards."""
        checked = 0
        for shard in global_arr.addressable_shards:
            got = np.asarray(shard.data)
            if not np.array_equal(got, want[shard.index]):
                raise AssertionError(
                    f"{label}: process {jax.process_index()} shard "
                    f"{shard.index} diverged from the oracle")
            checked += 1
        return checked

    parity_shards = check_local(parity, oracle, "multihost encode")

    survivors = list(range(k - m)) + list(range(k, k + m))
    missing = list(range(k - m, k))
    reb_fn = sharded_rebuild_fn(mesh, k, len(missing), n)
    bm_dec = decode_bitmat(k, m, survivors, missing,
                           pad_to_mult=mesh.shape["shard"])
    surv = np.concatenate([data[: k - m], oracle], axis=0)
    rb_bm_spec, rb_surv_spec = rebuild_in_specs(mesh)
    rebuilt = reb_fn(_global(mesh, rb_bm_spec, bm_dec.astype(np.int8)),
                     _global(mesh, rb_surv_spec, surv))
    rebuilt_shards = check_local(rebuilt, data[k - m: k],
                                 "multihost rebuild")

    return {
        "process_index": int(jax.process_index()),
        "process_count": int(jax.process_count()),
        "global_devices": len(devices),
        "local_devices": len(jax.local_devices()),
        "mesh_shape": dict(mesh.shape),
        "parity_shards_checked": parity_shards,
        "rebuilt_shards_checked": rebuilt_shards,
        "ok": True,
    }
