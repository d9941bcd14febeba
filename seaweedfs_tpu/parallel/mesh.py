"""Device mesh construction helpers."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              axis_names: Sequence[str] = ("data", "shard"),
              devices=None):
    """Build a Mesh over the available devices.

    Default layout: as many devices as possible on the 'data' (stripe) axis
    with the 'shard' axis sized 2 when the device count is even — encode is
    embarrassingly parallel over stripes, so 'data' gets the bulk; 'shard'
    exists to exercise output-sharding + psum paths (and maps to real
    multi-host topologies where shard files live on different hosts).
    """
    import jax
    from jax.sharding import Mesh
    from ..util.jax_platform import configure_compile_cache
    configure_compile_cache()

    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if shape is None:
        if n % 2 == 0 and n > 1:
            shape = (n // 2, 2)
        else:
            shape = (n, 1)
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, axis_names=tuple(axis_names[: len(shape)]))


def make_codec_mesh(devices=None, width_devices: Optional[int] = None):
    """Mesh for MeshCodec dispatches: EVERY device on the 'data'
    (stripe-width) axis.

    The default make_mesh layout reserves half the devices for the
    'shard' axis (output sharding / psum paths), which is right for the
    distributed-rebuild programs but halves the width parallelism of a
    codec dispatch — the payload axis is the only one a plain
    encode/decode matmul shards over, so a (4, 2) mesh left 4 of 8
    devices idle on every MeshCodec call. Width is capped by
    SW_EC_MESH_WIDTH_DEVICES (0 = all visible devices).
    """
    import jax
    from ..util import config
    from ..util.jax_platform import configure_compile_cache
    configure_compile_cache()

    devices = list(devices if devices is not None else jax.devices())
    cap = (int(width_devices) if width_devices is not None
           else config.env_int("SW_EC_MESH_WIDTH_DEVICES"))
    width = len(devices) if cap <= 0 else min(cap, len(devices))
    return make_mesh(shape=(width, 1), axis_names=("data", "shard"),
                     devices=devices[:width])
