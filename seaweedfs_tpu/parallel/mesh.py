"""Device mesh construction helpers."""

from __future__ import annotations

from typing import Optional

import numpy as np


def make_codec_mesh(devices=None, width_devices: Optional[int] = None):
    """Mesh for MeshCodec dispatches: a (width, 1) mesh named
    ("data", "shard") with EVERY device on the 'data' (stripe-width)
    axis — the payload axis is the only one an encode/decode matmul
    shards over. Width is capped by SW_EC_MESH_WIDTH_DEVICES (0 = all
    visible devices).
    """
    import jax
    from jax.sharding import Mesh
    from ..util import config
    from ..util.jax_platform import configure_compile_cache
    configure_compile_cache()

    devices = list(devices if devices is not None else jax.devices())
    cap = (int(width_devices) if width_devices is not None
           else config.env_int("SW_EC_MESH_WIDTH_DEVICES"))
    width = len(devices) if cap <= 0 else min(cap, len(devices))
    return Mesh(np.asarray(devices[:width]).reshape(width, 1),
                axis_names=("data", "shard"))
