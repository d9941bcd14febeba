"""parallel — how one EC dispatch is laid over the chips of a host:
the mesh (mesh.make_codec_mesh) and the codec that shards a slab's
width over it (mesh_codec.MeshCodec). The programs are ops/rs_tpu's.
"""

from .mesh import make_codec_mesh  # noqa: F401
from .mesh_codec import MeshCodec  # noqa: F401
