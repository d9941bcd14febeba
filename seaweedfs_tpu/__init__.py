"""seaweedfs_tpu — a TPU-native re-design of SeaweedFS.

A distributed object store / file system (Facebook Haystack + f4 designs)
whose performance-critical erasure-coding pipeline runs on TPU:
the Reed-Solomon GF(2^8) encode/reconstruct — a SIMD assembly loop in the
reference (klauspost/reedsolomon) — is re-built as a batched GF(2) bit-plane
matmul on the MXU via JAX/XLA/Pallas, with a C++ native codec as the CPU
fallback and a numpy reference for conformance.

Reference: CodeLingoBot/seaweedfs @ /root/reference (Go, v1.71).
This is NOT a port; architecture is TPU-first (see SURVEY.md §7).
"""

VERSION = "0.1.0"

# before anything here starts a thread (util/malloc_policy.py says why)
from .util import malloc_policy as _malloc_policy  # noqa: E402

_malloc_policy.one_arena()
