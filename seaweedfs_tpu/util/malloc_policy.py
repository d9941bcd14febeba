"""glibc malloc policy for a process that streams multi-megabyte buffers.

Left alone, glibc maps every block above a (sliding, at most 32 MiB)
threshold anew and unmaps it when it is freed, and gives the top of the
heap back as soon as 128 KiB of it are free. A volume server's EC stream
allocates and frees about 6 GB of 8-80 MiB buffers for every GiB it
encodes (a stripe's fourteen 8 MiB ``tobytes`` chunks, their chunk-framed
copies in the HTTP client, the request bodies the holders read them
into, the 32 MiB drained parity arrays), so nearly every one of them is
memory the process has never touched: a page fault per 4 KiB on the way
in, an unmap and a TLB shootdown across every core on the way out. On
the v5e hosts — VMs, where both are dear — that, not any copy, was most
of an ``ec.encode``'s wall: the same code with the allocator told to keep
what is freed encoded 1.4x as fast (PERF.md, PR 26).
"""

import ctypes

# <malloc.h>
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_MAX = -1, -2, -4


def keep_freed_memory() -> bool:
    """Have malloc serve large blocks from its heaps and keep freed
    memory for the next one, instead of mapping and unmapping each: no
    mmap for blocks an arena can hold, the heap's top grown 64 MiB at a
    time and trimmed only beyond 2 GiB of free space (mallopt takes an
    int). The process then holds on to its high-water mark, as a server
    that encodes again tomorrow may. Process-wide and idempotent.
    False where the C library has no glibc ``mallopt``."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    return all(mallopt(param, value) == 1 for param, value in (
        (_M_MMAP_MAX, 0), (_M_TRIM_THRESHOLD, 2 ** 31 - 1),
        (_M_TOP_PAD, 64 << 20)))
