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

That policy holds only in malloc's main arena. glibc gives every new
thread an arena of its own (up to eight a core), built of heaps of at
most 64 MiB that are unmapped again as soon as they are empty: a worker
thread that asks for a 46 MiB block gets a fresh mapping each time, and
which threads share an arena that still has room is luck — the same
repair took 0.47 or 0.65 s by the arena its producer thread drew, and a
window's rate fell in two levels 20 % apart (PERF.md, PR 27). With one
arena every thread's blocks come from the one heap that keeps them.
"""

import ctypes
import os

# <malloc.h>
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_MAX, _M_ARENA_MAX = -1, -2, -4, -8


def _mallopt():
    """glibc's ``mallopt``, or None where the C library has none."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return None
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    return mallopt


def one_arena() -> bool:
    """Cap malloc at its main arena. glibc fixes its arena limit the
    first time a ninth arena is asked for and never drops one it made,
    so this only works before the process has threads: the package's
    ``__init__`` calls it, ahead of the TPU runtime's and the servers'
    (a server's ``start()`` is too late where the process already holds
    the chip). Every process that imports the package gets the cap, and
    its threads then share one allocator lock: measured on the EC stream
    only, not on the GET / PUT path. The resident peak of a benchmark
    process fell with it, 28.9 GB to 16.3-16.7 GB, one run against
    eleven (PERF.md, PR 27): fewer arenas keep less freed memory.
    Whoever set glibc's own ``MALLOC_ARENA_MAX`` keeps their setting.
    False where that is set or the C library has no glibc ``mallopt``."""
    if os.environ.get("MALLOC_ARENA_MAX"):
        return False
    mallopt = _mallopt()
    return mallopt is not None and mallopt(_M_ARENA_MAX, 1) == 1


def keep_freed_memory() -> bool:
    """Have malloc serve large blocks from its heaps and keep freed
    memory for the next one, instead of mapping and unmapping each: no
    mmap for blocks an arena can hold, the heap's top grown 64 MiB at a
    time and trimmed only beyond 2 GiB of free space (mallopt takes an
    int). The process then holds on to its high-water mark, as a server
    that encodes again tomorrow may. Process-wide and idempotent.
    False where the C library has no glibc ``mallopt``."""
    mallopt = _mallopt()
    if mallopt is None:
        return False
    return all(mallopt(param, value) == 1 for param, value in (
        (_M_MMAP_MAX, 0), (_M_TRIM_THRESHOLD, 2 ** 31 - 1),
        (_M_TOP_PAD, 64 << 20)))
