"""The C allocator's retention policy for a long-lived server process.

glibc's malloc, as shipped, moves two thresholds as it goes: a request over
`M_MMAP_THRESHOLD` (128 KiB at first, raised by freed chunks up to 32 MiB)
is mapped afresh and unmapped when freed, and a heap whose top has more than
`M_TRIM_THRESHOLD` free is cut back to the kernel. The data plane allocates
and frees buffers of 128 KiB to 16 MiB (shard frames, stripe blocks, the
decode's padded and packed windows) on dozens of threads, each with an
arena of its own. Whether those buffers are then recycled or paged in again
on every use — 256 page faults per MiB, each a trip through the sandbox's
kernel — falls out of how the arenas happened to be laid out in that
process: on the chip one boot of the same tree read degraded GETs at
148–159 MiB/s and the next at 120–130, with 7.4 against 2.6 CPU-s/GiB on the
request threads, the whole difference in `pad`, `pack` and `join`
(PERF.md §6, PR 29). Fixing both thresholds ends the moving (glibc stops
adjusting them once either is set) and keeps freed memory in the heaps:
every boot then runs in the recycling regime. The price is the process's
high-water mark: what was once needed stays resident, as in any pooled
allocator; the arenas of `erasure/bufpool.py` are kept apart, as before.
"""

from __future__ import annotations

import ctypes

M_TRIM_THRESHOLD = -1
M_MMAP_THRESHOLD = -3
# the largest mmap threshold glibc takes (half a 64 MiB heap); requests
# beyond it (the 64 MiB+ ingest arenas) are mapped as before
MMAP_THRESHOLD = 32 << 20
# a heap's top is cut back only beyond this: in effect never
TRIM_THRESHOLD = 1 << 30


def retain_freed_memory() -> bool:
    """Fix glibc's two thresholds for this process; False where the C
    library is another (musl, macOS) and nothing was changed."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes, mallopt.restype = (ctypes.c_int, ctypes.c_int), ctypes.c_int
    ok = mallopt(M_MMAP_THRESHOLD, MMAP_THRESHOLD) == 1
    return mallopt(M_TRIM_THRESHOLD, TRIM_THRESHOLD) == 1 and ok
