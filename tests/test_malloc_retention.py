"""`utils/malloc.retain_freed_memory`: what `python -m minio_tpu.server`
sets before the data plane allocates. In a process of its own (the setting
is process-wide): once it is in force a 16 MiB buffer comes out of a heap
and goes back into it, where glibc as shipped maps it afresh and unmaps it
when freed (`mallinfo2().hblkhd`, the bytes in mapped chunks, says which)."""

import subprocess
import sys
import textwrap

import pytest

PROBE = textwrap.dedent("""
    import ctypes, sys
    import numpy as np

    class MallInfo2(ctypes.Structure):
        _fields_ = [(n, ctypes.c_size_t) for n in (
            "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks", "fsmblks",
            "uordblks", "fordblks", "keepcost")]

    libc = ctypes.CDLL(None)
    libc.mallinfo2.restype = MallInfo2
    if sys.argv[1] == "tuned":
        from minio_tpu.utils import malloc
        assert malloc.retain_freed_memory() is True
    before = libc.mallinfo2()
    a = np.empty(16 << 20, np.uint8)
    a[::4096] = 1
    held = libc.mallinfo2()
    del a
    after = libc.mallinfo2()
    print(held.hblkhd - before.hblkhd, after.arena - before.arena)
""")


def probe(mode: str) -> tuple[int, int]:
    r = subprocess.run([sys.executable, "-c", PROBE, mode], capture_output=True, text=True,
                       timeout=120)
    if "mallinfo2" in r.stderr:
        pytest.skip("this C library has no mallinfo2")
    assert r.returncode == 0, r.stderr[-2000:]
    mapped, arena = r.stdout.split()
    return int(mapped), int(arena)


def test_a_large_buffer_is_mapped_afresh_as_shipped_and_kept_in_the_heap_once_set():
    mapped, _ = probe("shipped")
    assert mapped >= 16 << 20          # its own mapping, gone again when freed
    mapped, arena = probe("tuned")
    assert mapped == 0                 # out of a heap ...
    assert arena >= 15 << 20           # ... which keeps it once it is freed


def test_the_server_sets_it_before_anything_else():
    import inspect

    from minio_tpu.server import app

    src = inspect.getsource(app.main)
    assert src.index("retain_freed_memory()") < src.index("parse_endpoints")
