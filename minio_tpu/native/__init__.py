"""Native CPU kernel bindings (ctypes over gfhash.cpp).

Builds the shared library on first import (g++ -O3 -mavx2) and caches the
.so next to the source; every entry point has a pure-Python fallback in
ops/, so an environment without a toolchain still works (slower).
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_HERE, "gfhash.cpp"), os.path.join(_HERE, "dataplane.cpp")]
_SO = os.path.join(_HERE, "gfhash.so")

_lib = None
_lock = threading.Lock()
_build_failed = False


def _build() -> bool:
    # a temporary of this process's own: processes that import at once (a
    # fresh checkout under xdist) each publish a whole file, never one
    # another's half-written output
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-mavx2", "-shared", "-fPIC", *_SRCS,
           "-o", tmp, "-ldl"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (subprocess.SubprocessError, OSError):
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        return False


def _load():
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        if os.environ.get("MINIO_TPU_NO_NATIVE") == "1":
            _build_failed = True
            return None
        try:
            needs_build = not os.path.exists(_SO) or any(
                os.path.getmtime(_SO) < os.path.getmtime(s) for s in _SRCS
            )
            if needs_build and not _build():
                _build_failed = True
                return None
            lib = ctypes.CDLL(_SO)
        except OSError:
            _build_failed = True
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.gf_apply.argtypes = [u8p, ctypes.c_int, ctypes.c_int, u8p, u8p, ctypes.c_long]
        lib.hh256.argtypes = [u8p, u8p, ctypes.c_long, u8p]
        lib.hh256_batch.argtypes = [
            u8p, u8p, ctypes.c_long, ctypes.c_long, ctypes.c_int, u8p,
        ]
        lib.gf_encode_hash.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, u8p, u8p, ctypes.c_long, u8p, u8p,
        ]
        # streaming data plane (dataplane.cpp)
        ccp = ctypes.POINTER(ctypes.c_char_p)
        lp = ctypes.POINTER(ctypes.c_long)
        lib.dp_put_open.restype = ctypes.c_void_p
        lib.dp_put_open.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_long, u8p, u8p, ccp,
        ]
        lib.dp_put_feed.argtypes = [ctypes.c_void_p, u8p, ctypes.c_long]
        lib.dp_put_alive.argtypes = [ctypes.c_void_p]
        lib.dp_put_alive.restype = ctypes.c_int
        lib.dp_put_finish.argtypes = [
            ctypes.c_void_p, u8p, ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.dp_put_abort.argtypes = [ctypes.c_void_p]
        lib.dp_get_span.restype = ctypes.c_long
        lib.dp_get_span.argtypes = [ccp, ctypes.c_int, u8p, ctypes.c_long,
                                    lp, lp, lp, lp, u8p]
        lib.dp_md5.argtypes = [u8p, ctypes.c_long, u8p]
        lib.dp_crc32c.argtypes = [u8p, ctypes.c_long, ctypes.c_uint32]
        lib.dp_crc32c.restype = ctypes.c_uint32
        lib.dp_crc64nvme.argtypes = [u8p, ctypes.c_long, ctypes.c_uint64]
        lib.dp_crc64nvme.restype = ctypes.c_uint64
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def gf_apply(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out[r] = XOR_c mat[r,c] * data[c] over GF(2^8). data: [cols, n]."""
    lib = _load()
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    rows, cols = mat.shape
    n = data.shape[1]
    out = np.empty((rows, n), dtype=np.uint8)
    lib.gf_apply(_ptr(mat), rows, cols, _ptr(data), _ptr(out), n)
    return out


def hh256(key: bytes, data: bytes | np.ndarray) -> bytes:
    lib = _load()
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else np.ascontiguousarray(data, dtype=np.uint8)
    out = np.empty(32, dtype=np.uint8)
    karr = np.frombuffer(key, dtype=np.uint8)
    lib.hh256(_ptr(karr), _ptr(buf), buf.size, _ptr(out))
    return out.tobytes()


def hh256_batch(key: bytes, blocks: np.ndarray) -> np.ndarray:
    """[B, n] uint8 -> [B, 32] digests."""
    lib = _load()
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    b, n = blocks.shape
    out = np.empty((b, 32), dtype=np.uint8)
    karr = np.frombuffer(key, dtype=np.uint8)
    lib.hh256_batch(_ptr(karr), _ptr(blocks), n, n, b, _ptr(out))
    return out


def hh256_frames(
    key: bytes, buf: np.ndarray, first: int, stride: int, n: int, count: int
) -> np.ndarray:
    """Digests of `count` spans of `n` bytes that start `stride` bytes apart
    in ONE flat uint8 buffer, the first at `first` -> [count, 32]. The
    payloads of a run of digest||block frames hash where they were read,
    in one GIL-releasing call."""
    lib = _load()
    if first < 0 or count < 1 or first + (count - 1) * stride + n > buf.size:
        raise ValueError("frames reach past the buffer")
    out = np.empty((count, 32), dtype=np.uint8)
    karr = np.frombuffer(key, dtype=np.uint8)
    lib.hh256_batch(_ptr(karr), _ptr(buf[first:]), stride, n, count, _ptr(out))
    return out


class DataplanePut:
    """Streaming native PUT: feed raw bytes, shards land framed on disk.

    One GIL-releasing C++ pass per feed: md5 -> stripe split -> GF parity
    -> HighwayHash -> digest||block framing -> writev (dataplane.cpp).
    paths are per erasure-shard-index staged files; a failing drive marks
    its shard dead and the pass continues (quorum judged by the caller).
    """

    def __init__(self, d: int, p: int, block_size: int,
                 parity_mat: np.ndarray, key: bytes, paths: list[str]):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        mat = np.ascontiguousarray(parity_mat, dtype=np.uint8)
        karr = np.frombuffer(key, dtype=np.uint8)
        arr = (ctypes.c_char_p * len(paths))(*[s.encode() for s in paths])
        self._lib = lib
        self._ctx = lib.dp_put_open(d, p, block_size, _ptr(mat), _ptr(karr), arr)
        if not self._ctx:
            raise MemoryError("dp_put_open failed")

    def feed(self, chunk: bytes | bytearray | memoryview) -> None:
        n = len(chunk)
        if not n:
            return
        arr = np.frombuffer(chunk, dtype=np.uint8)  # zero-copy view
        self._lib.dp_put_feed(self._ctx, _ptr(arr), n)

    def alive(self) -> int:
        return self._lib.dp_put_alive(self._ctx)

    def finish(self) -> tuple[str, int]:
        """-> (md5-hex etag, dead shard bitmask). Frees the context."""
        out = np.empty(16, dtype=np.uint8)
        mask = ctypes.c_uint64(0)
        self._lib.dp_put_finish(self._ctx, _ptr(out), ctypes.byref(mask))
        self._ctx = None
        return out.tobytes().hex(), int(mask.value)

    def abort(self) -> None:
        if self._ctx:
            self._lib.dp_put_abort(self._ctx)
            self._ctx = None

    def __del__(self):  # noqa: D105 — safety net for abandoned contexts
        try:
            self.abort()
        except Exception:  # noqa: BLE001
            pass


def dataplane_available() -> bool:
    return _load() is not None


def crc32c(data: bytes, prev: int = 0) -> int:
    arr = np.frombuffer(data, dtype=np.uint8)
    return int(_load().dp_crc32c(_ptr(arr), arr.size, prev))


def crc64nvme(data: bytes, prev: int = 0) -> int:
    arr = np.frombuffer(data, dtype=np.uint8)
    return int(_load().dp_crc64nvme(_ptr(arr), arr.size, prev))


DP_GET_ENOMEM = -(1 << 40)  # resource failure sentinel: blames no shard


def dp_get_span(paths: list[str], d: int, key: bytes, f_off: np.ndarray,
                per: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                out: np.ndarray) -> int:
    """Read+verify+assemble stripe blocks from local shard files.

    Returns bytes written (== sum(hi-lo)), a negative failure code
    -(block*64 + shard + 1) on the first read/bitrot failure, or
    DP_GET_ENOMEM (no shard at fault)."""
    lib = _load()
    arr = (ctypes.c_char_p * d)(*[s.encode() for s in paths[:d]])
    karr = np.frombuffer(key, dtype=np.uint8)
    lp = ctypes.POINTER(ctypes.c_long)
    return int(lib.dp_get_span(
        arr, d, _ptr(karr), len(f_off),
        f_off.ctypes.data_as(lp), per.ctypes.data_as(lp),
        lo.ctypes.data_as(lp), hi.ctypes.data_as(lp), _ptr(out)))


def gf_encode_hash(
    parity_mat: np.ndarray, data: np.ndarray, key: bytes
) -> tuple[np.ndarray, np.ndarray]:
    """Fused CPU encode+hash: data [d, n] -> (parity [p, n], digests [d+p, 32])."""
    lib = _load()
    parity_mat = np.ascontiguousarray(parity_mat, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    p, d = parity_mat.shape
    n = data.shape[1]
    parity = np.empty((p, n), dtype=np.uint8)
    digests = np.empty((d + p, 32), dtype=np.uint8)
    karr = np.frombuffer(key, dtype=np.uint8)
    lib.gf_encode_hash(
        _ptr(parity_mat), p, d, _ptr(data), _ptr(parity), n, _ptr(karr), _ptr(digests)
    )
    return parity, digests
