"""The plain reference of what a PUT must leave on the drives.

Reed-Solomon over GF(2^8) as MinIO's codec builds it (klauspost/
reedsolomon: systematic matrix from a Vandermonde matrix, field polynomial
0x11D) and HighwayHash-256 with MinIO's bitrot key, in straightforward
numpy. It imports nothing of `minio_tpu` and takes nothing the program
made: the tables and the matrix are built here. (The program's own
`ops/rs.py`, `ops/gf.py` and `ops/highwayhash.py` were the starting point;
the copy is the yardstick, the originals may change.)

On a drive a shard file is a sequence of frames, one per stripe block:
32 digest bytes, then the shard's bytes of that block (MinIO's streaming
bitrot format). `object_frames` returns exactly those bytes per shard.
"""

from __future__ import annotations

import functools

import numpy as np

BLOCK = 1 << 20  # stripe block of user bytes
DIGEST = 32

# ---- GF(2^8), polynomial x^8 + x^4 + x^3 + x^2 + 1, generator 2 -----------

_POLY = 0x11D


def _tables():
    exp = np.zeros(255, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    nz = np.arange(1, 256)
    mul = np.zeros((256, 256), dtype=np.uint8)
    mul[1:, 1:] = exp[(log[nz][:, None] + log[nz][None, :]) % 255]
    inv = np.zeros(256, dtype=np.uint8)
    inv[1:] = exp[(255 - log[nz]) % 255]
    return exp, log, mul, inv


EXP, LOG, MUL, INV = _tables()


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(int(LOG[a]) * n) % 255])


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.bitwise_xor.reduce(MUL[a[:, :, None], b[None, :, :]], axis=1)


def gf_inv(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8); raises ValueError where singular."""
    n = m.shape[0]
    aug = np.concatenate([m.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if aug[r, col]), None)
        if pivot is None:
            raise ValueError("matrix is singular")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        aug[col] = MUL[INV[aug[col, col]], aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= MUL[aug[r, col], aug[col]]
    return aug[:, n:].copy()


@functools.lru_cache(maxsize=None)
def parity_matrix(d: int, p: int) -> np.ndarray:
    """[p, d]: the parity rows of the systematic matrix. vandermonde[r, c]
    = r**c, times the inverse of its top square."""
    vm = np.array([[gf_pow(r, c) for c in range(d)] for r in range(d + p)],
                  dtype=np.uint8)
    full = gf_matmul(vm, gf_inv(vm[:d]))
    if not np.array_equal(full[:d], np.eye(d, dtype=np.uint8)):
        raise AssertionError("encoding matrix is not systematic")
    return full[d:].copy()


def shard_len(d: int, block: int = BLOCK) -> int:
    return -(-block // d)


def split(body: bytes | np.ndarray, d: int) -> np.ndarray:
    """User bytes -> [blocks, d, n] data shards, each stripe block split
    over d shards of n = ceil(block/d) bytes, the tail zero-padded. A last
    short block is NOT handled: the benchmark's objects are whole MiB."""
    buf = np.frombuffer(body, dtype=np.uint8) if not isinstance(body, np.ndarray) else body
    if buf.size == 0 or buf.size % BLOCK:
        raise ValueError("the reference handles whole 1 MiB stripe blocks only")
    blocks = buf.size // BLOCK
    n = shard_len(d)
    out = np.zeros((blocks, d * n), dtype=np.uint8)
    out[:, :BLOCK] = buf.reshape(blocks, BLOCK)
    return out.reshape(blocks, d, n)


def encode(data: np.ndarray, p: int) -> np.ndarray:
    """[blocks, d, n] data shards -> [blocks, p, n] parity shards."""
    blocks, d, n = data.shape
    pm = parity_matrix(d, p)
    parity = np.zeros((blocks, p, n), dtype=np.uint8)
    for j in range(d):
        col = data[:, j, :]
        for i in range(p):
            parity[:, i, :] ^= MUL[pm[i, j]][col]
    return parity


# ---- HighwayHash-256 (google/highwayhash), MinIO's bitrot key --------------

INIT0 = (0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0, 0x13198A2E03707344, 0x243F6A8885A308D3)
INIT1 = (0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C, 0xBE5466CF34E90C6C, 0x452821E638D01377)
# HighwayHash-256, zero key, of the first 100 decimals of pi (cmd/bitrot.go)
MINIO_KEY = bytes(
    [0x4B, 0xE7, 0x34, 0xFA, 0x8E, 0x23, 0x8A, 0xCD, 0x26, 0x3E, 0x83, 0xE6,
     0xBB, 0x96, 0x85, 0x52, 0x04, 0x0F, 0x93, 0x5D, 0xA3, 0x9F, 0x44, 0x14,
     0x97, 0xE0, 0x9D, 0x13, 0x22, 0xDE, 0x36, 0xA0]
)
_U = np.uint64


def _zipper(v1, v0, add1, add0):
    add0 += (
        (((v0 & _U(0x00000000FF000000)) | (v1 & _U(0x000000FF00000000))) >> _U(24))
        | (((v0 & _U(0x0000FF0000000000)) | (v1 & _U(0x00FF000000000000))) >> _U(16))
        | (v0 & _U(0x0000000000FF0000))
        | ((v0 & _U(0x000000000000FF00)) << _U(32))
        | ((v1 & _U(0xFF00000000000000)) >> _U(8))
        | (v0 << _U(56))
    )
    add1 += (
        (((v1 & _U(0x00000000FF000000)) | (v0 & _U(0x000000FF00000000))) >> _U(24))
        | (v1 & _U(0x0000000000FF0000))
        | ((v1 & _U(0x0000FF0000000000)) >> _U(16))
        | ((v1 & _U(0x000000000000FF00)) << _U(24))
        | ((v0 & _U(0x00FF000000000000)) >> _U(8))
        | ((v1 & _U(0x00000000000000FF)) << _U(48))
        | (v0 & _U(0xFF00000000000000))
    )
    return add1, add0


class _State:
    __slots__ = ("v0", "v1", "mul0", "mul1")

    def __init__(self, batch: int, key: bytes):
        k = np.array([int.from_bytes(key[8 * i: 8 * i + 8], "little") for i in range(4)],
                     dtype=np.uint64)
        i0 = np.array(INIT0, dtype=np.uint64)
        i1 = np.array(INIT1, dtype=np.uint64)
        krot = (k >> _U(32)) | (k << _U(32))
        self.v0 = np.repeat((i0 ^ k)[:, None], batch, axis=1)
        self.v1 = np.repeat((i1 ^ krot)[:, None], batch, axis=1)
        self.mul0 = np.repeat(i0[:, None], batch, axis=1)
        self.mul1 = np.repeat(i1[:, None], batch, axis=1)

    def update(self, a) -> None:
        """a: [4, B] uint64 lanes of one 32-byte packet per message."""
        m32 = _U(0xFFFFFFFF)
        self.v1 += self.mul0 + a
        self.mul0 ^= (self.v1 & m32) * (self.v0 >> _U(32))
        self.v0 += self.mul1
        self.mul1 ^= (self.v0 & m32) * (self.v1 >> _U(32))
        self.v0[1], self.v0[0] = _zipper(self.v1[1], self.v1[0], self.v0[1], self.v0[0])
        self.v0[3], self.v0[2] = _zipper(self.v1[3], self.v1[2], self.v0[3], self.v0[2])
        self.v1[1], self.v1[0] = _zipper(self.v0[1], self.v0[0], self.v1[1], self.v1[0])
        self.v1[3], self.v1[2] = _zipper(self.v0[3], self.v0[2], self.v1[3], self.v1[2])


def hash256(msgs: np.ndarray, key: bytes = MINIO_KEY) -> np.ndarray:
    """B messages of equal length, [B, n] uint8 -> [B, 32] digests; the
    batch is the vector axis, the packets are walked in order."""
    msgs = np.ascontiguousarray(msgs, dtype=np.uint8)
    with np.errstate(over="ignore"):
        return _hash256(msgs, key)


def _hash256(msgs: np.ndarray, key: bytes) -> np.ndarray:
    b, n = msgs.shape
    s = _State(b, key)
    whole = n - (n % 32)
    if whole:
        # packet-major [packets, 4, B] so each step reads contiguous lanes
        lanes = np.ascontiguousarray(
            msgs[:, :whole].reshape(b, whole // 32, 4, 8).view(np.uint64)[..., 0]
            .transpose(1, 2, 0)
        )
        for pi in range(whole // 32):
            s.update(lanes[pi])
    rem = n - whole
    if rem:
        size = _U(rem)
        s.v0 += (size << _U(32)) + size
        m32 = _U(0xFFFFFFFF)
        lo, hi = s.v1 & m32, s.v1 >> _U(32)
        lo = ((lo << size) | (lo >> (_U(32) - size))) & m32
        hi = ((hi << size) | (hi >> (_U(32) - size))) & m32
        s.v1 = (hi << _U(32)) | lo
        packet = np.zeros((b, 32), dtype=np.uint8)
        whole4 = rem & ~3
        packet[:, :whole4] = msgs[:, whole: whole + whole4]
        if rem & 16:
            packet[:, 28:32] = msgs[:, whole + rem - 4: whole + rem]
        elif rem & 3:
            size4 = rem & 3
            tail = msgs[:, whole + whole4:]
            packet[:, 16] = tail[:, 0]
            packet[:, 17] = tail[:, size4 >> 1]
            packet[:, 18] = tail[:, size4 - 1]
        s.update(np.ascontiguousarray(packet.reshape(b, 4, 8).view(np.uint64)[..., 0].T))
    for _ in range(10):
        s.update(np.stack([(s.v0[i] >> _U(32)) | (s.v0[i] << _U(32)) for i in (2, 3, 0, 1)]))
    out = np.zeros((b, 4), dtype=np.uint64)
    for oi, half in ((0, 0), (1, 2)):
        a0 = s.v0[half] + s.mul0[half]
        a1 = s.v0[half + 1] + s.mul0[half + 1]
        a2 = s.v1[half] + s.mul1[half]
        a3 = (s.v1[half + 1] + s.mul1[half + 1]) & _U(0x3FFFFFFFFFFFFFFF)
        out[:, 2 * oi] = a0 ^ (a2 << _U(1)) ^ (a2 << _U(2))
        out[:, 2 * oi + 1] = a1 ^ ((a3 << _U(1)) | (a2 >> _U(63))) ^ ((a3 << _U(2)) | (a2 >> _U(62)))
    return out.view(np.uint8).reshape(b, 32)


# ---- what a PUT leaves on the drives ---------------------------------------


def object_frames(body: bytes, d: int, p: int) -> list[bytes]:
    """The d+p shard files of an object, by erasure index (0-based): for
    each stripe block a 32-byte HighwayHash-256 digest, then the shard's
    bytes of that block."""
    data = split(body, d)
    shards = np.concatenate([data, encode(data, p)], axis=1)  # [blocks, d+p, n]
    blocks, t, n = shards.shape
    digests = hash256(shards.reshape(blocks * t, n)).reshape(blocks, t, DIGEST)
    framed = np.concatenate([digests, shards], axis=2)  # [blocks, t, 32+n]
    return [framed[:, i, :].tobytes() for i in range(t)]
