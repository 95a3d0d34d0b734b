"""The plain reference of what a degraded GET must return: an object rebuilt
from the shard files that are left on the drives.

MinIO's read path in straightforward numpy (docs, Erasure Coding; cmd/
erasure-decode.go): a set of d+p drives keeps serving an object while any d
of its shard files can be read; every frame read is verified against its
HighwayHash-256 first; the missing data shards of each stripe block are the
survivors times the inverse of the survivors' rows of the systematic
encoding matrix. It builds on `chipbench/reference.py` (`gf_inv`,
`parity_matrix`, `hash256`, the frame layout), imports nothing of
`minio_tpu` and takes nothing the program made but the bytes on the drives.

Which drive holds which shard is MinIO's `hashOrder` (cmd/erasure-metadata-
utils.go): the shard numbers 1..n rotated by crc32("bucket/object") mod n,
so drive i of the set holds erasure index `shard_order(...)[i]`. The
reference does not trust it blindly: `decode_object` re-encodes what it
rebuilt and every surviving shard it did not use must come out byte for
byte, which fails where a file is not the shard the order says.

What it receives: `read_shards` the drive directories in the order of the
server's command line, the bucket, the key and the drive positions to leave
unread (the offline drives); `decode_object` that dict {erasure index: file
bytes} and the geometry. It returns the object's bytes, whole stripe blocks
only, as `reference.split` handles them.
"""

from __future__ import annotations

import glob
import os
import zlib

import numpy as np

from chipbench import reference
from chipbench.reference import BLOCK, DIGEST, MUL


class BadFrame(ValueError):
    """A frame whose bytes do not hash to the digest stored before them."""


def shard_order(bucket: str, key: str, n: int) -> list[int]:
    """0-based erasure index of the shard file on drive 0..n-1 of the set."""
    start = zlib.crc32(f"{bucket}/{key}".encode()) % n
    return [(start + i) % n for i in range(1, n + 1)]


def shard_path(drive: str, bucket: str, key: str) -> str | None:
    """The one `part.1` of the object on this drive, as written (the data
    directory is a uuid the reference does not know), or None."""
    found = glob.glob(os.path.join(glob.escape(os.path.join(drive, bucket, key)), "*", "part.1"))
    return found[0] if len(found) == 1 else None


def read_shards(drives: list[str], bucket: str, key: str, skip=()) -> dict[int, bytes]:
    """{erasure index: shard file bytes} of the files found on the drives,
    those at the positions in `skip` left unread."""
    order = shard_order(bucket, key, len(drives))
    out = {}
    for pos, drive in enumerate(drives):
        path = None if pos in skip else shard_path(drive, bucket, key)
        if path is not None:
            with open(path, "rb") as f:
                out[order[pos]] = f.read()
    return out


def verified_shards(files: dict[int, bytes], n: int) -> dict[int, np.ndarray]:
    """{erasure index: shard file} -> {index: [blocks, n] shard bytes}, every
    frame's HighwayHash-256 checked against the 32 bytes stored before it
    (all frames of all files in one vectorised pass)."""
    frames = {}
    for i, shard_file in sorted(files.items()):
        buf = np.frombuffer(shard_file, dtype=np.uint8)
        if buf.size == 0 or buf.size % (DIGEST + n):
            raise BadFrame(f"shard {i + 1}: {buf.size} bytes are no whole frames of {DIGEST}+{n}")
        frames[i] = buf.reshape(-1, DIGEST + n)
    if len({f.shape[0] for f in frames.values()}) != 1:
        raise BadFrame("the shard files hold different numbers of frames")
    every = np.concatenate(list(frames.values()))
    blocks = np.ascontiguousarray(every[:, DIGEST:])
    bad = np.flatnonzero((reference.hash256(blocks) != every[:, :DIGEST]).any(axis=1))
    if bad.size:
        per = next(iter(frames.values())).shape[0]
        raise BadFrame(f"shard {sorted(frames)[int(bad[0]) // per] + 1}, frame "
                       f"{int(bad[0]) % per}: the digest is not its block's HighwayHash-256")
    return {i: blocks[k * f.shape[0]:(k + 1) * f.shape[0]]
            for k, (i, f) in enumerate(frames.items())}


def decode_matrix(d: int, p: int, present: list[int], missing: list[int]) -> np.ndarray:
    """[len(missing), d]: the rows that map the shards `present` (d erasure
    indices) onto the data shards `missing` — rows of the inverse of the
    survivors' d x d submatrix of the systematic matrix [I; parity]."""
    full = np.concatenate([np.eye(d, dtype=np.uint8), reference.parity_matrix(d, p)])
    return reference.gf_inv(full[present])[missing]


def gf_apply(mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """[r, d] x [blocks, d, n] -> [blocks, r, n] over GF(2^8)."""
    blocks, d, n = shards.shape
    out = np.zeros((blocks, mat.shape[0], n), dtype=np.uint8)
    for j in range(d):
        col = shards[:, j, :]
        for i in range(mat.shape[0]):
            if mat[i, j]:
                out[:, i, :] ^= MUL[mat[i, j]][col]
    return out


def shard_of(body: bytes, d: int, p: int, index: int) -> np.ndarray:
    """[blocks, n]: the one shard of a body at an erasure index, data or
    parity, as a PUT must have left it between the digests."""
    data = reference.split(body, d)
    if index < d:
        return data[:, index, :]
    return gf_apply(reference.parity_matrix(d, p)[[index - d]], data)[:, 0, :]


def decode_object(files: dict[int, bytes], d: int, p: int) -> bytes:
    """The object's bytes from any >= d of its d+p shard files. Every frame
    of every file given is verified (a wrong digest raises `BadFrame`); the
    first d by erasure index are the survivors used; what was rebuilt is
    re-encoded and must reproduce every file given that was not used."""
    n = reference.shard_len(d)
    if len(files) < d:
        raise ValueError(f"{len(files)} shard files cannot give back {d} data shards")
    have = verified_shards(files, n)
    present = sorted(have)[:d]
    missing = [i for i in range(d) if i not in have]
    survivors = np.stack([have[i] for i in present], axis=1)  # [blocks, d, n]
    data = np.zeros_like(survivors)
    for i in range(d):
        if i in have:
            data[:, i, :] = have[i]
    if missing:
        data[:, missing, :] = gf_apply(decode_matrix(d, p, present, missing), survivors)
    unused = [i for i in have if i not in present]
    if unused:
        again = gf_apply(reference.parity_matrix(d, p)[[i - d for i in unused]], data)
        for k, i in enumerate(unused):
            if not np.array_equal(again[:, k, :], have[i]):
                raise BadFrame(f"shard {i + 1} on the drives is not the parity of what was rebuilt")
    blocks = data.shape[0]
    return data.reshape(blocks, d * n)[:, :BLOCK].tobytes()
