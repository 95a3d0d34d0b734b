"""Streaming-bitrot shard file format: digest || block, per shard block.

The on-disk format matches the reference's streaming bitrot writer
(/root/reference/cmd/bitrot-streaming.go): a shard file holding K shard
blocks of `shard_size` bytes (last may be short) is stored as
    hash(block_0) || block_0 || hash(block_1) || block_1 || ...
with HighwayHash-256 (32-byte digests, MinIO magic key). Verification reads
recompute each block's digest (/root/reference/cmd/bitrot.go:164-216).

The legacy WHOLE-FILE format (/root/reference/cmd/bitrot-whole.go) is also
supported for reading: the shard file holds raw shard bytes and ONE digest
over the whole file lives in the version metadata
(ErasureInfo.checksums[part].hash). New writes always produce the
streaming format, like the reference; whole-file is a read/verify/heal
compatibility surface for imported legacy data.

FAMILY FRAMING — the shard-block frame depends on the erasure code
family recorded in xl.meta (ErasureInfo.algorithm):

- ``reedsolomon``:  hash(block) || block            (one frame)
- ``cauchy``:       hash(sub1) || sub1 || hash(sub2) || sub2

The cauchy family (ops/cauchy.py) sub-packetizes every shard block into
two sub-chunks so single-shard repair can fetch PARTIAL shards; each
sub-chunk carries its own digest so a sub-chunk ranged read stays
bitrot-verified without touching the other half (``sub_chunk_span`` +
``verify_sub_chunk`` are that read path). Unknown family strings raise
the typed ``errors.UnknownErasureFamily``.
"""

from __future__ import annotations

import os

from ..ops.bitrot import DEFAULT_BITROT_ALGO, BitrotAlgorithm
from ..storage import errors

DIGEST_SIZE = 32

FAMILY_RS = "reedsolomon"
FAMILY_CAUCHY = "cauchy"
FAMILIES = (FAMILY_RS, FAMILY_CAUCHY)


def check_family(family: str) -> str:
    """Validate an xl.meta code-family string; single choke point for the
    'unknown-family is a typed error, never a misread frame' contract."""
    if family not in FAMILIES:
        raise errors.UnknownErasureFamily(
            f"unknown erasure code family {family!r} (known: {FAMILIES})"
        )
    return family


def frames_per_block(family: str = FAMILY_RS) -> int:
    """Bitrot frames (digests) per shard block for a code family."""
    return 2 if check_family(family) == FAMILY_CAUCHY else 1


def sub_lens(shard_size: int) -> tuple[int, int]:
    """(len(sub-chunk 1), len(sub-chunk 2)) of a sub-packetized shard
    block. Single source: ops/cauchy.sub_lens (floor half first) —
    duplicated arithmetic here would let the framing drift from the
    codec."""
    from ..ops.cauchy import sub_lens as _cs

    return _cs(shard_size)


_sub_lens = sub_lens


def block_offset(shard_size: int, block_index: int, family: str = FAMILY_RS) -> int:
    """Shard-file offset of block `block_index` (its digest(s) included)."""
    return block_index * (
        frames_per_block(family) * DIGEST_SIZE + shard_size
    )


def block_disk_size(shard_size: int, family: str = FAMILY_RS) -> int:
    """On-disk bytes of one shard-block frame group."""
    return frames_per_block(family) * DIGEST_SIZE + shard_size


def sub_chunk_in_block(shard_size: int, which: int) -> tuple[int, int]:
    """(offset within the block's frame group, data length) of one
    sub-chunk frame — the single source for the cauchy frame layout
    that the partial-repair readers (GET + heal) and ``sub_chunk_span``
    all share. ``shard_size`` is THIS block's shard length (tail blocks
    differ from full blocks)."""
    h1, h2 = _sub_lens(shard_size)
    if which == 0:
        return 0, h1
    if which == 1:
        return DIGEST_SIZE + h1, h2
    raise ValueError("sub-chunk index must be 0 or 1")


def sub_chunk_span(
    shard_size: int, block_index: int, which: int, family: str = FAMILY_CAUCHY
) -> tuple[int, int, int]:
    """(file offset, on-disk length, data length) of one sub-chunk frame
    of a cauchy shard block in a uniform-geometry shard file."""
    if check_family(family) != FAMILY_CAUCHY:
        raise ValueError("sub-chunk reads exist only for sub-packetized families")
    base = block_offset(shard_size, block_index, family)
    rel, dlen = sub_chunk_in_block(shard_size, which)
    return base + rel, DIGEST_SIZE + dlen, dlen


def _digest(block: bytes, algo: BitrotAlgorithm) -> bytes:
    if algo in (BitrotAlgorithm.HIGHWAYHASH256, BitrotAlgorithm.HIGHWAYHASH256S):
        from ..ops.bitrot import fast_hash256

        return fast_hash256(block)
    h = algo.new()
    h.update(block)
    return h.digest()


def frame_block(
    block: bytes, family: str = FAMILY_RS,
    algo: BitrotAlgorithm = DEFAULT_BITROT_ALGO,
) -> bytes:
    """Digest-frame one shard block for its family's on-disk format."""
    if check_family(family) == FAMILY_CAUCHY:
        h1, _h2 = _sub_lens(len(block))
        sub1, sub2 = block[:h1], block[h1:]
        return _digest(sub1, algo) + sub1 + _digest(sub2, algo) + sub2
    return _digest(block, algo) + block


def verify_block(
    buf: bytes, expect_len: int, algo: BitrotAlgorithm = DEFAULT_BITROT_ALGO,
    family: str = FAMILY_RS, view: bool = False,
):
    """Split one shard-block frame group and verify it; returns the block.

    Raises FileCorrupt on short reads or digest mismatch — the bitrot
    detection that triggers healing in the read path. Single source of
    truth for the record layout (used by reads, inline verify, heal).
    For the cauchy family the buffer holds TWO digest||sub-chunk frames;
    both verify and the sub-chunks concatenate back into the block.

    ``view=True`` returns a zero-copy memoryview of the payload where
    the frame layout allows (reedsolomon: the payload is one contiguous
    span of ``buf``, which must stay alive while the view is used). The
    cauchy frame interleaves digests between its sub-chunks, so a
    contiguous block always assembles once into a fresh buffer —
    regardless of ``view``, that one copy is inherent to the format."""
    if check_family(family) == FAMILY_CAUCHY:
        if len(buf) != 2 * DIGEST_SIZE + expect_len:
            raise errors.FileCorrupt("short shard block")
        h1, h2 = _sub_lens(expect_len)
        mv = memoryview(buf)
        sub1 = verify_sub_chunk(mv[: DIGEST_SIZE + h1], h1, algo)
        sub2 = verify_sub_chunk(mv[DIGEST_SIZE + h1 :], h2, algo)
        out = bytearray(expect_len)
        out[:h1] = sub1
        out[h1:] = sub2
        return out
    if len(buf) != DIGEST_SIZE + expect_len:
        raise errors.FileCorrupt("short shard block")
    mv = memoryview(buf)
    digest, block = mv[:DIGEST_SIZE], mv[DIGEST_SIZE:]
    if _digest(block, algo) != digest:
        raise errors.FileCorrupt("bitrot detected")
    return block if view else bytes(block)


class FrameRun(list):
    """A run's verified payloads, in order. ``rows`` is the same bytes as ONE
    array, ``[frames, length]`` with its rows a frame apart, where the
    payloads are equal-length views of one read buffer (more than one of
    them): what a single strided copy of the run takes. Else None."""

    rows = None


def verify_run(
    buf: bytes, lens, algo: BitrotAlgorithm = DEFAULT_BITROT_ALGO,
    view: bool = False,
) -> FrameRun:
    """Split a run of consecutive reedsolomon frames (digest || block, the
    blocks ``lens`` bytes long, read from the shard file in ONE piece) and
    verify EVERY frame before any payload is handed out; returns the
    payloads in order.

    A run of one frame is ``verify_block``'s reedsolomon case. Raises
    FileCorrupt on a short read or on any frame whose digest is wrong: the
    whole run is then unusable to the caller, which reads those blocks
    from another shard. The payloads hash where they were read — no copy
    of the run first — and a stretch of equal-length frames in one native
    call. ``view=True`` returns views of ``buf`` (which the views keep
    alive) and, where they are equally long, the run as one array
    (``FrameRun.rows``); else bytes."""
    if len(buf) != sum(lens) + DIGEST_SIZE * len(lens):
        raise errors.FileCorrupt("short shard run")
    mv = memoryview(buf)
    offs = []  # of the payloads; a frame's digest ends where its payload starts
    off = 0
    for n in lens:
        offs.append(off + DIGEST_SIZE)
        off += DIGEST_SIZE + n
    for k, dig in enumerate(_run_digests(mv, offs, lens, algo)):
        if dig != mv[offs[k] - DIGEST_SIZE : offs[k]]:
            raise errors.FileCorrupt(f"bitrot detected (frame {k} of run)")
    if not view:
        return FrameRun(bytes(mv[o : o + n]) for o, n in zip(offs, lens))
    out = FrameRun(mv[o : o + n] for o, n in zip(offs, lens))
    if len(lens) > 1 and len(set(lens)) == 1:
        import numpy as np

        out.rows = np.frombuffer(mv, dtype=np.uint8).reshape(
            len(lens), DIGEST_SIZE + lens[0]
        )[:, DIGEST_SIZE:]
    return out


def _run_digests(mv: memoryview, offs, lens, algo: BitrotAlgorithm) -> list[bytes]:
    """Digest of each payload ``mv[offs[k]:][:lens[k]]``; native
    HighwayHash takes a stretch of equal-length frames in one strided call."""
    from .. import native

    if algo not in (
        BitrotAlgorithm.HIGHWAYHASH256, BitrotAlgorithm.HIGHWAYHASH256S
    ) or not native.available():
        return [_digest(mv[o : o + n], algo) for o, n in zip(offs, lens)]
    import numpy as np

    from ..ops.highwayhash import MINIO_KEY

    flat = np.frombuffer(mv, dtype=np.uint8)
    digs: list[bytes] = []
    i = 0
    while i < len(lens):
        j = i + 1
        while j < len(lens) and lens[j] == lens[i]:
            j += 1
        digs += [
            d.tobytes() for d in native.hh256_frames(
                MINIO_KEY, flat, offs[i], DIGEST_SIZE + lens[i], lens[i], j - i
            )
        ]
        i = j
    return digs


def verify_sub_chunk(
    buf: bytes, expect_len: int, algo: BitrotAlgorithm = DEFAULT_BITROT_ALGO
) -> bytes:
    """Verify one digest||sub-chunk frame (the partial-repair read unit)."""
    if len(buf) != DIGEST_SIZE + expect_len:
        raise errors.FileCorrupt("short sub-chunk frame")
    digest, sub = buf[:DIGEST_SIZE], buf[DIGEST_SIZE:]
    if _digest(sub, algo) != digest:
        raise errors.FileCorrupt("bitrot detected (sub-chunk)")
    return sub


def whole_file_digest(data: bytes, algo: BitrotAlgorithm = DEFAULT_BITROT_ALGO) -> bytes:
    """Digest of a whole raw shard file (legacy whole-file bitrot mode)."""
    if algo in (BitrotAlgorithm.HIGHWAYHASH256, BitrotAlgorithm.HIGHWAYHASH256S):
        from ..ops.bitrot import fast_hash256

        return fast_hash256(data)
    h = algo.new()
    h.update(data)
    return h.digest()


def verify_whole_file(
    data: bytes, expect_digest: bytes,
    algo: BitrotAlgorithm = DEFAULT_BITROT_ALGO,
) -> bytes:
    """Verify a whole raw shard against its stored metadata digest
    (reference cmd/bitrot-whole.go wholeBitrotVerifier)."""
    if whole_file_digest(data, algo) != expect_digest:
        raise errors.FileCorrupt("bitrot detected (whole-file)")
    return data


def bitrot_verify_file(
    path: str,
    want_file_size: int,
    shard_size: int,
    algo: BitrotAlgorithm = DEFAULT_BITROT_ALGO,
    family: str = FAMILY_RS,
) -> None:
    """Whole-file streaming verification (heal/scanner path).

    want_file_size is the *data* size of the shard (without digests); the
    on-disk file must be exactly want_file_size plus the family's digest
    overhead (one 32-byte digest per frame, frames_per_block per block).
    """
    frames = frames_per_block(family)
    n_blocks = -(-want_file_size // shard_size) if want_file_size else 0
    expect_disk = want_file_size + n_blocks * frames * DIGEST_SIZE
    try:
        actual = os.path.getsize(path)
    except FileNotFoundError:
        raise errors.FileNotFound(path) from None
    if actual != expect_disk:
        raise errors.FileCorrupt(
            f"shard file size {actual} != expected {expect_disk}"
        )
    with open(path, "rb") as f:
        left = want_file_size
        while left > 0:
            n = min(shard_size, left)
            buf = f.read(frames * DIGEST_SIZE + n)
            if len(buf) != frames * DIGEST_SIZE + n:
                raise errors.FileCorrupt("short read during verify")
            verify_block(buf, n, algo, family)
            left -= n
