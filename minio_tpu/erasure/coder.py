"""ErasureCoder — routes stripe blocks to the right codec backend.

TPU-first split: every FULL stripe block of an object has the same shape
([d, ceil(block_size/d)]), so all full blocks batch into fixed-shape fused
encode+hash device dispatches (ops/rs_jax.py + ops/bitrot_jax.py — no
recompilation). Only the object's final partial block has a variable shard
size; it runs on the numpy codec (ops/rs.py + ops/highwayhash.py), which is
byte-identical. GetObject/Heal reconstruction follows the same split.

CODE FAMILIES: two TPU-batchable families share this interface —
``reedsolomon`` (ops/rs.py / ops/rs_jax.py, the default) and ``cauchy``
(ops/cauchy.py: Cauchy MDS with piggybacked sub-chunks for partial
repair). The family is chosen per storage class at write time
(MINIO_TPU_EC_FAMILY*), recorded in xl.meta (ErasureInfo.algorithm),
and every decode/heal path dispatches on the STORED family, so objects
of both families coexist on the same drives. Per-family counters
(encode/decode blocks, heal/degraded ingress bytes) aggregate here for
the metrics-v3 /api/tpu group.

Backend forced with MINIO_TPU_BACKEND=numpy|jax (default: jax when any
device is available).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Iterator

import numpy as np

from .. import obs
from ..ops import rs
from ..ops.highwayhash import hash256_batch_numpy
from . import bitrot_io, bufpool
from .bitrot_io import FAMILY_CAUCHY, FAMILY_RS

# max shards per device dispatch (HBM headroom: the hash lane arrays
# OOM above ~3072 shards of 128 KiB on a 16 GB chip)
MAX_DEVICE_SHARDS = 3072

BLOCK_SIZE = 1 << 20  # 1 MiB stripe block, reference blockSizeV2
# (/root/reference/cmd/object-api-common.go:37)


def _use_jax() -> bool:
    mode = os.environ.get("MINIO_TPU_BACKEND", "jax")
    return mode != "numpy"


def default_ec_family() -> str:
    """Write-time code family (MINIO_TPU_EC_FAMILY). Malformed values
    fall back to reedsolomon — a tuning typo must not take down PUTs —
    but reads always dispatch on the family RECORDED in xl.meta."""
    fam = os.environ.get("MINIO_TPU_EC_FAMILY", FAMILY_RS)
    return fam if fam in bitrot_io.FAMILIES else FAMILY_RS


def repair_reads_enabled() -> bool:
    """MINIO_TPU_EC_REPAIR gates the sub-chunk partial-repair read plans
    (heal + degraded GET) of sub-packetized families; decode correctness
    never depends on it — off means full-shard reads everywhere."""
    return os.environ.get("MINIO_TPU_EC_REPAIR", "1") != "0"


class SurvivorStack:
    """The survivors of one decode group — W same-pattern stripe blocks, d
    shards of ``per`` bytes each — written ONCE, in the form the rung that
    will decode them takes (``ErasureCoder.survivor_stack`` says which):

    - ``packed``: the decode mega-kernel's own input, chunk-major
      ``[per / CHUNK_BYTES, bpad, d, CHUNK_BYTES]`` with W rounded up to
      the kernel's multiple of 16 and the pad rows zero (written here, on
      an arena that held other bytes): what ``device_put`` takes;
    - else ``[d, W, per]``, shard-major: what the XLA rung and the host's
      GF apply take.

    ``shape`` is (d, W, per) in both. Pooled scratch: ``release`` when the
    rebuilt rows are on the host (they are fresh arrays, never views)."""

    __slots__ = ("array", "shape", "packed", "_lease")

    def __init__(self, d: int, w: int, per: int, chunk: int = 0,
                 pooled: bool = True):
        self.shape = (d, w, per)
        self.packed = chunk > 0
        bpad = -(-w // 16) * 16 if self.packed else w
        nb = d * bpad * per
        self._lease = bufpool.get_pool().acquire(nb) if pooled else None
        flat = (
            self._lease.array[:nb] if pooled else np.empty(nb, dtype=np.uint8)
        )
        if self.packed:
            self.array = flat.reshape(per // chunk, bpad, d, chunk)
            self.array[:, w:] = 0
        else:
            self.array = flat.reshape(d, w, per)

    def put(self, k: int, w0: int, rows: np.ndarray) -> None:
        """Survivor k of blocks w0 .. w0 + len(rows): ONE strided copy of
        ``rows`` ([blocks, per], its rows any distance apart)."""
        n = len(rows)
        if self.packed:
            nc, _bpad, _d, chunk = self.array.shape
            self.array[:, w0 : w0 + n, k] = rows.reshape(
                n, nc, chunk
            ).transpose(1, 0, 2)
        else:
            self.array[k, w0 : w0 + n] = rows

    def block_major(self) -> np.ndarray:
        """[W, d, per]: a view of the shard-major stack; the rows taken
        back out of a packed one (a copy: the rare path on which the
        kernel it was laid out for did not take it)."""
        if not self.packed:
            return self.array.transpose(1, 0, 2)
        from ..ops import fused_pallas as fp

        return fp.unpack_chunk_major(self.array)[: self.shape[1]]

    def release(self) -> None:
        if self._lease is not None:
            self._lease.release()
            self._lease = None


# -- per-family counters (metrics-v3 /api/tpu) ------------------------------

_FSTATS_LOCK = threading.Lock()
_FAMILY_STATS: dict[str, dict[str, int]] = {}
_FSTAT_KEYS = (
    "encode_blocks", "decode_blocks", "decode_host_blocks",
    "heal_ingress_bytes", "degraded_ingress_bytes", "repair_partial_blocks",
)


def family_stats_add(family: str, key: str, n: int = 1) -> None:
    with _FSTATS_LOCK:
        st = _FAMILY_STATS.get(family)
        if st is None:
            st = _FAMILY_STATS[family] = {k: 0 for k in _FSTAT_KEYS}
        st[key] = st.get(key, 0) + n


def family_stats_snapshot() -> dict[str, dict[str, int]]:
    """Copy of the per-family counter table; families that served no
    traffic yet report zeroed rows so metrics series exist from boot."""
    with _FSTATS_LOCK:
        out = {f: dict(st) for f, st in _FAMILY_STATS.items()}
    for fam in bitrot_io.FAMILIES:
        out.setdefault(fam, {k: 0 for k in _FSTAT_KEYS})
    return out


def decode_matrix_cache_snapshot() -> dict:
    """Per-family decode-matrix LRU hit/miss counters + entry count
    (ops/decode_cache) — the /api/tpu series that make pattern-churn
    storms diagnosable from a scrape."""
    from ..ops import decode_cache

    return decode_cache.snapshot()


def encode_blocks_numpy(
    np_codec, blocks: np.ndarray, family: str = FAMILY_RS
) -> tuple[np.ndarray, np.ndarray]:
    """CPU full-block encode+hash, byte-identical to the device rungs.

    [B, d, n] -> (shards [B, t, n], digests [B, t, 32] rs /
    [B, t, 2, 32] cauchy). Shared by ErasureCoder's no-device path and
    the dispatcher's numpy degradation rung, so the two can never
    drift."""
    from ..ops.bitrot import fast_hash256_batch

    b, d, n = blocks.shape
    t = np_codec.total_shards
    shards = np.zeros((b, t, n), dtype=np.uint8)
    shards[:, :d] = blocks
    for i in range(b):
        shards[i] = np_codec.encode(shards[i])
    if family == FAMILY_CAUCHY:
        h1 = n // 2
        # per-sub-chunk digests: two bitrot frames per shard block. The
        # halves hash as separate batches (unequal lengths when n is odd).
        d1 = fast_hash256_batch(
            np.ascontiguousarray(shards[:, :, :h1]).reshape(b * t, h1)
        )
        d2 = fast_hash256_batch(
            np.ascontiguousarray(shards[:, :, h1:]).reshape(b * t, n - h1)
        )
        digests = np.stack(
            [np.asarray(d1), np.asarray(d2)], axis=1
        ).reshape(b, t, 2, 32)
        return shards, digests
    digests = np.asarray(
        fast_hash256_batch(shards.reshape(b * t, -1))
    ).reshape(b, t, 32)
    return shards, digests


@dataclass
class EncodedPart:
    """One erasure-coded part: per-drive shard file bytes (bitrot
    interleaved) in erasure-index order [0..d+p)."""

    shard_files: list[bytes]
    size: int  # input size


class EncodedBatch:
    """One streaming-encode batch on the zero-copy plane.

    ``shard_vecs[i]`` is the writev-style buffer sequence for erasure
    index i — alternating digest-row / shard-row views, framed exactly
    like the legacy bytearray chunks. Digest rows and the shard rows of
    parity indices (i >= d) are views of the dispatch's result arrays;
    the shard rows of data indices (i < d) are views of the INGEST ARENA
    itself — the dispatcher hands no data byte back. ``raw`` is the input
    slice this batch encoded (md5/size folding), a memoryview into the
    same arena on the pooled path. So the caller MUST finish both the md5
    fold and every ``append_file(shard_vecs[i])`` before calling
    :meth:`release` — the release returns the arena to the pool, and a
    data row read after it reads another request's bytes
    (docs/ERASURE.md buffer-ownership contract)."""

    __slots__ = ("shard_vecs", "raw", "_lease")

    def __init__(self, shard_vecs, raw, lease=None):
        self.shard_vecs: list[list] = shard_vecs
        self.raw = raw
        self._lease = lease

    def release(self) -> None:
        """Return the backing ingest arena (if pooled). Idempotent."""
        lease, self._lease = self._lease, None
        if lease is not None:
            lease.release()


class ErasureCoder:
    def __init__(
        self, data_blocks: int, parity_blocks: int,
        block_size: int = BLOCK_SIZE, family: str = FAMILY_RS,
    ):
        self.family = bitrot_io.check_family(family)
        self.d = data_blocks
        self.p = parity_blocks
        self.t = data_blocks + parity_blocks
        self.block_size = block_size
        self.shard_size = -(-block_size // data_blocks)
        # on-disk digest overhead per shard block (1 frame for rs, 2 for
        # the sub-packetized cauchy family)
        self.frame_digests = bitrot_io.frames_per_block(self.family)
        if self.family == FAMILY_CAUCHY:
            from ..ops import cauchy as cauchy_mod

            self._np = cauchy_mod.get_codec(self.d, self.p)
        else:
            self._np = rs.get_codec(self.d, self.p)
        self._jax = None
        if _use_jax():
            from ..ops import runtime

            # before the first device compile of this process: place the
            # persistent compile cache, then say which device this is
            runtime.ensure_compile_cache()
            if self.family == FAMILY_CAUCHY:
                from ..ops import cauchy as cauchy_mod

                self._jax = cauchy_mod.get_tpu_codec(self.d, self.p)
            else:
                from ..ops import rs_jax  # deferred: jax import is heavy

                self._jax = rs_jax.get_tpu_codec(self.d, self.p)
            runtime.announce_device_plane()

    @property
    def device_active(self) -> bool:
        """True when writes should route through the device dispatcher:
        either a real accelerator backend is live, or the operator
        explicitly forced MINIO_TPU_BACKEND=jax (CI exercises the device
        plane on virtual CPU devices that way). A merely-importable jax on
        a CPU-only host must NOT disable the native C++ plane."""
        if self._jax is None:
            return False
        if os.environ.get("MINIO_TPU_BACKEND") == "jax":
            return True
        import jax

        return jax.default_backend() != "cpu"

    # -- encode ------------------------------------------------------------

    def _encode_block_np(self, block: bytes) -> tuple[np.ndarray, np.ndarray]:
        from .. import native
        from ..ops.highwayhash import MINIO_KEY

        # tail blocks count like full blocks so the per-family encode
        # series stays comparable across families
        family_stats_add(self.family, "encode_blocks", 1)
        if native.available():
            shards = self._np.split(block)
            parity, digests = native.gf_encode_hash(
                self._np.parity_matrix, shards[: self.d], MINIO_KEY
            )
            shards[self.d :] = parity
            return shards, digests
        shards = self._np.encode_data(block)  # [t, per]
        digests = hash256_batch_numpy(shards)
        return shards, digests

    def _encode_full_blocks(self, blocks: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """blocks: [B, d, shard_size] -> (parity [B, p, n], digests).

        Parity rows only: data row i of block b is the caller's own
        ``blocks[b, i]``. digests cover all t rows: [B, t, 32] for
        reedsolomon, [B, t, 2, 32] (per sub-chunk) for cauchy. The device
        path goes through the batching dispatcher: blocks from concurrent
        requests of BOTH families coalesce into one stream (family tag
        per batch entry). The cauchy composite matmul needs an even shard
        size; odd geometries take the numpy path below."""
        if self._jax is not None and (
            self.family != FAMILY_CAUCHY or blocks.shape[2] % 2 == 0
        ):
            from ..parallel.dispatcher import get_dispatcher

            # submit -> result: queue wait + the whole dispatch
            with obs.phase("put", "encode_wait"):
                return get_dispatcher(self._jax, blocks.shape[2]).encode(
                    blocks, codec=self._jax
                )
        family_stats_add(self.family, "encode_blocks", blocks.shape[0])
        shards, digests = encode_blocks_numpy(self._np, blocks, self.family)
        return shards[:, self.d :], digests

    def _encode_full_buffer(self, data: memoryview) -> list[bytearray]:
        """len(data) is a multiple of block_size -> per-shard file chunks
        (family-framed digest || shard interleave) for these blocks."""
        full = len(data) // self.block_size
        per = self.shard_size
        padded_block = self.d * per  # >= block_size; zero padding at tail
        bufpool.count_copy("staging")  # bytes -> numpy staging materialization
        with obs.phase("put", "stage"):
            arr = np.zeros((full, self.d, per), dtype=np.uint8)
            flat = np.frombuffer(data, dtype=np.uint8)
            if padded_block == self.block_size:
                arr[:] = flat.reshape(full, self.d, per)
            else:
                for b in range(full):
                    blk = flat[b * self.block_size : (b + 1) * self.block_size]
                    a = arr[b].reshape(-1)
                    a[: self.block_size] = blk
        files = [bytearray() for _ in range(self.t)]
        max_blocks = max(1, MAX_DEVICE_SHARDS // self.t)
        cauchy = self.family == FAMILY_CAUCHY
        h1 = per // 2
        for start in range(0, full, max_blocks):
            chunk = arr[start : start + max_blocks]
            parity, digests = self._encode_full_blocks(chunk)
            with obs.phase("put", "frame"):
                for b in range(chunk.shape[0]):
                    for i in range(self.t):
                        row = chunk[b, i] if i < self.d else parity[b, i - self.d]
                        if cauchy:
                            files[i] += digests[b, i, 0].tobytes()
                            files[i] += row[:h1].tobytes()
                            files[i] += digests[b, i, 1].tobytes()
                            files[i] += row[h1:].tobytes()
                        else:
                            files[i] += digests[b, i].tobytes()
                            files[i] += row.tobytes()
        bufpool.count_copy("frame-tobytes", full * self.t)
        return files

    def _encode_tail_buffer(self, data: bytes) -> list[bytearray]:
        """Partial final block (numpy codec, byte-identical)."""
        bufpool.count_copy("tail-block", self.t)
        if self.family == FAMILY_CAUCHY:
            shards = self._np.encode_data(data)
            family_stats_add(self.family, "encode_blocks", 1)
            return [
                bytearray(bitrot_io.frame_block(shards[i].tobytes(), self.family))
                for i in range(self.t)
            ]
        shards, digests = self._encode_block_np(data)
        files = [bytearray() for _ in range(self.t)]
        for i in range(self.t):
            files[i] += digests[i].tobytes()
            files[i] += shards[i].tobytes()
        return files

    def iter_encode(
        self, reader, max_batch_bytes: int | None = None
    ) -> "Iterator[tuple[list[bytearray], bytes]]":
        """Streaming encode: consume an iterator of byte chunks, yield
        (per-shard file chunks, the raw input slice encoded) per batch.

        Bounded memory: at most one batch of input is resident, mirroring
        the reference's block-at-a-time ring buffer
        (/root/reference/cmd/bitrot-streaming.go:108-133) at device-batch
        granularity. The raw slice lets callers fold md5/size incrementally.
        max_batch_bytes clamps the batch below the device HBM cap —
        streaming callers pass their memory bound; in-memory callers leave
        it None for full-width device dispatches.
        """
        batch_bytes = max(1, MAX_DEVICE_SHARDS // self.t) * self.block_size
        if max_batch_bytes is not None:
            batch_bytes = min(batch_bytes, max(self.block_size, max_batch_bytes))
        buf = bytearray()
        # `ingest` is what this generator spends pulling reader chunks
        # between two batches (about a thousand chunks per object): one
        # clock pair per batch, booked when the batch is cut
        ingest = obs.PhaseClock("put", "ingest")
        for chunk in reader:
            if not chunk:
                continue
            buf += chunk
            while len(buf) >= batch_bytes:
                ingest.book()
                yield self._cut(buf, batch_bytes)
                ingest.restart()
        if buf:
            ingest.book()
        full = (len(buf) // self.block_size) * self.block_size
        if full:
            yield self._cut(buf, full)
        if buf:
            piece = bytes(buf)
            yield self._encode_tail_buffer(piece), piece

    def _cut(self, buf: bytearray, nbytes: int) -> tuple[list[bytearray], bytes]:
        """Take the first nbytes (whole stripe blocks) off `buf`, encoded."""
        bufpool.count_copy("staging")
        with obs.phase("put", "stage"):
            piece = bytes(buf[:nbytes])
            del buf[:nbytes]
        return self._encode_full_buffer(memoryview(piece)), piece

    def _frame_into(
        self, vecs: list[list], blocks: np.ndarray, parity: np.ndarray,
        digests: np.ndarray,
    ) -> None:
        """Append digest/shard ROW VIEWS to the per-shard writev vectors
        — same on-disk frame interleave as _encode_full_buffer, zero
        materialization. Data rows are views of `blocks`, the [B, d, n]
        array that was submitted; parity and digest rows are views of
        the dispatch's result. The views pin all three alive until the
        disk layer consumes them (a pooled arena behind `blocks` is the
        caller's to keep leased that long)."""
        cauchy = self.family == FAMILY_CAUCHY
        h1 = blocks.shape[2] // 2
        for b in range(blocks.shape[0]):
            for i in range(self.t):
                v = vecs[i]
                row = blocks[b, i] if i < self.d else parity[b, i - self.d]
                if cauchy:
                    v.append(digests[b, i, 0].data)
                    v.append(row[:h1].data)
                    v.append(digests[b, i, 1].data)
                    v.append(row[h1:].data)
                else:
                    v.append(digests[b, i].data)
                    v.append(row.data)

    def _emit_zc(self, lease, nbytes: int) -> EncodedBatch:
        """Encode the first nbytes (whole stripe blocks) of a pooled
        ingest arena. The arena IS the dispatch geometry — reshape, no
        copy — and the batch takes over the lease (released by the
        caller once md5 + shard appends are done)."""
        full = nbytes // self.block_size
        arr = lease.array[:nbytes].reshape(full, self.d, self.shard_size)
        vecs: list[list] = [[] for _ in range(self.t)]
        max_blocks = max(1, MAX_DEVICE_SHARDS // self.t)
        for start in range(0, full, max_blocks):
            chunk = arr[start : start + max_blocks]
            parity, digests = self._encode_full_blocks(chunk)
            with obs.phase("put", "frame"):
                self._frame_into(vecs, chunk, parity, digests)
        return EncodedBatch(vecs, lease.view(nbytes), lease)

    def iter_encode_zc(
        self, reader, max_batch_bytes: int | None = None
    ) -> "Iterator[EncodedBatch]":
        """Zero-copy streaming encode: reader chunks land DIRECTLY in a
        pooled arena laid out in dispatcher geometry [B, d, shard_size],
        the device consumes the arena view, and framing yields shard-row
        views for writev-style appends — no staging copy anywhere on the
        full-block path (site "staging" stays 0; the partial tail block
        is the one inherent copy, counted as "tail-block").

        Falls back to the counting legacy path when MINIO_TPU_ZEROCOPY=0
        (the A/B lever) or when d does not divide block_size (the flat
        stream cannot alias as [B, d, per] — shard padding interleaves).
        Every yielded batch must be release()d by the caller; abandoning
        the generator releases the in-fill arena via close().
        """
        per = self.shard_size
        if self.d * per != self.block_size or not bufpool.zerocopy_enabled():
            for chunks, raw in self.iter_encode(reader, max_batch_bytes):
                with obs.phase("put", "frame"):
                    vecs = [[bytes(c)] for c in chunks]
                yield EncodedBatch(vecs, raw)
            return
        batch_blocks = max(1, MAX_DEVICE_SHARDS // self.t)
        if max_batch_bytes is not None:
            batch_blocks = max(1, min(batch_blocks, max_batch_bytes // self.block_size))
        # round DOWN to a power of two: the dispatcher buckets batch
        # sizes to powers of two, so an exact-fit arena dispatches as-is
        # (no bucket copy, no pad) instead of padding 192 -> 256
        p2 = 1
        while p2 * 2 <= batch_blocks:
            p2 <<= 1
        batch_blocks = p2
        batch_bytes = batch_blocks * self.block_size
        pool = bufpool.get_pool()
        lease = None
        mv: memoryview | None = None
        pos = 0
        ingest = obs.PhaseClock("put", "ingest")  # as in iter_encode: booked once per batch
        try:
            for chunk in reader:
                if not chunk:
                    continue
                cmv = memoryview(chunk)
                off = 0
                while off < len(cmv):
                    if lease is None:
                        lease = pool.acquire(batch_bytes)
                        mv = lease.view(batch_bytes)
                        pos = 0
                    n = min(len(cmv) - off, batch_bytes - pos)
                    mv[pos : pos + n] = cmv[off : off + n]
                    pos += n
                    off += n
                    if pos == batch_bytes:
                        ingest.book()
                        batch, lease, mv = self._emit_zc(lease, pos), None, None
                        yield batch
                        ingest.restart()
            if lease is not None:
                ingest.book()
                full = (pos // self.block_size) * self.block_size
                # the tail residue is copied OUT of the arena before the
                # full-block batch hands the lease to the caller
                tail = bytes(mv[full:pos]) if pos > full else b""
                if full:
                    batch, lease, mv = self._emit_zc(lease, full), None, None
                    yield batch
                else:
                    lease.release()
                    lease = mv = None
                if tail:
                    yield EncodedBatch(
                        [[bytes(c)] for c in self._encode_tail_buffer(tail)], tail
                    )
        finally:
            if lease is not None:
                lease.release()

    def encode_part(self, data: bytes) -> EncodedPart:
        """Erasure-code one in-memory part into per-drive shard files.

        Full stripe blocks go to the device in batches; the partial tail
        block (if any) uses the numpy codec. Output per drive is the
        bitrot-interleaved shard file (digest || shard block per stripe).
        Large/streamed parts should use iter_encode via the streaming
        put path instead of materializing here.
        """
        n = len(data)
        files = [bytearray() for _ in range(self.t)]
        if n == 0:
            return EncodedPart([bytes(f) for f in files], 0)
        for chunks, _raw in self.iter_encode(iter([data])):
            for i in range(self.t):
                files[i] += chunks[i]
        return EncodedPart([bytes(f) for f in files], n)

    # -- decode ------------------------------------------------------------

    def reconstruct_block(
        self, present: dict[int, np.ndarray], per_shard: int
    ) -> dict[int, np.ndarray]:
        """Rebuild ALL missing shards of one stripe block from >= d present.

        present: {erasure_index: shard bytes [per_shard]}. Returns the full
        {index: shard} map. numpy path (single block; device batching is for
        the heal plane)."""
        idxs = sorted(present.keys())
        if len(idxs) < self.d:
            raise ValueError("not enough shards to reconstruct")
        shards: list[np.ndarray | None] = [None] * self.t
        for i in idxs:
            shards[i] = present[i]
        rec = self._np.reconstruct(shards)
        family_stats_add(self.family, "decode_blocks", 1)
        family_stats_add(self.family, "decode_host_blocks", 1)
        return {i: rec[i] for i in range(self.t)}

    def _decodes_on_device(self, w: int) -> bool:
        """A group of w blocks goes to a device rung: not the cauchy
        family, not a CPU-plane process, not under the device floor."""
        return (
            self.family != FAMILY_CAUCHY
            and self._jax is not None
            and w * self.t >= int(os.environ.get("MINIO_TPU_DECODE_MIN_SHARDS", "64"))
        )

    def survivor_stack(
        self, w: int, per: int, missing: int, pooled: bool = True
    ) -> SurvivorStack:
        """The stack a group of w blocks with `missing` shards to rebuild
        is gathered into, laid out for the rung that will decode it, from
        what can be observed now: packed where the decode mega-kernel will
        take the group (family, the device floor, its shapes, no cooldown),
        [d, w, per] everywhere else."""
        chunk = 0
        if self._decodes_on_device(w):
            from ..ops import bitrot_jax, fused_pallas as fp

            if bitrot_jax.fused_decode_takes(self.d, missing, w, per):
                chunk = fp.CHUNK_BYTES
        return SurvivorStack(self.d, w, per, chunk, pooled)

    def reconstruct_data_flat(
        self,
        survivors: np.ndarray | SurvivorStack,
        present: tuple[int, ...],
        missing: tuple[int, ...],
        pool=None,
    ) -> np.ndarray:
        """Rebuild missing data shards from [d, W, per] (shard-major) input,
        a plain array or the stack `survivor_stack` prepared.

        Returns [len(missing), W, per]. The GET hot layout: survivors land
        contiguous per shard row, the native AVX2 GF apply consumes them
        without a transpose, and a thread pool splits the column range so
        the apply scales past one core (ctypes releases the GIL); a packed
        stack is the decode mega-kernel's input as it stands.
        """
        d_, w, per = survivors.shape
        packed = isinstance(survivors, SurvivorStack) and survivors.packed
        if isinstance(survivors, SurvivorStack) and not packed:
            survivors = survivors.array
        family_stats_add(self.family, "decode_blocks", w)
        if self._decodes_on_device(w):
            from ..ops.bitrot_jax import _try_fused_decode, xla_decode
            from ..ops.highwayhash import MINIO_KEY

            # degraded GET rides the decode mega-kernel when shapes allow
            if packed:
                fused = _try_fused_decode(
                    self._jax, survivors.array, present, missing, MINIO_KEY,
                    packed_blocks=w,
                )
            else:
                arr = survivors.transpose(1, 0, 2)  # [W, d, per]
                fused = _try_fused_decode(self._jax, arr, present, missing, MINIO_KEY)
            if fused is not None:
                return fused[0].transpose(1, 0, 2)
            if packed:
                # the kernel the stack was laid out for did not take it
                arr = survivors.block_major()
            return xla_decode(self._jax, arr, present, missing).transpose(1, 0, 2)
        # the cauchy family, a group under the device floor, a CPU-plane
        # process: the host's GF apply, on the calling thread and the pool
        family_stats_add(self.family, "decode_host_blocks", w)
        if packed:
            survivors = survivors.block_major().transpose(1, 0, 2)
        with obs.phase("decode", "host"):
            return self._reconstruct_flat_host(survivors, present, missing, pool)

    def _reconstruct_flat_host(self, survivors, present, missing, pool):
        from .. import native

        if self.family == FAMILY_CAUCHY:
            # cauchy decode runs on the numpy/native GF plane: the
            # piggyback purify step chains two applies, and repair-path
            # reads (the family's point) are bandwidth- not compute-
            # bound. Device decode is a named PERF round-9 next lever.
            return self._np.reconstruct_flat(survivors, present, missing)
        d_, w, per = survivors.shape
        mat = self._decode_rows(present, missing)
        flat = survivors.reshape(self.d, w * per)
        if native.available():
            cols = w * per
            shards_split = max(1, min(4, cols // (1 << 20)))
            if pool is not None and shards_split > 1:
                step = -(-cols // shards_split)
                out = np.empty((len(missing), cols), dtype=np.uint8)

                def apply_slice(s):
                    # the strided->contiguous copy happens in the worker too
                    return native.gf_apply(mat, flat[:, s:s + step])

                futs = [(s, pool.submit(apply_slice, s)) for s in range(0, cols, step)]
                for s, f in futs:
                    piece = f.result()
                    out[:, s:s + piece.shape[1]] = piece
            else:
                out = native.gf_apply(mat, flat)
            return out.reshape(len(missing), w, per)
        return self._np_reconstruct_batch(
            survivors.transpose(1, 0, 2), present, missing
        ).transpose(1, 0, 2)

    def _decode_rows(
        self, present: tuple[int, ...], missing: tuple[int, ...]
    ) -> np.ndarray:
        return self._np.reconstruct_rows_for(list(present), list(missing))

    def _np_reconstruct_batch(
        self,
        survivors: np.ndarray,
        present: tuple[int, ...],
        missing: tuple[int, ...],
    ) -> np.ndarray:
        from .. import native
        from ..ops import gf

        mat = self._decode_rows(present, missing)  # [m, d]
        w, _, per = survivors.shape
        if native.available():
            # AVX2 GF apply: fold the window into the column length
            flat = np.ascontiguousarray(survivors.transpose(1, 0, 2)).reshape(
                self.d, w * per
            )
            return native.gf_apply(mat, flat).reshape(len(missing), w, per).transpose(1, 0, 2)
        out = np.zeros((w, len(missing), per), dtype=np.uint8)
        for r, row in enumerate(mat):
            acc = out[:, r]
            for k in range(self.d):
                c = int(row[k])
                if c:
                    acc ^= gf.MUL_TABLE[c][survivors[:, k]]
        return out

    # -- partial repair (sub-packetized families) --------------------------

    def repair_schedule(self, missing: int):
        """Sub-chunk repair plan for ONE lost data shard, or None when
        the family has no shortcut (reedsolomon, parity loss, p < 2, or
        repair reads disabled via MINIO_TPU_EC_REPAIR=0)."""
        if self.family != FAMILY_CAUCHY or not repair_reads_enabled():
            return None
        return self._np.repair_schedule(missing)

    def repair_data_shard(self, sched, shard_size, sub2, pb_sub2, sub1):
        """Execute a repair schedule (ops/cauchy.repair_data_shard)."""
        family_stats_add(self.family, "repair_partial_blocks", 1)
        return self._np.repair_data_shard(sched, shard_size, sub2, pb_sub2, sub1)

    # -- geometry ----------------------------------------------------------

    def shard_sizes_for(self, total: int) -> list[tuple[int, int]]:
        """[(block_data_len, per_shard)] for each stripe block of a part."""
        out = []
        full = total // self.block_size
        for _ in range(full):
            out.append((self.block_size, self.shard_size))
        tail = total - full * self.block_size
        if tail:
            out.append((tail, -(-tail // self.d)))
        return out
