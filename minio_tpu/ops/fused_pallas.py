"""Fused RS-encode + HighwayHash mega-kernel (Pallas TPU, chunk-major).

One kernel produces parity AND all per-shard bitrot digests for a batch of
stripe blocks, reading the data exactly once from HBM and writing parity
exactly once — shards never round-trip through HBM between encode and hash.
Replaces the reference's per-request CPU pipeline (encode loop
/root/reference/cmd/erasure-encode.go:76-108 + streaming bitrot hashing
/root/reference/cmd/bitrot-streaming.go:44-75) with one device dispatch for
the whole concurrent batch.

Why chunk-major ([nc, B, shard, CB] with CB = CHUNK*32 bytes): TPU DMA
engines move contiguous slabs well but collapse on the 1 KiB-run strided
reads a row-major [B, shard, n] layout forces per grid step (measured
~85 GiB/s vs ~340 GiB/s HBM copy on v5e). With chunk-major input each grid
step DMAs one contiguous slab; all repacking happens in VMEM where 2-D u32
transposes run near register bandwidth. The host-side packer writes the
same bytes it would have memcpy'd anyway, just at chunk-strided offsets.

Three hard-won kernel facts (see PERF.md):
- Strided HBM DMA is the enemy; layout beats arithmetic.
- The packet chain's live state (32 x [8, S8] u32) must be processed in
  shard sub-batches of SUB=128 lanes or it blows the VREG file and every
  hash round spills to VMEM.
- Bit-plane extraction feeds the MXU via a host-permuted weight matrix so
  plane rows assemble with free major-axis concats (no relayouts); two
  stripe blocks share one [128, 128] block-diagonal matmul for full MXU
  utilization at EC <= 8+8.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .highwayhash import MINIO_KEY

__all__ = [
    "supports",
    "fused_encode_hash_cm",
    "fused_decode_hash_cm",
    "pack_chunk_major",
    "unpack_chunk_major",
    "CHUNK_BYTES",
]

CHUNK = 32                  # hash packets per chunk
CHUNK_BYTES = CHUNK * 32    # bytes per shard per chunk (CB)


def supports(d: int, p: int, batch: int, n: int) -> bool:
    """Whether the mega-kernel handles this shape (else use the XLA path).

    Identical gates for encode (p = parity count) and decode (p = missing
    count): the pipeline is one [128, 128] paired bit-plane matmul plus a
    hash chain over the d + p resident shards either way.
    """
    if jax.default_backend() != "tpu":
        return False
    if d > 8 or p > 8 or p < 1:  # pair-packed W is [2*8p, 2*8d] <= [128, 128]
        return False
    if batch < 16 or batch % 16 != 0:   # pairs + 8-row shard groups
        return False
    return n % CHUNK_BYTES == 0 and n > 0


def pack_chunk_major(blocks: np.ndarray) -> np.ndarray:
    """[B, d, n] u8 -> [nc, B, d, CB] u8 (host-side, one strided copy)."""
    b, d, n = blocks.shape
    nc = n // CHUNK_BYTES
    return np.ascontiguousarray(
        blocks.reshape(b, d, nc, CHUNK_BYTES).transpose(2, 0, 1, 3)
    )


def unpack_chunk_major(cm: np.ndarray) -> np.ndarray:
    """[nc, B, s, CB] u8 -> [B, s, n] u8 (host-side)."""
    nc, b, s, cb = cm.shape
    return np.ascontiguousarray(cm.transpose(1, 2, 0, 3)).reshape(b, s, nc * cb)


def _pick_ng(pairs: int, cb: int) -> int:
    """Pair-groups per chunk: matmul cols (pairs/NG)*CB ~ 24K sweet spot."""
    for ng in range(1, pairs + 1):
        if pairs % ng == 0 and (pairs // ng) * cb <= 24576:
            return ng
    return pairs


def _pick_sub(s8: int) -> int:
    """Chain sub-batch lane width: largest divisor of S8 <= 128 (VREG file)."""
    for sub in range(min(128, s8), 0, -1):
        if s8 % sub == 0:
            return sub
    return s8


def _paired_weight(w_encode: np.ndarray, d: int, p: int) -> np.ndarray:
    """Host-permuted 2-block block-diag weight [128, 128].

    Base w_encode is [8p, 8d] with rows 8*pi+bit' and cols 8*di+bit
    (ops/rs_jax.py gf_matrix_to_bitplanes). The kernel's rhs rows are
    (bit, s, di) where s is the block-in-pair — planes of the combined
    [2d, CB] tile concat along the major axis for free — and its output
    rows are (s, bit', pi) so parity bytes pack with free major splits.
    """
    w0 = np.asarray(w_encode, dtype=np.int8)
    rperm = np.array([8 * pi + bitp for bitp in range(8) for pi in range(p)])
    cperm = np.array([8 * di + bit for bit in range(8) for di in range(d)])
    w1 = w0[np.ix_(rperm, cperm)]        # [8p, 8d] rows (bit',pi) cols (bit,di)
    w3 = np.zeros((128, 128), dtype=np.int8)
    for bit in range(8):
        for di in range(d):
            c_old = bit * d + di
            w3[:8 * p, bit * 2 * d + di] = w1[:, c_old]
            w3[64:64 + 8 * p, bit * 2 * d + d + di] = w1[:, c_old]
    return w3


@functools.lru_cache(maxsize=64)
def _build(d: int, p: int, batch: int, nc: int, key: bytes):
    """Compiled mega pipeline for one (d, p, B, nc) shape.

    The same kernel serves encode (w3 from the parity matrix, p = parity
    shards) and decode (w3 from the per-failure-pattern reconstruction
    matrix, p = missing shards): in both cases d input shards produce p
    output shards via one paired bit-plane matmul, and all d+p shards are
    HighwayHashed while VMEM-resident. The [128, 128] paired weight is a
    RUNTIME input to the compiled pipeline, so the hundreds of possible
    decode failure patterns share one compilation per shape (and encode/
    decode share when p == missing count).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .bitrot_jax import (
        _St,
        _init_state,
        _permute_and_update,
        _reduce_words,
        _update,
    )

    t = d + p
    B = batch
    CB, C8 = CHUNK_BYTES, CHUNK * 8
    B8 = B // 8
    S8 = B8 * t
    NG = _pick_ng(B // 2, CB)
    PPG = B // 2 // NG
    SUB = _pick_sub(S8)

    def kern(w_ref, x_ref, init_ref, pout_ref, dig_ref, st_ref, par_ref):
        c = pl.program_id(0)
        g = pl.program_id(1)

        @pl.when((c == 0) & (g == 0))
        def _():
            st_ref[:] = init_ref[:]

        # ---- encode: PPG pairs -> one [128, PPG*CB] matmul ----
        pair_rhs = []
        for q in range(PPG):
            xx = x_ref[0, pl.ds((g * PPG + q) * 2, 2)]       # [2, d, CB] u8
            xt = xx.reshape(2 * d, CB).astype(jnp.int32)
            planes = [((xt >> b) & 1).astype(jnp.int8) for b in range(8)]
            pair_rhs.append(jnp.concatenate(planes, axis=0))  # [16d<=128, CB]
        rhs = jnp.concatenate(pair_rhs, axis=1)
        acc = jax.lax.dot_general(
            w_ref[:, : rhs.shape[0]], rhs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [128, PPG*CB]
        pa = jnp.zeros((p, PPG * CB), jnp.int32)
        pb_ = jnp.zeros((p, PPG * CB), jnp.int32)
        for b in range(8):
            pa = pa | ((acc[b * p:(b + 1) * p] & 1) << b)
            pb_ = pb_ | ((acc[64 + b * p:64 + (b + 1) * p] & 1) << b)
        pa = pa.astype(jnp.uint8)
        pb_ = pb_.astype(jnp.uint8)
        for q in range(PPG):
            both = jnp.stack(
                [pa[:, q * CB:(q + 1) * CB], pb_[:, q * CB:(q + 1) * CB]],
                axis=0,
            )
            par_ref[pl.ds((g * PPG + q) * 2, 2)] = both
        pout_ref[0] = par_ref[pl.ds(g * 2 * PPG, 2 * PPG)]

        # ---- hash: repack + packet chain, once per chunk ----
        @pl.when(g == NG - 1)
        def _hash():
            groups = []
            for s in range(8):
                g8 = jnp.concatenate(
                    [x_ref[0, s * B8:(s + 1) * B8],
                     par_ref[s * B8:(s + 1) * B8]],
                    axis=1,
                ).reshape(B8 * t, CB)
                y = jnp.transpose(g8.astype(jnp.uint32), (1, 0)).reshape(
                    C8, 4, B8 * t
                )
                groups.append(
                    y[:, 0] | (y[:, 1] << 8) | (y[:, 2] << 16) | (y[:, 3] << 24)
                )
            xt = jnp.stack(groups, axis=1)       # [C8, 8, S8]

            for sb in range(0, S8, SUB):
                state = tuple(st_ref[i, :, sb:sb + SUB] for i in range(32))
                for k in range(CHUNK):           # static unroll: VREG resident
                    st = _St.of(state)
                    pk = xt[k * 8:(k + 1) * 8, :, sb:sb + SUB]
                    ahi = [pk[2 * i + 1] for i in range(4)]
                    alo = [pk[2 * i] for i in range(4)]
                    state = _update(st, ahi, alo).tup()
                for i in range(32):
                    st_ref[i, :, sb:sb + SUB] = state[i]

        @pl.when((c == nc - 1) & (g == NG - 1))
        def _():
            # in-kernel epilogue (PERF.md "next levers" #3): the 10
            # HighwayHash finalization rounds + modular reduction run in
            # this last grid step on the VMEM-resident state, replacing
            # the ~0.1 ms XLA epilogue the host used to chain after every
            # dispatch. Same SUB sub-batching as the chain: 32 live
            # [8, SUB] lanes fit the register file.
            for sb in range(0, S8, SUB):
                state = tuple(st_ref[i, :, sb:sb + SUB] for i in range(32))
                state = jax.lax.fori_loop(
                    0, 10,
                    lambda _i, st: _permute_and_update(_St.of(st)).tup(),
                    state,
                )
                words = _reduce_words(_St.of(state))
                for w in range(8):
                    dig_ref[w, :, sb:sb + SUB] = words[w]

    CP = pltpu.CompilerParams(vmem_limit_bytes=110 * 1024 * 1024)

    # stable names for a device trace: the program reads
    # `jit_fused_rs_hash`, the Pallas kernel in it `fused_rs_hash_kernel`
    @jax.jit
    def fused_rs_hash(x, w3):
        s = _init_state(B * t, key)
        init = jnp.concatenate(
            [jnp.stack(s.v0h), jnp.stack(s.v0l), jnp.stack(s.v1h),
             jnp.stack(s.v1l), jnp.stack(s.m0h), jnp.stack(s.m0l),
             jnp.stack(s.m1h), jnp.stack(s.m1l)], axis=0,
        ).reshape(32, 8, S8)
        parity, out = pl.pallas_call(
            kern,
            out_shape=[jax.ShapeDtypeStruct((nc, B, p, CB), jnp.uint8),
                       jax.ShapeDtypeStruct((8, 8, S8), jnp.uint32)],
            grid=(nc, NG),
            in_specs=[
                pl.BlockSpec((128, 128), lambda c, g: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, B, d, CB), lambda c, g: (c, 0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((32, 8, S8), lambda c, g: (0, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, 2 * PPG, p, CB), lambda c, g: (c, g, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((8, 8, S8), lambda c, g: (0, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            scratch_shapes=[pltpu.VMEM((32, 8, S8), jnp.uint32),
                            pltpu.VMEM((B, p, CB), jnp.uint8)],
            compiler_params=CP,
            name="fused_rs_hash_kernel",
        )(w3, x, init)
        # the kernel already finalized: out carries the 8 LE u32 digest
        # words per shard; only byte assembly remains on the XLA side
        words = jnp.stack([out[w].reshape(B * t) for w in range(8)], axis=-1)
        dig = jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(B * t, 32)
        return parity, dig.reshape(B, t, 32)

    return fused_rs_hash


@functools.lru_cache(maxsize=32)
def _encode_w3(d: int, p: int) -> np.ndarray:
    from .rs_jax import get_tpu_codec

    return _paired_weight(np.asarray(get_tpu_codec(d, p).w_encode), d, p)


@functools.lru_cache(maxsize=256)
def _decode_w3(d: int, p: int, present: tuple, missing: tuple) -> np.ndarray:
    """Paired weight for a failure pattern: rows map the first d present
    shards onto the missing ones (inverse-matrix rows for missing data,
    parity-composed rows for missing parity — ops/rs.py
    reconstruct_rows_for, mirroring klauspost's Reconstruct)."""
    from .rs import get_codec
    from .rs_jax import gf_matrix_to_bitplanes

    m = get_codec(d, p).reconstruct_rows_for(list(present), list(missing))
    return _paired_weight(gf_matrix_to_bitplanes(m), d, len(missing))


def fused_encode_hash_cm(
    data_cm: jax.Array | np.ndarray, d: int, p: int, key: bytes = MINIO_KEY
):
    """Chunk-major fused dispatch.

    data_cm: [nc, B, d, CB] u8 -> (parity_cm [nc, B, p, CB] u8,
    digests [B, d+p, 32] u8). Digest order matches
    ops.bitrot_jax.hash256_blocks over shards [B, d+p, n] (flat b*t + j).
    """
    nc, B, d_, cb = data_cm.shape
    assert d_ == d and cb == CHUNK_BYTES
    return _build(d, p, B, nc, key)(data_cm, jnp.asarray(_encode_w3(d, p)))


def fused_decode_hash_cm(
    survivors_cm: jax.Array | np.ndarray,
    d: int,
    p: int,
    present: tuple,
    missing: tuple,
    key: bytes = MINIO_KEY,
):
    """Chunk-major fused reconstruct + hash — the decode mega-kernel
    (reference: cmd/erasure-decode.go:239-315 reconstructs, then
    cmd/bitrot-streaming.go hashes in separate CPU passes; here both
    happen in one dispatch while shards are VMEM-resident).

    survivors_cm: [nc, B, d, CB] u8 — the first d present shards in
    present[:d] order. Returns (rebuilt_cm [nc, B, m, CB] u8, digests
    [B, d+m, 32] u8): digests[:, :d] are the survivors' (the verify
    verdicts — compare against the stored frame digests), digests[:, d:]
    the rebuilt shards' (ready for heal frames).
    """
    nc, B, d_, cb = survivors_cm.shape
    assert d_ == d and cb == CHUNK_BYTES
    m = len(missing)
    w3 = _decode_w3(d, p, tuple(present[:d]), tuple(missing))
    return _build(d, m, B, nc, key)(survivors_cm, jnp.asarray(w3))
