"""Batched HighwayHash-256 on TPU + the fused encode+bitrot kernel.

The reference hashes every shard block on the CPU while streaming
(/root/reference/cmd/bitrot-streaming.go:44-75). Here digests are computed
on-device over the same resident shard blocks the RS kernel just produced —
one fused dispatch returns parity AND all per-shard digests, so shard bytes
never make an extra host pass.

HighwayHash state is 4 lanes of uint64. TPUs are 32-bit machines, so all
64-bit arithmetic is expressed natively as (hi, lo) uint32 pairs — adds with
carry, and the hash's 32x32->64 multiply via 16-bit limbs — instead of
leaning on XLA's int64 emulation. The packet loop is a lax.scan (hashing is
a chain, sequential by construction); parallelism comes from the batch lane:
all shards of all concurrent stripe blocks hash in lockstep on the VPU.

Validated against ops/highwayhash.py (scalar + numpy), which matches the
reference's golden chain (/root/reference/cmd/bitrot.go:228-229).
"""

from __future__ import annotations

import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp

from .highwayhash import INIT0, INIT1, MINIO_KEY

__all__ = ["hash256_blocks", "encode_and_hash"]

_M16 = np.uint32(0xFFFF)
_B3 = np.uint32(0xFF000000)


def _add64(ahi, alo, bhi, blo):
    lo = alo + blo
    carry = (lo < alo).astype(jnp.uint32)
    return ahi + bhi + carry, lo


def _mul32x32(a, b):
    """Full 32x32 -> 64 product as (hi, lo) uint32, via 16-bit limbs."""
    al, ah = a & _M16, a >> 16
    bl, bh = b & _M16, b >> 16
    ll = al * bl
    mid = al * bh + ah * bl  # may wrap: track the carry into bit 48
    midc = (mid < al * bh).astype(jnp.uint32)
    lo = ll + ((mid & _M16) << 16)
    c = (lo < ll).astype(jnp.uint32)
    hi = ah * bh + (mid >> 16) + (midc << 16) + c
    return hi, lo


def _zipper_lo_half(v1hi, v1lo, v0hi, v0lo):
    """(hi, lo) of the 64-bit zipper-merge shuffle added into add0."""
    masked = (v0hi & 0xFF00) | (v1hi & 0xFF0000)
    lo = (
        ((v0lo & _B3) >> 24)
        | ((v1hi & 0xFF) << 8)
        | (masked << 16)
        | (v0lo & 0xFF0000)
    )
    hi = (
        (masked >> 16)
        | (v0lo & 0xFF00)
        | ((v1hi & _B3) >> 8)
        | ((v0lo & 0xFF) << 24)
    )
    return hi, lo


def _zipper_hi_half(v1hi, v1lo, v0hi, v0lo):
    """(hi, lo) of the 64-bit zipper-merge shuffle added into add1."""
    lo = (
        ((v1lo & _B3) >> 24)
        | ((v0hi & 0xFF) << 8)
        | (v1lo & 0xFF0000)
        | ((v1hi & 0xFF00) << 16)
    )
    hi = (
        ((v1lo & 0xFF00) >> 8)
        | ((v0hi & 0xFF0000) >> 8)
        | ((v1lo & 0xFF) << 16)
        | (v0hi & _B3)
    )
    return hi, lo


class _St:
    """State bundle: each field is a list of 4 per-lane [B] uint32 arrays.

    Per-lane scalars (not a stacked [4, B] array) keep every op a pure
    elementwise [B] op — no gathers/scatters inside the packet loop, which
    is what the XLA TPU vectorizer wants.
    """

    __slots__ = ("v0h", "v0l", "v1h", "v1l", "m0h", "m0l", "m1h", "m1l")

    def tup(self):
        return tuple(
            x
            for field in (self.v0h, self.v0l, self.v1h, self.v1l,
                          self.m0h, self.m0l, self.m1h, self.m1l)
            for x in field
        )

    @staticmethod
    def of(t):
        s = _St()
        t = list(t)
        (s.v0h, s.v0l, s.v1h, s.v1l, s.m0h, s.m0l, s.m1h, s.m1l) = (
            t[4 * i : 4 * i + 4] for i in range(8)
        )
        return s


def _update(s: _St, ahi, alo) -> _St:
    """One HighwayHash round. ahi/alo: lists of 4 per-lane [B] arrays."""
    for i in range(4):
        s.v1h[i], s.v1l[i] = _add64(
            s.v1h[i], s.v1l[i], *_add64(s.m0h[i], s.m0l[i], ahi[i], alo[i])
        )
        ph, pl = _mul32x32(s.v1l[i], s.v0h[i])
        s.m0h[i], s.m0l[i] = s.m0h[i] ^ ph, s.m0l[i] ^ pl
        s.v0h[i], s.v0l[i] = _add64(s.v0h[i], s.v0l[i], s.m1h[i], s.m1l[i])
        ph, pl = _mul32x32(s.v0l[i], s.v1h[i])
        s.m1h[i], s.m1l[i] = s.m1h[i] ^ ph, s.m1l[i] ^ pl
    # zipper merges: lane pairs (1,0) and (3,2), v1 -> v0 then v0 -> v1
    for lo_, hi_ in ((0, 1), (2, 3)):
        zh, zl = _zipper_lo_half(s.v1h[hi_], s.v1l[hi_], s.v1h[lo_], s.v1l[lo_])
        n0h, n0l = _add64(s.v0h[lo_], s.v0l[lo_], zh, zl)
        zh, zl = _zipper_hi_half(s.v1h[hi_], s.v1l[hi_], s.v1h[lo_], s.v1l[lo_])
        n1h, n1l = _add64(s.v0h[hi_], s.v0l[hi_], zh, zl)
        s.v0h[lo_], s.v0l[lo_] = n0h, n0l
        s.v0h[hi_], s.v0l[hi_] = n1h, n1l
    for lo_, hi_ in ((0, 1), (2, 3)):
        zh, zl = _zipper_lo_half(s.v0h[hi_], s.v0l[hi_], s.v0h[lo_], s.v0l[lo_])
        n0h, n0l = _add64(s.v1h[lo_], s.v1l[lo_], zh, zl)
        zh, zl = _zipper_hi_half(s.v0h[hi_], s.v0l[hi_], s.v0h[lo_], s.v0l[lo_])
        n1h, n1l = _add64(s.v1h[hi_], s.v1l[hi_], zh, zl)
        s.v1h[lo_], s.v1l[lo_] = n0h, n0l
        s.v1h[hi_], s.v1l[hi_] = n1h, n1l
    return s


def _permute_and_update(s: _St) -> _St:
    # Permute(v0) = lanes [2,3,0,1], each with 32-bit halves swapped
    perm = (2, 3, 0, 1)
    return _update(
        s, [s.v0l[j] for j in perm], [s.v0h[j] for j in perm]
    )


def _init_state(batch: int, key: bytes) -> _St:
    k = [int.from_bytes(key[8 * i : 8 * i + 8], "little") for i in range(4)]
    s = _St()

    def col(vals):
        hs, ls = [], []
        for v in vals:
            hs.append(jnp.full((batch,), np.uint32(v >> 32), dtype=jnp.uint32))
            ls.append(jnp.full((batch,), np.uint32(v & 0xFFFFFFFF), dtype=jnp.uint32))
        return hs, ls

    v0 = [INIT0[i] ^ k[i] for i in range(4)]
    krot = [((x >> 32) | (x << 32)) & ((1 << 64) - 1) for x in k]
    v1 = [INIT1[i] ^ krot[i] for i in range(4)]
    s.v0h, s.v0l = col(v0)
    s.v1h, s.v1l = col(v1)
    s.m0h, s.m0l = col(list(INIT0))
    s.m1h, s.m1l = col(list(INIT1))
    return s


def _load_packets(blocks: jax.Array) -> tuple[list, list]:
    """[B, P*32] uint8 -> (hi, lo): lists of 4 per-lane [P, B] uint32 arrays."""
    b, nb = blocks.shape
    p = nb // 32
    u32 = jax.lax.bitcast_convert_type(blocks.reshape(b, p, 4, 2, 4), jnp.uint32)
    # u32: [B, P, 4, 2] where [..., 0] = lo word, [..., 1] = hi word (LE)
    lo = [jnp.transpose(u32[:, :, i, 0], (1, 0)) for i in range(4)]
    hi = [jnp.transpose(u32[:, :, i, 1], (1, 0)) for i in range(4)]
    return hi, lo


@functools.partial(jax.jit, static_argnames=("key",))
def hash256_blocks(blocks: jax.Array, key: bytes = MINIO_KEY) -> jax.Array:
    """HighwayHash-256 of B equal-length messages on device.

    blocks: [B, n] uint8 -> [B, 32] uint8 digests. n is static per
    compilation (the dispatcher pads to shard-size buckets).
    """
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    b, n = blocks.shape
    s = _init_state(b, key)
    return _finish_from_state(s, blocks, 0, n)


def _finish_from_state(s: "_St", blocks: jax.Array, done: int, n: int) -> jax.Array:
    """Continue a hash from packet offset `done` bytes: remaining whole
    packets (XLA scan), the tail packet, finalization, digest assembly.
    Shared by the pure-XLA path (done=0) and the Pallas chain kernel."""
    b = blocks.shape[0]
    whole = n - (n % 32)
    if whole > done:
        hi, lo = _load_packets(blocks[:, done:whole])

        def step(carry, x):
            xhi, xlo = x
            return _update(_St.of(carry), xhi, xlo).tup(), ()

        # unrolling amortizes loop overhead on TPU; on CPU it only slows
        # compilation of the (n/32)-step chain
        unroll = 8 if jax.default_backend() == "tpu" else 1
        carry, _ = jax.lax.scan(step, s.tup(), (hi, lo), unroll=unroll)
        s = _St.of(carry)
    rem = n - whole
    if rem:
        size_lo = jnp.uint32(rem)
        sh = jnp.uint32(rem)
        inv = jnp.uint32(32 - rem)
        for i in range(4):
            # v0 += (size << 32) + size
            s.v0h[i], s.v0l[i] = _add64(s.v0h[i], s.v0l[i], size_lo, size_lo)
            # each 32-bit half of v1 rotated left by size
            s.v1h[i] = (s.v1h[i] << sh) | (s.v1h[i] >> inv)
            s.v1l[i] = (s.v1l[i] << sh) | (s.v1l[i] >> inv)
        # build the padded 32-byte packet (static layout, traced data)
        whole4 = rem & ~3
        packet = jnp.zeros((b, 32), dtype=jnp.uint8)
        packet = packet.at[:, :whole4].set(blocks[:, whole : whole + whole4])
        if rem & 16:
            packet = packet.at[:, 28:32].set(blocks[:, whole + rem - 4 : whole + rem])
        elif rem & 3:
            size4 = rem & 3
            tail = blocks[:, whole + whole4 :]
            packet = packet.at[:, 16].set(tail[:, 0])
            packet = packet.at[:, 17].set(tail[:, size4 >> 1])
            packet = packet.at[:, 18].set(tail[:, size4 - 1])
        hi, lo = _load_packets(packet)
        s = _update(s, [h[0] for h in hi], [l[0] for l in lo])

    # 10 finalization rounds as a scan: one compiled body instead of a
    # 10x-unrolled graph (XLA CPU compile time explodes on the unroll)
    def _fin(carry, _):
        return _permute_and_update(_St.of(carry)).tup(), ()

    carry, _ = jax.lax.scan(_fin, s.tup(), None, length=10)
    s = _St.of(carry)
    words = jnp.stack(_reduce_words(s), axis=-1)  # [B, 8] uint32, LE order
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(b, 32)


def _reduce_words(s: "_St") -> list:
    """Modular reduction of a finalized HighwayHash-256 state into the 8
    little-endian uint32 digest words (m0l, m0h, m1l, m1h per 128-bit
    half). Pure elementwise ops on whatever shape the state lanes carry
    — shared by the XLA finisher above and the Pallas mega-kernel's
    in-kernel epilogue (ops/fused_pallas.py), so the two paths cannot
    drift."""
    outs = []
    for half in (0, 2):
        a0h, a0l = _add64(s.v0h[half], s.v0l[half], s.m0h[half], s.m0l[half])
        a1h, a1l = _add64(s.v0h[half + 1], s.v0l[half + 1], s.m0h[half + 1], s.m0l[half + 1])
        a2h, a2l = _add64(s.v1h[half], s.v1l[half], s.m1h[half], s.m1l[half])
        a3h, a3l = _add64(s.v1h[half + 1], s.v1l[half + 1], s.m1h[half + 1], s.m1l[half + 1])
        a3h = a3h & jnp.uint32(0x3FFFFFFF)
        # m1 = a1 ^ ((a3<<1)|(a2>>63)) ^ ((a3<<2)|(a2>>62))
        s1h, s1l = (a3h << 1) | (a3l >> 31), (a3l << 1) | (a2h >> 31)
        s2h, s2l = (a3h << 2) | (a3l >> 30), (a3l << 2) | (a2h >> 30)
        m1h, m1l = a1h ^ s1h ^ s2h, a1l ^ s1l ^ s2l
        # m0 = a0 ^ (a2<<1) ^ (a2<<2)
        t1h, t1l = (a2h << 1) | (a2l >> 31), a2l << 1
        t2h, t2l = (a2h << 2) | (a2l >> 30), a2l << 2
        m0h, m0l = a0h ^ t1h ^ t2h, a0l ^ t1l ^ t2l
        outs += [m0l, m0h, m1l, m1h]
    return outs


def _select_hash_fn():
    """Pallas chain kernel on TPU (unless disabled), XLA scan elsewhere."""
    import os

    if (
        jax.default_backend() == "tpu"
        and os.environ.get("MINIO_TPU_PALLAS", "1") != "0"
    ):
        from .bitrot_pallas import hash256_blocks_pallas

        return hash256_blocks_pallas
    return hash256_blocks


# imported here, below the hash chain, so that the lines above keep their
# numbers: the Mosaic kernels carry source locations of the functions traced
# into them, and a shifted line is another key in the compile cache
from .. import obs  # noqa: E402

# decode mega-kernel fallback discipline: transient failures back off
# exponentially and re-probe (same policy as the encode dispatcher)
_fused_dec_cooldown = 0
_fused_dec_backoff = 8

# served-traffic observability: lets integration tests (and the admin
# plane) assert the decode mega-kernel actually carried degraded reads.
# Lock-guarded: concurrent degraded GETs reconstruct on server worker
# threads, and a bare += would drop counts.
# "fused"/"blocks" count mega-kernel dispatches and their blocks, "xla"/
# "xla_blocks" the row-major XLA decode rung, "failures" swallowed
# mega-kernel exceptions. Exported on /api/tpu (server/metrics.py): a
# degraded GET that was rebuilt on the CPU moves none of the four.
# "by_missing" splits the same dispatches and blocks by how many shards a
# dispatch rebuilt ({(rung, m): [dispatches, blocks]}: a hedge that wins
# puts a second parity shard in a straggler's place, and the kernel for
# m = 2 is another program); "pad_blocks" counts the zero blocks that
# filled a fused batch up to the kernel's multiple of 16; "first_calls" /
# "first_call_s" the first call of each (rung, m, batch) in this process
# and its `kernel`-phase seconds (trace-and-lower, compile or cache load,
# the run), counted when the call ENDS, as the dispatcher counts its own.
decode_stats = {
    "fused": 0, "blocks": 0, "failures": 0, "xla": 0, "xla_blocks": 0,
    "pad_blocks": 0, "by_missing": {}, "first_calls": {}, "first_call_s": {},
}
_decode_stats_lock = threading.Lock()
_decode_called: set[tuple] = set()  # (rung, d, m, batch, n) that ended once


def decode_stats_snapshot() -> dict:
    with _decode_stats_lock:
        return {k: (dict(v) if isinstance(v, dict) else v)
                for k, v in decode_stats.items()}


def _count_decode(rung: str, blocks: int, shape: tuple, kernel_s: float,
                  pad: int = 0) -> None:
    """One device decode dispatch that ended: `shape` is (d, m, batch, n)
    as the program was built for it."""
    m, batch = shape[1], shape[2]
    with _decode_stats_lock:
        st = decode_stats
        if rung == "fused":
            st["fused"] += 1
            st["blocks"] += blocks
        else:
            st["xla"] += 1
            st["xla_blocks"] += blocks
        st["pad_blocks"] += pad
        row = st["by_missing"].setdefault((rung, m), [0, 0])
        row[0] += 1
        row[1] += blocks
        if (rung, *shape) not in _decode_called:
            _decode_called.add((rung, *shape))
            key = (rung, m, batch)
            st["first_calls"][key] = st["first_calls"].get(key, 0) + 1
            st["first_call_s"][key] = st["first_call_s"].get(key, 0.0) + kernel_s


def xla_decode(codec, survivors, present, missing) -> np.ndarray:
    """The XLA rung of a device reconstruct: [B, d, n] survivors (any
    strides) -> rebuilt [B, m, n], row-major on the host. Books the same
    `decode` leaves as the fused rung, but `pack` (nothing is relaid out)."""
    took: dict[str, float] = {}
    with obs.phase("decode", "pad"):
        surv = np.ascontiguousarray(survivors, dtype=np.uint8)
    b, d, n = surv.shape
    with obs.phase("decode", "h2d"):
        on_dev = jax.block_until_ready(jax.device_put(surv))
    with obs.phase("decode", "kernel", into=took):
        out = jax.block_until_ready(
            codec.reconstruct_blocks(on_dev, present, missing)
        )
    with obs.phase("decode", "d2h"):
        host = np.asarray(out)
    with obs.phase("decode", "unpack"):
        # the host layout of a TPU array is not promised row-major, and
        # callers copy shard ROWS out of this
        host = np.ascontiguousarray(host)
    _count_decode("xla", b, (d, len(missing), b, n), took["kernel"])
    return host


def _fused_cm_on() -> bool:
    import os

    return os.environ.get("MINIO_TPU_FUSED_CM", "1") != "0"


def fused_decode_takes(d: int, m: int, b: int, n: int) -> bool:
    """Whether the next `_try_fused_decode` of b blocks of d survivors, n
    bytes a shard, m to rebuild, will go to the mega-kernel: the knob, the
    shapes, and no cooldown after a failure. A caller that lays the
    survivors out chunk-major itself asks this first; it observes and
    changes nothing."""
    if not _fused_cm_on() or _fused_dec_cooldown > 0:
        return False
    from . import fused_pallas as fp

    return fp.supports(d, m, -(-b // 16) * 16, n)


def _try_fused_decode(codec, survivors, present, missing, key,
                      packed_blocks: int | None = None):
    """Chunk-major fused reconstruct+verify+hash when shapes allow.

    `survivors` is [B, d, n] (any strides), padded and packed here; or,
    with `packed_blocks` = B, the kernel's own input already: chunk-major
    [n / CHUNK_BYTES, bpad, d, CHUNK_BYTES], B rounded up to 16, the pad
    rows zero (erasure/coder.py SurvivorStack), and `pad` and `pack` are
    skipped.

    Returns (rebuilt [B, m, n], rebuilt_digests [B, m, 32], survivor_
    digests [B, d, 32]) as numpy, or None for the XLA path."""
    global _fused_dec_cooldown, _fused_dec_backoff

    if not _fused_cm_on():
        return None
    if _fused_dec_cooldown > 0:
        _fused_dec_cooldown -= 1
        return None
    from . import fused_pallas as fp

    if packed_blocks is None:
        surv = np.asarray(survivors, dtype=np.uint8)
        b, d, n = surv.shape
        bpad = -(-b // 16) * 16
    else:
        packed = survivors
        nc, bpad, d, cb = packed.shape
        b, n = packed_blocks, nc * cb
    m = len(missing)
    if not fp.supports(d, m, bpad, n):
        return None
    took: dict[str, float] = {}
    try:
        if packed_blocks is None:
            with obs.phase("decode", "pad"):
                if bpad != b:
                    surv = np.concatenate(
                        [surv, np.zeros((bpad - b, d, n), dtype=np.uint8)],
                        axis=0,
                    )
            with obs.phase("decode", "pack"):
                packed = fp.pack_chunk_major(surv)
        with obs.phase("decode", "h2d"):
            surv_cm = jax.block_until_ready(jax.device_put(packed))
            del packed
        # call -> ready: launch + execution, and on a shape's first call
        # trace-and-lower and the compile
        with obs.phase("decode", "kernel", into=took):
            rebuilt_cm, digests = jax.block_until_ready(
                fp.fused_decode_hash_cm(
                    surv_cm, d, codec.parity_shards,
                    tuple(present), tuple(missing), key,
                )
            )
        with obs.phase("decode", "d2h"):
            rebuilt_host, digs_host = np.asarray(rebuilt_cm), np.asarray(digests)
        with obs.phase("decode", "unpack"):
            rebuilt = fp.unpack_chunk_major(rebuilt_host)[:b]
            # host layout of a TPU array is not promised row-major
            digs = np.ascontiguousarray(digs_host[:b])
        _fused_dec_backoff = 8
        _count_decode("fused", b, (d, m, bpad, n), took["kernel"], pad=bpad - b)
        return rebuilt, digs[:, d:, :], digs[:, :d, :]
    except Exception as e:  # noqa: BLE001 — lowering/device failure: XLA path
        from . import runtime

        runtime.report_rung_failure("fused-decode", f"{d}+{m}x{bpad}x{n}", e)
        _fused_dec_cooldown = _fused_dec_backoff
        _fused_dec_backoff = min(_fused_dec_backoff * 2, 1024)
        with _decode_stats_lock:
            decode_stats["failures"] += 1
        return None


def reconstruct_and_hash(
    codec,
    survivors: jax.Array,
    present: tuple[int, ...],
    missing: tuple[int, ...],
    key: bytes = MINIO_KEY,
) -> tuple[jax.Array, jax.Array]:
    """HealObject's hot loop in ONE device dispatch: rebuild the missing
    shards (bit-plane MXU matmul) and produce their bitrot digests while
    they are still device-resident — the reference decodes then hashes the
    rebuilt shards in separate CPU passes
    (/root/reference/cmd/erasure-decode.go:317 + cmd/bitrot-streaming.go).

    On TPU with mega-kernel-compatible shapes this runs the chunk-major
    fused decode kernel (ops/fused_pallas.fused_decode_hash_cm); otherwise
    the XLA bit-plane path below.

    survivors: [B, d, n] (shards at indices present[:d]); returns
    (rebuilt [B, m, n], digests [B, m, 32]).
    """
    fused = _try_fused_decode(codec, survivors, present, missing, key)
    if fused is not None:
        rebuilt, rdig, _sdig = fused
        return rebuilt, rdig
    survivors = jnp.asarray(survivors, dtype=jnp.uint8)
    b, d, n = survivors.shape
    m = len(missing)
    took: dict[str, float] = {}
    # heal keeps its results on the device: the phase ends when the calls
    # return (a first call's trace-and-lower and compile are inside it, the
    # run itself may not be), and nothing is synced for the clock's sake
    with obs.phase("decode", "kernel", into=took):
        rebuilt = codec.reconstruct_blocks(survivors, present, missing)
        hash_fn = _select_hash_fn()
        digests = hash_fn(rebuilt.reshape(b * m, n), key).reshape(b, m, 32)
    _count_decode("xla", b, (d, m, b, n), took["kernel"])
    return rebuilt, digests


def encode_and_hash(
    codec, data: jax.Array, key: bytes = MINIO_KEY
) -> tuple[jax.Array, jax.Array]:
    """The north-star fused dispatch: RS-encode + bitrot-hash in one go.

    codec: TpuRSCodec. data: [B, d, n] uint8 stripe blocks.
    Returns (parity [B, p, n], digests [B, d+p, 32]) — parity computed on the
    MXU, per-shard HighwayHash digests on the VPU, shards never leaving HBM.
    Replaces the reference's encode-then-hash-per-shard CPU pipeline
    (/root/reference/cmd/erasure-encode.go:76-108 +
    /root/reference/cmd/bitrot-streaming.go:44-75).
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    b, d, n = data.shape
    parity = codec.encode_blocks(data)
    shards = jnp.concatenate([data, parity], axis=1)  # [B, t, n]
    t = d + codec.parity_shards
    hash_fn = _select_hash_fn()
    digests = hash_fn(shards.reshape(b * t, n), key).reshape(b, t, 32)
    return parity, digests
