"""Zero-copy data plane (erasure/bufpool.py): byte identity against the
legacy copying path across families and backend rungs, pool lease
discipline (sanitizer-witnessed), copy-site accounting, and chaos around
buffers still referenced by in-flight requests.

The native C plane preads/appends below the Python data plane, so every
end-to-end test here pins MINIO_TPU_NATIVE_PLANE=0 — the zero-copy path
under test is the Python one the A/B lever switches."""

import hashlib
import os

os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")

import numpy as np
import pytest

from minio_tpu.analysis import sanitizer
from minio_tpu.erasure import bufpool
from minio_tpu.erasure.coder import ErasureCoder
from minio_tpu.erasure.set import ErasureSet
from minio_tpu.storage.xlstorage import XLStorage

RNG = np.random.default_rng(13)


def _store(tmp_path, tag, n=4):
    disks = [XLStorage(str(tmp_path / f"{tag}{i}")) for i in range(n)]
    es = ErasureSet(disks)
    es.make_bucket("zc")
    return es


def _gen(data, step=700_001):
    for i in range(0, len(data), step):
        yield data[i : i + step]


def _drain(it):
    # chunks may be memoryviews (zero-copy serve); bytes() each for joins
    return b"".join(bytes(c) for c in it)


# ---------------------------------------------------------------------------
# byte identity: zerocopy on vs off, both families, numpy + jax rungs
# ---------------------------------------------------------------------------


# (d, p): 2 + 2 divides a stripe block; 12 + 4 (the 16-drive default) and
# 6 + 2 do not, so their arena lays a block at d × shard_size with a zero pad
GEOMETRIES = [(2, 2), (12, 4), (6, 2)]


@pytest.mark.parametrize("geometry", GEOMETRIES, ids=lambda g: f"{g[0]}+{g[1]}")
@pytest.mark.parametrize("family", ["reedsolomon", "cauchy"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_streaming_put_byte_identity(tmp_path, monkeypatch, family, backend, geometry):
    """Streaming PUT + GET payloads and etags are byte-identical with
    MINIO_TPU_ZEROCOPY=1 and =0 — the pooled-arena path changes where
    bytes live, never what they are."""
    d, p = geometry
    monkeypatch.setenv("MINIO_TPU_BACKEND", backend)
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", family)
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    data = RNG.integers(0, 256, size=5 * 1024 * 1024 + 12_345,
                        dtype=np.uint8).tobytes()
    etags, payloads, ranges = [], [], []
    for zc in ("1", "0"):
        monkeypatch.setenv("MINIO_TPU_ZEROCOPY", zc)
        es = _store(tmp_path, f"{family[:2]}-{backend[:1]}-{zc}-", n=d + p)
        oi = es.put_object("zc", "obj", _gen(data), parity=p)
        assert oi.size == len(data)
        etags.append(oi.etag)
        _, it = es.get_object("zc", "obj")
        payloads.append(_drain(it))
        # unaligned range spanning a block boundary
        _, it = es.get_object("zc", "obj", offset=1_048_000, length=200_000)
        ranges.append(_drain(it))
    assert payloads[0] == payloads[1] == data
    assert ranges[0] == ranges[1] == data[1_048_000 : 1_048_000 + 200_000]
    assert etags[0] == etags[1] == hashlib.md5(data).hexdigest()


@pytest.mark.parametrize("family", ["reedsolomon", "cauchy"])
def test_degraded_get_byte_identity(tmp_path, monkeypatch, family):
    """Reconstructing GET (one drive gone) serves identical bytes on
    both sides of the lever — the pooled survivors stack and view-based
    decode feed the same reconstruction."""
    import shutil

    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", family)
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    data = RNG.integers(0, 256, size=3 * 1024 * 1024 + 999,
                        dtype=np.uint8).tobytes()
    for zc in ("1", "0"):
        monkeypatch.setenv("MINIO_TPU_ZEROCOPY", zc)
        tag = f"dg-{family[:2]}-{zc}-"
        es = _store(tmp_path, tag)
        es.put_object("zc", "obj", _gen(data))
        shutil.rmtree(tmp_path / f"{tag}2" / "zc")
        _, it = es.get_object("zc", "obj")
        assert _drain(it) == data


def test_pallas_interpret_encode_from_arena_view():
    """The Pallas encode kernel (Mosaic interpreter on CPU) consumes an
    arena-backed [B, d, n] view and produces parity identical to the GF
    reference — zero-copy views are bit-exact kernel inputs."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from minio_tpu.ops import gf, rs, rs_jax, rs_pallas

    d, p, n = 4, 2, 1024
    codec = rs.get_codec(d, p)
    w = rs_jax.gf_matrix_to_bitplanes(codec.parity_matrix)
    pool = bufpool.BufferPool()
    lease = pool.acquire(2 * d * n)
    try:
        arena = lease.array[: 2 * d * n].reshape(2, d, n)
        arena[:] = RNG.integers(0, 256, size=(2, d, n), dtype=np.uint8)
        out = np.asarray(rs_pallas.gf_apply_pallas(w, arena, p, interpret=True))
        for b in range(2):
            np.testing.assert_array_equal(
                out[b], gf.gf_matvec_blocks(codec.parity_matrix, arena[b])
            )
    finally:
        lease.release()


# ---------------------------------------------------------------------------
# copy accounting: staging == 0 on the zero-copy ingest path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("geometry", [(2, 2), (12, 4)], ids=lambda g: f"{g[0]}+{g[1]}")
def test_streaming_put_staging_zero(tmp_path, monkeypatch, geometry):
    """An aligned streaming PUT through the Python plane counts ZERO
    staging copies — chunks land directly in pooled arenas, at 12 + 4 too,
    where d does not divide the block — while the legacy lever counts at
    least one per batch."""
    d, p = geometry
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", "1")
    data = RNG.integers(0, 256, size=8 * 1024 * 1024, dtype=np.uint8).tobytes()
    es = _store(tmp_path, "st1-", n=d + p)
    bufpool.copies_reset()
    es.put_object("zc", "obj", _gen(data, step=1 << 20), parity=p)
    snap = bufpool.copies_snapshot()
    assert snap["staging"] == 0, snap
    ps = bufpool.pool_stats_snapshot()
    assert ps["acquires"] > 0 and ps["violations"] == 0

    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", "0")
    es2 = _store(tmp_path, "st0-", n=d + p)
    bufpool.copies_reset()
    es2.put_object("zc", "obj", _gen(data, step=1 << 20), parity=p)
    assert bufpool.copies_snapshot()["staging"] > 0


def test_a_reused_arena_leaves_no_byte_in_the_shard_pad(tmp_path, monkeypatch):
    """A pooled arena's bytes are another PUT's: one of the 12 + 4 batch's
    size, filled with 0xFF and released, is the next streaming PUT's
    arena — and every data shard's pad bytes on the drives are zero, and
    every shard is what the numpy codec makes of the block."""
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", "1")
    monkeypatch.setenv("MINIO_TPU_POOL_MB", "1024")  # room beside other tests' arenas
    monkeypatch.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    monkeypatch.delenv("MINIO_TPU_STREAM_BATCH_MB", raising=False)
    coder = ErasureCoder(12, 4)
    per, bs = coder.shard_size, coder.block_size
    pad = 12 * per - bs
    assert pad == 8
    pool = bufpool.get_pool()
    dirty = pool.acquire(64 * 12 * per)  # the batch at the 64 MiB cap
    dirty.array[:] = 0xFF
    dirty.release()
    hits0 = pool.stats_snapshot()["hits"]
    data = RNG.integers(0, 256, size=3 * bs + 5, dtype=np.uint8).tobytes()
    es = _store(tmp_path, "pad-", n=16)
    es.put_object("zc", "obj", _gen(data, step=65_537), parity=4)
    assert pool.stats_snapshot()["hits"] > hits0  # it took the dirty arena

    files: list[bytes] = [b""] * 16
    for i in range(16):
        root = str(tmp_path / f"pad-{i}")
        fi = XLStorage(root).read_version("zc", "obj")
        with open(os.path.join(root, "zc", "obj", fi.data_dir, "part.1"), "rb") as f:
            files[fi.erasure.index - 1] = f.read()
    frame = 32 + per
    for b in range(3):
        want = coder._np.encode_data(data[b * bs : (b + 1) * bs])  # [16, per]
        for i in range(16):
            row = files[i][b * frame + 32 : (b + 1) * frame]
            assert row == want[i].tobytes(), (b, i)
        assert files[11][(b + 1) * frame - pad : (b + 1) * frame] == bytes(pad)


def test_dispatcher_exact_fit_arena_direct(tmp_path, monkeypatch):
    """Power-of-two ingest batches hit the dispatcher's exact-fit fast
    path: the arena dispatches as-is (arena_direct), no bucket copy, no
    pad blocks."""
    pytest.importorskip("jax")
    from minio_tpu.parallel.dispatcher import aggregate_stats

    monkeypatch.setenv("MINIO_TPU_BACKEND", "jax")
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", "1")
    monkeypatch.setenv("MINIO_TPU_STREAM_BATCH_MB", "4")
    before = aggregate_stats()
    data = RNG.integers(0, 256, size=8 * 1024 * 1024, dtype=np.uint8).tobytes()
    es = _store(tmp_path, "ad-")
    bufpool.copies_reset()
    es.put_object("zc", "obj", _gen(data, step=1 << 20))
    after = aggregate_stats()
    assert after.get("arena_direct", 0) > before.get("arena_direct", 0)
    assert after.get("pad_blocks", 0) == before.get("pad_blocks", 0)
    snap = bufpool.copies_snapshot()
    assert snap["staging"] == 0 and snap["dispatch-concat"] == 0, snap
    _, it = es.get_object("zc", "obj")
    assert _drain(it) == data


# ---------------------------------------------------------------------------
# pool lease discipline (the poisoning surface)
# ---------------------------------------------------------------------------


def test_pool_recycles_only_at_refcount_zero():
    pool = bufpool.BufferPool(budget_bytes=64 << 20)
    owner = pool.acquire(1 << 20)
    arena = owner.array
    reader = owner.retain()  # response iterator outliving the owner
    owner.release()
    # re-lease while a reader lease is live must be impossible: the
    # arena is not in the free list until the LAST holder releases
    other = pool.acquire(1 << 20)
    assert other.array is not arena
    assert pool.stats_snapshot()["resident_bytes"] == 0
    reader.release()
    recycled = pool.acquire(1 << 20)
    assert recycled.array is arena  # now recyclable — and recycled
    assert pool.stats_snapshot()["hits"] == 1
    recycled.release()
    other.release()
    assert pool.stats_snapshot()["violations"] == 0


def test_pool_poisoning_witnessed():
    """Double release and retain-after-death are counted and sanitizer-
    witnessed (pool.lease-violation), and a dead lease's arena is
    unreachable — use-after-recycle cannot be expressed."""
    sanitizer.clear_events()
    try:
        pool = bufpool.BufferPool()
        lease = pool.acquire(4096)
        lease.release()
        lease.release()  # double release
        assert pool.stats_snapshot()["violations"] == 1
        lease.retain()  # retain on a dead lease
        assert pool.stats_snapshot()["violations"] == 2
        with pytest.raises(bufpool.LeaseViolation):
            lease.array
        kinds = [e["kind"] for e in sanitizer.events("pool.lease-violation")]
        assert kinds == ["double-release", "retain-dead"]
    finally:
        sanitizer.clear_events()


def test_pool_budget_and_oversize():
    pool = bufpool.BufferPool(budget_bytes=1 << 20)
    a = pool.acquire(1 << 20)
    b = pool.acquire(1 << 20)
    a.release()
    b.release()  # over budget: freed, not retained
    assert pool.stats_snapshot()["resident_bytes"] == 1 << 20
    huge = pool.acquire((1 << 27) + 1)  # above the top size class
    huge.release()
    s = pool.stats_snapshot()
    assert s["unpooled"] == 1 and s["resident_bytes"] == 1 << 20


def test_pool_classes_above_one_mib_are_whole_mib():
    """The 12 + 4 ingest arena (64 blocks of 12 × 87,382 B, 512 B over
    64 MiB) is pooled in the 65 MiB class, recycled and hit; 64 MiB and
    1 MiB keep their classes; the budget counts a class's real bytes."""
    cls = bufpool.BufferPool._class_for
    assert cls(67_109_376) == 65 << 20 < 1 << 27
    assert cls(64 << 20) == 64 << 20 and cls(128 << 20) == 128 << 20
    assert cls(1 << 20) == 1 << 20 and cls(4096) == 1 << 16
    assert cls((1 << 20) + 1) == 2 << 20 and cls(16 * 12 * 87_382) == 17 << 20
    pool = bufpool.BufferPool(budget_bytes=65 << 20)
    a = pool.acquire(67_109_376)
    arena = a.array
    assert arena.nbytes == 65 << 20
    a.release()
    s = pool.stats_snapshot()
    assert s["resident_bytes"] == 65 << 20 and s["unpooled"] == 0
    b = pool.acquire(67_109_376)
    assert b.array is arena and pool.stats_snapshot()["hits"] == 1
    c = pool.acquire(64 << 20)  # another class: a miss, not the 65 MiB arena
    assert c.array.nbytes == 64 << 20 and pool.stats_snapshot()["misses"] == 2
    b.release()
    c.release()  # over the budget beside the 65 MiB arena: not kept
    assert pool.stats_snapshot()["resident_bytes"] == 65 << 20


# ---------------------------------------------------------------------------
# chaos: buffers referenced by in-flight requests never get recycled
# ---------------------------------------------------------------------------


def test_mid_put_drive_failure_keeps_pool_clean(tmp_path, monkeypatch):
    """A drive failing appends mid-PUT aborts/degrades the write without
    recycling arenas still referenced by outstanding shard appends —
    zero lease violations, and surviving data reads back exact."""
    from minio_tpu import fault
    from minio_tpu.fault.storage import FaultInjectedDisk

    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", "1")
    fault.clear()
    try:
        disks = [
            FaultInjectedDisk(XLStorage(str(tmp_path / f"f{i}")))
            for i in range(4)
        ]
        es = ErasureSet(disks)
        es.make_bucket("zc")
        data = RNG.integers(0, 256, size=4 * 1024 * 1024 + 321,
                            dtype=np.uint8).tobytes()
        violations0 = bufpool.pool_stats_snapshot()["violations"]
        fault.inject({
            "boundary": "storage", "mode": "error",
            "target": disks[3].endpoint, "op": "append_file", "seed": 3,
        })
        es.put_object("zc", "obj", _gen(data))  # d+1=3 write quorum holds
        fault.clear()
        _, it = es.get_object("zc", "obj")
        assert _drain(it) == data
        assert bufpool.pool_stats_snapshot()["violations"] == violations0
    finally:
        fault.clear()


def test_mid_get_invalidation_never_poisons_served_chunks(tmp_path, monkeypatch):
    """Chunks already served from a GET stay byte-stable while the
    object's cache entries are invalidated and the pool churns under
    fresh ingest — a served buffer is never recycled while referenced.
    (Overwriting the SAME key mid-read is serialized by the namespace
    lock, so cache invalidation + foreign-key churn is the surface that
    can actually race a live response iterator.)"""
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", "1")
    es = _store(tmp_path, "mg-")
    data1 = RNG.integers(0, 256, size=3 * 1024 * 1024, dtype=np.uint8).tobytes()
    data2 = RNG.integers(0, 256, size=3 * 1024 * 1024, dtype=np.uint8).tobytes()
    es.put_object("zc", "obj", _gen(data1))
    violations0 = bufpool.pool_stats_snapshot()["violations"]
    _, it = es.get_object("zc", "obj")
    first = next(it)
    held = bytes(first)  # what the consumer saw at serve time
    # invalidate the object's cache entries mid-GET + churn the pool
    es.cache.invalidate_object("zc", "obj")
    for j in range(3):
        es.put_object("zc", f"churn{j}", _gen(data2))
    assert bytes(first) == held == data1[: len(held)]
    rest = _drain(it)  # the response finishes byte-exact
    assert held + rest == data1
    assert bufpool.pool_stats_snapshot()["violations"] == violations0


# ---------------------------------------------------------------------------
# coder-level identity + the miniovet copy-discipline rule
# ---------------------------------------------------------------------------


MIB = 1024 * 1024
# (body bytes, reader chunk bytes): chunks that straddle block edges
BODIES = [
    (0, 1), (1, 1), (MIB - 1, 65_537), (MIB, 8), (MIB + 1, 700_001),
    (3 * MIB + 777, 700_001), (64 * MIB + 777, MIB + 3),
]


@pytest.mark.parametrize("body,step", BODIES, ids=lambda v: str(v))
@pytest.mark.parametrize("geometry", GEOMETRIES, ids=lambda g: f"{g[0]}+{g[1]}")
@pytest.mark.parametrize("family", ["reedsolomon", "cauchy"])
def test_iter_encode_zc_matches_legacy_shards(family, geometry, body, step):
    """iter_encode_zc's writev vectors concatenate to the exact shard
    files the legacy staging path produces, tail block included, at the
    streaming PUT's 64 MiB batch; its raw pieces are the body."""
    d, p = geometry
    coder = ErasureCoder(d, p, family=family)
    data = RNG.integers(0, 256, size=body, dtype=np.uint8).tobytes()
    want = coder.encode_part(data).shard_files if body else [b""] * coder.t
    files = [bytearray() for _ in range(coder.t)]
    md5, raw = hashlib.md5(), bytearray()
    for batch in coder.iter_encode_zc(iter(_gen(data, step)), 64 * MIB):
        for piece in batch.raw:
            md5.update(piece)
            raw += piece
        for i in range(coder.t):
            for piece in batch.shard_vecs[i]:
                files[i] += piece
        batch.release()
    assert bytes(raw) == data
    assert md5.hexdigest() == hashlib.md5(data).hexdigest()
    assert [bytes(f) for f in files] == want


def test_copy_site_obs_record(tmp_path, monkeypatch):
    """A streaming PUT publishes one `copy.site` TYPE_TPU record with
    the per-site copy deltas over the PUT window and the lever state."""
    from minio_tpu import obs

    class Pub:
        active = True

        def __init__(self):
            self.recs = []

        def publish(self, rec):
            self.recs.append(rec)

    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", "1")
    prev = obs.publisher()
    pub = Pub()
    obs.set_publisher(pub)
    try:
        es = _store(tmp_path, "ob-")
        data = RNG.integers(0, 256, size=2 * 1024 * 1024 + 5,
                            dtype=np.uint8).tobytes()
        es.put_object("zc", "obj", _gen(data))
        recs = [r for r in pub.recs if r.get("name") == "copy.site"]
        assert recs, "streaming PUT published no copy.site record"
        rec = recs[-1]
        assert rec["type"] == obs.TYPE_TPU and rec["zerocopy"] is True
        assert rec["bytes"] == len(data)
        assert rec["sites"].get("staging", 0) == 0
        assert rec["sites"].get("tail-block", 0) > 0  # the 5-byte tail
    finally:
        obs.set_publisher(prev)


def test_copy_discipline_rule_fires_and_scopes():
    from minio_tpu.analysis.core import analyze_source

    src = "def hot(x):\n    return x.tobytes()\n"
    found = analyze_source(
        src, path="minio_tpu/parallel/dispatcher.py",
        rules=["copy-discipline"],
    )
    assert [f.rule for f in found] == ["copy-discipline"]
    assert analyze_source(
        src, path="minio_tpu/server/app.py", rules=["copy-discipline"]
    ) == []


def test_copy_discipline_clean_on_hot_files():
    """The shipped hot-path files carry no unwhitelisted
    materializations — the boundary table matches reality."""
    import minio_tpu
    from minio_tpu.analysis.core import analyze_file

    root = os.path.dirname(minio_tpu.__file__)
    for rel in ("erasure/set.py", "erasure/coder.py",
                "parallel/dispatcher.py"):
        assert analyze_file(
            os.path.join(root, rel), rules=["copy-discipline"]
        ) == []
