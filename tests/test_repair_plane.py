"""Degraded-plane hardening (windowed + hedged partial repair):

- windowed plan executor serves full and ranged degraded GETs
  byte-identically across window boundaries (READ_WINDOW=2)
- injected sub-chunk bitrot mid-plan degrades per BLOCK to the generic
  gather (repair_fallback_blocks advances, bytes stay correct)
- a straggling helper past the hedge budget fires the repair-plane
  hedge (repair_hedge_reads advances, bytes stay correct)
- heal under straggler latency still partial-repairs and the healed
  shard re-verifies (disk.verify_file); corrupt helper reads during
  heal fall back per block and the heal stays byte-correct
- an overwrite racing a degraded-GET repair plan withdraws cleanly
  (old bytes or a typed storage error — never wrong bytes)
- the decode-matrix LRU (ops/decode_cache): hit/miss accounting, LRU
  eviction at capacity, capacity-0 disable
- scenario keyspace shapes (hive-partitioned, timestamp-sorted runs)
  are unique and well-formed
"""

import os

os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")

import re
import shutil

import numpy as np
import pytest

from minio_tpu import fault
from minio_tpu.erasure.coder import family_stats_snapshot
from minio_tpu.erasure.set import ErasureSet
from minio_tpu.fault.storage import FaultInjectedDisk
from minio_tpu.ops import decode_cache, rs
from minio_tpu.storage import errors
from minio_tpu.storage.health import HealthCheckedDisk
from minio_tpu.storage.xlstorage import XLStorage

BKT = "rp"


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    # the native GET fast path preads via local_path and would bypass
    # the injection wrapper — force the Python read path; every test
    # starts and ends with a sterile fault registry and decode cache
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")
    fault.clear()
    decode_cache.clear()
    yield
    fault.clear()
    decode_cache.clear()


def _rig(tmp_path, tag, n=16, parity=8):
    """Production wrap order: HealthCheckedDisk(FaultInjectedDisk(...))
    so injected rules fire and the breaker/EWMA see them."""
    paths = [str(tmp_path / tag / f"d{i}") for i in range(n)]
    disks = [
        HealthCheckedDisk(FaultInjectedDisk(XLStorage(p)),
                          fail_threshold=4, cooldown=0.2)
        for p in paths
    ]
    es = ErasureSet(disks, default_parity=parity)
    es.make_bucket(BKT)
    return es, paths


def _drain(it) -> bytes:
    return b"".join(bytes(c) for c in it)


def _drive_of_shard(es, shard: int) -> int:
    """Drive index hosting erasure-position ``shard`` (distribution is
    1-based shard order per drive)."""
    fi, _ = es._cached_fileinfo(BKT, "o", "")
    return fi.erasure.distribution.index(shard + 1)


def _lose_shard0(es, tmp_path, tag) -> int:
    lost = _drive_of_shard(es, 0)
    shutil.rmtree(tmp_path / tag / f"d{lost}" / BKT / "o")
    es.cache.clear()
    return lost


def _counters() -> dict:
    return fault.status()["counters"]


# ---------------------------------------------------------------------------
# degraded GET: windowed plan executor
# ---------------------------------------------------------------------------


def test_windowed_repair_ranges_across_windows(tmp_path, monkeypatch):
    """READ_WINDOW=2 forces multiple windows; full and ranged degraded
    GETs are byte-identical, and the partial-repair plan actually ran
    (repair_partial_blocks advances)."""
    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", "2")
    es, _ = _rig(tmp_path, "win")
    body = os.urandom((5 << 20) + 12345)  # 6 stripe blocks -> 3 windows
    es.put_object(BKT, "o", body)
    _lose_shard0(es, tmp_path, "win")

    before = family_stats_snapshot()["cauchy"]["repair_partial_blocks"]
    es.cache.clear()
    _, it = es.get_object(BKT, "o")
    assert _drain(it) == body
    after = family_stats_snapshot()["cauchy"]["repair_partial_blocks"]
    assert after > before, "plan did not run"
    # ranges that start mid-block, span a window boundary, and
    # cover the tail
    for off, ln in ((4096, 65536), ((2 << 20) - 7, 1 << 20),
                    (len(body) - 9000, 9000)):
        es.cache.clear()
        _, h = es.open_object(BKT, "o")
        assert _drain(h.read(off, ln)) == body[off : off + ln], (off, ln)


def test_plan_block_falls_back_on_bitrot(tmp_path, monkeypatch):
    """Sub-chunk bitrot on a helper drive mid-plan: every block spills
    to the generic verified gather (repair_fallback_blocks advances),
    no wrong bytes, and the plan is never abandoned wholesale."""
    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", "2")
    es, paths = _rig(tmp_path, "rot")
    body = os.urandom(3 << 20)
    es.put_object(BKT, "o", body)
    helper_drive = _drive_of_shard(es, 1)  # shard 1 is a b_helper of 0
    _lose_shard0(es, tmp_path, "rot")
    fault.inject({
        "boundary": "storage", "mode": "bitrot", "op": "read_file",
        "target": paths[helper_drive], "seed": 7,
    })
    before = _counters()["repair_fallback_blocks"]
    _, it = es.get_object(BKT, "o")
    assert _drain(it) == body
    assert _counters()["repair_fallback_blocks"] > before
    assert _counters()["storage"] > 0  # the rule really fired


def test_plan_hedges_on_straggling_helper(tmp_path, monkeypatch):
    """A helper read stalled past the EWMA hedge budget races the
    generic full gather (repair_hedge_reads advances); whichever side
    wins, the bytes are identical."""
    monkeypatch.setenv("MINIO_TPU_HEDGE_MIN_MS", "20")
    es, paths = _rig(tmp_path, "lag")
    body = os.urandom(2 << 20)
    es.put_object(BKT, "o", body)
    helper_drive = _drive_of_shard(es, 1)
    _lose_shard0(es, tmp_path, "lag")
    fault.inject({
        "boundary": "storage", "mode": "latency", "op": "read_file",
        "latency_ms": 150, "target": paths[helper_drive], "seed": 11,
    })
    before = _counters()["repair_hedge_reads"]
    _, it = es.get_object(BKT, "o")
    assert _drain(it) == body
    after = _counters()
    assert after["repair_hedge_reads"] > before
    # the race settled one way or the other, never both for one fire
    assert (after["repair_hedge_wins"] + after["repair_hedge_losses"]
            + after["repair_fallback_blocks"]) >= 0


def test_overwrite_racing_plan_withdraws_cleanly(tmp_path, monkeypatch):
    """An overwrite racing a degraded-GET repair plan mid stream: the
    namespace lock serializes them, so the reader either finishes with
    the OLD bytes intact or fails with a typed storage error — never
    mixed/wrong bytes — and the overwrite lands afterwards."""
    import threading

    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", "1")
    es, _ = _rig(tmp_path, "ow")
    old = os.urandom(4 << 20)
    new = os.urandom(1 << 20)
    es.put_object(BKT, "o", old)
    _lose_shard0(es, tmp_path, "ow")
    _, it = es.get_object(BKT, "o")
    got = bytearray(bytes(next(it)))  # plan is live mid-object
    put_err: list = []

    def overwrite():
        try:
            es.put_object(BKT, "o", new)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            put_err.append(e)

    t = threading.Thread(target=overwrite)
    t.start()
    try:
        for c in it:
            got += bytes(c)
        assert bytes(got) == old
    except (errors.StorageError, OSError):
        pass  # clean withdrawal is also acceptable
    t.join(timeout=60)
    assert not t.is_alive() and not put_err, put_err
    es.cache.clear()
    _, it2 = es.get_object(BKT, "o")
    assert _drain(it2) == new


# ---------------------------------------------------------------------------
# heal: windowed partial repair
# ---------------------------------------------------------------------------


def test_heal_straggler_partial_repairs_and_reverifies(tmp_path, monkeypatch):
    """Heal under helper-latency: the windowed executor still partial-
    repairs (or per-block falls back), the result byte-verifies, and the
    healed drive's shard passes a full bitrot verify_file pass."""
    monkeypatch.setenv("MINIO_TPU_HEDGE_MIN_MS", "20")
    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", "2")
    es, paths = _rig(tmp_path, "heal")
    body = os.urandom(3 << 20)
    es.put_object(BKT, "o", body)
    helper_drive = _drive_of_shard(es, 1)
    lost = _lose_shard0(es, tmp_path, "heal")
    fault.inject({
        "boundary": "storage", "mode": "latency", "op": "read_file",
        "latency_ms": 60, "prob": 0.5, "target": paths[helper_drive],
        "seed": 3,
    })
    res = es.heal_object(BKT, "o")
    assert res["healed"], res
    assert res["partialRepair"]
    fault.clear()
    es.cache.clear()
    _, it = es.get_object(BKT, "o")
    assert _drain(it) == body
    # the rebuilt shard on the healed drive passes streaming bitrot
    metas, _ = es._read_all_fileinfo(BKT, "o", "", read_data=False)
    assert metas[lost] is not None
    es.disks[lost].verify_file(BKT, "o", metas[lost])


def test_heal_corrupt_helper_falls_back_per_block(tmp_path, monkeypatch):
    """Bitrot on a helper's reads during heal: blocks whose sub-chunk
    reads fail verification rebuild from the generic survivor set
    (repair_fallback_blocks advances) and the heal stays byte-correct."""
    es, paths = _rig(tmp_path, "hrot")
    body = os.urandom(3 << 20)
    es.put_object(BKT, "o", body)
    helper_drive = _drive_of_shard(es, 1)
    lost = _lose_shard0(es, tmp_path, "hrot")
    fault.inject({
        "boundary": "storage", "mode": "bitrot", "op": "read_file",
        "target": paths[helper_drive], "seed": 5,
    })
    before = _counters()["repair_fallback_blocks"]
    res = es.heal_object(BKT, "o")
    assert res["healed"], res
    assert _counters()["repair_fallback_blocks"] > before
    fault.clear()
    es.cache.clear()
    _, it = es.get_object(BKT, "o")
    assert _drain(it) == body
    metas, _ = es._read_all_fileinfo(BKT, "o", "", read_data=False)
    es.disks[lost].verify_file(BKT, "o", metas[lost])


# ---------------------------------------------------------------------------
# decode-matrix LRU
# ---------------------------------------------------------------------------


def test_decode_cache_hits_misses_and_eviction(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_DECODE_MATRIX_CACHE", "2")
    decode_cache.clear()
    builds = []

    def build(tag):
        def _b():
            builds.append(tag)
            return np.full((2, 2), tag, dtype=np.uint8)
        return _b

    a = decode_cache.get("reedsolomon", 4, 2, (0, 1), build(1))
    assert builds == [1] and a[0, 0] == 1
    # hit: same pattern, no rebuild, same matrix back
    a2 = decode_cache.get("reedsolomon", 4, 2, (0, 1), build(1))
    assert builds == [1] and a2 is a
    decode_cache.get("reedsolomon", 4, 2, (0, 2), build(2))
    # third insert evicts the LRU entry, (0, 1) — its hit made it MRU,
    # but (0, 2) and (0, 3) both landed after it
    decode_cache.get("reedsolomon", 4, 2, (0, 3), build(3))
    decode_cache.get("reedsolomon", 4, 2, (0, 1), build(1))
    assert builds == [1, 2, 3, 1]  # (0,1) was evicted and rebuilt
    # the rebuild evicted (0,2); (0,3) is still resident
    decode_cache.get("reedsolomon", 4, 2, (0, 3), build(3))
    assert builds == [1, 2, 3, 1]
    snap = decode_cache.snapshot()
    assert snap["entries"] == 2
    st = snap["families"]["reedsolomon"]
    assert st["hits"] == 2 and st["misses"] == 4


def test_decode_cache_capacity_zero_disables(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_DECODE_MATRIX_CACHE", "0")
    decode_cache.clear()
    builds = []
    for _ in range(3):
        decode_cache.get("cauchy", 4, 2, (1, 2), lambda: (
            builds.append(1), np.zeros((1, 1), dtype=np.uint8))[1])
    assert len(builds) == 3  # every lookup builds
    snap = decode_cache.snapshot()
    assert snap["entries"] == 0
    # disabled lookups are not counted (A/B runs price the cache off)
    assert snap["families"]["cauchy"] == {"hits": 0, "misses": 0}


def test_rs_decode_rides_cache(monkeypatch):
    """decode_matrix_for / reconstruct_rows_for hit the LRU on pattern
    repeats and the matrices stay correct."""
    monkeypatch.setenv("MINIO_TPU_DECODE_MATRIX_CACHE", "64")
    decode_cache.clear()
    c = rs.get_codec(4, 2)
    m1 = c.decode_matrix_for([1, 2, 3, 4])
    m2 = c.decode_matrix_for([1, 2, 3, 4])
    assert np.array_equal(m1, m2)
    st = decode_cache.snapshot()["families"]["reedsolomon"]
    assert st["hits"] >= 1
    # and the cached matrix still decodes: encode, drop shard 0, rebuild
    data = np.random.default_rng(3).integers(
        0, 256, size=4 * 64, dtype=np.uint8).tobytes()
    shards = c.encode_data(data)
    rec = c.reconstruct([None] + list(shards[1:]))
    assert np.array_equal(rec[0], shards[0])


# ---------------------------------------------------------------------------
# scenario keyspace shapes
# ---------------------------------------------------------------------------


def test_keyspace_shapes_unique_and_wellformed():
    from benchmarks.scenarios.engine import hive_keys, timestamp_run_keys

    hv = hive_keys(24)
    assert len(hv) == 24 and len(set(hv)) == 24
    pat = re.compile(r"^dt=2026-07-\d{2}/hour=\d{2}/part-\d{5}\.parquet$")
    assert all(pat.match(k) for k in hv), hv[:3]

    ts = timestamp_run_keys(37, runs=8)
    assert len(ts) == 37 and len(set(ts)) == 37
    pat2 = re.compile(r"^events/run\d{2}/\d+-\d{6}\.log$")
    assert all(pat2.match(k) for k in ts), ts[:3]
    # within one run-prefix the keys sort in time order (the
    # timestamp-sorted-runs shape the scenario engine promises)
    run0 = [k for k in ts if k.startswith("events/run00/")]
    assert run0 == sorted(run0)
