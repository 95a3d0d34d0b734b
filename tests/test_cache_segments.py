"""Range-segment data cache (cache/segment.py + prefetch.py): stripe-block
fills over the verified read path, ranged-GET short-circuit of
open_object, the NVMe second tier (demote/promote/quarantine), sequential
read-ahead, and write-through coherence under overwrite/heal churn.

Covers the PR acceptance criteria: a warm-memory ranged GET's trace tree
carries no ns-lock/drive spans; injected disk-tier faults (read error,
torn write) fall back to the erasure path with zero wrong bytes; and
concurrent overwrite/heal with ranged cached GETs in flight never serve
stale bytes or etags.
"""

import os

os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")

import threading
import time

import pytest

from minio_tpu.cache import core as cache_core
from minio_tpu.cache import prefetch as pfmod
from minio_tpu.cache import segment as segmod
from minio_tpu.erasure.set import (
    ErasureSet,
    ObjectHandle,
    SegmentCachedObjectHandle,
)
from minio_tpu.fault import registry as freg
from minio_tpu.storage.xlstorage import XLStorage

MIB = 1 << 20


@pytest.fixture(autouse=True)
def _seg_env(monkeypatch, tmp_path):
    monkeypatch.setenv("MINIO_TPU_CACHE", "1")
    monkeypatch.setenv("MINIO_TPU_CACHE_SEGMENTS", "1")
    # small whole-object gate so modest objects exercise the segment tier
    monkeypatch.setenv("MINIO_TPU_CACHE_OBJECT_MAX", str(256 * 1024))
    monkeypatch.setenv("MINIO_TPU_CACHE_ADMIT_TOUCHES", "2")
    monkeypatch.setenv("MINIO_TPU_CACHE_MEM_MB", "256")
    monkeypatch.setenv("MINIO_TPU_CACHE_DISK_MB", "0")
    monkeypatch.setenv("MINIO_TPU_CACHE_DISK_DIR", str(tmp_path / "spool"))
    monkeypatch.setenv("MINIO_TPU_CACHE_PREFETCH_SEGMENTS", "0")
    pfmod.reset()
    # the segment cache is the process's: what an earlier test file of this
    # xdist worker left in it is not this test's to count or to demote
    segmod.segment_cache().drop_where(lambda dk: True)
    yield
    freg.clear()


def _rig(tmp_path, n=4):
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(n)]
    es = ErasureSet(disks)
    es.make_bucket("sb")
    return es, disks


def _ranged(es, key, off, ln, vid=""):
    oi, h = es.open_object("sb", key, vid, ("abs", off, off + ln - 1))
    data = b"".join(bytes(c) for c in h.read(off, ln))
    return h, oi, data


def _warm(es, key, size, passes=2):
    """Sequentially read every 1 MiB range `passes` times (admission
    wants two object touches; fills begin on the second)."""
    for _ in range(passes):
        for off in range(0, size, MIB):
            _ranged(es, key, off, min(MIB, size - off))


def _snap():
    return segmod.segment_cache().snapshot()


# -- fills + hits -----------------------------------------------------------


def test_two_touch_admission_then_fill_then_hit(tmp_path):
    es, _ = _rig(tmp_path)
    body = os.urandom(3 * MIB)
    es.put_object("sb", "k", body)
    f0 = _snap()["fills"]
    h, _, d = _ranged(es, "k", 0, MIB)  # touch 1: observes, no fill
    assert isinstance(h, ObjectHandle) and d == body[:MIB]
    assert _snap()["fills"] == f0
    h, _, d = _ranged(es, "k", 0, MIB)  # touch 2: fills
    assert isinstance(h, ObjectHandle) and d == body[:MIB]
    assert _snap()["fills"] > f0
    h, oi, d = _ranged(es, "k", 0, MIB)  # hit: short-circuits open_object
    assert isinstance(h, SegmentCachedObjectHandle)
    assert d == body[:MIB]
    assert oi.size == len(body) and oi.etag


def test_partial_and_cross_segment_ranges_byte_identical(tmp_path):
    es, _ = _rig(tmp_path)
    body = os.urandom(3 * MIB + 12345)
    es.put_object("sb", "k2", body)
    _warm(es, "k2", len(body))
    for off, ln in [
        (0, 100), (MIB - 7, 14), (MIB + 5, 2 * MIB), (3 * MIB, 12345),
        (517, 3 * MIB + 11000),
    ]:
        h, _, d = _ranged(es, "k2", off, ln)
        assert isinstance(h, SegmentCachedObjectHandle), (off, ln)
        assert d == body[off : off + ln], (off, ln)


def test_suffix_and_open_ended_hints_resolve(tmp_path):
    es, _ = _rig(tmp_path)
    body = os.urandom(3 * MIB)
    es.put_object("sb", "k3", body)
    _warm(es, "k3", len(body))
    oi, h = es.open_object("sb", "k3", "", ("suffix", 1000))
    assert isinstance(h, SegmentCachedObjectHandle)
    got = b"".join(
        bytes(c) for c in h.read(len(body) - 1000, 1000)
    )
    assert got == body[-1000:]
    oi, h = es.open_object("sb", "k3", "", ("abs", 2 * MIB, None))
    assert isinstance(h, SegmentCachedObjectHandle)
    got = b"".join(bytes(c) for c in h.read(2 * MIB, MIB))
    assert got == body[2 * MIB :]


def test_small_objects_stay_on_whole_object_tier(tmp_path):
    es, _ = _rig(tmp_path)
    body = os.urandom(100 * 1024)  # below MINIO_TPU_CACHE_OBJECT_MAX
    es.put_object("sb", "small", body)
    f0 = _snap()["fills"]
    for _ in range(3):
        _ranged(es, "small", 0, 50 * 1024)
    assert _snap()["fills"] == f0  # segment tier never admits it


def test_read_outside_hinted_range_falls_back(tmp_path):
    es, _ = _rig(tmp_path)
    body = os.urandom(3 * MIB)
    es.put_object("sb", "k4", body)
    _warm(es, "k4", len(body))
    oi, h = es.open_object("sb", "k4", "", ("abs", 0, MIB - 1))
    assert isinstance(h, SegmentCachedObjectHandle)
    # the handle was pinned for [0, 1MiB) but a caller may read elsewhere
    got = b"".join(bytes(c) for c in h.read(2 * MIB, 1000))
    assert got == body[2 * MIB : 2 * MIB + 1000]


def test_disabled_segments_knob_bypasses(tmp_path, monkeypatch):
    es, _ = _rig(tmp_path)
    body = os.urandom(3 * MIB)
    es.put_object("sb", "koff", body)
    monkeypatch.setenv("MINIO_TPU_CACHE_SEGMENTS", "0")
    f0 = _snap()["fills"]
    _warm(es, "koff", len(body), passes=3)
    assert _snap()["fills"] == f0
    h, _, d = _ranged(es, "koff", 0, MIB)
    assert isinstance(h, ObjectHandle) and d == body[:MIB]


# -- coherence --------------------------------------------------------------


def test_overwrite_invalidates_segments_and_serves_new_bytes(tmp_path):
    es, _ = _rig(tmp_path)
    body = os.urandom(3 * MIB)
    es.put_object("sb", "ow", body)
    _warm(es, "ow", len(body))
    h, _, _d = _ranged(es, "ow", 0, MIB)
    assert isinstance(h, SegmentCachedObjectHandle)
    body2 = os.urandom(3 * MIB)
    oi2 = es.put_object("sb", "ow", body2)
    h, oi, d = _ranged(es, "ow", 0, MIB)
    assert isinstance(h, ObjectHandle)  # cache dropped, real path
    assert d == body2[:MIB] and oi.etag == oi2.etag


def test_delete_invalidates_segments(tmp_path):
    es, _ = _rig(tmp_path)
    es.put_object("sb", "del", os.urandom(3 * MIB))
    _warm(es, "del", 3 * MIB)
    es.delete_object("sb", "del")
    from minio_tpu.erasure.quorum import ObjectNotFound

    with pytest.raises(ObjectNotFound):
        es.open_object("sb", "del", "", ("abs", 0, MIB - 1))


def test_epoch_bump_revalidates_before_serving(tmp_path):
    es, _ = _rig(tmp_path)
    body = os.urandom(3 * MIB)
    es.put_object("sb", "ep", body)
    _warm(es, "ep", len(body))
    r0 = _snap()["revalidations"]
    es.cache.bump_epoch()
    h, _, d = _ranged(es, "ep", 0, MIB)
    assert isinstance(h, SegmentCachedObjectHandle)
    assert d == body[:MIB]
    assert _snap()["revalidations"] > r0


def test_concurrent_overwrites_and_heals_never_serve_stale(tmp_path):
    """The chaos coherence schedule: ranged cached GETs in flight while
    writers overwrite and a healer heals. Every read must return bytes
    matching ONE committed version, never a mix and never a version
    older than the last write a reader could have observed started."""
    import shutil as _sh

    es, _ = _rig(tmp_path)
    size = 2 * MIB
    bodies = [bytes([v]) * size for v in range(1, 6)]
    etags = {}
    etags[0] = es.put_object("sb", "chaos", bodies[0]).etag
    _warm(es, "chaos", size)
    stop = threading.Event()
    errors: list[str] = []

    def reader(rid: int) -> None:
        while not stop.is_set():
            try:
                off = (rid % 2) * MIB
                oi, h = es.open_object(
                    "sb", "chaos", "", ("abs", off, off + MIB - 1)
                )
                d = b"".join(bytes(c) for c in h.read(off, MIB))
            except Exception:  # noqa: BLE001 — raced a delete window: fine
                continue
            if len(set(d)) != 1:
                errors.append(f"torn read: {sorted(set(d))[:4]}")
                return
            v = d[0]
            if bytes([v]) * size != bodies[v - 1]:
                errors.append(f"unknown byte {v}")
                return
            if oi.etag != etags.get(v - 1):
                errors.append(f"etag mismatch for version {v}")
                return

    readers = [
        threading.Thread(target=reader, args=(i,)) for i in range(4)
    ]
    for t in readers:
        t.start()
    try:
        for i, body in enumerate(bodies[1:], start=1):
            etags[i] = es.put_object("sb", "chaos", body).etag
            # wound one drive's copy out-of-band and heal it back while
            # readers hammer the cached path
            _sh.rmtree(tmp_path / "d0" / "sb" / "chaos", ignore_errors=True)
            es.heal_object("sb", "chaos")
            time.sleep(0.05)
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=30)
    assert not errors, errors[:3]
    # cache still coherent after the dust settles
    h, oi, d = _ranged(es, "chaos", 0, MIB)
    assert d == bodies[-1][:MIB] and oi.etag == etags[4]


# -- disk/NVMe second tier --------------------------------------------------


def test_demote_promote_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CACHE_MEM_MB", "2")  # force demotion
    monkeypatch.setenv("MINIO_TPU_CACHE_DISK_MB", "64")
    es, _ = _rig(tmp_path)
    body = os.urandom(4 * MIB)
    es.put_object("sb", "dp", body)
    s0 = _snap()
    _warm(es, "dp", len(body))
    s1 = _snap()
    assert s1["demotions"] > s0["demotions"]
    assert s1["disk_entries"] > 0
    spool = s1["disk_dir"]
    assert spool and os.path.isdir(spool) and os.listdir(spool)
    # every range still serves, promoting off the files, byte-identical
    for off in range(0, len(body), MIB):
        h, _, d = _ranged(es, "dp", off, MIB)
        assert isinstance(h, SegmentCachedObjectHandle), off
        assert d == body[off : off + MIB]
    assert _snap()["promotions"] > s1["promotions"] - 1
    # invalidation unlinks this object's segment files
    es.put_object("sb", "dp", os.urandom(4 * MIB))
    assert _snap()["disk_entries"] == 0


def test_disk_tier_disabled_evicts_instead(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CACHE_MEM_MB", "2")
    monkeypatch.setenv("MINIO_TPU_CACHE_DISK_MB", "0")
    es, _ = _rig(tmp_path)
    body = os.urandom(4 * MIB)
    es.put_object("sb", "ev", body)
    e0 = _snap()["evictions"]
    _warm(es, "ev", len(body))
    s = _snap()
    assert s["disk_entries"] == 0
    assert s["evictions"] > e0


def test_disk_read_error_falls_back_and_quarantines(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CACHE_MEM_MB", "2")
    monkeypatch.setenv("MINIO_TPU_CACHE_DISK_MB", "64")
    es, _ = _rig(tmp_path)
    body = os.urandom(4 * MIB)
    es.put_object("sb", "fr", body)
    _warm(es, "fr", len(body))
    assert _snap()["disk_entries"] > 0
    q0 = _snap()["quarantined"]
    freg.inject({"boundary": "storage", "target": "cache-disk",
                 "op": "read", "mode": "error"})
    try:
        # every read must still return the right bytes — via the erasure
        # fallback once the faulted disk tier quarantines
        for off in range(0, len(body), MIB):
            h, _, d = _ranged(es, "fr", off, MIB)
            assert d == body[off : off + MIB], off
    finally:
        freg.clear()
    assert _snap()["quarantined"] > q0


def test_disk_torn_write_detected_zero_wrong_bytes(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CACHE_MEM_MB", "2")
    monkeypatch.setenv("MINIO_TPU_CACHE_DISK_MB", "64")
    es, _ = _rig(tmp_path)
    body = os.urandom(4 * MIB)
    es.put_object("sb", "tw", body)
    # torn writes during DEMOTION: files land truncated on disk
    freg.inject({"boundary": "storage", "target": "cache-disk",
                 "op": "write", "mode": "torn-write"})
    try:
        _warm(es, "tw", len(body))
    finally:
        freg.clear()
    # promote attempts must detect the tear (length/digest) and fall
    # back — reads stay byte-perfect throughout
    q0 = _snap()["quarantined"]
    for off in range(0, len(body), MIB):
        _h, _, d = _ranged(es, "tw", off, MIB)
        assert d == body[off : off + MIB], off
    if _snap()["disk_entries"] or q0 < _snap()["quarantined"]:
        assert _snap()["quarantined"] >= q0


def test_disk_bitrot_detected_by_digest(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CACHE_MEM_MB", "2")
    monkeypatch.setenv("MINIO_TPU_CACHE_DISK_MB", "64")
    es, _ = _rig(tmp_path)
    body = os.urandom(4 * MIB)
    es.put_object("sb", "br", body)
    _warm(es, "br", len(body))
    assert _snap()["disk_entries"] > 0
    freg.inject({"boundary": "storage", "target": "cache-disk",
                 "op": "read", "mode": "bitrot", "seed": 7})
    q0 = _snap()["quarantined"]
    try:
        for off in range(0, len(body), MIB):
            _h, _, d = _ranged(es, "br", off, MIB)
            assert d == body[off : off + MIB], off
    finally:
        freg.clear()
    assert _snap()["quarantined"] > q0


def test_data_cache_fill_sheds_segments_not_itself(tmp_path, monkeypatch):
    """Shared-budget fairness: when the whole-object tier fills while
    segments hold the budget, the SEGMENTS shed (demoting to NVMe) —
    the data cache must keep its just-inserted entry instead of evicting
    itself to zero against bytes it cannot reclaim."""
    monkeypatch.setenv("MINIO_TPU_CACHE_MEM_MB", "4")
    monkeypatch.setenv("MINIO_TPU_CACHE_DISK_MB", "64")
    es, _ = _rig(tmp_path)
    big = os.urandom(4 * MIB)
    es.put_object("sb", "bigseg", big)
    _warm(es, "bigseg", len(big))  # segments now hold ~the whole budget
    small = os.urandom(200 * 1024)
    es.put_object("sb", "hot", small)

    def drain():
        _oi, it = es.get_object("sb", "hot")
        return b"".join(bytes(c) for c in it)

    drain()
    drain()  # two-touch: fills the whole-object tier
    # the shed runs its demote I/O on a helper thread; give it a beat
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if cache_core.data_cache().get(es, "sb", "hot", "") is not None:
            break
        drain()
        time.sleep(0.05)
    assert cache_core.data_cache().get(es, "sb", "hot", "") is not None, (
        "data-cache entry evicted against segment-held budget",
        _snap(),
    )


# -- prefetch ---------------------------------------------------------------


def test_sequential_run_prefetches_ahead(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CACHE_PREFETCH_SEGMENTS", "4")
    es, _ = _rig(tmp_path)
    body = os.urandom(8 * MIB)
    es.put_object("sb", "pf", body)
    s0 = pfmod.stats()
    # one sequential pass: the run is detected after 2 contiguous reads
    # and the worker fills ahead of the client
    for off in range(0, 4 * MIB, MIB):
        _ranged(es, "pf", off, MIB)
    pfmod.drain_for_tests()
    s1 = pfmod.stats()
    assert s1["runs_detected"] > s0["runs_detected"]
    assert s1["scheduled"] > s0["scheduled"]
    assert s1["errors"] == s0["errors"]
    # segments PAST what the client read must be resident now
    d = segmod.segment_cache().directory(es, "sb", "pf", "")
    assert d is not None
    covered_past_client = segmod.segment_cache().coverage(d, 4 * MIB, MIB)
    assert covered_past_client == MIB
    # and a jump-ahead read is served from cache
    h, _, got = _ranged(es, "pf", 4 * MIB, MIB)
    assert isinstance(h, SegmentCachedObjectHandle)
    assert got == body[4 * MIB : 5 * MIB]


def test_random_reads_do_not_prefetch(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CACHE_PREFETCH_SEGMENTS", "4")
    es, _ = _rig(tmp_path)
    body = os.urandom(8 * MIB)
    es.put_object("sb", "rnd", body)
    s0 = pfmod.stats()
    for off_mib in (5, 1, 6, 0, 3, 7):  # no two contiguous
        _ranged(es, "rnd", off_mib * MIB, MIB)
    pfmod.drain_for_tests()
    s1 = pfmod.stats()
    assert s1["runs_detected"] == s0["runs_detected"]
    assert s1["scheduled"] == s0["scheduled"]


def test_prefetch_disabled_by_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_CACHE_PREFETCH_SEGMENTS", "0")
    es, _ = _rig(tmp_path)
    es.put_object("sb", "npf", os.urandom(4 * MIB))
    s0 = pfmod.stats()
    for off in range(0, 4 * MIB, MIB):
        _ranged(es, "npf", off, MIB)
    assert pfmod.stats()["observed"] == s0["observed"]


def test_prefetch_rides_background_lane(tmp_path, monkeypatch):
    """The guard invariant: the read-ahead worker's erasure reads run
    under BOTH qos.background_context (dispatcher bg lane — leftover
    capacity only) and qos.prefetch_context (the lane's accounting tag),
    and fg_deferred_behind_bg stays flat."""
    from minio_tpu.qos.context import (
        PRI_BACKGROUND,
        current_priority,
        in_prefetch,
    )

    seen: list[tuple[int, bool]] = []
    orig = ErasureSet.open_object

    def spy(self, *a, **kw):
        if in_prefetch():  # record only the worker's own reads
            seen.append((current_priority(), in_prefetch()))
        return orig(self, *a, **kw)

    monkeypatch.setattr(ErasureSet, "open_object", spy)
    monkeypatch.setenv("MINIO_TPU_CACHE_PREFETCH_SEGMENTS", "2")
    es, _ = _rig(tmp_path)
    es.put_object("sb", "bg", os.urandom(4 * MIB))
    for off in range(0, 3 * MIB, MIB):
        _ranged(es, "bg", off, MIB)
    pfmod.drain_for_tests()
    assert seen, "prefetch worker never issued a read"
    assert all(pri == PRI_BACKGROUND and pf for pri, pf in seen)
    from minio_tpu.parallel import dispatcher as disp

    assert disp.aggregate_stats().get("fg_deferred_behind_bg", 0) == 0


# -- observability ----------------------------------------------------------


def test_aggregate_stats_and_spans(tmp_path):
    from minio_tpu import obs
    from minio_tpu.server.metrics import TracePubSub

    es, _ = _rig(tmp_path)
    body = os.urandom(3 * MIB)
    es.put_object("sb", "obs", body)
    _warm(es, "obs", len(body))
    st = cache_core.aggregate_stats(es)
    assert st["segments"]["fills"] >= 3
    assert "prefetch" in st and "scheduled" in st["prefetch"]
    # a warm ranged GET publishes a cache.segment hit span
    prev = obs.publisher()
    pub = TracePubSub()
    obs.set_publisher(pub)
    sub = pub.subscribe()
    try:
        _ranged(es, "obs", 0, MIB)
        recs = []
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            try:
                recs.append(sub.q.get(timeout=0.2))
            except Exception:  # noqa: BLE001 — queue.Empty
                break
    finally:
        pub.unsubscribe(sub)
        obs.set_publisher(prev)
    names = [r.get("name") for r in recs]
    assert "cache.segment" in names
    # the hit's trace tree has NO ns-lock/open_object/storage spans
    assert "erasure.open_object" not in names
    assert not [r for r in recs if r.get("type") == "storage"]
