"""Active lock refresh + loss abort (reference internal/dsync/drwmutex.go:340).

A crashed/partitioned lock plane must abort the guarded write promptly —
not let the holder keep writing as a zombie until the 120 s TTL."""

import os
import threading
import time

os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")

import numpy as np
import pytest

from minio_tpu.cluster.locks import DRWMutex, LocalLocker, NamespaceLock
from minio_tpu.erasure.quorum import QuorumError
from minio_tpu.erasure.set import ErasureSet
from minio_tpu.storage.xlstorage import XLStorage


def test_refresher_detects_quorum_loss():
    lockers = [LocalLocker() for _ in range(3)]
    mtx = DRWMutex(lockers, "bkt/obj")
    assert mtx.lock(1.0)
    fired = threading.Event()
    mtx.start_refresher(write=True, interval=0.05, on_lost=fired.set)
    # healthy refreshes keep the lock
    time.sleep(0.2)
    assert not mtx.lost
    # two of three lock servers lose state (crash/restart)
    lockers[0].force_unlock("bkt/obj")
    lockers[1].force_unlock("bkt/obj")
    assert fired.wait(2.0), "loss callback must fire"
    assert mtx.lost
    mtx.unlock()


def test_refresher_stops_on_unlock():
    lockers = [LocalLocker()]
    mtx = DRWMutex(lockers, "bkt/obj2")
    assert mtx.lock(1.0)
    mtx.start_refresher(write=True, interval=0.05)
    mtx.unlock()
    # after unlock the refresher must not flag loss
    time.sleep(0.2)
    assert not mtx.lost


def test_streaming_put_aborts_on_lock_loss(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_LOCK_REFRESH_S", "0.05")
    lockers = [LocalLocker() for _ in range(3)]
    ns = NamespaceLock(lockers)
    disks = [XLStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    es = ErasureSet(disks, ns_lock=ns)
    es.make_bucket("lkb")
    old = b"old-object-must-survive"
    es.put_object("lkb", "obj", old)

    chunk = np.random.default_rng(0).integers(
        0, 256, size=1024 * 1024, dtype=np.uint8
    ).tobytes()

    def gen():
        for i in range(64):
            if i == 2:
                # the lock plane loses our lock mid-stream
                lockers[0].force_unlock("lkb/obj")
                lockers[1].force_unlock("lkb/obj")
            time.sleep(0.08)
            yield chunk

    t0 = time.monotonic()
    with pytest.raises(QuorumError, match="lost"):
        es.put_object("lkb", "obj", gen())
    # aborted promptly, not after a 120 s TTL wedge
    assert time.monotonic() - t0 < 20
    # pre-existing object untouched
    _, it = es.get_object("lkb", "obj")
    assert b"".join(it) == old


def test_writer_not_starved_by_reader_stream():
    """Writer priority must survive the LAST reader's unlock: the parked
    writer's marker lives on the lock-table entry, and dropping the entry
    with the last reader handed the resource straight back to the reader
    stream — a heal's write lock then timed out (30 s) behind four GET
    loops. More readers than the GIL can run at once, busy holds, and
    every writer attempt must get in well inside its deadline."""
    ns = NamespaceLock()
    stop = threading.Event()
    reads = [0]

    def reader():
        while not stop.is_set():
            m = ns.new("bkt", "hot")
            if m.rlock(5.0):
                t = time.monotonic()
                while time.monotonic() - t < 0.01:
                    pass  # busy hold: contend for the GIL like a decode
                reads[0] += 1
                m.runlock()

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        waits = []
        for _ in range(4):
            before = reads[0]
            w = ns.new("bkt", "hot")
            t0 = time.monotonic()
            assert w.lock(10.0), f"writer starved by readers: {waits}"
            waits.append(time.monotonic() - t0)
            w.unlock()
            # readers flow again between writers (no permanent lockout)
            deadline = time.monotonic() + 5.0
            while reads[0] == before and time.monotonic() < deadline:
                time.sleep(0.005)
            assert reads[0] > before, "readers never resumed after the writer"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
