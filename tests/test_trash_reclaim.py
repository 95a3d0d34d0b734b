"""The trash is emptied (PR 36): what a DELETE or an overwrite renames into
`<drive>/.minio.sys/trash` is removed by that drive's `TrashReclaimer`, off
the request path, woken by the rename; the counters agree; nothing outside
the trash directory is touched; an entry that cannot be removed is counted
and not tried again; a drive that moves nothing aside runs no thread; the
thread stops with the drive, and the drives stop with the server."""

import os
import time

import pytest

from minio_tpu.storage import xlstorage
from minio_tpu.storage.datatypes import FileInfo
from minio_tpu.storage.xlstorage import TRASH_DIR, TrashReclaimer, XLStorage, trash_stats


def wait_for(cond, timeout=5.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def moved_since(before: dict) -> dict:
    now = trash_stats()
    return {k: now[k] - before[k] for k in before}


def put_object(drive: XLStorage, key: str, data: bytes, ddir: str) -> FileInfo:
    """One version with a data dir, committed as the erasure layer does."""
    fi = FileInfo(volume="bkt", name=key)
    fi.data_dir, fi.size = ddir, len(data)
    fi.mod_time = time.time_ns()
    drive.create_file(".minio.sys/tmp", f"stage-{ddir}/{ddir}/part.1", data)
    drive.rename_data(".minio.sys/tmp", f"stage-{ddir}", fi, "bkt", key)
    return fi


@pytest.fixture
def drive(tmp_path):
    d = XLStorage(str(tmp_path / "d0"))
    d.make_vol("bkt")
    yield d
    d.close()


def trash_of(drive: XLStorage) -> list[str]:
    return os.listdir(os.path.join(drive.root, TRASH_DIR))


def test_a_delete_is_reclaimed_within_a_bound_and_the_counters_agree(drive):
    before = trash_stats()
    fi = put_object(drive, "a/key", b"x" * 70_000, "dd-1")
    assert not drive.trash.running  # a fresh key moves nothing aside: no thread
    drive.delete_version("bkt", "a/key", fi)
    # the request's part is the rename: the key is gone from the bucket at once
    assert not os.path.exists(os.path.join(drive.root, "bkt", "a"))
    assert wait_for(lambda: trash_stats()["pending"] == before["pending"] and not trash_of(drive))
    assert moved_since(before) == {"moved": 1, "moved_bytes": 70_000, "reclaimed": 1,
                                   "reclaimed_bytes": 70_000, "failed": 0, "pending": 0}


def test_an_overwrite_moves_the_old_data_dir_aside_and_it_is_reclaimed(drive):
    before = trash_stats()
    put_object(drive, "k", b"1" * 1000, "dd-old")
    put_object(drive, "k", b"2" * 3000, "dd-new")  # the null version again: replaces
    assert wait_for(lambda: moved_since(before)["reclaimed"] == 1 and not trash_of(drive))
    got = moved_since(before)
    assert (got["moved"], got["moved_bytes"], got["reclaimed_bytes"]) == (1, 1000, 1000)
    assert sorted(os.listdir(os.path.join(drive.root, "bkt", "k"))) == ["dd-new", "xl.meta"]
    with open(os.path.join(drive.root, "bkt", "k", "dd-new", "part.1"), "rb") as f:
        assert f.read() == b"2" * 3000


def test_nothing_outside_the_trash_is_touched(drive, tmp_path):
    outside = tmp_path / "outside"
    outside.mkdir()
    (outside / "kept.txt").write_bytes(b"keep me")
    fi = put_object(drive, "k", b"z" * 10, "dd-1")
    # a symlink inside what is moved aside, to a directory that is not ours
    os.symlink(outside, os.path.join(drive.root, "bkt", "k", "dd-1", "link"))
    before = trash_stats()
    drive.delete_version("bkt", "k", fi)
    assert wait_for(lambda: moved_since(before)["reclaimed"] == 1)
    assert (outside / "kept.txt").read_bytes() == b"keep me"  # unlinked, never followed
    assert moved_since(before)["moved_bytes"] == 10  # and never counted
    # an entry that is not a direct child of this drive's trash is refused
    stray = tmp_path / "stray"
    stray.mkdir()
    drive.trash.put(str(stray), 0)
    nested = os.path.join(drive.trash.trash_dir, "a", "b")
    os.makedirs(nested)
    drive.trash.put(nested, 0)
    assert wait_for(lambda: moved_since(before)["failed"] == 2)
    assert stray.is_dir() and os.path.isdir(nested)


def test_remove_tree_removes_what_rmtree_would_and_follows_no_link(tmp_path):
    outside = tmp_path / "outside"
    (outside / "sub").mkdir(parents=True)
    (outside / "sub" / "kept.txt").write_bytes(b"keep me")
    entry = tmp_path / "entry"
    (entry / "deep" / "deeper").mkdir(parents=True)
    (entry / "part.1").write_bytes(b"1")
    (entry / "deep" / "part.2").write_bytes(b"22")
    (entry / "deep" / "deeper" / "part.3").write_bytes(b"333")
    os.symlink(outside, entry / "deep" / "to-a-directory")
    os.symlink(outside / "sub" / "kept.txt", entry / "to-a-file")
    assert xlstorage._tree_bytes(str(entry)) == 6  # the links' targets are not counted
    xlstorage._remove_tree(str(entry))
    assert not entry.exists() and (outside / "sub" / "kept.txt").read_bytes() == b"keep me"
    # an entry that IS a link to a directory, and one that is a plain file
    link, plain = tmp_path / "link", tmp_path / "plain"
    os.symlink(outside, link)
    plain.write_bytes(b"x")
    xlstorage._remove_tree(str(link))
    xlstorage._remove_tree(str(plain))
    assert not os.path.lexists(link) and not plain.exists()
    assert (outside / "sub" / "kept.txt").exists()
    with pytest.raises(FileNotFoundError):
        xlstorage._remove_tree(str(tmp_path / "never-there"))


def test_an_entry_that_cannot_be_removed_is_counted_and_not_tried_again(drive, monkeypatch):
    calls = []

    def refuses(path):
        calls.append(path)
        raise PermissionError(13, "not allowed", path)

    monkeypatch.setattr(xlstorage, "_remove_tree", refuses)
    before = trash_stats()
    fi = put_object(drive, "k", b"q" * 5, "dd-1")
    drive.delete_version("bkt", "k", fi)
    assert wait_for(lambda: moved_since(before)["failed"] == 1)
    time.sleep(0.2)
    got = moved_since(before)
    assert (got["moved"], got["reclaimed"], got["failed"], got["pending"]) == (1, 0, 1, 0)
    assert len(calls) == 1 and len(trash_of(drive)) == 1  # left where it is, once
    # the worker lives on: the next entry is removed
    monkeypatch.undo()
    fi = put_object(drive, "k2", b"q" * 5, "dd-2")
    drive.delete_version("bkt", "k2", fi)
    assert wait_for(lambda: moved_since(before)["reclaimed"] == 1)


def test_what_a_previous_process_left_is_adopted_when_the_drive_opens(tmp_path):
    root = tmp_path / "d0"
    first = XLStorage(str(root))
    first.close()
    left = root / TRASH_DIR / "left-behind"
    (left / "dd").mkdir(parents=True)
    (left / "dd" / "part.1").write_bytes(b"o" * 4096)
    before = trash_stats()
    again = XLStorage(str(root))
    try:
        assert wait_for(lambda: moved_since(before)["reclaimed"] == 1 and not trash_of(again))
        got = moved_since(before)
        assert (got["moved"], got["moved_bytes"], got["reclaimed_bytes"]) == (1, 4096, 4096)
    finally:
        again.close()


def test_the_worker_stops_with_the_drive_and_an_idle_one_ends_by_itself(drive, monkeypatch):
    monkeypatch.setattr(xlstorage, "_TRASH_IDLE_S", 0.05)
    fi = put_object(drive, "k", b"q", "dd-1")
    drive.delete_version("bkt", "k", fi)
    assert wait_for(lambda: not trash_of(drive))
    assert wait_for(lambda: not drive.trash.running)  # idle: gone, no close needed
    fi = put_object(drive, "k", b"q", "dd-2")
    drive.delete_version("bkt", "k", fi)  # the next rename starts another
    assert wait_for(lambda: not trash_of(drive))
    monkeypatch.setattr(xlstorage, "_TRASH_IDLE_S", 30.0)
    fi = put_object(drive, "k", b"q", "dd-3")
    drive.delete_version("bkt", "k", fi)
    assert wait_for(lambda: not trash_of(drive)) and drive.trash.running
    drive.close()
    assert not drive.trash.running
    # closed: nothing is handed over any more, nothing raises
    fi = put_object(drive, "k", b"q", "dd-4")
    drive.delete_version("bkt", "k", fi)
    time.sleep(0.1)
    assert len(trash_of(drive)) == 1 and not drive.trash.running


def test_many_threads_trash_at_once_while_the_worker_keeps_ending_and_starting(drive,
                                                                              monkeypatch):
    """More threads than cores, a short switch interval, and an idle time so
    short that the worker ends between entries: a `put` that raced its exit
    would leave an entry queued with no thread, and a lost update a count short."""
    import sys
    import threading

    monkeypatch.setattr(xlstorage, "_TRASH_IDLE_S", 0.0005)
    before = trash_stats()
    threads, each = 16, 25
    errors: list = []

    def churn(t: int) -> None:
        try:
            for i in range(each):
                fi = put_object(drive, f"t{t}/k{i}", b"x" * 100, f"dd-{t}-{i}")
                drive.delete_version("bkt", f"t{t}/k{i}", fi)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=churn, args=(t,)) for t in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts) and not errors, errors[:1]
        assert wait_for(lambda: moved_since(before)["reclaimed"] == threads * each, timeout=30)
    finally:
        sys.setswitchinterval(interval)
    got = moved_since(before)
    assert (got["moved"], got["moved_bytes"], got["reclaimed_bytes"], got["failed"],
            got["pending"]) == (threads * each, 100 * threads * each, 100 * threads * each, 0, 0)
    assert trash_of(drive) == []


def test_a_reclaimer_survives_a_vanished_entry(tmp_path):
    trash = tmp_path / "trash"
    trash.mkdir()
    r = TrashReclaimer(str(trash))
    before = trash_stats()
    r.put(str(trash / "never-there"), 7)  # a sibling process removed it first
    try:
        assert wait_for(lambda: moved_since(before)["reclaimed"] == 1)
        assert moved_since(before)["failed"] == 0
    finally:
        r.stop()


def test_the_servers_close_stops_the_drives_through_their_wrappers(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_BACKEND", "numpy")
    monkeypatch.setenv("MINIO_TPU_SCAN_INTERVAL", "0")
    from minio_tpu.server.app import make_server

    srv = make_server([str(tmp_path / f"d{i}") for i in range(4)])
    store = srv.store
    store.make_bucket("bkt")
    store.put_object("bkt", "k", b"v" * 300_000)
    store.delete_object("bkt", "k")
    inner = [d._inner._inner for d in store.disks]  # breaker -> fault proxy -> drive
    assert all(isinstance(d, XLStorage) for d in inner)
    assert wait_for(lambda: not any(trash_of(d) for d in inner))
    assert any(d.trash.running for d in inner)
    # a drive whose breaker is open still closes: teardown is not a drive call
    store.disks[0]._open_until = time.monotonic() + 60
    srv.close()
    assert not any(d.trash.running for d in inner)
