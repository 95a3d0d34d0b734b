"""XLStorage — local POSIX drive backend.

Behavioral mirror of the reference's xlStorage (/root/reference/cmd/
xl-storage.go): one directory per drive; objects live at
<drive>/<bucket>/<object>/xl.meta with erasure shard files in a
uuid-named data dir next to it; writes stage in <drive>/.minio.sys/tmp and
move into place with atomic renames; what a delete or an overwrite
replaces is renamed into <drive>/.minio.sys/trash on the request path
(moveToTrash, xl-storage.go:1295) and removed from there by the drive's
reclaimer thread (`TrashReclaimer`), which the rename wakes: the request
is acknowledged after its renames, the removal follows.

Differences from the reference, by design:
- No O_DIRECT (Python path; the native IO helper can add it later) — but
  the write path preserves the same atomicity contract: data dirs and
  xl.meta never visible half-written.
- xl.meta is our msgpack schema (storage/format.py), same semantics.
"""

from __future__ import annotations

import os
import queue
import shutil
import stat
import threading
import uuid
from typing import BinaryIO, Iterator

from .. import obs
from . import errors
from .datatypes import DiskInfo, FileInfo, VolInfo
from .format import XLMeta
from .interface import StorageAPI

SYS_DIR = ".minio.sys"
TMP_DIR = f"{SYS_DIR}/tmp"
TRASH_DIR = f"{SYS_DIR}/trash"
MULTIPART_DIR = f"{SYS_DIR}/multipart"
BUCKETS_META_DIR = f"{SYS_DIR}/buckets"
META_FILE = "xl.meta"

_FSYNC = os.environ.get("MINIO_TPU_FSYNC", "0") == "1"
# O_DIRECT for large shard writes (reference cmd/xl-storage.go:316);
# off by default: tmpfs/test dirs refuse it and benchmarks on page-cached
# local disks are faster without it — enable for production spinning/NVMe
_ODIRECT = (
    os.environ.get("MINIO_TPU_ODIRECT", "off") in ("on", "true", "1")
    and hasattr(os, "O_DIRECT")
)
_ODIRECT_MIN = 1 << 20  # small files stay buffered

# ---- shard-file fan-out counters -------------------------------------------
# Deterministic proof obligations for the inline small-object fast path:
# raw IOPS on a CPU-shadowed container don't transfer, but "this op opened
# zero shard files" does. Every shard-file read/write on this drive bumps
# one counter, split by plane — user volumes vs `.minio.sys` system
# volumes (metacache persistence, staging, multipart) — so a test or a
# bench gate can assert that inline PUT/GET/HEAD leave the user-plane
# counters flat. xl.meta I/O goes through direct open() in _read_meta/
# _write_meta and is invisible here BY DESIGN: the metadata plane is
# allowed; shard-file fan-out is what the inline path must never do.

_FANOUT_LOCK = threading.Lock()
_FANOUT = {
    "shard_reads_user": 0,
    "shard_reads_sys": 0,
    "shard_writes_user": 0,
    "shard_writes_sys": 0,
    "shard_commits_user": 0,  # rename_data data-dir moves into place
    "shard_commits_sys": 0,
}


def _fanout_bump(kind: str, volume: str) -> None:
    plane = "sys" if volume.startswith(SYS_DIR) else "user"
    with _FANOUT_LOCK:
        _FANOUT[f"{kind}_{plane}"] += 1


def fanout_stats() -> dict:
    """Snapshot of the process-wide shard-file I/O counters."""
    with _FANOUT_LOCK:
        return dict(_FANOUT)


# ---- the trash -------------------------------------------------------------
# Process-wide counts of what `_to_trash` moved aside and what the drives'
# reclaimers made of it. `moved` is booked at the rename (and for what a
# previous process left in a trash directory, when the drive adopts it),
# `reclaimed` when a removal ENDS, `failed` for an entry whose removal
# raised: it stays where it is, is not tried again by this process, and
# the next one to open the drive adopts it once more.

_TRASH_LOCK = threading.Lock()
_TRASH = {
    "moved": 0,
    "moved_bytes": 0,
    "reclaimed": 0,
    "reclaimed_bytes": 0,
    "failed": 0,
}


def _trash_add(**counts: int) -> None:
    with _TRASH_LOCK:
        for name, n in counts.items():
            _TRASH[name] += n


def trash_stats() -> dict:
    """Snapshot of the trash counters; `pending` is what was moved aside
    and has been neither removed nor given up."""
    with _TRASH_LOCK:
        out = dict(_TRASH)
    out["pending"] = out["moved"] - out["reclaimed"] - out["failed"]
    return out


def _tree_bytes(path: str) -> int:
    """Bytes of the regular files under `path` (itself, where it is one);
    a symlink is neither followed nor counted."""
    try:
        st = os.lstat(path)
        if not stat.S_ISDIR(st.st_mode):
            return st.st_size if stat.S_ISREG(st.st_mode) else 0
        return sum(_tree_bytes(os.path.join(path, name)) for name in os.listdir(path))
    except OSError:
        return 0


def _remove_tree(path: str) -> None:
    """`shutil.rmtree` for what a trash holds — a data directory of part
    files, seldom deeper — in half its system calls (a drive's reclaimer
    makes them beside the requests' own): every name is unlinked, which
    removes a file and a symlink alike and never follows one, and only what
    refuses to be unlinked because it is a directory is listed."""
    try:
        os.unlink(path)
        return
    except IsADirectoryError:
        pass
    except PermissionError:  # what some platforms say of a directory
        if os.path.islink(path) or not os.path.isdir(path):
            raise
    for name in os.listdir(path):
        _remove_tree(os.path.join(path, name))
    os.rmdir(path)


_TRASH_IDLE_S = 5.0  # an idle reclaimer thread ends after this long


class TrashReclaimer:
    """One drive's trash, emptied off the request path. `put` hands over
    an entry of the trash directory — it never blocks and starts the
    thread where none runs, so a drive that moves nothing aside has none —
    and the thread removes entry after entry, woken by each `put`. It
    touches nothing but direct children of its trash directory, and
    `_remove_tree` unlinks a symlink where it finds one, never what it
    points to. `stop` lets it finish the entry in hand and ends it; what
    is still queued stays in the directory for the next process."""

    def __init__(self, trash_dir: str):
        self.trash_dir = trash_dir
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._mu = threading.Lock()
        self._thread: threading.Thread | None = None
        self._stopped = False

    def put(self, path: str, nbytes: int) -> None:
        with self._mu:
            if self._stopped:
                return
            self._q.put((path, nbytes))  # never blocks
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="trash-reclaim", daemon=True
                )
                self._thread.start()

    def _run(self) -> None:
        while True:
            try:
                item = self._q.get(timeout=_TRASH_IDLE_S)
            except queue.Empty:
                # nothing for a while: the thread ends, the next `put`
                # starts another (a drive nobody closes leaks none)
                with self._mu:
                    if self._q.empty():
                        self._thread = None
                        return
                continue
            if item is None:
                return
            path, nbytes = item
            with obs.phase("trash", "reclaim"):
                gone = self._remove(path)
            if gone:
                _trash_add(reclaimed=1, reclaimed_bytes=nbytes)
            else:
                _trash_add(failed=1)

    def _remove(self, path: str) -> bool:
        if os.path.dirname(path) != self.trash_dir:
            return False  # not an entry of this drive's trash: not ours
        try:
            _remove_tree(path)
        except OSError:
            # gone all the same (a sibling process adopted it too)?
            return not os.path.lexists(path)
        return True

    def stop(self, timeout: float = 10.0) -> None:
        with self._mu:
            self._stopped = True
            thread, self._thread = self._thread, None
        if thread is not None:
            self._q.put(None)
            thread.join(timeout)

    @property
    def running(self) -> bool:
        with self._mu:
            return self._thread is not None and self._thread.is_alive()


def _clean_rel(path: str) -> str:
    """Reject traversal; normalize an object path to a safe relative path."""
    if path.startswith("/"):
        path = path.lstrip("/")
    norm = os.path.normpath(path) if path else ""
    if norm.startswith("..") or os.path.isabs(norm):
        raise errors.FileAccessDenied(path)
    return "" if norm == "." else norm


class XLStorage(StorageAPI):
    def __init__(self, root: str, endpoint: str = ""):
        self.root = os.path.abspath(root)
        self.endpoint = endpoint or self.root
        self.disk_id = ""
        self._meta_lock = threading.RLock()
        for sysdir in (TMP_DIR, TRASH_DIR, MULTIPART_DIR, BUCKETS_META_DIR):
            os.makedirs(os.path.join(self.root, sysdir), exist_ok=True)
        self.trash = TrashReclaimer(os.path.join(self.root, TRASH_DIR))
        self.empty_trash()  # what a previous process moved aside and left

    # -- path helpers ------------------------------------------------------

    def _vol_path(self, volume: str) -> str:
        # system volumes may be nested (".minio.sys/tmp"), like the
        # reference's minioMetaTmpBucket
        v = _clean_rel(volume)
        if not v:
            raise errors.FileAccessDenied(volume)
        return os.path.join(self.root, v)

    def _file_path(self, volume: str, path: str) -> str:
        return os.path.join(self._vol_path(volume), _clean_rel(path))

    def local_path(self, volume: str, path: str) -> str | None:
        return self._file_path(volume, path)

    def _check_vol(self, volume: str) -> str:
        p = self._vol_path(volume)
        if not os.path.isdir(p):
            raise errors.VolumeNotFound(volume)
        return p

    # -- volumes -----------------------------------------------------------

    def disk_info(self) -> DiskInfo:
        st = os.statvfs(self.root)
        total = st.f_blocks * st.f_frsize
        free = st.f_bavail * st.f_frsize
        return DiskInfo(
            total=total,
            free=free,
            used=total - free,
            used_inodes=max(st.f_files - st.f_ffree, 0),
            free_inodes=st.f_favail,
            fs_type="posix",
            endpoint=self.endpoint,
            mount_path=self.root,
            disk_id=self.disk_id,
        )

    def make_vol(self, volume: str) -> None:
        p = self._vol_path(volume)
        if os.path.isdir(p):
            raise errors.VolumeExists(volume)
        os.makedirs(p, exist_ok=True)

    def list_vols(self) -> list[VolInfo]:
        out = []
        for name in sorted(os.listdir(self.root)):
            full = os.path.join(self.root, name)
            if os.path.isdir(full):
                out.append(VolInfo(name, int(os.stat(full).st_ctime_ns)))
        return out

    def stat_vol(self, volume: str) -> VolInfo:
        p = self._check_vol(volume)
        return VolInfo(_clean_rel(volume), int(os.stat(p).st_ctime_ns))

    def delete_vol(self, volume: str, force: bool = False) -> None:
        p = self._check_vol(volume)
        if force:
            self._to_trash(p)
            return
        try:
            os.rmdir(p)
        except OSError:
            raise errors.VolumeNotEmpty(volume) from None

    # -- xl.meta -----------------------------------------------------------

    def _meta_path(self, volume: str, path: str) -> str:
        return os.path.join(self._file_path(volume, path), META_FILE)

    def _read_meta(self, volume: str, path: str) -> XLMeta:
        try:
            with open(self._meta_path(volume, path), "rb") as f:
                return XLMeta.from_bytes(f.read())
        except FileNotFoundError:
            self._check_vol(volume)
            raise errors.FileNotFound(f"{volume}/{path}") from None

    def _write_meta(self, volume: str, path: str, meta: XLMeta) -> None:
        dst = self._meta_path(volume, path)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        tmp = os.path.join(self.root, TMP_DIR, str(uuid.uuid4()))
        buf = meta.to_bytes()
        with open(tmp, "wb") as f:
            f.write(buf)
            if _FSYNC:
                f.flush()
                os.fsync(f.fileno())
        os.replace(tmp, dst)

    def _trash_replaced_data_dir(self, volume: str, path: str, meta: XLMeta, fi: FileInfo) -> None:
        """When add_version will replace an existing version, its old data
        dir must not leak (reference trashes the destination data path on
        replace, /root/reference/cmd/xl-storage.go RenameData)."""
        idx = meta.find_version(fi.version_id)
        if idx < 0:
            return
        old_ddir = meta.versions[idx]["meta"].get("ddir", "")
        if not old_ddir or old_ddir == fi.data_dir:
            return
        if meta.data_dir_refcount(old_ddir) > 1:
            return
        full = os.path.join(self._file_path(volume, path), old_ddir)
        if os.path.isdir(full):
            self._to_trash(full)

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        self._check_vol(volume)
        with self._meta_lock:
            try:
                meta = self._read_meta(volume, path)
            except errors.FileNotFound:
                meta = XLMeta()
            self._trash_replaced_data_dir(volume, path, meta, fi)
            meta.add_version(fi)
            self._write_meta(volume, path, meta)

    def update_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        """Replace an existing version's record. CAUTION: fi is persisted
        as-is — callers must have read it with read_data=True or an inline
        object's payload would be replaced by the metadata-only marker."""
        with self._meta_lock:
            meta = self._read_meta(volume, path)
            if meta.find_version(fi.version_id) < 0:
                raise errors.FileVersionNotFound(fi.version_id)
            meta.add_version(fi)
            self._write_meta(volume, path, meta)

    def read_version(
        self, volume: str, path: str, version_id: str = "", read_data: bool = False
    ) -> FileInfo:
        meta = self._read_meta(volume, path)
        fi = meta.file_info(version_id)
        fi.volume = volume
        fi.name = path
        if not read_data:
            # callers that only need metadata shouldn't lug inline payloads
            # around, but they do need to know data is inline (empty marker)
            if fi.inline_data is not None:
                fi.inline_data = b"" if len(fi.inline_data) else fi.inline_data
        return fi

    def read_versions(self, volume: str, path: str) -> list[FileInfo]:
        meta = self._read_meta(volume, path)
        out = meta.list_versions()
        for fi in out:
            fi.volume = volume
            fi.name = path
        return out

    def delete_version(self, volume: str, path: str, fi: FileInfo) -> None:
        with self._meta_lock:
            meta = self._read_meta(volume, path)
            removed = meta.delete_version(fi.version_id)
            if removed.data_dir and meta.data_dir_refcount(removed.data_dir) == 0:
                ddir = os.path.join(self._file_path(volume, path), removed.data_dir)
                if os.path.isdir(ddir):
                    self._to_trash(ddir)
            if meta.versions:
                self._write_meta(volume, path, meta)
            else:
                # last version gone: remove xl.meta and prune empty dirs
                obj_dir = self._file_path(volume, path)
                try:
                    os.remove(os.path.join(obj_dir, META_FILE))
                except FileNotFoundError:
                    pass
                self._prune_empty(obj_dir, self._check_vol(volume))

    def delete_versions(
        self, volume: str, path: str, versions: list[FileInfo]
    ) -> list[Exception | None]:
        out: list[Exception | None] = []
        for fi in versions:
            try:
                self.delete_version(volume, path, fi)
                out.append(None)
            except Exception as e:
                out.append(e)
        return out

    # -- data --------------------------------------------------------------

    def rename_data(
        self, src_volume: str, src_path: str, fi: FileInfo, dst_volume: str, dst_path: str
    ) -> None:
        """Atomically move a staged data dir into place + commit the version.

        Mirrors the reference's RenameData (/root/reference/cmd/
        xl-storage.go): shards are written under a tmp uuid dir first; commit
        is rename(tmp/dataDir -> object/dataDir) then xl.meta update.
        """
        self._check_vol(dst_volume)
        src = self._file_path(src_volume, src_path)
        dst_dir = self._file_path(dst_volume, dst_path)
        with self._meta_lock:
            if fi.data_dir:
                _fanout_bump("shard_commits", dst_volume)
                src_data = os.path.join(src, fi.data_dir)
                dst_data = os.path.join(dst_dir, fi.data_dir)
                if not os.path.isdir(src_data):
                    raise errors.FileNotFound(src_data)
                os.makedirs(dst_dir, exist_ok=True)
                if os.path.isdir(dst_data):
                    self._to_trash(dst_data)
                os.replace(src_data, dst_data)
            try:
                meta = self._read_meta(dst_volume, dst_path)
            except errors.FileNotFound:
                meta = XLMeta()
            self._trash_replaced_data_dir(dst_volume, dst_path, meta, fi)
            meta.add_version(fi)
            self._write_meta(dst_volume, dst_path, meta)
            # clean the now-empty staging dir
            shutil.rmtree(src, ignore_errors=True)

    def create_file(self, volume: str, path: str, data: bytes | BinaryIO) -> None:
        _fanout_bump("shard_writes", volume)
        full = self._file_path(volume, path)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        if (
            _ODIRECT
            and isinstance(data, (bytes, bytearray, memoryview))
            and len(data) >= _ODIRECT_MIN
        ):
            if self._create_file_direct(full, data):
                return
        with open(full, "wb") as f:
            if isinstance(data, (bytes, bytearray, memoryview)):
                f.write(data)
            else:
                shutil.copyfileobj(data, f, 1 << 20)
            if _FSYNC:
                f.flush()
                os.fsync(f.fileno())

    @staticmethod
    def _create_file_direct(full: str, data: bytes) -> bool:
        """O_DIRECT shard write: the aligned body bypasses the page cache
        (large sequential shard files would otherwise evict hot data —
        the reference's odirectWriter, cmd/xl-storage.go:316,452-489);
        the unaligned tail lands through a normal buffered append. Returns
        False when the filesystem refuses O_DIRECT (tmpfs etc.) so the
        caller falls back to buffered IO."""
        align = 4096
        view = memoryview(data)
        body = len(data) // align * align
        try:
            fd = os.open(
                full, os.O_WRONLY | os.O_CREAT | os.O_TRUNC | os.O_DIRECT,
                0o644,
            )
        except OSError:
            return False  # filesystem without O_DIRECT support
        try:
            if body:
                import mmap

                # fixed-size page-aligned bounce buffer, reused per chunk:
                # a body-sized buffer (+ slice copies) would triple memory
                # for GiB-scale shards
                chunk = min(body, 4 << 20)
                buf = mmap.mmap(-1, chunk)
                try:
                    off = 0
                    while off < body:
                        n = min(chunk, body - off)
                        buf[:n] = view[off : off + n]
                        w = 0
                        while w < n:
                            w += os.write(fd, memoryview(buf)[w:n])
                        off += n
                finally:
                    buf.close()
        except OSError:
            os.close(fd)
            return False
        else:
            os.close(fd)
        if body < len(data):
            with open(full, "r+b") as f:
                f.seek(body)
                f.write(view[body:])
        if _FSYNC:
            fd2 = os.open(full, os.O_RDONLY)
            try:
                os.fsync(fd2)
            finally:
                os.close(fd2)
        return True

    def append_file(self, volume: str, path: str, data) -> None:
        """Append bytes-like data OR a writev-style sequence of buffers
        (the zero-copy shard-frame vectors: digest/shard views appended
        in one pass, never joined into an intermediate bytes)."""
        _fanout_bump("shard_writes", volume)
        full = self._file_path(volume, path)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        with open(full, "ab") as f:
            if isinstance(data, (bytes, bytearray, memoryview)):
                f.write(data)
            else:
                f.writelines(data)

    def read_file(self, volume: str, path: str, offset: int = 0, length: int = -1) -> bytes:
        _fanout_bump("shard_reads", volume)
        full = self._file_path(volume, path)
        try:
            with open(full, "rb") as f:
                if offset:
                    f.seek(offset)
                return f.read() if length < 0 else f.read(length)
        except FileNotFoundError:
            self._check_vol(volume)
            raise errors.FileNotFound(f"{volume}/{path}") from None
        except IsADirectoryError:
            raise errors.IsNotRegular(path) from None

    def read_file_stream(self, volume: str, path: str, offset: int, length: int) -> BinaryIO:
        _fanout_bump("shard_reads", volume)
        full = self._file_path(volume, path)
        try:
            f = open(full, "rb")
        except FileNotFoundError:
            self._check_vol(volume)
            raise errors.FileNotFound(f"{volume}/{path}") from None
        f.seek(offset)
        return f

    def rename_file(
        self, src_volume: str, src_path: str, dst_volume: str, dst_path: str
    ) -> None:
        src = self._file_path(src_volume, src_path)
        dst = self._file_path(dst_volume, dst_path)
        if not os.path.exists(src):
            raise errors.FileNotFound(src_path)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        os.replace(src, dst)

    def delete(self, volume: str, path: str, recursive: bool = False) -> None:
        full = self._file_path(volume, path)
        if not os.path.exists(full):
            self._check_vol(volume)
            raise errors.FileNotFound(f"{volume}/{path}")
        if os.path.isdir(full):
            if recursive:
                self._to_trash(full)
            else:
                try:
                    os.rmdir(full)
                except OSError:
                    raise errors.VolumeNotEmpty(path) from None
        else:
            os.remove(full)

    # -- listing -----------------------------------------------------------

    def list_dir(self, volume: str, path: str, count: int = -1) -> list[str]:
        """Directory entries, dirs suffixed '/' (mirrors ListDir RPC)."""
        full = self._file_path(volume, path)
        try:
            names = sorted(os.listdir(full))
        except FileNotFoundError:
            self._check_vol(volume)
            raise errors.FileNotFound(f"{volume}/{path}") from None
        out = []
        for n in names:
            if os.path.isdir(os.path.join(full, n)):
                out.append(n + "/")
            else:
                out.append(n)
            if 0 <= count <= len(out):
                break
        return out

    def walk_dir(self, volume: str, base: str = "") -> Iterator[str]:
        """Yield object paths (dirs containing xl.meta) under base, sorted
        so DECODED keys come out in order (dir markers before their subtree)
        — the per-drive feed of distributed listing
        (/root/reference/cmd/metacache-walk.go:73)."""
        from .pathutil import walk_sort_key

        vol_path = self._check_vol(volume)
        base_rel = _clean_rel(base)
        start = os.path.join(vol_path, base_rel) if base_rel else vol_path

        def walk(dir_path: str, rel: str) -> Iterator[str]:
            try:
                names = os.listdir(dir_path)
            except (FileNotFoundError, NotADirectoryError):
                return
            if META_FILE in names and rel:
                yield rel
            entries = []
            for n in names:
                if n == META_FILE:
                    continue
                is_dir = os.path.isdir(os.path.join(dir_path, n))
                entries.append((walk_sort_key(n, is_dir), n, is_dir))
            entries.sort()
            for _, n, is_dir in entries:
                if is_dir:
                    yield from walk(
                        os.path.join(dir_path, n), f"{rel}/{n}" if rel else n
                    )

        yield from walk(start, base_rel)

    def stat_info_file(self, volume: str, path: str) -> int:
        full = self._file_path(volume, path)
        try:
            return os.stat(full).st_size
        except FileNotFoundError:
            raise errors.FileNotFound(f"{volume}/{path}") from None

    # -- integrity ---------------------------------------------------------

    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        """Streaming-bitrot verify of all parts of a version on this drive
        (mirrors /root/reference/cmd/bitrot.go:164 bitrotVerify)."""
        from ..erasure.bitrot_io import bitrot_verify_file  # local import: avoid cycle

        if fi.inline_data is not None:
            return
        shard_size = fi.erasure.shard_size()
        for part in fi.parts:
            part_path = os.path.join(
                self._file_path(volume, path), fi.data_dir, f"part.{part.number}"
            )
            wh = next(
                (c for c in fi.erasure.checksums
                 if c.part_number == part.number and c.hash), None,
            )
            if wh is not None:
                # legacy whole-file bitrot: raw shard on disk, digest in
                # the metadata (/root/reference/cmd/bitrot-whole.go), hashed
                # with the STORED algorithm (legacy may be sha256/blake2b)
                from ..erasure.bitrot_io import verify_whole_file
                from ..ops.bitrot import algorithm_from_string

                expect = fi.erasure.shard_file_size(part.size)
                try:
                    with open(part_path, "rb") as f:
                        data = f.read()
                except FileNotFoundError:
                    raise errors.FileNotFound(part_path) from None
                if len(data) != expect:
                    raise errors.FileCorrupt(
                        f"whole-file shard size {len(data)} != {expect}"
                    )
                verify_whole_file(data, wh.hash, algorithm_from_string(wh.algorithm))
                continue
            bitrot_verify_file(
                part_path,
                fi.erasure.shard_file_size(part.size),
                shard_size,
                family=fi.erasure.algorithm or "reedsolomon",
            )

    # -- trash -------------------------------------------------------------

    def _to_trash(self, full_path: str) -> None:
        """Rename `full_path` into the trash — all the caller waits for —
        and wake the reclaimer, which removes it."""
        dst = os.path.join(self.trash.trash_dir, str(uuid.uuid4()))
        try:
            os.replace(full_path, dst)
        except OSError:
            shutil.rmtree(full_path, ignore_errors=True)
            return
        self._reclaim(dst)

    def _reclaim(self, entry: str) -> None:
        nbytes = _tree_bytes(entry)
        _trash_add(moved=1, moved_bytes=nbytes)
        self.trash.put(entry, nbytes)

    def empty_trash(self) -> None:
        """Hand every entry that lies in the trash directory to the
        reclaimer: at construction, what a previous process left there."""
        try:
            names = os.listdir(self.trash.trash_dir)
        except OSError:
            return
        for name in names:
            self._reclaim(os.path.join(self.trash.trash_dir, name))

    def close(self) -> None:
        self.trash.stop()

    def _prune_empty(self, dir_path: str, stop_at: str) -> None:
        """Remove empty parent dirs up to (not incl.) the volume root."""
        cur = dir_path
        while cur != stop_at and cur.startswith(self.root):
            try:
                os.rmdir(cur)
            except OSError:
                return
            cur = os.path.dirname(cur)
