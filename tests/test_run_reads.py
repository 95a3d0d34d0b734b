"""The reconstructing read path plans a window's reads once, as runs: one
`read_file` of each of the d shards a run decodes from covers the run's
consecutive frames, the stand-in parity among them, all submitted before the
window is waited on, none to an offline drive, every frame verified. Counted
on in-process sets whose drives record what is asked of them; the numpy
backend, small objects, no sleeps but the one straggler."""

import os
import shutil
import sys
import time

os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from minio_tpu import fault, native, obs  # noqa: E402
from minio_tpu.erasure import bitrot_io  # noqa: E402
from minio_tpu.erasure import set as es_mod  # noqa: E402
from minio_tpu.erasure.multipart import MultipartManager  # noqa: E402
from minio_tpu.erasure.quorum import ErasureError  # noqa: E402
from minio_tpu.erasure.set import ErasureSet  # noqa: E402
from minio_tpu.fault.storage import FaultInjectedDisk  # noqa: E402
from minio_tpu.ops.highwayhash import MINIO_KEY  # noqa: E402
from minio_tpu.storage import errors  # noqa: E402
from minio_tpu.storage.xlstorage import XLStorage  # noqa: E402
from minio_tpu.utils.hashing import hash_order  # noqa: E402

MIB, DIG = 1 << 20, 32
BUCKET = "runs"


# --------------------------------------------------------------------------
# the frames' verify, alone
# --------------------------------------------------------------------------


def framed(blocks) -> bytes:
    return b"".join(bitrot_io.frame_block(b) for b in blocks)


def blocks_of(lens, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.bytes(n) for n in lens]


LENS = {"eight-equal": [4096] * 8, "short-tail": [4096] * 3 + [1234], "one": [777],
        "tail-only": [5], "odd": [33, 33, 31, 64, 64, 1]}


@pytest.mark.parametrize("view", [False, True], ids=["bytes", "views"])
@pytest.mark.parametrize("no_native", [False, True], ids=["native", "python"])
@pytest.mark.parametrize("lens", LENS.values(), ids=LENS.keys())
def test_verify_run_returns_every_payload(lens, no_native, view, monkeypatch):
    if no_native:
        monkeypatch.setattr(native, "available", lambda: False)
    blks = blocks_of(lens)
    out = bitrot_io.verify_run(framed(blks), lens, view=view)
    assert [bytes(b) for b in out] == blks
    assert all(isinstance(b, memoryview if view else bytes) for b in out)
    if len(lens) == 1:
        assert bytes(out[0]) == bitrot_io.verify_block(framed(blks), lens[0])


@pytest.mark.parametrize("no_native", [False, True], ids=["native", "python"])
@pytest.mark.parametrize("where", ["payload", "digest"])
@pytest.mark.parametrize("frame", [0, 2, 3])
def test_verify_run_refuses_a_run_with_one_bad_frame(frame, where, no_native, monkeypatch):
    if no_native:
        monkeypatch.setattr(native, "available", lambda: False)
    lens = LENS["short-tail"]
    buf = bytearray(framed(blocks_of(lens)))
    at = sum(DIG + n for n in lens[:frame]) + (DIG + 7 if where == "payload" else 3)
    buf[at] ^= 0x01
    with pytest.raises(errors.FileCorrupt, match=f"frame {frame} of run"):
        bitrot_io.verify_run(bytes(buf), lens)


def test_verify_run_refuses_a_short_or_long_read():
    lens = LENS["eight-equal"]
    buf = framed(blocks_of(lens))
    for spoiled in (buf[:-1], buf + b"\0", b""):
        with pytest.raises(errors.FileCorrupt, match="short shard run"):
            bitrot_io.verify_run(spoiled, lens)


def test_hh256_frames_hashes_in_place_and_stays_inside_the_buffer():
    lens = [4096] * 5
    blks = blocks_of(lens, seed=9)
    flat = np.frombuffer(framed(blks), dtype=np.uint8)
    digs = native.hh256_frames(MINIO_KEY, flat, DIG, DIG + 4096, 4096, 5)
    assert [d.tobytes() for d in digs] == [native.hh256(MINIO_KEY, b) for b in blks]
    for first, count in ((DIG, 6), (-1, 1), (DIG + 1, 5), (DIG, 0)):
        with pytest.raises(ValueError):
            native.hh256_frames(MINIO_KEY, flat, first, DIG + 4096, 4096, count)


# --------------------------------------------------------------------------
# a set whose drives record their reads
# --------------------------------------------------------------------------


class Recorded:
    """A drive that notes every `read_file` asked of it and, `offline`, fails
    every call as a drive out of reach does."""

    def __init__(self, inner):
        self._inner = inner
        self.offline = False
        self.reads: list[tuple[str, int, int]] = []
        self.reads_ended = 0
        self.fail_reads_after: int | None = None

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if not callable(attr) or name.startswith("_"):
            return attr

        def call(*a, **kw):
            if name == "read_file" and "/part." in a[1]:
                self.reads.append((a[1].rsplit("/", 1)[1], a[2], a[3]))
            if self.offline:
                raise errors.DiskNotFound(self._inner.endpoint)
            if (name == "read_file" and self.fail_reads_after is not None
                    and len(self.reads) > self.fail_reads_after):
                raise OSError("drive failed mid-read")
            try:
                return attr(*a, **kw)
            finally:
                if name == "read_file" and "/part." in a[1]:
                    self.reads_ended += 1

        return call


class CountingPool:
    """The read pool, counting the shard reads submitted to it."""

    def __init__(self, inner):
        self._inner = inner
        self.reads = 0

    def submit(self, fn, *a, **kw):
        if getattr(fn, "__name__", "") == "read_shard_run":
            self.reads += 1
        return self._inner.submit(fn, *a, **kw)


GEOMETRIES = {"8+8": 8, "12+4": 4}
TAIL = 10 * MIB + 12345          # 11 blocks, the last one short
PARTS = (5 * MIB + 777, 3 * MIB + 1)  # 6 + 4 blocks: part 1 ends inside window 0


class Rig:
    def __init__(self, base, parity):
        self.disks = [Recorded(XLStorage(str(base / f"d{i:02d}"))) for i in range(16)]
        self.es = ErasureSet(self.disks, default_parity=parity)
        self.d = 16 - parity
        self.es.make_bucket(BUCKET)
        rng = np.random.default_rng([30, parity])
        self.bodies = {"tail": rng.bytes(TAIL)}
        self.es.put_object(BUCKET, "tail", self.bodies["tail"])
        mp = MultipartManager(self.es)
        up = mp.new_upload(BUCKET, "multipart", {})
        parts = [rng.bytes(n) for n in PARTS]
        etags = [mp.put_part(BUCKET, "multipart", up, i + 1, p) for i, p in enumerate(parts)]
        mp.complete(BUCKET, "multipart", up, [(i + 1, e) for i, e in enumerate(etags)])
        self.bodies["multipart"] = b"".join(parts)
        self.part_sizes = {"tail": [TAIL], "multipart": list(PARTS)}
        self.degraded: list[tuple[str, str]] = []
        self.es.on_degraded = lambda b, o: self.degraded.append((b, o))

    def drive_of(self, key: str, shard: int) -> int:
        return hash_order(f"{BUCKET}/{key}", 16).index(shard + 1)

    def reset(self, key: str = "", offline_shards=()):
        for dk in self.disks:
            dk.offline, dk.fail_reads_after = False, None
        for s in offline_shards:
            self.disks[self.drive_of(key, s)].offline = True
        self.es.cache.clear()
        for dk in self.disks:
            dk.reads.clear()
        self.degraded.clear()


@pytest.fixture(scope="module", params=GEOMETRIES.items(), ids=GEOMETRIES.keys())
def rig(request, tmp_path_factory):
    mp = pytest.MonkeyPatch()
    # the native span path reads healthy local data shards itself; every
    # case here is to take the reconstructing path. No hedge: counts are exact
    mp.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    mp.setenv("MINIO_TPU_HEDGE", "0")
    mp.delenv("MINIO_TPU_EC_FAMILY", raising=False)
    mp.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    try:
        yield Rig(tmp_path_factory.mktemp("runs-" + request.param[0].replace("+", "p")),
                  request.param[1])
    finally:
        mp.undo()


def plan_of(part_sizes, per_block, offset, length):
    """(part#, block# in the part, shard bytes of the block) of every stripe
    block [offset, offset + length) touches."""
    out, pos = [], 0
    for pn, size in enumerate(part_sizes, 1):
        for bi in range(-(-size // MIB)):
            n = min(MIB, size - bi * MIB)
            if pos + n > offset and pos < offset + length:
                out.append((pn, bi, -(-n // per_block[0])))
            pos += n
    return out


def runs_of(plan, window):
    """Per window: its runs, each (part#, first block#, [shard bytes...])."""
    wins = []
    for i in range(0, len(plan), window):
        runs = []
        for pn, bi, per in plan[i:i + window]:
            if runs and runs[-1][0] == pn and runs[-1][1] + len(runs[-1][2]) == bi:
                runs[-1][2].append(per)
            else:
                runs.append((pn, bi, [per]))
        wins.append(runs)
    return wins


def expected_reads(wins, shard_size):
    """(file, offset, length) of the one read a shard sees for each run."""
    return sorted(
        (f"part.{pn}", bi * (DIG + shard_size), sum(DIG + p for p in pers))
        for runs in wins for pn, bi, pers in runs
    )


def counts():
    return obs.phases_snapshot()["get", "shard_io"][2], es_mod.shard_frames_snapshot()


# shards offline, by erasure index: data shards are 0..d-1
OFFLINE = {
    "8+8": {"one-data": (2,), "data-and-parity": (3, 11), "p-of-them": (0, 1, 4, 7, 8, 9, 12, 15)},
    "12+4": {"one-data": (5,), "data-and-parity": (0, 13), "p-of-them": (1, 6, 11, 14)},
}


@pytest.mark.parametrize("window", [1, 8])
@pytest.mark.parametrize("key", ["tail", "multipart"])
@pytest.mark.parametrize("offline", ["one-data", "data-and-parity", "p-of-them"])
def test_a_window_is_d_run_reads_all_readahead_none_to_an_offline_drive(
        rig, offline, key, window, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", str(window))
    geometry = f"{rig.d}+{16 - rig.d}"
    gone = OFFLINE[geometry][offline]
    rig.reset(key, gone)
    pool = CountingPool(es_mod._read_pool())
    monkeypatch.setattr(es_mod, "_READ_POOL", pool)
    body, d = rig.bodies[key], rig.d
    shard_size = -(-MIB // d)
    wins = runs_of(plan_of(rig.part_sizes[key], [d], 0, len(body)), window)
    per_window = [d * len(runs) for runs in wins]
    calls0, frames0 = counts()
    _, it = rig.es.get_object(BUCKET, key)
    got, at_block = [], 0
    for wi, runs in enumerate(wins):
        for _ in range(sum(len(r[2]) for r in runs)):
            got.append(bytes(next(it)))
            at_block += 1
            # a window's pieces come out once the NEXT window's reads are in
            # the pool, every one of them, the stand-in parity too
            assert pool.reads == sum(per_window[:wi + 2]), (wi, at_block)
    assert next(it, None) is None and b"".join(got) == body
    calls1, frames1 = counts()
    # reads per window = d per run, by the pool, the phase table and the drives
    assert pool.reads == calls1 - calls0 == sum(per_window)
    in_runs = d * sum(len(r[2]) for runs in wins for r in runs if len(r[2]) > 1)
    in_blocks = d * sum(len(r[2]) for runs in wins for r in runs if len(r[2]) == 1)
    assert frames1["run"] - frames0["run"] == in_runs
    assert frames1["block"] - frames0["block"] == in_blocks
    if window == 1:
        assert in_runs == 0
    want = expected_reads(wins, shard_size)
    dist = hash_order(f"{BUCKET}/{key}", 16)
    # the d shards a run decodes from: the data shards that are there, then
    # the lowest parity shards
    picked = [s for s in range(16) if s not in gone][:d]
    for i, dk in enumerate(rig.disks):
        shard = dist[i] - 1
        if shard in gone:
            assert dk.reads == [], f"offline drive {i} was asked for shard bytes"
        elif shard in picked:
            assert sorted(dk.reads) == want, (i, shard)
        else:
            assert dk.reads == [], f"drive {i} (shard {shard}) was read for nothing"
    assert rig.degraded == [(BUCKET, key)]  # a drive lacks this version: heal hint


RANGES = {
    # (offset, length): starts and ends mid-block and mid-window
    "inside-one-block": (3 * MIB + 17, 1000),
    "mid-block-to-mid-block": (MIB // 2, 2 * MIB),
    "across-the-window-edge": (7 * MIB + 5, MIB),
    "from-mid-window-to-the-end": (5 * MIB - 1, None),
    "last-three-bytes": (-3, 3),
    "first-byte": (0, 1),
}


@pytest.mark.parametrize("window", [1, 8])
@pytest.mark.parametrize("key", ["tail", "multipart"])
@pytest.mark.parametrize("rng_id", RANGES.keys())
def test_a_ranged_degraded_get_reads_the_runs_its_range_touches(
        rig, rng_id, key, window, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", str(window))
    gone = OFFLINE[f"{rig.d}+{16 - rig.d}"]["data-and-parity"]
    rig.reset(key, gone)
    body, d = rig.bodies[key], rig.d
    off, ln = RANGES[rng_id]
    off = off if off >= 0 else len(body) + off
    ln = ln if ln is not None else len(body) - off
    wins = runs_of(plan_of(rig.part_sizes[key], [d], off, ln), window)
    calls0, frames0 = counts()
    _, it = rig.es.get_object(BUCKET, key, offset=off, length=ln)
    assert b"".join(bytes(p) for p in it) == body[off:off + ln]
    calls1, frames1 = counts()
    assert calls1 - calls0 == d * sum(len(runs) for runs in wins)
    blocks = sum(len(r[2]) for runs in wins for r in runs)
    assert sum(frames1.values()) - sum(frames0.values()) == d * blocks
    want = expected_reads(wins, -(-MIB // d))
    dist = hash_order(f"{BUCKET}/{key}", 16)
    picked = [s for s in range(16) if s not in gone][:d]
    for i, dk in enumerate(rig.disks):
        assert sorted(dk.reads) == (want if dist[i] - 1 in picked else []), i


# --------------------------------------------------------------------------
# failures and stragglers, at the run's grain
# --------------------------------------------------------------------------


def shard_file(rig, key, shard, part=1):
    drive = rig.disks[rig.drive_of(key, shard)]
    fi = drive.read_version(BUCKET, key)
    return drive.local_path(BUCKET, f"{key}/{fi.data_dir}/part.{part}")


@pytest.mark.parametrize("frame", [1, 9], ids=["in-window-0", "in-window-1"])
def test_one_flipped_byte_spills_that_shard_for_its_runs_blocks_only(rig, frame, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", "8")
    rig.reset("tail", (3,))
    d = rig.d
    shard_size = -(-MIB // d)
    victim = 1  # a data shard on a drive that is there
    path = shard_file(rig, "tail", victim)
    with open(path, "rb") as f:
        orig = f.read()
    spoiled = bytearray(orig)
    spoiled[frame * (DIG + shard_size) + DIG + 40] ^= 0x01
    with open(path, "wb") as f:
        f.write(spoiled)
    try:
        rig.es.cache.clear()
        calls0, frames0 = counts()
        _, it = rig.es.get_object(BUCKET, "tail")
        assert b"".join(bytes(p) for p in it) == rig.bodies["tail"]
        calls1, _ = counts()
    finally:
        with open(path, "wb") as f:
            f.write(orig)
    assert (BUCKET, "tail") in rig.degraded
    wins = runs_of(plan_of([TAIL], [d], 0, TAIL), 8)
    assert [len(w) for w in wins] == [1, 1]
    # one more read than d a run: the bad run's blocks from the next shard
    assert calls1 - calls0 == 2 * d + 1
    runs = expected_reads(wins, shard_size)
    bad_win = frame // 8
    victim_reads = sorted(rig.disks[rig.drive_of("tail", victim)].reads)
    # the shard is read up to the window that finds it bad, and never after
    assert victim_reads == runs[:bad_win + 1]
    # stand-in for the offline data shard from the start: the lowest parity;
    # the next one takes over the bad shard's run and every later window
    first_parity, second_parity = d, d + 1
    assert sorted(rig.disks[rig.drive_of("tail", first_parity)].reads) == runs
    assert sorted(rig.disks[rig.drive_of("tail", second_parity)].reads) == runs[bad_win:]
    for other in range(d + 2, 16):
        assert rig.disks[rig.drive_of("tail", other)].reads == []


def test_a_drive_that_fails_mid_get_is_read_around(rig, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", "2")
    rig.reset("tail", (0,))
    dying = rig.disks[rig.drive_of("tail", 2)]
    dying.fail_reads_after = 2  # two windows answered, then it raises
    _, it = rig.es.get_object(BUCKET, "tail")
    assert b"".join(bytes(p) for p in it) == rig.bodies["tail"]
    assert (BUCKET, "tail") in rig.degraded
    assert len(dying.reads) == 3  # the third raised; marked bad, not asked again
    # every window after it decodes from one more parity shard
    takeover = rig.disks[rig.drive_of("tail", rig.d + 1)]
    assert len(takeover.reads) == -(-11 // 2) - 2


def test_more_shards_gone_than_parity_fails_and_reads_no_offline_drive(rig):
    gone = tuple(range(16 - rig.d + 1))
    rig.reset("tail", gone)
    # the metadata read already lacks its quorum: a typed error of either layer
    with pytest.raises((ErasureError, errors.StorageError)):
        _, it = rig.es.get_object(BUCKET, "tail")
        b"".join(bytes(p) for p in it)
    for s in gone:
        assert rig.disks[rig.drive_of("tail", s)].reads == []


def test_a_straggler_past_the_hedge_budget_is_raced_by_run_reads(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_HEDGE_MIN_MS", "40")
    monkeypatch.setenv("MINIO_TPU_READ_WINDOW", "8")
    monkeypatch.delenv("MINIO_TPU_HEDGE", raising=False)
    disks = [Recorded(FaultInjectedDisk(XLStorage(str(tmp_path / f"h{i:02d}"))))
             for i in range(16)]
    es = ErasureSet(disks, default_parity=8)
    es.make_bucket(BUCKET)
    body = np.random.default_rng(31).bytes(10 * MIB)
    es.put_object(BUCKET, "slow", body)
    dist = hash_order(f"{BUCKET}/slow", 16)
    straggler = disks[dist.index(1)]
    fault.clear()
    # long against a GET of some 0.15 s, so that a loaded host still returns first
    fault.inject({"boundary": "storage", "mode": "latency", "latency_ms": 1500,
                  "target": straggler.endpoint, "op": "read_file", "seed": 3})
    before = dict(fault.status()["counters"])
    calls0, frames0 = counts()
    try:
        _, it = es.get_object(BUCKET, "slow")
        assert b"".join(bytes(p) for p in it) == body
        # two windows behind a 1.5 s drive took less than one of its reads:
        # both were asked for, and neither had come back
        assert (len(straggler.reads), straggler.reads_ended) == (2, 0)
    finally:
        fault.clear()
    after = fault.status()["counters"]
    fired = after["hedge_reads"] - before.get("hedge_reads", 0)
    assert fired >= 2  # at least once a window
    assert after["hedge_wins"] - before.get("hedge_wins", 0) == fired  # every one won
    # the race is one run read of the next parity shard for each window
    hedged = disks[dist.index(9)]
    assert sorted(hedged.reads) == [("part.1", 0, 8 * (DIG + 131072)),
                                    ("part.1", 8 * (DIG + 131072), 2 * (DIG + 131072))]
    # the straggler's two reads were running, so no cancel reaches them: they
    # end after the GET (and would be counted into a later test's reads)
    deadline = time.monotonic() + 5.0
    while counts()[0] - calls0 < 2 * 8 + 2 and time.monotonic() < deadline:
        time.sleep(0.02)
    calls1, frames1 = counts()
    assert calls1 - calls0 == 2 * 8 + 2
    assert frames1["block"] == frames0["block"]


# --------------------------------------------------------------------------
# formats whose frames are not `digest || block` on a drive: runs of one
# --------------------------------------------------------------------------


def small_set(base, n=8, parity=4):
    disks = [XLStorage(str(base / f"s{i}")) for i in range(n)]
    es = ErasureSet(disks, default_parity=parity)
    es.make_bucket(BUCKET)
    return es


@pytest.mark.parametrize("kind", ["inline", "whole-file", "cauchy", "cauchy-two-gone",
                                  "reedsolomon"])
def test_run_length_by_what_the_read_finds(kind, tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    if kind.startswith("cauchy"):
        monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")
    else:
        monkeypatch.delenv("MINIO_TPU_EC_FAMILY", raising=False)
    es = small_set(tmp_path)
    body = np.random.default_rng(32).bytes(2000 if kind == "inline" else 5 * MIB + 99)
    es.put_object(BUCKET, "obj", body)
    if kind == "whole-file":
        from test_whole_file_bitrot import _to_whole_file

        _to_whole_file(es, BUCKET, "obj")
    dist = hash_order(f"{BUCKET}/obj", 8)
    for shard in {"cauchy-two-gone": (0, 1)}.get(kind, (0,) if kind != "inline" else ()):
        shutil.rmtree(os.path.join(es.disks[dist.index(shard + 1)].root, BUCKET, "obj"))
    es.cache.clear()
    calls0, frames0 = counts()
    _, it = es.get_object(BUCKET, "obj")
    assert b"".join(bytes(p) for p in it) == body
    calls1, frames1 = counts()
    run, block = (frames1[u] - frames0[u] for u in ("run", "block"))
    reads = calls1 - calls0
    if kind == "reedsolomon":
        # the control: 6 blocks in one window, 4 shards, one read each
        assert (run, block, reads) == (4 * 6, 0, 4)
    else:
        assert run == 0 and block >= 1 and reads >= block
        if kind in ("whole-file", "cauchy-two-gone"):
            assert block == 4 * 6  # d frames a block, a read each


def test_the_counter_and_the_phase_table_give_run_length_and_reads_per_get(tmp_path, monkeypatch):
    """What docs/OBSERVABILITY.md says a scrape of `/api/tpu` tells."""
    monkeypatch.setenv("MINIO_TPU_HEDGE", "0")
    monkeypatch.delenv("MINIO_TPU_EC_FAMILY", raising=False)
    from minio_tpu.server.metrics import _g_api_tpu

    def scrape():
        rows = {}
        for line in _g_api_tpu(None):
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                rows[name] = float(value)
        return rows

    disks = [XLStorage(str(tmp_path / f"m{i:02d}")) for i in range(16)]
    es = ErasureSet(disks, default_parity=8)
    es.make_bucket(BUCKET)
    body = np.random.default_rng(33).bytes(16 * MIB)
    es.put_object(BUCKET, "obj", body)
    dist = hash_order(f"{BUCKET}/obj", 16)
    for shard in (3, 11):
        shutil.rmtree(os.path.join(disks[dist.index(shard + 1)].root, BUCKET, "obj"))
    es.cache.clear()
    first = scrape()
    for unit in ("run", "block"):
        assert f'minio_tpu_get_shard_frames_total{{unit="{unit}"}}' in first
    for _ in range(3):
        _, it = es.get_object(BUCKET, "obj")
        assert b"".join(bytes(p) for p in it) == body
    last = scrape()

    def moved(name):
        return last[name] - first[name]

    frames = moved('minio_tpu_get_shard_frames_total{unit="run"}') \
        + moved('minio_tpu_get_shard_frames_total{unit="block"}')
    reads = moved('minio_tpu_phase_calls_total{layer="get",phase="shard_io"}')
    gets = moved('minio_tpu_phase_calls_total{layer="get",phase="start"}')
    assert gets == 3
    assert frames / reads == 8.0   # run length: the window's 8 blocks in one read
    assert reads / gets == 16.0    # reads per GET: 2 windows x d = 8 shards
    assert moved('minio_tpu_get_shard_frames_total{unit="block"}') == 0
