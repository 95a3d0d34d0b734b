"""erasure/shardread.py without an ErasureSet: the shard reader over a drive
that records every `read_file(volume, path, offset, length)` asked of it, and
the repair-plan executor over reads that are plain functions."""

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")

import numpy as np
import pytest

from minio_tpu import fault
from minio_tpu.erasure import bitrot_io, shardread
from minio_tpu.erasure.coder import ErasureCoder
from minio_tpu.erasure.quorum import QuorumError
from minio_tpu.ops.bitrot import DEFAULT_BITROT_ALGO
from minio_tpu.storage import errors
from minio_tpu.storage.datatypes import ChecksumInfo, ErasureInfo, FileInfo

DIG = 32
PER = 4096  # shard bytes of a full block: 4 data shards of a 16 KiB block


class Drive:
    """Holds shard files by path; notes every read."""

    def __init__(self, files: dict[str, bytes], cut: int = 0):
        self.files, self.cut = files, cut
        self.reads: list[tuple[str, str, int, int]] = []
        self._mu = threading.Lock()

    def read_file(self, volume, path, off, n):
        with self._mu:
            self.reads.append((volume, path, off, n))
        data = self.files[path]
        out = data[off:] if n < 0 else data[off : off + n]
        return out[: len(out) - self.cut]


def coder_of(family="reedsolomon") -> ErasureCoder:
    return ErasureCoder(4, 2, block_size=4 * PER, family=family)


def reader_of(shard_file: bytes, family="reedsolomon", meta=None, cut=0, **kw):
    """A reader whose shard 0 is `shard_file` on a recording drive."""
    fi = FileInfo(volume="bkt", name="obj", data_dir="dd")
    drive = Drive({"obj/dd/part.1": shard_file}, cut=cut)
    m = meta or FileInfo(erasure=ErasureInfo(algorithm=family))
    rd = shardread.ShardReader(
        "bkt", "obj", fi, coder_of(family), {0: (drive, m)}, **kw
    )
    return rd, drive


def blocks_of(lens, seed=3) -> list[bytes]:
    rng = np.random.default_rng(seed)
    return [rng.bytes(n) for n in lens]


def framed(blks, family="reedsolomon") -> bytes:
    return b"".join(bitrot_io.frame_block(b, family) for b in blks)


# --------------------------------------------------------------------------
# ShardReader
# --------------------------------------------------------------------------


@pytest.mark.parametrize("view", [False, True], ids=["bytes", "views"])
def test_a_run_of_eight_frames_is_one_read(view):
    blks = blocks_of([PER] * 10)
    rd, drive = reader_of(framed(blks), view=view)
    out = rd.run(1, 0, (PER,) * 8, 2 * (DIG + PER))
    assert drive.reads == [("bkt", "obj/dd/part.1", 2 * (DIG + PER), 8 * (DIG + PER))]
    assert [bytes(b) for b in out] == blks[2:]
    assert all(isinstance(b, memoryview if view else bytes) for b in out)
    # a block is a run of one
    assert bytes(rd.block(1, 0, PER, 0)) == blks[0]
    assert drive.reads[-1] == ("bkt", "obj/dd/part.1", 0, DIG + PER)


def test_a_flipped_byte_in_one_frame_refuses_the_whole_run():
    buf = bytearray(framed(blocks_of([PER] * 8)))
    buf[5 * (DIG + PER) + DIG + 17] ^= 0x40
    rd, drive = reader_of(bytes(buf))
    with pytest.raises(errors.FileCorrupt, match="frame 5 of run"):
        rd.run(1, 0, (PER,) * 8, 0)
    assert len(drive.reads) == 1


def test_a_short_read_raises():
    rd, _ = reader_of(framed(blocks_of([PER] * 8)), cut=1)
    with pytest.raises(errors.FileCorrupt, match="short shard run"):
        rd.run(1, 0, (PER,) * 8, 0)
    with pytest.raises(errors.FileCorrupt, match="short sub-chunk"):
        reader_of(framed(blocks_of([PER]), "cauchy"), "cauchy", cut=1)[0].sub_chunk(
            1, 0, PER, 0, 1)


def test_an_inline_part_is_sliced_from_the_metadata_and_no_drive_is_read():
    blks = blocks_of([PER, PER, 1000])
    m = FileInfo(inline_data=framed(blks), erasure=ErasureInfo())
    seen = []
    rd, drive = reader_of(b"", meta=m, on_bytes=seen.append)
    assert bytes(rd.block(1, 0, 1000, 2 * (DIG + PER))) == blks[2]
    assert rd.run(1, 0, (PER, PER), 0) == blks[:2]
    assert drive.reads == [] and seen == [DIG + 1000, 2 * (DIG + PER)]


def test_a_whole_file_shard_is_read_and_hashed_once_by_racing_threads(monkeypatch):
    raw = b"".join(blocks_of([PER, PER, 1234]))
    m = FileInfo(erasure=ErasureInfo(checksums=[ChecksumInfo(
        1, DEFAULT_BITROT_ALGO.string, bitrot_io.whole_file_digest(raw))]))
    seen = []
    rd, drive = reader_of(raw, meta=m, on_bytes=seen.append)
    hashed = []
    verify = bitrot_io.verify_whole_file

    def counting(data, *a):
        hashed.append(len(data))
        time.sleep(0.05)  # hold the load open while the others arrive
        return verify(data, *a)

    monkeypatch.setattr(bitrot_io, "verify_whole_file", counting)
    gate = threading.Barrier(8)

    def one(k):
        gate.wait(timeout=10)
        block_i = k % 3
        per = PER if block_i < 2 else 1234
        return block_i, rd.block(1, 0, per, block_i * (DIG + PER))

    with ThreadPoolExecutor(8) as tp:
        got = list(tp.map(one, range(8)))
    for block_i, blk in got:
        assert bytes(blk) == raw[block_i * PER:][:PER]
    assert drive.reads == [("bkt", "obj/dd/part.1", 0, -1)]
    assert hashed == [len(raw)] and seen == [len(raw)]
    # a block past the file's end is a short shard, not an empty payload
    with pytest.raises(errors.FileCorrupt, match="short whole-file"):
        rd.block(1, 0, PER, 3 * (DIG + PER))


def test_a_cauchy_block_is_two_sub_frames_and_a_sub_chunk_reads_one():
    blks = blocks_of([PER, 1001])
    file = framed(blks, "cauchy")
    seen = []
    rd, drive = reader_of(file, "cauchy", on_bytes=seen.append)
    f1 = 2 * DIG + PER  # the tail block's frame group
    assert bytes(rd.block(1, 0, 1001, f1)) == blks[1]
    h1, h2 = bitrot_io.sub_lens(1001)
    sub = rd.sub_chunk(1, 0, 1001, f1, 1)
    assert sub.dtype == np.uint8 and sub.tobytes() == blks[1][h1:]
    assert rd.sub_chunk(1, 0, PER, 0, 0).tobytes() == blks[0][: PER // 2]
    assert drive.reads == [
        ("bkt", "obj/dd/part.1", f1, 2 * DIG + 1001),
        ("bkt", "obj/dd/part.1", f1 + DIG + h1, DIG + h2),
        ("bkt", "obj/dd/part.1", 0, DIG + PER // 2),
    ]
    # on_bytes: exactly the bytes the drive returned, read by read
    assert seen == [n for _v, _p, _o, n in drive.reads]
    # bitrot in the half that is not read goes unseen; in the read half not
    bad = bytearray(file)
    bad[f1 + DIG + 3] ^= 1
    rd2, _ = reader_of(bytes(bad), "cauchy")
    assert rd2.sub_chunk(1, 0, 1001, f1, 1).tobytes() == blks[1][h1:]
    with pytest.raises(errors.FileCorrupt, match="sub-chunk"):
        rd2.sub_chunk(1, 0, 1001, f1, 0)


# --------------------------------------------------------------------------
# run_repair_plan
# --------------------------------------------------------------------------


class Reads:
    """The executor's two reads as functions of (shard index, frame offset):
    what they return says who was asked; `fail` and `slow` name the reads
    that raise or stall."""

    def __init__(self, fail=(), slow=(), slow_s=0.0):
        self.fail, self.slow, self.slow_s = set(fail), set(slow), slow_s
        self.asked: list[tuple] = []
        self.slow_done = threading.Event()
        self._mu = threading.Lock()

    def _read(self, kind, idx, f_off):
        with self._mu:
            self.asked.append((kind, idx, f_off))
        if (kind, idx, f_off) in self.slow:
            time.sleep(self.slow_s)
            self.slow_done.set()
        if (kind, idx, f_off) in self.fail:
            raise errors.FileCorrupt(f"{kind} {idx}@{f_off}")
        return (kind, idx, f_off)

    def full(self, pnum, idx, per, f_off):
        return self._read("full", idx, f_off)

    def sub(self, pnum, idx, per, f_off, which):
        assert which == 1
        return self._read("sub", idx, f_off)


def run_plan(reads, pool, n_blocks=5, window=2, hedge_budget=None):
    """Blocks whose plan is: shards 1 and 2 as full frames, rows 3 and 6 as
    sub-chunks; the fallback needs d=4 full frames of shards 1..5."""
    blocks = [(1, PER, k * 100, f"b{k}") for k in range(n_blocks)]
    return list(shardread.run_repair_plan(
        blocks, window, pool=pool, d=4, candidates=[1, 2, 3, 4, 5],
        full_frame=reads.full, sub_frame=reads.sub,
        plan_reads=lambda blk: ((1, 2), (3, 6)),
        from_plan=lambda blk, full, subs: ("plan", blk[3], sorted(full), sorted(subs)),
        from_frames=lambda blk, frames: ("fallback", blk[3], sorted(frames)),
        hedge_budget=hedge_budget, fire_fields={"bucket": "b", "object": "o"},
    ))


@pytest.fixture()
def pool():
    with ThreadPoolExecutor(8) as tp:
        yield tp


@pytest.fixture()
def counters():
    fault.clear()
    before = dict(fault.status()["counters"])
    yield lambda: {k: v - before.get(k, 0) for k, v in fault.status()["counters"].items()}
    fault.clear()


def test_the_plan_serves_every_block_in_order_from_its_own_reads(pool, counters):
    reads = Reads()
    out = run_plan(reads, pool)
    assert out == [("plan", f"b{k}", [1, 2], [3, 6]) for k in range(5)]
    assert sorted(reads.asked) == sorted(
        (kind, i, k * 100) for k in range(5)
        for kind, idxs in (("full", (1, 2)), ("sub", (3, 6))) for i in idxs
    )
    assert counters()["repair_fallback_blocks"] == 0


def test_a_failed_plan_read_degrades_its_block_alone_and_a_bad_frame_is_not_repicked(pool, counters):
    # block 1's sub-chunk of row 3 fails; so does its fallback frame of shard 2
    reads = Reads(fail={("sub", 3, 100), ("full", 2, 100)})
    out = run_plan(reads, pool)
    assert out[1] == ("fallback", "b1", [1, 3, 4, 5])
    assert [o[0] for o in out] == ["plan", "fallback", "plan", "plan", "plan"]
    assert counters()["repair_fallback_blocks"] == 1
    # the frame that failed the fallback is asked for once more by no block
    assert sum(1 for a in reads.asked if a == ("full", 2, 100)) <= 2


def test_a_block_that_can_neither_repair_nor_gather_is_a_quorum_error(pool):
    reads = Reads(fail={("sub", 6, 0), ("full", 4, 0), ("full", 5, 0)})
    with pytest.raises(QuorumError, match="only 3 of 4") as e:
        run_plan(reads, pool)
    assert isinstance(e.value.__cause__, errors.FileCorrupt)


def test_a_stalled_plan_read_is_raced_by_the_fallback_and_loses(pool, counters):
    reads = Reads(slow={("sub", 6, 100)}, slow_s=0.6)
    out = run_plan(reads, pool, n_blocks=2, hedge_budget=0.05)
    # the plan came back while the stalled read was still asleep
    assert not reads.slow_done.is_set()
    assert ("fallback", "b1", [1, 2, 3, 4]) in out
    got = counters()
    assert got["repair_hedge_reads"] == 1 and got["repair_hedge_wins"] >= 1
    assert reads.slow_done.wait(5.0)
