"""The erasure read plane's two shared decisions, for GET and heal alike.

``ShardReader``: where a shard's bytes are — inline in xl.meta, a legacy
raw file under one whole-file digest, or streaming ``digest || block``
frames in ``<obj>/<data_dir>/part.N`` — and how they are verified
(erasure/bitrot_io.py) before a payload is handed out.

``run_repair_plan``: how a partial-repair plan's reads (sub-packetized
family, one lost data shard) race their full-frame fallback, a window of
blocks at a time with readahead. The caller says what a block's plan
reads are and what the plan's and the fallback's reads become.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ALL_COMPLETED, FIRST_COMPLETED, Future
from concurrent.futures import wait as _fut_wait
from typing import Callable, Iterator

import numpy as np

from ..fault import registry as fault_registry
from ..storage.errors import FileCorrupt, StorageError
from . import bitrot_io
from .quorum import QuorumError

DIGEST = bitrot_io.DIGEST_SIZE
# what a failed shard read raises: the reader's next shard takes its place
_SPILL = (StorageError, OSError)


def whole_file_hash(m, part_number: int):
    """This drive's stored (digest, algorithm) for a part, or None when the
    shard uses the streaming format (reference cmd/bitrot-whole.go: legacy
    shards carry one metadata digest instead of interleaved frames). The
    stored algorithm matters: legacy data may be sha256/blake2b hashed."""
    from ..ops.bitrot import algorithm_from_string

    for c in m.erasure.checksums:
        if c.part_number == part_number and c.hash:
            return c.hash, algorithm_from_string(c.algorithm)
    return None


class ShardReader:
    """Verified reads of one object version's shards.

    ``sources`` maps erasure shard index -> (drive, that drive's FileInfo).
    ``view=True`` hands reedsolomon payloads out as views of the read
    buffer (zero-copy GET); ``on_bytes(n)`` is told the bytes of every
    read (heal's survivor ingress). Safe to call from many pool threads."""

    def __init__(self, bucket: str, obj: str, fi, coder, sources: dict,
                 view: bool = False,
                 on_bytes: Callable[[int], None] | None = None):
        self.sources = sources
        self._volume = bucket
        self._prefix = f"{obj}/{fi.data_dir}/part."
        self._coder = coder
        self._fdig = coder.frame_digests * DIGEST  # digest bytes a block
        self._view = view
        self._on_bytes = on_bytes
        # legacy whole-file shards are read and verified once per part:
        # futures memoize the load, so racing pool threads share ONE
        # read+hash instead of racing past a bare dict check
        self._whole: dict[tuple[int, int], Future] = {}
        self._whole_lock = threading.Lock()

    def _read(self, idx: int, part_num: int, off: int, n: int):
        disk, m = self.sources[idx]
        if m.inline_data:
            buf = m.inline_data if n < 0 else m.inline_data[off : off + n]
        else:
            buf = disk.read_file(
                self._volume, f"{self._prefix}{part_num}", off, n
            )
        if self._on_bytes is not None:
            self._on_bytes(len(buf))
        return buf

    def _whole_shard(self, idx: int, part_num: int, wh, algo) -> bytes:
        k = (idx, part_num)
        with self._whole_lock:
            fut = self._whole.get(k)
            owner = fut is None
            if owner:
                fut = self._whole[k] = Future()
        if owner:
            try:
                raw = self._read(idx, part_num, 0, -1)
                fut.set_result(
                    bitrot_io.verify_whole_file(bytes(raw), wh, algo)
                )
            except Exception as e:  # noqa: BLE001 — typed via the future
                fut.set_exception(e)
        return fut.result()

    def run(self, part_num: int, idx: int, pers: tuple, f_off: int) -> list:
        """ONE read of a shard's consecutive frames from `f_off` (their
        blocks `pers` bytes long), every frame verified before its
        payload is returned: the payloads, in order. Only reedsolomon
        streaming parts have runs longer than one frame."""
        coder = self._coder
        wf = whole_file_hash(self.sources[idx][1], part_num)
        if wf is not None:
            (per,) = pers
            block_i = f_off // (self._fdig + coder.shard_size)
            data = self._whole_shard(idx, part_num, *wf)
            blk = data[block_i * coder.shard_size:][:per]
            if len(blk) != per:
                raise FileCorrupt("short whole-file shard")
            return [blk]
        buf = self._read(
            idx, part_num, f_off, sum(pers) + self._fdig * len(pers)
        )
        if coder.family != bitrot_io.FAMILY_RS:
            (per,) = pers  # two sub-frames a block: single frames too
            return [bitrot_io.verify_block(
                buf, per, family=coder.family, view=self._view
            )]
        return bitrot_io.verify_run(buf, pers, view=self._view)

    def block(self, part_num: int, idx: int, per: int, f_off: int):
        """One block's verified payload: a run of one."""
        return self.run(part_num, idx, (per,), f_off)[0]

    def sub_chunk(
        self, part_num: int, idx: int, per: int, f_off: int, which: int
    ) -> np.ndarray:
        """Partial-repair read unit: ONE digest||sub-chunk frame of a
        sub-packetized shard block (the other half never moves)."""
        rel, dlen = bitrot_io.sub_chunk_in_block(per, which)
        buf = self._read(idx, part_num, f_off + rel, DIGEST + dlen)
        return np.frombuffer(
            bitrot_io.verify_sub_chunk(bytes(buf), dlen), dtype=np.uint8
        )


def repair_shard(coder, sched, per: int, full: dict, subs: dict) -> np.ndarray:
    """The lost data shard of one block from its plan reads: `full` holds
    verified full frames (every group mate among them), `subs` the
    sub-chunk-2 frames of the other b_helpers and the piggyback parity."""
    h1, _h2 = bitrot_io.sub_lens(per)
    got = {r: np.frombuffer(v, dtype=np.uint8) for r, v in full.items()}
    sub2 = {r: got[r][h1:] if r in got else subs[r] for r in sched.b_helpers}
    sub1 = {r: got[r][:h1] for r in sched.mates}
    return coder.repair_data_shard(sched, per, sub2, subs[sched.pb_parity], sub1)


def run_repair_plan(
    blocks: list[tuple],
    window: int,
    *,
    pool,
    d: int,
    candidates: list[int],
    full_frame: Callable,
    sub_frame: Callable,
    plan_reads: Callable,
    from_plan: Callable,
    from_frames: Callable,
    hedge_budget: float | None,
    fire_fields: dict,
) -> Iterator:
    """Execute a partial-repair plan over ``blocks`` — tuples that start
    ``(part#, shard bytes of the block, frame offset)`` — yielding what
    each block becomes, in order.

    A window's plan reads issue together on ``pool`` and the next window's
    start as readahead before the current one is assembled. Each block is
    its own race: its plan reads against — once the hedge budget blows, or
    a plan read fails (breaker trip mid-read, bitrot, second fault) — a
    gather of ``d`` verified full frames from ``candidates``; whichever
    completes first serves THAT block. The plan is never abandoned, and a
    fallback frame re-verifies like any read, so wrong bytes cannot be
    served. Raises QuorumError for a block that can do neither.

    The caller's half: ``plan_reads(block)`` -> (shard indices read as
    full frames, rows read as sub-chunk-2 frames); ``from_plan(block,
    full, subs)`` -> a plan-complete block's result (run under the next
    window's readahead); ``from_frames(block, frames)`` -> the same from
    the fallback's d full frames; ``full_frame(part#, idx, per, f_off)``
    and ``sub_frame(part#, idx, per, f_off, which)`` are the reads."""
    bad: set[int] = set()  # shards whose fallback read failed: never re-picked

    def start(win) -> dict:
        futs = {}
        for bi, blk in enumerate(win):
            pnum, per, f_off = blk[:3]
            full_idx, sub_rows = plan_reads(blk)
            for idx in full_idx:
                futs[(bi, "full", idx)] = pool.submit(
                    full_frame, pnum, idx, per, f_off
                )
            for r in sub_rows:
                futs[(bi, "sub", r)] = pool.submit(
                    sub_frame, pnum, r, per, f_off, 1
                )
        return futs

    def gather(win, futs):
        """Resolve one window. Returns (pieces, full, subs): pieces[bi] is
        the fallback's result, or None where the plan reads landed in
        full[bi] / subs[bi] and ``from_plan`` is left to the caller."""
        nwin = len(win)
        full = [dict() for _ in range(nwin)]    # bi -> idx: payload
        subs = [dict() for _ in range(nwin)]    # bi -> row: array
        fb_got = [dict() for _ in range(nwin)]  # fallback frames
        fb_mode = [False] * nwin
        fb_hedge = [False] * nwin
        plan_done = [False] * nwin
        pieces: list = [None] * nwin
        pending: dict[tuple, Future] = dict(futs)
        rev = {f: k for k, f in pending.items()}
        plan_keys: list[set] = [set() for _ in range(nwin)]
        for k in futs:
            plan_keys[k[0]].add(k)
        last_err: BaseException | None = None
        hedge_fired = False
        deadline = (
            time.monotonic() + hedge_budget
            if hedge_budget is not None else None
        )

        def unserved(bi) -> bool:
            return pieces[bi] is None and not plan_done[bi]

        def fb_inflight(bi) -> list[int]:
            return [k[2] for k in pending if k[0] == bi and k[1] == "fb"]

        def drop(keys) -> None:
            for k in list(keys):
                f = pending.pop(k, None)
                if f is not None:
                    rev.pop(f, None)
                    f.cancel()

        def drop_plan(bi) -> None:
            drop(plan_keys[bi])
            plan_keys[bi].clear()

        def fb_submit(bi) -> int:
            """Keep fallback block bi able to reach d frames."""
            pnum, per, f_off = win[bi][:3]
            inflight = fb_inflight(bi)
            tried = set(fb_got[bi]) | bad | set(inflight)
            want = max(d - len(fb_got[bi]) - len(inflight), 0)
            picked = [i for i in candidates if i not in tried][:want]
            for idx in picked:
                f = pool.submit(full_frame, pnum, idx, per, f_off)
                pending[(bi, "fb", idx)] = f
                rev[f] = (bi, "fb", idx)
            return len(picked)

        def enter_fallback(bi, racing: bool) -> int:
            """Degrade block bi to the full-frame gather. ``racing`` (the
            hedge) leaves the plan reads inflight to race; a failed plan
            read drops them instead."""
            if fb_mode[bi]:
                return 0
            fb_mode[bi] = True
            fb_hedge[bi] = racing
            if not racing:
                drop_plan(bi)
            return fb_submit(bi)

        def finish_plan(bi) -> None:
            plan_done[bi] = True
            if fb_mode[bi]:
                if fb_hedge[bi]:
                    fault_registry.stats_add("repair_hedge_losses")
                drop([(bi, "fb", i) for i in fb_inflight(bi)])

        def finish_fallback(bi) -> None:
            if not unserved(bi) or len(fb_got[bi]) < d:
                return
            pieces[bi] = from_frames(win[bi], fb_got[bi])
            fault_registry.stats_add("repair_fallback_blocks")
            if fb_hedge[bi]:
                fault_registry.stats_add("repair_hedge_wins")
            drop_plan(bi)

        try:
            while any(unserved(bi) for bi in range(nwin)):
                # fallback blocks must stay able to reach d
                for bi in range(nwin):
                    if not (unserved(bi) and fb_mode[bi]):
                        continue
                    inflight = len(fb_inflight(bi))
                    if (len(fb_got[bi]) + inflight < d
                            and fb_submit(bi) == 0 and inflight == 0
                            and not plan_keys[bi]):
                        pnum, _per, f_off = win[bi][:3]
                        raise QuorumError(
                            f"cannot read part {pnum} shard offset {f_off}:"
                            f" only {len(fb_got[bi])} of {d} shards"
                        ) from last_err
                if not pending:
                    continue  # spills just submitted; re-check
                timeout = None
                if deadline is not None and not hedge_fired:
                    timeout = max(deadline - time.monotonic(), 0.0)
                # plan-only mode needs every read anyway: one ALL_COMPLETED
                # wait registers each future once. Once any block races
                # its fallback, settle per completion (FIRST_COMPLETED) —
                # whichever side lands first serves without waiting on
                # the loser.
                racing = hedge_fired or any(fb_mode)
                done, _ = _fut_wait(
                    set(pending.values()), timeout=timeout,
                    return_when=FIRST_COMPLETED if racing else ALL_COMPLETED,
                )
                if not done:
                    # plan reads blew the hedge budget: race the full-frame
                    # gather for every unserved block
                    hedge_fired = True
                    fired = sum(
                        enter_fallback(bi, True)
                        for bi in range(nwin) if unserved(bi)
                    )
                    if fired:
                        fault_registry.stats_add("repair_hedge_reads")
                        fault_registry.emit(
                            "hedge.fire", plane="repair",
                            budgetMs=round((hedge_budget or 0.0) * 1e3, 1),
                            reads=fired, **fire_fields,
                        )
                    else:
                        deadline = None  # nothing left to hedge with
                    continue
                for f in done:
                    key = rev.pop(f, None)
                    if key is None:
                        continue  # read dropped after its race
                    pending.pop(key, None)
                    bi, kind, idx = key
                    if kind == "fb":
                        try:
                            fb_got[bi][idx] = f.result()
                        except _SPILL as e:
                            last_err = e
                            bad.add(idx)
                        else:
                            finish_fallback(bi)
                        continue
                    plan_keys[bi].discard(key)
                    try:
                        (full if kind == "full" else subs)[bi][idx] = f.result()
                    except _SPILL as e:
                        # THIS block degrades to the full-frame gather;
                        # sibling blocks keep their plan reads
                        last_err = e
                        if not unserved(bi):
                            continue
                        if fb_mode[bi]:
                            drop_plan(bi)  # already racing: the plan lost
                        else:
                            enter_fallback(bi, False)
                    else:
                        if unserved(bi) and not plan_keys[bi]:
                            finish_plan(bi)
        finally:
            # decided, or failed: leave no read hogging the shared pool
            for f in pending.values():
                f.cancel()
        return pieces, full, subs

    wins = [blocks[i : i + window] for i in range(0, len(blocks), window)]
    futs = start(wins[0]) if wins else {}
    try:
        for wi, win in enumerate(wins):
            pieces, full, subs = gather(win, futs)
            futs = start(wins[wi + 1]) if wi + 1 < len(wins) else {}
            for bi, blk in enumerate(win):
                if pieces[bi] is None:
                    pieces[bi] = from_plan(blk, full[bi], subs[bi])
                yield pieces[bi]
    finally:
        # abandoned iterator or error: cancel the readahead
        for f in futs.values():
            f.cancel()
