"""The comparison that decides `correct`, made once the window has closed.

It compares what the TIMED path produced — the S3 requests of the window,
at their size, from all clients — with the plain reference
(`chipbench/reference.py`), and holds the system to the guarantees the
configuration states. The steps are files of their own, `checks/<name>.py`,
listed by the traffic file (`"checks"`), so a mix brings the steps that fit
what it sends:

- `answers`: front end, SigV4 — status and ETag (= md5 of the body) of
  every PUT;
- `readback`: object layer, storage — the body acknowledged for a sample
  of keys drawn from the seed, so many from every client, is read back over
  HTTP and compared byte for byte;
- `ondrive_frames`: dispatcher, ladder, kernels — for a sample of objects
  drawn from the seed among those the window wrote, every frame (digest +
  shard bytes, every stripe block) of every one of the d+p shard files on
  the drives against the reference's Reed-Solomon parity and HighwayHash-256;
- `degraded_read`: "readable with up to p drives missing" — a sample object
  is read back with p of its d+p shard files removed (drawn from the seed),
  bitrot verification and reconstruction included;
- `device_served`, `device_rung`, `blocks_dispatched`: the device served
  the window, on the rung the configuration names, every block of it.

A step is `run(v) -> {check name: (value, limit)}` over a `Verification`.
Every number compared is a count that must be 0: the comparison is exact,
so each limit is 0 (no lower and upper reading to sit between; the control
must make at least one count positive — PERF.md gives its readings).
"""

from __future__ import annotations

import glob
import os
import random
import shutil
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

from . import plugins, reference
from .procs import total


@dataclass
class Verification:
    """What a step may read. `records`: every request since boot; `window`:
    (t0, t1) on the clock the requests were timed by; `before`/`after`: the
    `/api/tpu` scrapes at the window's ends."""

    srv: object
    cli: object
    bucket: str
    records: list
    window: tuple
    gen: object
    config: dict
    mix: dict
    seed: int
    before: dict
    after: dict
    platform: str
    details: dict = field(default_factory=lambda: {"notes": []})
    spoiled: set = field(default_factory=set)   # keys a step has damaged on the drives

    def __post_init__(self):
        # {key: Request}: the last PUT acknowledged 200 for each key. A key is
        # written by one client, one request at a time, so the order is total
        self.last = {}
        for r in sorted(self.records, key=lambda r: r.done):
            if r.op == "PUT" and r.status == 200:
                self.last[r.key] = r
        t0 = self.window[0]
        self.timed_keys = sorted(k for k, r in self.last.items() if r.done >= t0)
        self.details["keys"] = len(self.last)
        self.details["keys_written_in_window"] = len(self.timed_keys)

    def rng(self, step: str) -> random.Random:
        return random.Random(f"{self.seed}/{step}")

    def pool(self) -> list[str]:
        """The keys a sample is drawn from: those the window wrote (all that
        were written, where it wrote none), but for what a step has damaged."""
        return [k for k in (self.timed_keys or sorted(self.last)) if k not in self.spoiled]

    def expected(self, key: str) -> tuple[bytes, str]:
        return self.gen.sent(self.last[key])

    def delta(self, name: str, **m) -> float:
        return total(self.after, name, **m) - total(self.before, name, **m)

    def note(self, text: str) -> None:
        self.details["notes"].append(text)


def threads(fn, items, n=8):
    """fn(item) for every item, on up to n threads; waits for all."""
    with ThreadPoolExecutor(max_workers=n) as pool:
        list(pool.map(fn, items))


def get_differs(cli, bucket, key, body: bytes, md5: str, timeout=60.0) -> str:
    """'' where a GET returns exactly the body and its ETag, else why not.
    An answer that comes late is late, not wrong: it is waited for a minute
    past the close (`verify.timeout_s`); one that never comes is wrong."""
    try:
        r = cli.request("GET", f"/{bucket}/{key}", timeout=timeout)
    except OSError as e:
        return f"{type(e).__name__}: {e}"
    if r.status != 200:
        return f"status {r.status} {r.body[:120]!r}"
    if r.headers.get("etag", "").strip('"') != md5:
        return "ETag differs"
    if r.body != body:
        return "bytes differ"
    return ""


def shard_files(drives: list[str], bucket: str, key: str) -> list[tuple[str, str]]:
    """[(drive, path of part.1)] as found on the drives — the layout as
    written; nothing of the program is asked."""
    out = []
    for drive in drives:
        for path in glob.glob(os.path.join(glob.escape(os.path.join(drive, bucket, key)),
                                           "*", "part.1")):
            out.append((drive, path))
    return out


def bad_shards(drives, bucket, key, body: bytes, d: int, p: int) -> tuple[int, str]:
    """How many of the d+p erasure indices have NO shard file on the drives
    that equals the reference's frames for it, byte for byte. Which drive
    holds which index is read off the bytes themselves."""
    frames = reference.object_frames(body, d, p)
    by_head = {f[:reference.DIGEST]: i for i, f in enumerate(frames)}
    good, why = set(), ""
    for drive, path in shard_files(drives, bucket, key):
        with open(path, "rb") as f:
            got = f.read()
        idx = by_head.get(got[:reference.DIGEST])
        if idx is None:
            why = why or f"{path}: first digest matches no reference shard"
        elif got != frames[idx]:
            why = why or f"{path}: shard {idx + 1} differs from the reference"
        else:
            good.add(idx)
    missing = d + p - len(good)
    if missing and not why:
        why = f"{key}: {len(good)} of {d + p} shard files found"
    return missing, why


def remove_object_dirs(drives, bucket, key, which: list[int]) -> None:
    """Remove the OBJECT dir (never the bucket volume) on the chosen drives."""
    for i in which:
        shutil.rmtree(os.path.join(drives[i], bucket, key), ignore_errors=True)


def run_checks(v: Verification) -> tuple[dict, dict]:
    """-> ({check name: (value, limit)}, details): the steps the traffic file
    lists, in its order."""
    checks: dict = {}
    for step in v.mix["checks"]:
        clock = time.monotonic()
        checks.update(plugins.load("checks", step).run(v))
        v.details[f"{step}_s"] = time.monotonic() - clock
    return checks, v.details
