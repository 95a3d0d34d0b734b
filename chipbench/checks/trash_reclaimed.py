"""The stated guarantee of the trash: what a DELETE moved aside is reclaimed
while the load runs. At rest, once the drives' reclaimers have been given
`verify.trash_drain_s` seconds (at most; the step returns as soon as nothing
is left) after the last request:

- `trash_entries_left`: entries lying in the d+p directories
  `<drive>/.minio.sys/trash`, read off the drives;
- `trash_moved_not_reclaimed`: `minio_tpu_trash_moved_total` less
  `minio_tpu_trash_reclaimed_total` on `/api/tpu`;
- `trash_moved_not_as_deleted`: entries moved since boot against one per
  drive for every DELETE the run acknowledged since boot (absolute): the
  run overwrites nothing, so only a DELETE moves anything aside, and one that
  was acknowledged and moved nothing is seen here.

A program without the counters (an older commit under these files) reads 0
moved and 0 reclaimed: its DELETEs are then all unaccounted for, and what it
left in the trash counts as left.

What it receives: a `verify.Verification`."""

import os
import time

from chipbench.procs import scrape

MOVED, RECLAIMED = "minio_tpu_trash_moved_total", "minio_tpu_trash_reclaimed_total"


def entries_left(drives) -> int:
    n = 0
    for drive in drives:
        try:
            n += len(os.listdir(os.path.join(drive, ".minio.sys", "trash")))
        except FileNotFoundError:
            pass
    return n


def counters(port) -> tuple[float, float]:
    tpu = scrape(port, "/api/tpu")
    return tuple(sum(val for _, val in tpu.get(name, [])) for name in (MOVED, RECLAIMED))


def run(v):
    t0 = time.monotonic()
    deadline = t0 + v.mix["verify"]["trash_drain_s"]
    while True:
        left = entries_left(v.srv.drives)
        moved, reclaimed = counters(v.srv.port)
        if (left == 0 and moved == reclaimed) or time.monotonic() >= deadline:
            break
        time.sleep(0.1)
    deletes = sum(1 for r in v.records if r.op == "DELETE" and r.status == 200)
    v.details["trash_drained_after_s"] = time.monotonic() - t0
    v.details["trash_moved_since_boot"] = moved
    v.details["trash_reclaimed_since_boot"] = reclaimed
    v.details["deletes_since_boot"] = deletes
    return {"trash_entries_left": (left, 0),
            "trash_moved_not_reclaimed": (moved - reclaimed, 0),
            "trash_moved_not_as_deleted": (abs(moved - deletes * len(v.srv.drives)), 0)}
