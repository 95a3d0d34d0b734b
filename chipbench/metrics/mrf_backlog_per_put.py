"""Growth of the heal queue per acknowledged streaming PUT: the move of the
gauge `minio_tpu_heal_mrf_pending` (objects waiting in the most-recent-
failures queue) over calls of `put`/`commit`. Every PUT to a set with drives
offline leaves an object short of shards; with no heal worker draining (the
harness runs the server with the scanner off) 1.0 says each was queued once
and 0 that none was. None from a program without the gauge and from a window
without a PUT. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.phase_counters import CALLS

PENDING = "minio_tpu_heal_mrf_pending"


def read(w):
    if PENDING not in w.after or CALLS not in w.after:
        return None
    puts = w.delta(CALLS, layer="put", phase="commit")
    return w.delta(PENDING) / puts if puts > 0 else None
