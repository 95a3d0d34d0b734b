"""Copies into the survivor stacks of a GET's decode groups
(`minio_tpu_get_stack_copies_total`, every `unit` and `layout`, over calls
of `get`/`start`): one per shard and run where a run's payloads are one
strided array, one per block where they are not. Read 64 when added, all
`unit="run"`, `layout="packed"` (8 windows of 8 survivors; 66 copies a
window in three passes before PR 32; PERF.md §6, PR 32). None from a
program that does not count them. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g

COPIES = "minio_tpu_get_stack_copies_total"


def read(w):
    n = g.gets(w)
    if n is None or COPIES not in w.after:
        return None
    return w.delta(COPIES) / n
