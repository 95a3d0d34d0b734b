"""Mean ms per GET between handing a stripe block to the front end and being
resumed for the next (`get`/`respond`: yield -> resumption, the front end's
write of 1 MiB and the executor hop; wall only, 64 hops per 64 MiB GET).
Read 683 ms when added, 72 % of a GET (PERF.md §5, PR 32).
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "get", "respond")
