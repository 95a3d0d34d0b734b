"""Thread CPU seconds per GiB acknowledged on the threads that serve
reconstructing GETs (layer `get` but `shard_io`; `decode_wait` holds the
`decode` leaves'; `respond` books wall only). Source: program_counter.
Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.get_cpu_s_per_gib(w, without="shard_io")
