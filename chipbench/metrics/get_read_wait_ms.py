"""Mean ms per GET that its thread waited for shard reads (`get`/
`read_wait`: a window's reads, submitted as the last window's readahead,
until every block has d shards, hedges included). Source: program_counter.
Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "get", "read_wait")
