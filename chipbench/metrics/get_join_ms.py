"""Mean ms per GET in the per-block gather-join copy (`get`/`join`: d shard
payloads into one stripe block). Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "get", "join")
