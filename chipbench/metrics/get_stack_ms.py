"""Mean ms per GET that its thread spent laying the survivors of its decode
groups out (`get`/`stack`: since PR 32 the ONE pass over a group's verified
payloads, one strided copy per shard and run into the layout the decoding
rung takes, its pad rows zeroed in the same phase; `decode_host_copy_ms`
reads only what is left under `decode`). Read 92 ms when added (PERF.md §5,
PR 32; 303 before it). Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "get", "stack")
