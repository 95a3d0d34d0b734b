"""Mean ms per GET inside `reconstruct_data_flat` (`get`/`decode_wait`: the
`decode` leaves tile it on the device rungs, `host` on the host's).
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "get", "decode_wait")
