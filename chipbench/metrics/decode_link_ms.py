"""Mean ms per GET on the host-device link (`decode`/`h2d` + `d2h`: host
clock of synced transfers). Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "decode", "h2d", "d2h")
