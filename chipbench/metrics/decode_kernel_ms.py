"""Mean ms per GET in the jitted reconstruct call (`decode`/`kernel`: call
to ready, launch and sync included; a first call's trace-and-lower too).
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "decode", "kernel")
