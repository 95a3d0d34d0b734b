"""Mean ms per GET inside the leaves of its device reconstructs on the XLA
rung (`decode`: `pad` + `h2d` + `kernel` + `d2h` + `unpack`, over calls of
`get`/`start`): what a GET from a set with d > 8 pays the decode rung that
keeps the row-major copy and relays nothing out on the device (a group of
the `rows` layout; `pack` never runs there). Source: program_counter. Moves
s3_mib_s. `read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "decode", "pad", "h2d", "kernel", "d2h", "unpack")
