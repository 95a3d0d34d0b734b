"""Mean ms per GET that its thread spent relaying out on the host around
the device reconstruct (`decode`/`pad` + `pack` + `unpack`: survivors made
block-major and padded, chunk-major pack, the rebuilt shards unpacked).
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "decode", "pad", "pack", "unpack")
