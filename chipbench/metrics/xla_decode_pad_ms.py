"""Mean ms per GET in `decode`/`pad` alone: on the XLA rung the one
`ascontiguousarray` of a window's survivors, [d, W, per] rows made [W, d, per]
(8.4 MB a window at 12 data shards) — the copy the packed layout took away
where the decode mega-kernel takes the group, and that every group keeps at
d > 8. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.ms_per_get(w, "decode", "pad")
