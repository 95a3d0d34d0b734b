"""Read windows per GET that fired hedged parity reads past the straggler
budget (`minio_tpu_get_hedges_total{event="reads"}`, the read path's
counter mirrored on `/api/tpu`, over calls of `get`/`start`): each may put
a second parity shard in a straggler's place and split the window's decode.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    n = g.gets(w)
    if n is None or g.HEDGES not in w.after:
        return None
    return w.delta(g.HEDGES, event="reads") / n
