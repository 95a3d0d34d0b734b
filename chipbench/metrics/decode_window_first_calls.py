"""First device reconstructs of a (rung, shards rebuilt, batch) that ENDED
inside the window (`minio_tpu_decode_first_calls_total`): each traced and
lowered a kernel on the thread of the GET that met it. Should be 0: the
warm-up is there to meet them. None from a program that does not count them.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return w.delta(g.FIRST_CALLS) if g.FIRST_CALLS in w.after else None
