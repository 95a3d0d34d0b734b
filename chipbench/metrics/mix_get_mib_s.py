"""MiB per second of GET body that the erasure read path finished inside the
window, both planes together: the move of `minio_tpu_get_bytes_total`
(`path="native"` + `path="windowed"`, booked when a read's body ends) over
the window's seconds. The other side of `mix_put_mib_s`. None from a program
without the counter. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

BYTES = "minio_tpu_get_bytes_total"


def read(w):
    if BYTES not in w.after or w.seconds <= 0:
        return None
    return w.delta(BYTES) / (1 << 20) / w.seconds
