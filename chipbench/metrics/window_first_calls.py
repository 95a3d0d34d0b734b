"""First dispatches of a (rung, bucket) that ENDED inside the window:
`minio_tpu_dispatch_first_calls_total` delta. Each one traced, lowered and
compiled (or loaded) on the dispatch thread while requests waited. Should
read 0: otherwise the warm-up missed a shape. Unlike `window_compiles` it
moves when the first dispatch is done, not when it starts. Moves s3_mib_s."""

SERIES = "minio_tpu_dispatch_first_calls_total"


def read(w):
    if SERIES not in w.after:
        return None
    return w.delta(SERIES)
