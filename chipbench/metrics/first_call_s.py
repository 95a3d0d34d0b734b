"""Seconds the first dispatch of every (rung, bucket) spent in the `kernel`
phase, SINCE BOOT (the scrape at the window's end, not a delta):
`minio_tpu_dispatch_first_call_seconds_total` summed. Trace-and-lower,
compile or cache load, and the run, all on the dispatch thread during
warm-up. Moves setup_s."""

SERIES = "minio_tpu_dispatch_first_call_seconds_total"


def read(w):
    if SERIES not in w.after:
        return None
    return sum(v for _, v in w.after[SERIES])
