"""Mean ms a callback handed to the server's event loop from another thread
waits to run: once a tick of the continuous sampler (~19 Hz) a
`call_soon_threadsafe` hand-off, the route a GET body's pieces and a PUT's
body chunks take to and from the loop —
Δ`minio_tpu_loop_lag_seconds_sum` ÷ Δ`minio_tpu_loop_lag_seconds_count` × 1e3.
None from a program without the series (the parent) and from a window
without a hand-off.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

NAME = "minio_tpu_loop_lag_seconds"


def read(w):
    if f"{NAME}_count" not in w.after:
        return None
    handed = w.delta(f"{NAME}_count")
    if handed <= 0:
        return None
    return 1e3 * w.delta(f"{NAME}_sum") / handed
