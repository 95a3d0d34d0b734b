"""Mean ms a thread of the server process waits to come back from a blocking
call: the continuous sampler's wake-up, once a tick (~19 Hz), late by the OS
wake-up (the floor, the server at rest) and by the queue for the interpreter
lock (GIL) that some forty threads share —
Δ`minio_tpu_gil_wait_seconds_sum` ÷ Δ`minio_tpu_gil_wait_seconds_count` × 1e3.
A phase that blocks n times pays about n × this for the lock. None from a
program without the series (the parent, and a server with
MINIO_TPU_PROFILE_CONTINUOUS=0, whose rows stay 0) and from a window
without a tick.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

NAME = "minio_tpu_gil_wait_seconds"


def read(w):
    if f"{NAME}_count" not in w.after:
        return None
    ticks = w.delta(f"{NAME}_count")
    if ticks <= 0:
        return None
    return 1e3 * w.delta(f"{NAME}_sum") / ticks
