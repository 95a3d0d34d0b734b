"""Share of the window, in %, that the dispatch thread had nothing to do:
phases `wait` (both lanes empty, in `cv.wait`) and `window` (the straggler
window) of `minio_tpu_phase_seconds_total{layer="dispatch"}`. Where this
is large the thread is not the cap; the requests are late. Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.dispatch_share(w, "wait", "window")
