"""Mean wall ms of one quorum read of `xl.meta` under a stat: Δ seconds ÷ Δ
calls of `stat`/`meta_read`, the read of every drive of the set that
`get_object_info` makes where the FileInfo cache has no fresh entry — under a
HEAD, and under the look-up a PUT's and a DELETE's handler make of their key
first. None from a program without the row and from a window in which every
stat was answered from the cache.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.op_counters import ms_per_call


def read(w):
    return ms_per_call(w, "stat", "meta_read")
