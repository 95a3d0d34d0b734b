"""S3 object operations a second, all four kinds together: the move of the
calls of the front end's `op` phases (`get_object`, `head_object`, `put_object`,
`delete_object`: one call per request handled, whatever its answer) over the
window's seconds. It is the server's count beside the end-to-end `s3_ops_s`,
the client's count of the requests acknowledged 200 inside the window: a gap
between the two is requests that failed, or requests in flight at the
window's edges. None from a program without the rows.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.op_counters import OPS, calls


def read(w):
    moved = [calls(w, "op", op) for op in OPS]
    if None in moved or w.seconds <= 0:
        return None
    return sum(moved) / w.seconds
