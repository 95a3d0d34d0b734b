"""S3 object operations a second, all four kinds together: the move of the
calls of the front end's `op` phases (`get_object`, `head_object`, `put_object`,
`delete_object`: one call per request handled, whatever its answer) over the
window's seconds. It is the operations rate until the benchmark has an
end-to-end `s3_ops_s`: `s3_mib_s` counts the object bytes moved, which only
GETs and PUTs carry. None from a program without the rows.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.op_counters import OPS, calls


def read(w):
    moved = [calls(w, "op", op) for op in OPS]
    if None in moved or w.seconds <= 0:
        return None
    return sum(moved) / w.seconds
