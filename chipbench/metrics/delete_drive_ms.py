"""Mean wall ms a DELETE spends on the drives: Δ seconds ÷ Δ calls of
`delete`/`drive_delete`, the `delete_version` call on every drive of the set
(each renames the object's data directory into that drive's trash and removes
its `xl.meta`) and their join, under the namespace write lock. None from a
program without the row and from a window without a DELETE.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.op_counters import ms_per_call


def read(w):
    return ms_per_call(w, "delete", "drive_delete")
