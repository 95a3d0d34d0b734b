"""Growth of the trash per DELETE: the move of the gauge
`minio_tpu_trash_pending` (entries renamed into a drive's trash directory and
not yet removed) over the DELETEs the window handled (calls of
`op`/`delete_object`). About 0 where the drives' reclaimers keep up with the
load; 16, one entry a drive, where nothing is reclaimed. None from a program
without the gauge and from a window without a DELETE.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.op_counters import calls

PENDING = "minio_tpu_trash_pending"


def read(w):
    deletes = calls(w, "op", "delete_object")
    if PENDING not in w.after or not deletes or deletes <= 0:
        return None
    return w.delta(PENDING) / deletes
