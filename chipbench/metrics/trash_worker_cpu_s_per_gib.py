"""What emptying the trash costs the foreground: thread CPU seconds of the
drives' reclaimers (Δ`phase_cpu_seconds{trash,reclaim}`: they share the
process, its GIL and the host's cores with every request) per GiB they removed
(Δ`minio_tpu_trash_reclaimed_bytes_total`). None from a program without the
rows and from a window in which nothing was reclaimed.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.op_counters import has_row
from chipbench.phase_counters import CPU, GIB

BYTES = "minio_tpu_trash_reclaimed_bytes_total"


def read(w):
    if BYTES not in w.after or not has_row(w, "trash", "reclaim"):
        return None
    gone = w.delta(BYTES)
    return w.delta(CPU, layer="trash", phase="reclaim") / (gone / GIB) if gone > 0 else None
