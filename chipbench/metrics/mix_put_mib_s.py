"""MiB per second of PUT body that went through the dispatcher inside the
window: the move of `minio_tpu_dispatch_blocks_total` (one stripe block is
1 MiB of a PUT's body) over the window's seconds. In a mixed window
`s3_mib_s` is a sum in which the GETs weigh several times the PUTs, so the
ledger shows each side. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

BLOCKS = "minio_tpu_dispatch_blocks_total"


def read(w):
    if BLOCKS not in w.after or w.seconds <= 0:
        return None
    return w.delta(BLOCKS) / w.seconds
