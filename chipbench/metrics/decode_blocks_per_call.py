"""Real stripe blocks per device reconstruct dispatch over the window, both
rungs (`minio_tpu_decode_device_blocks_total` over
`minio_tpu_decode_dispatches_total`; padding excluded): 8 where every read
window goes to the device whole, less where hedged reads split windows into
groups. None where no dispatch ran. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    if g.DISPATCHES not in w.after:
        return None
    calls = w.delta(g.DISPATCHES)
    return w.delta(g.DEVICE_BLOCKS) / calls if calls > 0 else None
