"""Share, in %, of the stripe blocks rebuilt in the window that the device
rebuilt (`minio_tpu_decode_device_blocks_total`, both rungs, over
`minio_tpu_decode_blocks_total`, every family): the rest went to the host's
GF apply, a group under `MINIO_TPU_DECODE_MIN_SHARDS`. None where nothing
was rebuilt. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    if g.BLOCKS not in w.after or g.DEVICE_BLOCKS not in w.after:
        return None
    rebuilt = w.delta(g.BLOCKS)
    return 100.0 * w.delta(g.DEVICE_BLOCKS) / rebuilt if rebuilt > 0 else None
