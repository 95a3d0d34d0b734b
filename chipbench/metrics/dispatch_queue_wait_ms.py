"""Mean wait of one dispatcher item (one submitted batch of blocks) from
submit to dispatch start, in ms: `minio_tpu_queue_wait_seconds_total` delta
over the item count, which is the +Inf row of
`minio_tpu_queue_wait_seconds_distribution` (per item, so items ARE
exported). Moves s3_mib_s: in a closed loop a request that waits is a
client that sends nothing."""


def read(w):
    items = w.delta("minio_tpu_queue_wait_seconds_distribution", le="+Inf")
    if items <= 0:
        return None
    return 1e3 * w.delta("minio_tpu_queue_wait_seconds_total") / items
