"""Stripe blocks per device dispatch, padding excluded:
`minio_tpu_dispatch_blocks_total` delta over `minio_tpu_dispatch_total`
delta. Moves s3_mib_s (batch width is what the dispatcher buys)."""


def read(w):
    calls = w.delta("minio_tpu_dispatch_total")
    if calls <= 0:
        return None
    return w.delta("minio_tpu_dispatch_blocks_total") / calls
