"""Share, in %, of the stripe blocks the device was handed that were padding:
Δ`minio_tpu_dispatch_pad_blocks_total` ÷ (Δ`minio_tpu_dispatch_blocks_total` +
Δ pad). The dispatcher rounds a batch up to a power of two, so a 10-block PUT
alone goes out in bucket 16 with 6 zero blocks: 37.5 where every dispatch is
one such PUT, 0 where batches fill their buckets (a 64 MiB PUT). The pad
blocks are encoded and hashed like the others; they are the small PUT's cost
on the device and on the link. None from a program without the counter and
from a window without a dispatch.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

PAD, BLOCKS = "minio_tpu_dispatch_pad_blocks_total", "minio_tpu_dispatch_blocks_total"


def read(w):
    if PAD not in w.after or BLOCKS not in w.after:
        return None
    pad = w.delta(PAD)
    handed = w.delta(BLOCKS) + pad
    return 100.0 * pad / handed if handed > 0 else None
