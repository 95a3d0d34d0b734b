"""Programs through the backend compiler inside the window
(`minio_tpu_compile_programs_total` delta, cache loads included) plus the
batch buckets of `minio_tpu_dispatch_bucket_blocks_distribution` that were
first seen inside it (the compile counter is blind to the Python trace-and-
lower of the Pallas kernel, which costs seconds per new bucket). Should
read 0: otherwise the warm-up ladder missed a shape. Moves s3_mib_s in every cell (a
stall of the one dispatch thread is lost rate, and a tail where one is held)."""


def read(w):
    name = "minio_tpu_dispatch_bucket_blocks_distribution"
    before, after = w.histogram(w.before, name), w.histogram(w.after, name)
    new = sum(1 for le, n in after.items() if n > 0 and before.get(le, 0) == 0)
    return w.delta("minio_tpu_compile_programs_total") + new
