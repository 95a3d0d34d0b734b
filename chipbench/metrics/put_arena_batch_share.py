"""Share, in %, of the streaming PUT's encode batches that the pooled ingest
arena made: Δ`minio_tpu_put_batches_total{plane="arena"}` ÷ (Δ arena +
Δ`{plane="copy"}`). The arena lays each stripe block at a stride of d ×
shard_size, so every geometry takes it (100); the legacy plane copies the
batch about eight times on the request thread (0 where every PUT took it).
None from a program without the counter and from a window without a batch.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

BATCHES = "minio_tpu_put_batches_total"


def read(w):
    if BATCHES not in w.after:
        return None
    arena = w.delta(BATCHES, plane="arena")
    batches = arena + w.delta(BATCHES, plane="copy")
    return 100.0 * arena / batches if batches > 0 else None
