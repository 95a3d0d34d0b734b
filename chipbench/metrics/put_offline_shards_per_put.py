"""Shards per acknowledged streaming PUT that no drive took (the move of
`minio_tpu_put_offline_shards_total` over calls of `put`/`commit`): the count
of drives offline where the deployment's state holds — 4.0 with one node of
four down; 0 would mean the drives were back. None from a program without the
counter and from a window without a PUT. Source: program_counter. Moves
s3_mib_s. `read(w)` receives a `metrics.Window`."""

from chipbench.phase_counters import CALLS

SHARDS = "minio_tpu_put_offline_shards_total"


def read(w):
    if SHARDS not in w.after or CALLS not in w.after:
        return None
    puts = w.delta(CALLS, layer="put", phase="commit")
    return w.delta(SHARDS) / puts if puts > 0 else None
