"""Bitrot frames verified per shard read of the reconstructing read path
(`minio_tpu_get_shard_frames_total`, both `unit`s, over calls of
`get`/`shard_io`): the run length. Read 8.0 when added, every frame under
`unit="run"` (the shipped read window of 8 blocks; 1.0 before PR 30, and
still for inline, whole-file-checksum and cauchy parts; PERF.md §6, PRs 30
to 32). None from a program that does not count frames.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g
from chipbench.phase_counters import CALLS

FRAMES = "minio_tpu_get_shard_frames_total"


def read(w):
    if FRAMES not in w.after or g.gets(w) is None:
        return None
    reads = w.delta(CALLS, layer="get", phase="shard_io")
    return w.delta(FRAMES) / reads if reads > 0 else None
