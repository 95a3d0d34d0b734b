"""Thread CPU seconds per GiB acknowledged on the read pool's threads
(`get`/`shard_io`: the drive read and each frame's bitrot verify).
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g


def read(w):
    return g.get_cpu_s_per_gib(w, only="shard_io")
