"""Thread CPU seconds of the PUT request threads inside their named phases
(all of layer `put` but `drive_io`) per GiB acknowledged. A part of
`server_cpu_s_per_gib`. Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.cpu_s_per_gib(w, "put", without="drive_io")
