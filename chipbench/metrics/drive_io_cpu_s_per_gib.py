"""Thread CPU seconds of the drive pool's threads inside a streaming PUT's
drive calls (phase `drive_io`: create, append, rename) per GiB
acknowledged. A part of `server_cpu_s_per_gib`. Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.cpu_s_per_gib(w, "put", only="drive_io")
