"""Thread CPU seconds of the dispatch thread inside its phases (all of
layer `dispatch`) per GiB acknowledged. A part of `server_cpu_s_per_gib`.
Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.cpu_s_per_gib(w, "dispatch")
