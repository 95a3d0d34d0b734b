"""Test fixture (`tests/chipbench/fixtures/added_cell`): MiB acknowledged per
CPU second of the server process (/proc/<pid>/stat over the window). A
per-layer metric appended after the ones that are there, listing only the
cell that came with it. Moves s3_mib_s."""


def read(w):
    if w.server_cpu_s <= 0 or w.acked_bytes <= 0:
        return None
    return w.acked_bytes / (1 << 20) / w.server_cpu_s
