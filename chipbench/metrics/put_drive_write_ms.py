"""Mean ms per streaming PUT in phases `drive_write` (submit the appends
to the drive pool and join them) and `commit` (the `rename_data` fan-out
and join). Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.put_ms(w, "drive_write", "commit")
