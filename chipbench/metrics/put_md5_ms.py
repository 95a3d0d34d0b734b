"""Mean ms per streaming PUT in phase `md5` (the ETag, on the request
thread). Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.put_ms(w, "md5")
