"""Mean ms per streaming PUT in phase `encode_wait`: submit to the
dispatcher until the result, i.e. queue wait + the dispatch. No less than
`dispatch_queue_wait_ms`. Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.put_ms(w, "encode_wait")
