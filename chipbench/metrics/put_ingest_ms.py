"""Mean ms per streaming PUT in phase `ingest`: the request thread pulling
body chunks off the reader into the arena (or `buf`), waiting for the
client's bytes included. Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.put_ms(w, "ingest")
