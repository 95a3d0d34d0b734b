"""Share of the window, in %, that the dispatch thread spent copying on the
host: phases `assemble` (entries into the bucket arena, pad), `pack` and
`unpack` (chunk-major relayout for the mega-kernel and back) and `frame`
(data + parity concatenated into shards). Moves s3_mib_s: what on-device
relayout or a parity-only return would save is in here."""

from chipbench import phase_counters as pc


def read(w):
    return pc.dispatch_share(w, "assemble", "pack", "unpack", "frame")
