"""Share of the window, in %, that the dispatch thread spent in phase `d2h`:
`np.asarray` of parity and digests, after the kernel was ready. Host clock
of a synced transfer, not a device time. Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.dispatch_share(w, "d2h")
