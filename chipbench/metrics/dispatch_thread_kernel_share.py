"""Share of the window, in %, that the dispatch thread spent in phase
`kernel`: the jitted call(s) until `block_until_ready` of parity and
digests, so launch overhead + execution (and trace-and-lower on a first
call, which `window_first_calls` counts). Host clock; the device's own
busy time is `device_idle_share`'s. Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.dispatch_share(w, "kernel")
