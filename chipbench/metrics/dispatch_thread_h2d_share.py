"""Share of the window, in %, that the dispatch thread spent in phase `h2d`:
`device_put`/`jnp.asarray` of the batch until `block_until_ready`. Host
clock of a synced transfer, not a device time. Moves s3_mib_s."""

from chipbench import phase_counters as pc


def read(w):
    return pc.dispatch_share(w, "h2d")
