"""`degraded_reference`, as it stands, over the SET-UP objects alone: that
step draws its sample from every key PUT since boot and expects each to have
a shard file, untouched since set-up, on every offline drive — true of the
objects set-up wrote before the drives went offline, and not of what a
window's PUTs wrote afterwards (those have no file there, by the guarantee
`ondrive_online_frames` holds them to, and `readback` reads them through the
rebuild). So it is given a view of the run that holds the generator's
`setup_keys` only, and runs unchanged: the GET over HTTP with the drives
still offline against the plain reference's reconstruction from the shard
files on the surviving drives and against the body PUT, the offline drives'
files still there and byte for byte the reference's. With nothing offline
the same code reads all d+p files, verifies every frame, and re-encodes the
parity from the data shards: a healthy GET against the reference.

What it receives: a `verify.Verification`; `details.reference_gets` counts
its GETs for `served_as_stated`."""

import copy

from chipbench import plugins


def run(v):
    view = copy.copy(v)  # `details` and `spoiled` stay the run's own
    keys = set(v.gen.setup_keys)
    view.last = {k: r for k, r in v.last.items() if k in keys}
    return plugins.load("checks", "degraded_reference").run(view)
