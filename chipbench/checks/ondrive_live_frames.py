"""`ondrive_frames`, as it stands, over the keys that are still there: that
step draws its sample from every key the window PUT, and in a window that
also deletes some of those are gone by the time it looks. So it is given a
view of the run that holds only the keys the plain reference
(`chipbench/reference_keyspace.py`) says exist at rest, and runs unchanged:
`verify.ondrive_objects` objects the window PUT and did not delete, all d+p
shard files, every frame (digest + shard block) against
`chipbench/reference.py`'s Reed-Solomon parity and HighwayHash-256.

What it receives: a `verify.Verification`."""

import copy

from chipbench import plugins, reference_keyspace


def run(v):
    model, _ = reference_keyspace.replay(v.records)
    view = copy.copy(v)  # `details` and `spoiled` stay the run's own
    view.last = {k: r for k, r in v.last.items() if k in model.objects}
    view.timed_keys = [k for k in v.timed_keys if k in model.objects]
    return plugins.load("checks", "ondrive_frames").run(view)
