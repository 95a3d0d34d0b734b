"""Shard reads per GET on the read pool (calls of `get`/`shard_io` over
calls of `get`/`start`): d per read window where one read holds a shard's
run of the window's frames, d per block where it holds one frame. Read 64.0
when added (8 windows of 8 shards; 524-531 before PR 30; PERF.md §6, PRs 30
to 32); a hedged read adds one. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g
from chipbench.phase_counters import CALLS


def read(w):
    n = g.gets(w)
    if n is None:
        return None
    return w.delta(CALLS, layer="get", phase="shard_io") / n
