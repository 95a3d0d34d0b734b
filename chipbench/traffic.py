"""What every load generator shares: the record of one request, the traffic
file, the percentile.

A traffic mix is a data file, `traffic/<mix>.json`. Its `generator` key names
the general generator that reads it, `generators/<name>.py`; `checks` lists
the steps of the comparison that decides `correct`, `checks/<name>.py`;
everything else is parameters (clients, sizes, the warm-up ladder, samples).
A later cell with other parameters adds a traffic file and nothing else; one
that needs a generator or a check that is not here adds that file too, and
edits none. The harness (`run.py`) keeps only the window and the records.

What the harness reads of every traffic file: `generator`, `checks`,
`trace_s`, `drives_room_gib` and `warm` = {`quiet_s`, `min_s`, `max_s`, and
optionally `progress`: the counter whose advance, by two since the last new
batch bucket or compiled program, says the own-loop warm-up is getting work
done — `minio_tpu_dispatch_total` of `/api/tpu` where it is not named, which
only a loop that encodes moves; `progress_group` names another metrics-v3
group to find it in}. A `rehearse` object overrides top-level keys, each
whole, for the CPU rehearsal.

What a generator is: `Generator(mix, endpoint, bucket, seed)`; the harness
then sets `gen.config` (the content of the cell's `configs/<config>.json`)
and `gen.drives` (the server's drive directories), which is what a check is
given, and calls `prepare()`, `warm_up(seen, want) -> (records, tries)`,
`start()`, `stop()`, `records()`; the checks call `sent(record) -> (body,
md5)` for a PUT it recorded. A deployment's state belongs in the
configuration's file, and the generator enacts it in set-up.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
MIB = 1 << 20


@dataclass
class Request:
    client: int
    op: str
    key: str
    body: int          # index into the generator's bodies (PUT: the one sent)
    sent: float        # time.monotonic() when the request was sent
    done: float        # ... when the last byte of the response was read
    status: int        # 0 where no response came
    ok: bool           # 200, and the ETag as expected
    nbytes: int        # object bytes moved where status == 200
    error: str = ""


def load_mix(name: str, rehearse: bool) -> dict:
    """`traffic/<name>.json`, with its `rehearse` overrides applied for a
    CPU rehearsal."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        spec = json.load(f)
    over = spec.pop("rehearse", {})
    if rehearse:
        spec.update(over)
    return spec


def percentile(values: list[float], q: float) -> float:
    """Nearest rank: the smallest value with at least q of the sample at or
    below it."""
    s = sorted(values)
    return s[max(0, -(-int(q * 1000) * len(s) // 1000) - 1)]
