"""The traffic was what the cell states: of the operations the window
acknowledged, the count of each kind against its share of the deck (the
traffic file's `deck`: 9 GET, 6 HEAD, 3 PUT, 2 DELETE of 20 = 45 / 30 / 15 /
10 %). Every client walks whole decks, so a kind can be off its share only by
the two decks the window's edges cut for each client: a cut deck of L cards
holds between 0 and all n of a kind's cards where its share is L * n / 20,
which is off by at most n * (1 - n / 20). `mix_not_as_stated` sums, over the
kinds, the operations beyond `clients` * 2 * n * (1 - n / 20) that a kind's
count is off by: above 0, a generator that dealt another mix, or a server
that starved one kind of its acknowledgements.

`details.window_first_calls` gives Δ`minio_tpu_dispatch_first_calls_total`
between the window's two scrapes (the reader of that name lists the PUT cells
by name): 0 where every batch bucket the window met had been through the
device before it, which is what the cell's `warm_buckets` are for.

What it receives: a `verify.Verification`; of the generator `deck` and
`clients`."""

import math


def run(v):
    t0, t1 = v.window
    deck, size = v.gen.deck, sum(v.gen.deck.values())
    acked = [r for r in v.records if r.status == 200 and t0 <= r.done <= t1]
    counts = {op: sum(1 for r in acked if r.op == op) for op in deck}
    off = 0
    for op, n in deck.items():
        room = v.gen.clients * 2 * n * (1 - n / size)
        off += max(0, math.ceil(abs(counts[op] - len(acked) * n / size) - room))
    v.details["window_operations"] = counts
    v.details["window_shares_pct"] = {op: 100.0 * counts[op] / max(len(acked), 1) for op in deck}
    first = "minio_tpu_dispatch_first_calls_total"
    v.details["window_first_calls"] = v.delta(first) if first in v.after else None
    return {"mix_not_as_stated": (off, 0)}
