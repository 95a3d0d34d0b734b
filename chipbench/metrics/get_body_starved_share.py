"""Share, in %, of the time the writer of a GET's body spent waiting for the
next piece: Δ`get`/`body_wait` ÷ (Δ`body_wait` + Δ`body_write`), the two
phases the front end books per piece of every GET body on either read path
(wall only, on the event loop). `body_wait` is production that ran under no
write — the read path's reads, stack, decode or native span, and the loop's
wake-up — and `body_write` the time inside `resp.write`. Where the budget of
pieces ahead is full the writer finds its pieces waiting and the share is low
(13.7 in `ec12p4-16d.put-get` when added); where production is the slower
side it waits once a read window (65.7 in the GET cell, 74.0 in the 4-off
one; PERF.md §6, PR 35). It needs no per-GET count, so it reads the same on
the windowed and the native plane. None from a program without the two rows, and from a
window in which no piece was written.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.phase_counters import SECONDS


def read(w):
    if not any(labels.get("layer") == "get" and labels.get("phase") == "body_wait"
               for labels, _ in w.after.get(SECONDS, [])):
        return None
    waited = w.delta(SECONDS, layer="get", phase="body_wait")
    total = waited + w.delta(SECONDS, layer="get", phase="body_write")
    if total <= 0:
        return None
    return 100.0 * waited / total
