"""Mean ms per healthy GET inside its native span reads (`get`/`native`:
pread + bitrot verify + assembly of 16 MiB spans in one C++ pass, summed and
booked as one call when the read's native part ends), the healthy GET's own
work as against the front end's hand-over between its pieces. None from a
program without the phase, and from a window in which no GET rode the native
plane. Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.phase_counters import CALLS, SECONDS


def read(w):
    if not any(labels.get("layer") == "get" and labels.get("phase") == "native"
               for labels, _ in w.after.get(CALLS, [])):
        return None
    n = w.delta(CALLS, layer="get", phase="native")
    if n <= 0:
        return None
    return 1e3 * w.delta(SECONDS, layer="get", phase="native") / n
