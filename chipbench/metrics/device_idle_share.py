"""Share of the traced interval, in %, in which no operation ran on the
device: 1 - busy union / interval, from the profiler's trace. Source:
device_trace. Moves s3_mib_s: it says how far the host holds the chip
back."""


def read(w):
    if not w.trace or w.trace.get("busy_s") is None or not w.trace.get("window_s"):
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
