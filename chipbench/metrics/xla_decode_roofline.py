"""Share of its roofline, in %, at which the device ran the XLA decode rung
(rebuild of the missing shards, `jit_gf_apply_bits` over the survivors'
inverse) in the traced interval of a window that also encodes: the least
time the chip could take for the REAL stripe blocks that rung rebuilt between
the two scrapes that bracket the traced interval, over the device's busy time
in the trace.

The work of a block is d*n in and m*n rebuilt out, plus 32(d+m) digest bytes
(`work_decode.decode_bytes_per_block`: 1,311,210 B at 12 data shards and
m = 3), over the published 819 GB/s, m read off the `missing` label of
`minio_tpu_decode_device_blocks_total{rung="xla"}`. The time is the busy
UNION of the whole device, encodes included: what `trace_reduce` hands a
reader names the five programs with most device time, and the encode's
parity is the same `jit_gf_apply_bits` under other fingerprints, so the
decode's own device time cannot be told from the encode's. Dividing by more
time than the decode took can only understate the share; it can never pass
100 %. The counters' interval lies inside the trace's (the first scrape
follows the profiler's start, the second precedes its stop), so the work is
not overstated either. A program that does not split its decode counters by
`missing`, a run without a device trace, and an interval without a decode on
this rung give None, never 0. Source: device_trace. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g
from chipbench import work, work_decode


def xla_rows(series: dict) -> dict[int, float] | None:
    rows = [(labels, v) for labels, v in series.get(g.DEVICE_BLOCKS, [])
            if "missing" in labels and labels.get("rung") == "xla"]
    if not rows:
        return None
    return {int(labels["missing"]): v for labels, v in rows}


def read(w):
    if not w.trace or not w.trace.get("busy_s") or w.traced_before is None:
        return None
    now, then = xla_rows(w.after), xla_rows(w.traced_before)
    if now is None or then is None:
        return None
    moved = sum((now[m] - then.get(m, 0.0)) * work_decode.decode_bytes_per_block(w.data_shards, m)
                for m in now)
    if moved <= 0:
        return None
    least_s = moved / work.peaks(w.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / w.trace["busy_s"]
