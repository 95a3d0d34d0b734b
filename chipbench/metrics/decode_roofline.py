"""Share of its roofline, in %, at which the device ran the decode (rebuild
of the missing shards + bitrot hash, whatever implements it) in the traced
interval: the least time the chip could take for the REAL stripe blocks of
the decode dispatches in the trace over the device's busy time in it.

Work and time are of the same dispatches, as `codec_roofline` does it: the
programs that ran inside the trace (`trace["dispatches"]`, counted on the
device's own line; the window holds no PUT, so every one is a decode), each
with the mean work per dispatch that the counters give over the traced
interval. The work of a block is d*n in, m*n rebuilt out, 32(d+m) digest
bytes out (`work_decode.decode_bytes_per_block`: 1,179,936 B at 8 data
shards and m = 1) over the published 819 GB/s, m read off the `missing`
label of `minio_tpu_decode_device_blocks_total`. The zero blocks that pad a
batch to the kernel's multiple of 16 are waste, not work, so half-empty
batches halve the share. Busy time is the union of all operations on the
device: no kernel name is matched. A program that does not split its decode
counters by `missing` (an older commit) gives None, never 0.
Source: device_trace. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench import get_counters as g
from chipbench import work, work_decode


def read(w):
    if not w.trace or not w.trace.get("busy_s") or not w.trace.get("dispatches") \
            or w.traced_before is None:
        return None
    now, then = (g.missing_rows(s, g.DEVICE_BLOCKS) for s in (w.after, w.traced_before))
    calls = w.traced_delta(g.DISPATCHES)
    if now is None or then is None or not calls or calls <= 0:
        return None
    moved = sum((now[m] - then.get(m, 0.0)) * work_decode.decode_bytes_per_block(w.data_shards, m)
                for m in now)
    least_s = w.trace["dispatches"] * moved / calls / work.peaks(w.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / w.trace["busy_s"] if moved > 0 else None
