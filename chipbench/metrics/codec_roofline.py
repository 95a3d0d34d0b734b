"""Share of its roofline, in %, at which the device ran the codec (encode +
bitrot hash, whatever implements it) in the traced interval: the least
time the chip could take for the stripe blocks of the dispatches in the
trace over the device's busy time in it.

Work and time are of the same dispatches: those whose programs ran inside
the trace (`trace["dispatches"]`, counted on the device's own line, see
`trace_reduce.py`), each with the mean blocks per dispatch that the
counters give over the traced interval (`minio_tpu_dispatch_blocks_total`
over `minio_tpu_dispatch_total`, padding excluded, so padded work is waste,
not work). A dispatch at an edge of the interval, counted on one side and
not the other, moves that mean by its difference from the others over their
number — nothing where all are as wide, as at 8+8 — and no longer the work
by a whole dispatch. Busy time is the union of all operations on the
device: no kernel name is matched.

The bound is HBM bytes: per block d*n in, p*n parity out, (d+p)*32 digest
bytes out (`work.encode_bytes_per_block`) over the published 819 GB/s. (A
bit-plane operation count, 2*8p*8d*n per block against the int8 peak, comes
out within about a tenth of it at 8+8; the operations side is an open
question in PERF.md.) Source: device_trace. Moves s3_mib_s."""

from chipbench import work


def read(w):
    if not w.trace or not w.trace.get("busy_s") or not w.trace.get("dispatches"):
        return None
    calls = w.traced_delta("minio_tpu_dispatch_total")
    if not calls or calls <= 0:
        return None
    blocks = w.trace["dispatches"] * w.traced_delta("minio_tpu_dispatch_blocks_total") / calls
    least_s = (blocks * work.encode_bytes_per_block(w.data_shards, w.parity_shards)
               / work.peaks(w.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / w.trace["busy_s"]
