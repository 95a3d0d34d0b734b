"""What the read-side readers under `metrics/` share: the program's phase
clock for a GET on the reconstructing read path (`obs.phase`, layers `get`
and `decode`, the `minio_tpu_phase_*` series of `/api/tpu`) and its decode
counters split by the number of shards a dispatch rebuilt. "Per GET" is per
call of `get`/`start`, which the program books once for every read that
reaches that path. Every row is there from the program's first scrape, so a
phase that never ran reads 0.0; a program without these rows (an older
commit under these files) gives None, and so does a window without a GET.

What each function receives: a `metrics.Window`."""

from __future__ import annotations

from chipbench.phase_counters import CALLS, CPU, GIB, SECONDS

DISPATCHES = "minio_tpu_decode_dispatches_total"
DEVICE_BLOCKS = "minio_tpu_decode_device_blocks_total"
BLOCKS = "minio_tpu_decode_blocks_total"
FIRST_CALLS = "minio_tpu_decode_first_calls_total"
HEDGES = "minio_tpu_get_hedges_total"


def has_read_clock(w) -> bool:
    return any(labels.get("layer") == "get" for labels, _ in w.after.get(CALLS, []))


def gets(w) -> float | None:
    """Reads that reached the reconstructing path inside the window."""
    if not has_read_clock(w):
        return None
    n = w.delta(CALLS, layer="get", phase="start")
    return n if n > 0 else None


def ms_per_get(w, layer: str, *phases: str) -> float | None:
    """Mean wall ms per GET in these phases of the layer."""
    n = gets(w)
    if n is None:
        return None
    return 1e3 * sum(w.delta(SECONDS, layer=layer, phase=p) for p in phases) / n


def get_cpu_s_per_gib(w, *, only: str | None = None, without: str | None = None) -> float | None:
    """Thread CPU seconds booked inside layer `get` per GiB acknowledged:
    one phase, or all but one. `decode_wait` holds the `decode` leaves'."""
    if not has_read_clock(w) or w.acked_bytes <= 0:
        return None
    cpu = w.delta(CPU, layer="get", phase=only) if only else w.delta(CPU, layer="get")
    if without:
        cpu -= w.delta(CPU, layer="get", phase=without)
    return cpu / (w.acked_bytes / GIB)


def missing_rows(series: dict, name: str) -> dict[int, float] | None:
    """{shards rebuilt: value} of a decode counter, both rungs together, or
    None where the program does not split it by `missing`."""
    rows = [(labels, v) for labels, v in series.get(name, []) if "missing" in labels]
    if not rows:
        return None
    out: dict[int, float] = {}
    for labels, v in rows:
        out[int(labels["missing"])] = out.get(int(labels["missing"]), 0.0) + v
    return out
