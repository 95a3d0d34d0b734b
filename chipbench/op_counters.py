"""What the readers of the `warp mixed` cell under `metrics/` share: the
program's phase clock by S3 operation (`obs.phase`, layer `op`: one row per
kind of object request, wall from the parsed request to the finished
response, booked by the front end), the rows under a stat (`stat`), a DELETE
(`delete`) and the trash reclaimers (`trash`), all on `/api/tpu`. Every row is
there from the program's first scrape, so a phase that never ran reads 0.0; a
program without a row (an older commit under these files) gives None, and so
does a window in which the phase was never entered.

What each function receives: a `metrics.Window`."""

from __future__ import annotations

from chipbench.phase_counters import CALLS, SECONDS

OPS = ("get_object", "head_object", "put_object", "delete_object")


def has_row(w, layer: str, phase: str) -> bool:
    return any(labels.get("layer") == layer and labels.get("phase") == phase
               for labels, _ in w.after.get(CALLS, []))


def calls(w, layer: str, phase: str) -> float | None:
    """Entries of the phase inside the window, or None without the row."""
    return w.delta(CALLS, layer=layer, phase=phase) if has_row(w, layer, phase) else None


def ms_per_call(w, layer: str, phase: str) -> float | None:
    """Mean wall ms per entry of the phase inside the window."""
    n = calls(w, layer, phase)
    if not n or n <= 0:
        return None
    return 1e3 * w.delta(SECONDS, layer=layer, phase=phase) / n
