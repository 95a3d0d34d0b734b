"""What the phase-clock readers under `metrics/` share: the program's
`minio_tpu_phase_*` series on `/api/tpu` (`obs.phase`: wall seconds, thread
CPU seconds and calls per named phase, layers `dispatch` and `put`). Every
row is there from the program's first scrape, at zero until the phase runs,
so a phase that never ran reads 0.0; a program without the phase clock (an
older commit) exports no such series, and then a reader returns None."""

from __future__ import annotations

SECONDS = "minio_tpu_phase_seconds_total"
CPU = "minio_tpu_phase_cpu_seconds_total"
CALLS = "minio_tpu_phase_calls_total"
GIB = float(1 << 30)


def dispatch_share(w, *phases: str) -> float | None:
    """Share of the window, in %, that the ONE dispatch thread spent in
    these phases."""
    if SECONDS not in w.after or w.seconds <= 0:
        return None
    took = sum(w.delta(SECONDS, layer="dispatch", phase=p) for p in phases)
    return 100.0 * took / w.seconds


def put_ms(w, *phases: str) -> float | None:
    """Mean ms per committed streaming PUT in these request-thread phases
    (a PUT commits once: calls of `put`/`commit`)."""
    if SECONDS not in w.after:
        return None
    puts = w.delta(CALLS, layer="put", phase="commit")
    if puts <= 0:
        return None
    return 1e3 * sum(w.delta(SECONDS, layer="put", phase=p) for p in phases) / puts


def cpu_s_per_gib(w, layer: str, *, only: str | None = None,
                  without: str | None = None) -> float | None:
    """Thread CPU seconds booked inside the layer's phases per GiB
    acknowledged: one phase (`only`), or all but one (`without`)."""
    if CPU not in w.after or w.acked_bytes <= 0:
        return None
    cpu = w.delta(CPU, layer=layer, phase=only) if only else w.delta(CPU, layer=layer)
    if without:
        cpu -= w.delta(CPU, layer=layer, phase=without)
    return cpu / (w.acked_bytes / GIB)
