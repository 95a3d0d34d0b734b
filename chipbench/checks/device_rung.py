"""Which rung of the ladder encoded: dispatches that did not go where the
configuration's `expects.device_rung` says. Counted since boot on a scrape
made after the run, when no dispatch is in flight (the fused counter moves
before the dispatch counter does, so a delta between two scrapes of a busy
server can be off by one); where the rung is `fused` and the window
acknowledged PUTs, at least one fused dispatch must lie inside the window."""

from chipbench.procs import scrape, total


def run(v):
    tpu = scrape(v.srv.port, "/api/tpu")
    dispatches = total(tpu, "minio_tpu_dispatch_total")
    fused = total(tpu, "minio_tpu_fused_dispatches_total")
    v.details["dispatches_since_boot"] = dispatches
    v.details["fused_dispatches_since_boot"] = fused
    v.details["window_dispatches"] = v.delta("minio_tpu_dispatch_total")
    if v.platform != "tpu":
        # Mosaic needs a TPU: a CPU rehearsal serves every shape from the XLA rung
        v.note("rehearsal: the fused rung is not expected off the TPU")
        return {"wrong_rung_dispatches": (fused, 0)}
    if v.config["expects"]["device_rung"] != "fused":
        return {"wrong_rung_dispatches": (fused, 0)}
    t0, t1 = v.window
    puts = any(r.op == "PUT" and r.status == 200 and t0 <= r.done <= t1 for r in v.records)
    none_in_window = puts and v.delta("minio_tpu_fused_dispatches_total") <= 0
    return {"wrong_rung_dispatches": (dispatches - fused + (1 if none_in_window else 0), 0)}
