"""Which rung of the ladder rebuilt the degraded reads: device reconstruct
dispatches that did not go where the configuration's `expects.decode_rung`
says, counted since boot on a scrape made after the run, at rest; swallowed
mega-kernel failures; and, where the window acknowledged GETs, no dispatch
of the expected rung inside the window counts 1 (as `device_rung.py` does it
for encodes). Off the TPU Mosaic cannot run: a CPU rehearsal expects every
dispatch on the XLA rung. It reads series the program has exported since
PR 21 (`minio_tpu_decode_dispatches_total{rung}`,
`minio_tpu_fused_decode_failures_total`), so an older commit under these
files is held to the same.

What it receives: a `verify.Verification`."""

from chipbench.procs import scrape, total


def run(v):
    tpu = scrape(v.srv.port, "/api/tpu")
    want = v.config["expects"]["decode_rung"] if v.platform == "tpu" else "xla"
    if v.platform != "tpu":
        v.note("rehearsal: the fused decode rung is not expected off the TPU")
    name = "minio_tpu_decode_dispatches_total"
    rungs = {r: total(tpu, name, rung=r) for r in ("fused", "xla")}
    v.details["decode_dispatches_since_boot"] = rungs
    v.details["window_decode_dispatches"] = v.delta(name, rung=want)
    t0, t1 = v.window
    gets = any(r.op == "GET" and r.status == 200 and t0 <= r.done <= t1 for r in v.records)
    none_in_window = gets and v.delta(name, rung=want) <= 0
    elsewhere = sum(n for r, n in rungs.items() if r != want)
    return {
        "decode_rung": (elsewhere + (1 if none_in_window else 0), 0),
        "fused_decode_failures": (total(tpu, "minio_tpu_fused_decode_failures_total"), 0),
    }
