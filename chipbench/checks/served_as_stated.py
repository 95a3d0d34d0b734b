"""Every GET was served as the deployment states: rebuilt where drives are
offline, healthy where none are. Since boot, on a scrape made at rest, after
the steps that GET (`readback`, `degraded_reference_setup`) have run:

- `get_blocks_not_as_stated`: the stripe blocks the program rebuilt
  (`minio_tpu_decode_blocks_total`, every family) against what the state
  asks for — with drives offline one per MiB of EVERY GET answered 200 (the
  generator's since set-up, `readback`'s and the reference step's), with none
  offline 0. Fewer means a GET was served healthy (the drives came back, the
  cache answered); more, or any at all on a healthy set, means a GET was
  served degraded that no state asked for.
- `decode_off_rung`: device reconstruct dispatches that did not go where the
  configuration's `expects.decode_rung` says (absent: no dispatch at all is
  expected, and every one counts), plus 1 where a rung is named, the window
  acknowledged GETs and none of its dispatches lies inside the window. Off
  the TPU Mosaic cannot run: a rehearsal expects `xla` wherever a rung is
  named.
- `decode_missing_not_as_stated`: dispatches that rebuilt another number of
  shards than `deployment.offline_data_shards` (the data shards the offline
  drives hold of every object); skipped for a program that does not split its
  dispatches by `missing`.
- `fused_decode_failures`: swallowed mega-kernel failures, as `decode_rung`
  counts them.

What it receives: a `verify.Verification`; `details.readback_keys` and
`details.reference_gets` of the steps before it."""

from chipbench.procs import scrape, total
from chipbench.reference import BLOCK

DISPATCHES = "minio_tpu_decode_dispatches_total"
FIRST_CALLS = "minio_tpu_decode_first_calls_total"


def run(v):
    dep = v.config["deployment"]
    offline = dep.get("offline_drives", [])
    tpu = scrape(v.srv.port, "/api/tpu")
    rebuilt = total(tpu, "minio_tpu_decode_blocks_total")
    answered = sum(r.nbytes for r in v.records if r.op == "GET" and r.status == 200) // BLOCK
    answered += (v.details.get("readback_keys", 0) + v.details.get("reference_gets", 0)) \
        * v.mix["object_mib"]
    v.details["blocks_rebuilt_since_boot"] = rebuilt
    v.details["get_blocks_since_boot"] = answered

    want = v.config["expects"].get("decode_rung")
    if want and v.platform != "tpu":
        want = "xla"
        v.note("rehearsal: the fused decode rung is not expected off the TPU")
    rungs = {r: total(tpu, DISPATCHES, rung=r) for r in ("fused", "xla")}
    v.details["decode_dispatches_since_boot"] = rungs
    # first device reconstructs that ended between the window's two scrapes:
    # each compiled on a GET's thread (0 where the warm-up met them all)
    v.details["window_decode_first_calls"] = (
        v.delta(FIRST_CALLS) if FIRST_CALLS in v.after else None)
    off_rung = sum(n for r, n in rungs.items() if r != want)
    if want:
        in_window = v.delta(DISPATCHES, rung=want)
        v.details["window_decode_dispatches"] = in_window
        t0, t1 = v.window
        gets = any(r.op == "GET" and r.status == 200 and t0 <= r.done <= t1 for r in v.records)
        off_rung += 1 if gets and in_window <= 0 else 0
    by_missing = [(labels["missing"], n) for labels, n in tpu.get(DISPATCHES, [])
                  if "missing" in labels]
    m = dep.get("offline_data_shards", 0)
    return {
        "get_blocks_not_as_stated": (abs((answered if offline else 0) - rebuilt), 0),
        "decode_off_rung": (off_rung, 0),
        "decode_missing_not_as_stated": (sum(n for lab, n in by_missing if int(lab) != m), 0),
        "fused_decode_failures": (total(tpu, "minio_tpu_fused_decode_failures_total"), 0),
    }
