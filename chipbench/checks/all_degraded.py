"""No GET was served healthy: since boot, at rest, the stripe blocks the
program rebuilt (`minio_tpu_decode_blocks_total`, every family) against one
per MiB of every GET answered 200 — the generator's since set-up and the
`degraded_reference` step's own. With a data shard of every object out of
reach each of a GET's stripe blocks has to be rebuilt; fewer means the drives
came back (a breaker that let them in, files healed elsewhere) or the cache
answered, more means something rebuilt that no GET asked for.

What it receives: a `verify.Verification`; it runs after
`degraded_reference`, whose GETs `details.reference_gets` counts."""

from chipbench.procs import scrape, total
from chipbench.reference import BLOCK


def run(v):
    rebuilt = total(scrape(v.srv.port, "/api/tpu"), "minio_tpu_decode_blocks_total")
    answered = sum(r.nbytes for r in v.records if r.op == "GET" and r.status == 200)
    answered += v.details.get("reference_gets", 0) * v.mix["object_mib"] * BLOCK
    v.details["blocks_rebuilt_since_boot"] = rebuilt
    v.details["get_blocks_since_boot"] = answered // BLOCK
    return {"all_degraded": (abs(answered // BLOCK - rebuilt), 0)}
