"""Every stripe block of every acknowledged PUT went through the
dispatcher: acknowledged PUT MiB since boot less the dispatcher's blocks."""

from chipbench.procs import scrape, total


def run(v):
    put_blocks = sum(r.nbytes for r in v.records if r.op == "PUT" and r.status == 200) >> 20
    seen = total(scrape(v.srv.port, "/api/tpu"), "minio_tpu_dispatch_blocks_total")
    v.details["put_blocks_since_boot"] = put_blocks
    v.details["dispatcher_blocks_since_boot"] = seen
    return {"blocks_not_dispatched": (max(0, put_blocks - seen), 0)}
