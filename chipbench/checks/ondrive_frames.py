"""Dispatcher, ladder, kernels: the on-drive frames (digest + shard block,
every stripe block, all d+p shard files) of a seeded sample of the objects
the window wrote, `verify.ondrive_objects` of them, against the reference's
Reed-Solomon parity and HighwayHash-256."""

from chipbench.verify import bad_shards, threads


def run(v):
    dep = v.config["deployment"]
    d, p = dep["data_shards"], dep["parity_shards"]
    pool = v.pool()
    sample = v.rng("ondrive_frames").sample(pool, min(v.mix["verify"]["ondrive_objects"],
                                                      len(pool)))
    bad: list = []

    def ondrive(key):
        n, why = bad_shards(v.srv.drives, v.bucket, key, v.expected(key)[0], d, p)
        if n:
            bad.append((n, why))

    threads(ondrive, sample, n=4)
    v.details["ondrive_objects"] = len(sample)
    v.details["ondrive_shards_compared"] = len(sample) * (d + p)
    v.details["notes"] += [why for _, why in bad[:3]]
    return {"ondrive_shards_wrong": (sum(n for n, _ in bad), 0)}
