"""The stated guarantee, "readable with p of the d+p drives missing": a
seeded sample of the objects the window wrote, `verify.degraded_objects` of
them, is read back with p of its d+p object directories removed (drawn from
the seed), bitrot verification and reconstruction included."""

from chipbench.verify import get_differs, remove_object_dirs


def run(v):
    p = v.config["deployment"]["parity_shards"]
    rng = v.rng("degraded_read")
    pool = v.pool()
    sample = rng.sample(pool, min(v.mix["verify"]["degraded_objects"], len(pool)))
    r = v.cli.admin("POST", "cache/clear")
    if r.status != 200:
        v.note(f"cache/clear -> {r.status}")
    wrong = 0
    for key in sample:
        gone = rng.sample(range(len(v.srv.drives)), p)
        v.spoiled.add(key)
        remove_object_dirs(v.srv.drives, v.bucket, key, gone)
        why = get_differs(v.cli, v.bucket, key, *v.expected(key),
                          v.mix["verify"].get("timeout_s", 60.0))
        if why:
            wrong += 1
            v.note(f"degraded {key} (drives {sorted(gone)} removed): {why}")
    v.details["degraded_objects"] = len(sample)
    return {"degraded_read_wrong": (wrong, 0)}
