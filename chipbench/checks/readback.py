"""Object layer + storage: the acknowledged body of a seeded sample of the
keys the window wrote, `verify.readback_keys_per_client` from every client,
is read back over HTTP and compared byte for byte, ETag too."""

from chipbench.verify import get_differs, threads


def run(v):
    rng = v.rng("readback")
    by_client: dict = {}
    for k in v.pool():
        by_client.setdefault(v.last[k].client, []).append(k)
    per = v.mix["verify"]["readback_keys_per_client"]
    chosen = [k for c in sorted(by_client)
              for k in rng.sample(by_client[c], min(per, len(by_client[c])))]
    wrong: list = []

    def readback(key):
        why = get_differs(v.cli, v.bucket, key, *v.expected(key),
                          v.mix["verify"].get("timeout_s", 60.0))
        if why:
            wrong.append(f"{key}: {why}")

    threads(readback, chosen)
    v.details["readback_keys"] = len(chosen)
    v.details["notes"] += wrong[:3]
    return {"readback_wrong": (len(wrong), 0)}
