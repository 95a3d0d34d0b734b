"""Dispatcher, ladder, kernels and the write path's quorum, at the state the
configuration states: for a seeded sample of the objects the window wrote,
`verify.ondrive_objects` of them, EVERY drive of the set is looked at. On a
drive that is online the object's shard file must be there and every frame
of it (digest + shard block, every stripe block) must be the reference's
Reed-Solomon shard and HighwayHash-256 for the erasure index that drive holds
(`reference_decode.shard_order`: MinIO's hashOrder); on a drive the
configuration states offline there must be NO file of the object — an
acknowledged PUT keeps a shard on every drive it could reach and on none it
could not. With nothing offline that is all d+p files, as `ondrive_frames`
compares them; with p drives offline it is exactly the write quorum's d.

What it receives: a `verify.Verification`; of the configuration
`deployment.offline_drives` (absent: none)."""

from chipbench import reference, reference_decode
from chipbench.verify import threads


def run(v):
    dep = v.config["deployment"]
    d, p, n = dep["data_shards"], dep["parity_shards"], dep["drives"]
    offline = set(dep.get("offline_drives", []))
    pool = v.pool()
    sample = v.rng("ondrive_online_frames").sample(
        pool, min(v.mix["verify"]["ondrive_objects"], len(pool)))
    wrong: list[str] = []
    stray: list[str] = []

    def ondrive(key):
        frames = reference.object_frames(v.expected(key)[0], d, p)
        order = reference_decode.shard_order(v.bucket, key, n)
        for pos, drive in enumerate(v.srv.drives):
            path = reference_decode.shard_path(drive, v.bucket, key)
            if pos in offline:
                if path is not None:
                    stray.append(f"{key}: a shard file on offline drive {pos}")
                continue
            if path is None:
                wrong.append(f"{key}: no shard file on online drive {pos}")
                continue
            with open(path, "rb") as f:
                if f.read() != frames[order[pos]]:
                    wrong.append(f"{key}: drive {pos} does not hold the reference's "
                                 f"shard {order[pos] + 1}")

    threads(ondrive, sample, n=4)
    v.details["ondrive_objects"] = len(sample)
    v.details["online_shards_compared"] = len(sample) * (n - len(offline))
    v.details["notes"] += wrong[:3] + stray[:3]
    return {"online_shards_wrong": (len(wrong), 0), "offline_shards_written": (len(stray), 0)}
