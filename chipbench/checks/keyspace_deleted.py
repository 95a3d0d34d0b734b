"""The stated guarantee of a DELETE: a seeded sample of the keys whose DELETE
was acknowledged (`chipbench/reference_keyspace.py`'s replay: `deleted`),
`verify.deleted_keys` of them, is asked for over HTTP — GET and HEAD must
both answer 404 — and looked for on the drives: under the bucket no drive may
hold an `xl.meta` or a shard file of the key any more (what the DELETE moved
into the trash is `trash_reclaimed`'s). `keyspace_deleted_answering` counts
the keys that still answered, `keyspace_deleted_files_left` the files found.

What it receives: a `verify.Verification`; the layout on the drives is read
as written (`<drive>/<bucket>/<key>/xl.meta`, `.../<data dir>/part.N`),
nothing of the program is asked."""

import glob
import os

from chipbench import reference_keyspace
from chipbench.verify import threads


def files_left(drives, bucket, key) -> list[str]:
    out = []
    for drive in drives:
        at = glob.escape(os.path.join(drive, bucket, key))
        out += glob.glob(os.path.join(at, "xl.meta")) + glob.glob(os.path.join(at, "*", "part.*"))
    return out


def run(v):
    model, _ = reference_keyspace.replay(v.records)
    gone = sorted(model.deleted)
    sample = v.rng("keyspace_deleted").sample(
        gone, min(v.mix["verify"]["deleted_keys"], len(gone)))
    timeout = v.mix["verify"].get("timeout_s", 60.0)
    answering: list = []
    left: list = []

    def ask(key):
        for op in ("GET", "HEAD"):
            try:
                status = v.cli.request(op, f"/{v.bucket}/{key}", timeout=timeout).status
            except OSError as e:
                status = f"{type(e).__name__}: {e}"
            if status != 404:
                answering.append(f"deleted {key}: {op} -> {status}")
                break
        left.extend(files_left(v.srv.drives, v.bucket, key))

    threads(ask, sample)
    v.details["deleted_keys_at_rest"] = len(gone)
    v.details["deleted_keys_asked"] = len(sample)
    v.details["notes"] += answering[:3] + [f"left behind: {p}" for p in left[:3]]
    return {"keyspace_deleted_answering": (len(answering), 0),
            "keyspace_deleted_files_left": (len(left), 0)}
