"""Object layer, storage, front end — the key space at rest: the plain
reference (`chipbench/reference_keyspace.py`) replays every acknowledged PUT
and DELETE since boot, and a seeded sample of the keys it says exist,
`verify.live_keys` of them, set-up's and the window's alike, is asked for
over HTTP. A GET must return the body that was PUT, byte for byte, with the
model's ETag and size; a HEAD the model's ETag and size and no body.
`keyspace_live_wrong` counts the keys with an answer that differs.

`keyspace_answers_wrong` is the replay's other reading: every GET and HEAD
the run itself acknowledged, since boot, whose ETag and size were not the
model's entry for its key at that moment — `answers`' count, made by the
reference instead of by the generator.

What it receives: a `verify.Verification` whose records are the mixed
generator's (`generators/closed_loop_mixed.py`: `etag`, `length`)."""

from chipbench import reference_keyspace
from chipbench.verify import get_differs, threads


def head_differs(cli, bucket, key, md5: str, size: int, timeout: float) -> str:
    try:
        r = cli.request("HEAD", f"/{bucket}/{key}", timeout=timeout)
    except OSError as e:
        return f"{type(e).__name__}: {e}"
    if r.status != 200:
        return f"status {r.status}"
    if r.headers.get("etag", "").strip('"') != md5:
        return "ETag differs"
    if r.headers.get("content-length") != str(size):
        return f"Content-Length {r.headers.get('content-length')}, not {size}"
    return ""


def run(v):
    model, lied = reference_keyspace.replay(v.records)
    live = sorted(model.objects)
    sample = v.rng("keyspace_live").sample(live, min(v.mix["verify"]["live_keys"], len(live)))
    timeout = v.mix["verify"].get("timeout_s", 60.0)
    wrong: list = []

    def ask(key):
        md5, size = model.objects[key]
        body, sent_md5 = v.expected(key)
        why = "" if (sent_md5, len(body)) == (md5, size) else "the model's entry is not what was sent"
        why = why or get_differs(v.cli, v.bucket, key, body, md5, timeout)
        why = why or head_differs(v.cli, v.bucket, key, md5, size, timeout)
        if why:
            wrong.append(f"live {key}: {why}")

    threads(ask, sample)
    t0 = v.window[0]
    v.details["live_keys_at_rest"] = len(live)
    v.details["live_keys_asked"] = len(sample)
    v.details["live_keys_asked_written_in_window"] = sum(
        1 for k in sample if v.last[k].done >= t0)
    v.details["notes"] += wrong[:3] + [
        f"{r.op} {r.key}: answered ({r.etag}, {r.length}), not the model's entry" for r in lied[:3]]
    return {"keyspace_live_wrong": (len(wrong), 0), "keyspace_answers_wrong": (len(lied), 0)}
