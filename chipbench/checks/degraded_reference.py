"""The stated guarantee at the state the configuration states: a seeded
sample of the objects, `verify.reference_objects` of them, is read over
HTTP with the drives still offline, and the bytes GET returns are compared
with the plain reference's reconstruction (`reference_decode`) from the
shard files on the SURVIVING drives — every frame's HighwayHash-256
verified, the missing data shard rebuilt block by block — and with the body
PUT. The offline drives' shard files must still be there, untouched since
set-up (size and mtime as the generator noted them) and byte for byte the
reference's shard of the body PUT: the drives were out of reach, not gone.

What it receives: a `verify.Verification`; of the generator
(`generators/closed_loop_get.py`) `offline`, `offline_files` and `sent`. Its
own GETs are counted in `details.reference_gets` for `all_degraded`."""

import os

import numpy as np

from chipbench import reference_decode


def run(v):
    dep = v.config["deployment"]
    d, p = dep["data_shards"], dep["parity_shards"]
    keys = sorted(v.last)
    sample = v.rng("degraded_reference").sample(
        keys, min(v.mix["verify"]["reference_objects"], len(keys)))
    wrong = moved = gets = 0
    for key in sample:
        body, md5 = v.expected(key)
        obj = v.last[key].body
        try:
            r = v.cli.request("GET", f"/{v.bucket}/{key}",
                              timeout=v.mix["verify"].get("timeout_s", 60.0))
            got, status = r.body, r.status
            gets += status == 200
            etag = r.headers.get("etag", "").strip('"')
        except OSError as e:
            got, status, etag = b"", 0, f"{type(e).__name__}: {e}"
        try:
            files = reference_decode.read_shards(v.srv.drives, v.bucket, key,
                                                 skip=v.gen.offline)
            want = reference_decode.decode_object(files, d, p)
        except (ValueError, OSError) as e:
            want, files = None, {}
            v.note(f"{key}: the reference could not rebuild it: {e}")
        if status != 200 or got != want or got != body or etag != md5:
            wrong += 1
            v.note(f"degraded {key} (drives {v.gen.offline} offline, {len(files)} shard files "
                   f"read): status {status}, {len(got)} bytes, equal to the reference's "
                   f"{got == want}, to the body PUT {got == body}, ETag {etag == md5}")
        order = reference_decode.shard_order(v.bucket, key, dep["drives"])
        for i in v.gen.offline:
            path = reference_decode.shard_path(v.srv.drives[i], v.bucket, key)
            same = False
            if path is not None:
                st = os.stat(path)
                with open(path, "rb") as f:
                    held = f.read()
                try:
                    same = (st.st_size, st.st_mtime_ns) == v.gen.offline_files.get((obj, i)) \
                        and np.array_equal(
                            reference_decode.verified_shards({order[i]: held},
                                                             dep["shard_bytes"])[order[i]],
                            reference_decode.shard_of(body, d, p, order[i]))
                except reference_decode.BadFrame:
                    pass
            if not same:
                moved += 1
                v.note(f"{key}: the shard file on offline drive {i} is gone or was touched")
    v.details["reference_objects"] = len(sample)
    v.details["reference_gets"] = gets
    return {"degraded_reference_wrong": (wrong, 0), "offline_shards_touched": (moved, 0)}
