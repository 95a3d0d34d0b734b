"""`chipbench.serve` with the `warp mixed` cell's guarantees broken
underneath, for the tests and for the control runs on the chip of
`ec12p4-16d-warp.warp-mixed`; the sibling of `broken_serve.py` (the write
path), `broken_get_serve.py` (the degraded read path) and
`broken_put_get_serve.py` (PUT + GET at quorum's edge). `CHIPBENCH_FAULT`
names the fault; the harness is pointed here with its hidden `--launcher`
option and must then report `correct: false`. Each breaks a guarantee one
step down:

- `delete-noop` — a DELETE that is acknowledged and removes nothing: the
  set's `delete_object` answers for the harness's bucket without touching a
  drive, so the key still answers GET and HEAD and its files lie where they
  were: `keyspace_deleted_answering`, `keyspace_deleted_files_left`, and
  `trash_moved_not_as_deleted` (a DELETE that moved nothing aside).
- `stale-head` — a HEAD that answers for another object: a stat of a key of
  the harness's bucket is given the ETag of another object statted before it,
  so a HEAD's ETag is not the md5 of what was PUT under its key:
  `answers_wrong` (and the reference's own count, `keyspace_answers_wrong`).
- `trash-kept` — the cheaper server, which skips the removal: the drives'
  reclaimers are handed nothing, so what every DELETE renamed into the trash
  stays there: `trash_entries_left` and `trash_moved_not_reclaimed`, and
  nothing else — every answer is right.
- `put-lost` — an acknowledged PUT that does not last: once a PUT to a fresh
  key of the harness's bucket has committed, its `xl.meta` is removed on five
  drives, which leaves 11 where read quorum is 12, so the key the client was
  told is there cannot be read: `keyspace_live_wrong` (and the window's own
  GETs and HEADs of such keys fail).

What it receives: the server's own command line, passed on to
`chipbench.serve.main`."""

from __future__ import annotations

import os
import sys

BUCKET = "chipbench"  # the harness's bucket (`chipbench/run.py`)


def arm(fault: str) -> None:
    from minio_tpu.erasure.set import ErasureSet

    if fault == "delete-noop":
        from minio_tpu.erasure.types import ObjectInfo

        orig_delete = ErasureSet.delete_object

        def acknowledged_only(self, bucket, obj, version_id="", versioned=False):
            if bucket != BUCKET:
                return orig_delete(self, bucket, obj, version_id, versioned)
            return ObjectInfo(bucket=bucket, name=obj, version_id=version_id)

        ErasureSet.delete_object = acknowledged_only
    elif fault == "stale-head":
        orig_info = ErasureSet.get_object_info
        seen: list[str] = []

        def of_another_object(self, bucket, obj, version_id=""):
            oi = orig_info(self, bucket, obj, version_id)
            if bucket == BUCKET:
                other = next((e for e in seen if e != oi.etag), None)
                if oi.etag not in seen:
                    seen.append(oi.etag)
                if other is not None:
                    oi.etag = other
            return oi

        ErasureSet.get_object_info = of_another_object
    elif fault == "trash-kept":
        from minio_tpu.storage.xlstorage import TrashReclaimer

        TrashReclaimer.put = lambda self, path, nbytes: None
    elif fault == "put-lost":
        orig_put = ErasureSet.put_object

        def then_its_metadata_goes(self, bucket, obj, *a, **kw):
            oi = orig_put(self, bucket, obj, *a, **kw)
            if bucket == BUCKET and not obj.startswith(("obj/", "warm/")):
                for disk in self.disks[:5]:
                    os.remove(os.path.join(disk.endpoint, bucket, obj, "xl.meta"))
            return oi

        ErasureSet.put_object = then_its_metadata_goes
    else:
        raise SystemExit(f"broken_mixed_serve: unknown CHIPBENCH_FAULT {fault!r}")


if __name__ == "__main__":
    arm(os.environ.get("CHIPBENCH_FAULT", ""))
    from chipbench.serve import main

    main(sys.argv[1:])
