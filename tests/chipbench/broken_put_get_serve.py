"""`chipbench.serve` with the mixed PUT + GET cells' guarantees broken
underneath, for the tests and for the control runs on the chip of
`ec12p4-16d-4off.put-get` and `ec12p4-16d.put-get`; the sibling of
`broken_serve.py` (the write path of the PUT cells) and `broken_get_serve.py`
(the read path of the degraded-GET cell, whose `host-decode` and
`drives-online` serve the 4-off cell as they stand). `CHIPBENCH_FAULT` names
the fault; the harness is pointed here with its hidden `--launcher` option and
must then report `correct: false`. Each breaks a guarantee one step down:

- `fewer-shards` — an acknowledged PUT that kept fewer shards than the write
  quorum: once a streaming PUT of the bucket has committed, its shard file on
  the first drive is removed, so a PUT to the set with four drives offline
  keeps 11 of the 12 it was acknowledged for: `online_shards_wrong`, and
  `readback_wrong` (11 shards cannot give back 12).
- `offline-written` — a PUT that wrote to an "offline" drive: once a
  streaming PUT of the bucket has committed, what its first drive took is
  copied, behind the fault rule's back, onto every drive the rule holds
  offline (a rule for reads alone would not do: failed reads open the drive's
  breaker, and that refuses writes too), so a window-written object has 16
  shard files where the deployment states 12: `offline_shards_written`.
- `one-drive-off` — a healthy deployment whose state does not hold: once the
  first set-up object is written, the drive at position 3 of every set is
  taken offline by the storage fault rule, as a failed drive would be; the
  GETs of objects whose data shard it holds are served degraded where the
  configuration states none offline: `get_blocks_not_as_stated`.

What it receives: the server's own command line, passed on to
`chipbench.serve.main`."""

from __future__ import annotations

import os
import sys

BUCKET = "chipbench"  # the harness's bucket (`chipbench/run.py`)


def after_a_window_put(then) -> None:
    """`then(set, bucket, obj)` once a streaming PUT of the harness's bucket
    has committed; the set-up objects stay as they were written."""
    from minio_tpu.erasure.set import ErasureSet

    orig = ErasureSet._put_object_streaming

    def wrapped(self, bucket, obj, *a, **kw):
        oi = orig(self, bucket, obj, *a, **kw)
        if bucket == BUCKET and not obj.startswith("obj/"):
            then(self, bucket, obj)
        return oi

    ErasureSet._put_object_streaming = wrapped


def arm(fault: str) -> None:
    if fault == "fewer-shards":
        after_a_window_put(lambda es, bucket, obj: es.disks[0].delete(bucket, obj, recursive=True))
    elif fault == "offline-written":
        import shutil

        from minio_tpu.fault import registry

        def also_there(es, bucket, obj):
            # behind the fault rule's back: what drive 0 took, onto every
            # drive the rule holds offline
            src = os.path.join(es.disks[0].endpoint, bucket, obj)
            for disk in es.disks[1:]:
                if registry.check("storage", disk.endpoint, "create_file", modes=("error",)):
                    shutil.copytree(src, os.path.join(disk.endpoint, bucket, obj),
                                    dirs_exist_ok=True)

        after_a_window_put(also_there)
    elif fault == "one-drive-off":
        from minio_tpu.erasure.set import ErasureSet
        from minio_tpu.fault import registry

        orig = ErasureSet.put_object
        armed = []

        def then_a_drive_fails(self, bucket, obj, *a, **kw):
            oi = orig(self, bucket, obj, *a, **kw)
            if bucket == BUCKET and obj.startswith("obj/") and not armed:
                armed.append(registry.inject({"boundary": "storage", "mode": "error",
                                              "target": self.disks[3].endpoint}))
            return oi

        ErasureSet.put_object = then_a_drive_fails
    else:
        raise SystemExit(f"broken_put_get_serve: unknown CHIPBENCH_FAULT {fault!r}")


if __name__ == "__main__":
    arm(os.environ.get("CHIPBENCH_FAULT", ""))
    from chipbench.serve import main

    main(sys.argv[1:])
