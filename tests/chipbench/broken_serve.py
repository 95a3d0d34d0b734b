"""`chipbench.serve` with the timed path broken underneath, for the tests
and for the control runs on the chip. `CHIPBENCH_FAULT` names the fault;
the harness is pointed here with its hidden `--launcher` option and must
then report `correct: false`.

- `less-parity` — THE CONTROL: the deployment one step below what the
  configuration states (EC:8 -> EC:4, default parity 4 -> EC:2), the step
  that would tempt a later PR (less parity to compute, copy back and
  write). It breaks the guarantee "readable with p drives missing".
- `parity-flip` — an answer altered where it is produced: one parity byte
  of every device dispatch is flipped after the kernel returned.
- `stale-write` — a step that returns its state unchanged: a PUT is
  acknowledged (right ETag) but the object is never put in its place.
- `wrong-etag` — a token altered where it is produced: the PUT answers
  with an ETag that is not the md5 of the body.
- `half-batch` — half of the batch left out: the second half of every
  dispatch's parity comes back as zeros.
- `numpy-rung` — the device is not what serves: the program's own
  `tpu`-boundary fault rule `device-lost` is armed, so the ladder answers
  every request, correctly, from the CPU.
"""

from __future__ import annotations

import os
import sys


def _patch_parity(alter) -> None:
    """Wrap both device rungs' encode entry points; `alter(parity)` gets a
    writable host copy of the parity the kernel produced."""
    import numpy as np

    from minio_tpu.ops import bitrot_jax

    def wrap(fn):
        def broken(*a, **k):
            parity, digests = fn(*a, **k)
            parity = np.array(parity)
            alter(parity)
            return parity, np.asarray(digests)
        return broken

    bitrot_jax.encode_and_hash = wrap(bitrot_jax.encode_and_hash)
    try:
        from minio_tpu.ops import fused_pallas
    except Exception:  # noqa: BLE001 — no Mosaic off the TPU: the XLA rung is the only one
        return
    fused_pallas.fused_encode_hash_cm = wrap(fused_pallas.fused_encode_hash_cm)


def arm(fault: str) -> None:
    if fault == "less-parity":
        cur = os.environ.get("MINIO_STORAGE_CLASS_STANDARD")
        os.environ["MINIO_STORAGE_CLASS_STANDARD"] = "EC:4" if cur == "EC:8" else "EC:2"
    elif fault == "parity-flip":
        def flip(parity):
            parity.reshape(-1)[0] ^= 0x01
        _patch_parity(flip)
    elif fault == "half-batch":
        def drop(parity):
            parity[parity.shape[0] // 2:] = 0
        _patch_parity(drop)
    elif fault == "stale-write":
        from minio_tpu.storage.xlstorage import XLStorage

        orig = XLStorage.rename_data

        def stale(self, src_volume, src_path, fi, dst_volume, dst_path):
            if dst_volume.startswith("."):
                return orig(self, src_volume, src_path, fi, dst_volume, dst_path)
            return None  # acknowledged, and the drive stays as it was

        XLStorage.rename_data = stale
    elif fault == "wrong-etag":
        from minio_tpu.erasure.set import ErasureSet

        orig_info = ErasureSet._to_object_info

        def wrong(self, bucket, obj, fi):
            info = orig_info(self, bucket, obj, fi)
            if hasattr(info, "etag") and info.etag:
                info.etag = "0" * 32
            return info

        ErasureSet._to_object_info = wrong
    elif fault == "numpy-rung":
        from minio_tpu.fault import registry

        registry.inject({"boundary": "tpu", "mode": "device-lost", "seed": 1})
    else:
        raise SystemExit(f"broken_serve: unknown CHIPBENCH_FAULT {fault!r}")


if __name__ == "__main__":
    arm(os.environ.get("CHIPBENCH_FAULT", ""))
    from chipbench.serve import main

    main(sys.argv[1:])
