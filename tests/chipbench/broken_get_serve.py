"""`chipbench.serve` with the READ path broken underneath, for the tests and
for the control runs on the chip of the degraded-GET cell; the sibling of
`broken_serve.py`, which breaks the write path. `CHIPBENCH_FAULT` names the
fault; the harness is pointed here with its hidden `--launcher` option and
must then report `correct: false`. Each breaks the guarantee one step down:

- `rebuilt-flip` — an answer altered where it is produced: one byte of the
  first shard every reconstruct rebuilt is flipped, on whatever rung rebuilt
  it. The GET answers 200 with the right ETag (it is the md5 stored at PUT)
  and a wrong body: `answers_wrong`, `degraded_reference_wrong`.
- `host-decode` — the device is not what rebuilds: every group falls under
  the device floor (`MINIO_TPU_DECODE_MIN_SHARDS` beyond any window), so the
  host's GF apply answers every read, correctly: `decode_rung`.
- `drives-online` — the deployment's state is not enacted: the storage
  fault rule is taken and never armed, the drives stay online and the
  healthy read path answers without rebuilding anything: `all_degraded`.

What it receives: the server's own command line, passed on to
`chipbench.serve.main`."""

from __future__ import annotations

import os
import sys


def arm(fault: str) -> None:
    if fault == "rebuilt-flip":
        from minio_tpu.erasure.coder import ErasureCoder

        orig = ErasureCoder.reconstruct_data_flat

        def flipped(self, survivors, present, missing, pool=None):
            import numpy as np

            rec = np.array(orig(self, survivors, present, missing, pool))
            rec[0, 0, 0] ^= 0x01
            return rec

        ErasureCoder.reconstruct_data_flat = flipped
    elif fault == "host-decode":
        os.environ["MINIO_TPU_DECODE_MIN_SHARDS"] = str(1 << 30)
    elif fault == "drives-online":
        from minio_tpu import fault as fault_pkg
        from minio_tpu.fault import registry

        orig_inject = registry.inject

        def unarmed(spec):
            if spec.get("boundary") == "storage":
                return 0  # taken, acknowledged, never armed
            return orig_inject(spec)

        registry.inject = fault_pkg.inject = unarmed
    else:
        raise SystemExit(f"broken_get_serve: unknown CHIPBENCH_FAULT {fault!r}")


if __name__ == "__main__":
    arm(os.environ.get("CHIPBENCH_FAULT", ""))
    from chipbench.serve import main

    main(sys.argv[1:])
