"""How the tests run `python3 -m chipbench`: as the driver does, a process
of its own from the root of a checkout, bounded by a timeout; the compile
cache goes to a directory of the test, never into the shared checkout."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench(cache_dir, *argv, cwd=REPO, timeout=420, **env_overrides):
    env = {k: v for k, v in os.environ.items() if not k.startswith("MINIO_")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["BENCH_RUN"] = "a-driver-side-label"  # must be ignored
    env.update(env_overrides)
    r = subprocess.run([sys.executable, "-m", "chipbench", *argv], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=timeout)
    lines = r.stdout.splitlines()
    last = None
    if lines and lines[-1].startswith("{"):
        row = json.loads(lines[-1])
        if "correct" in row:
            last = row
    return r, last
