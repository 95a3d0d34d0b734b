"""How the tests run `python3 -m chipbench`: as the driver does, a process
of its own from the root of a checkout, bounded by a timeout; the compile
cache goes to a directory of the test, never into the shared checkout."""

import hashlib
import json
import os
import shutil
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


def tree_sha256(root=REPO) -> dict:
    """{relative path: sha256} of BENCHMARK.json and every file under its
    `paths`, build leftovers aside."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        tops = json.load(f)["paths"]
    out = {}
    for top in tops:
        for dirpath, dirs, files in os.walk(os.path.join(root, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for fn in files:
                if not fn.endswith(".pyc"):
                    path = os.path.join(dirpath, fn)
                    with open(path, "rb") as f:
                        out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def scratch_copy(dst) -> dict:
    """A checkout of the benchmark alone in `dst`: BENCHMARK.json and the
    directories under `paths` copied, the program (`minio_tpu/`) linked.
    What a later PR does to the benchmark, a test does to this copy.
    -> the benchmark as read from the copy."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    for top in bench_json["paths"]:
        shutil.copytree(os.path.join(REPO, top), os.path.join(dst, top),
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "minio_tpu"), os.path.join(dst, "minio_tpu"))
    return bench_json


def add_cell(dst, fixture: str) -> dict:
    """What a `model_config` PR does, done to the scratch copy in `dst`: the
    files of `fixtures/<fixture>/chipbench/` are ADDED (one that is there
    already is an error, never overwritten) and the entries of its
    `entries.json` are APPENDED to BENCHMARK.json's lists. -> the entries."""
    src = os.path.join(REPO, "tests", "chipbench", "fixtures", fixture)
    for dirpath, _, files in os.walk(os.path.join(src, "chipbench")):
        for fn in files:
            to = os.path.join(dst, os.path.relpath(os.path.join(dirpath, fn), src))
            if os.path.exists(to):
                raise FileExistsError(f"{to}: the fixture would edit a file that is there")
            shutil.copy(os.path.join(dirpath, fn), to)
    with open(os.path.join(src, "entries.json")) as f:
        entries = json.load(f)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        bench_json = json.load(f)
    for group, more in entries.items():
        bench_json[group] += more
    with open(path, "w") as f:
        json.dump(bench_json, f, indent=1)
    return entries


def pytest_in(root, *args, timeout=600):
    """The copy's own tests, run by a pytest of their own from the copy's
    root: they find the benchmark from where they lie, so they police the
    copy. One process, no plugins that reorder or distribute."""
    env = {k: v for k, v in os.environ.items() if not k.startswith(("MINIO_", "PYTEST_"))}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider", "-p", "no:xdist",
         "-p", "no:randomly", "--rootdir", str(root), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
