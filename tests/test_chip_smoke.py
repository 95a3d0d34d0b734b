"""chip_smoke.py, rehearsed on the CPU: the script's control flow end to
end at tiny size, and its teeth — without a TPU, or with a ladder rung
made to fail, it must exit non-zero and print no result line.

Each case runs the script from a COPY of the tree that holds only what
git would commit (no build products): chip_smoke.py removes and rebuilds
the native library, which must never happen under the feet of the other
xdist workers sharing this checkout — and the copy is what the driver's
checkout looks like. A chip result comes only from `python chip_smoke.py`
on the chip; nothing here is one."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("smoke-tree")
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), root / "chip_smoke.py")
    shutil.copytree(
        os.path.join(REPO, "minio_tpu"), root / "minio_tpu",
        ignore=shutil.ignore_patterns("__pycache__", "*.so", "*.so.tmp"),
    )
    return root


def _smoke(tree, *argv, **env_overrides):
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("MINIO_") and k != "JAX_COMPILATION_CACHE_DIR"
    }
    env.update(env_overrides)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", *argv], cwd=tree, env=env,
        capture_output=True, text=True, timeout=600,
    )
    rows = [json.loads(ln) for ln in r.stdout.splitlines() if ln.startswith("{")]
    return r, rows


def test_rehearsal_runs_end_to_end(tree):
    r, rows = _smoke(tree, "--rehearse", "--seed", "7")
    assert r.returncode == 0, r.stderr[-3000:]
    # the last line is the result, and it can never be read as a chip run
    assert json.loads(r.stdout.splitlines()[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    native = next(x for x in rows if x.get("phase") == "native")
    assert native["available"] and native["dataplane_available"]
    geos = {x["geometry"]: x for x in rows if x.get("phase") == "geometry"}
    assert sorted(geos) == ["ec12+4", "ec8+8"]
    for name, g in geos.items():
        c = g["counters"]
        assert c["dispatch_blocks"] >= c["full_blocks_put"] > 0, (name, c)
        assert c["numpy_blocks"] == c["device_faults"] == 0, (name, c)
        assert c["backend_level"] == 2 and c["fused_failures"] == 0, (name, c)
        assert g["bytes_and_etags_equal"] and g["degraded_get_equal"]
        assert g["post_heal_get_equal"] and g["healed_shards_equal_reference"]
        assert g["on_drive_shards_equal_reference"] == 16
        dec = g["degraded_decode"]
        assert dec["dispatches"] >= 1 and dec["host_blocks"] == 0, (name, dec)
        assert g["device"]["platform"] == "cpu"
    assert geos["ec12+4"]["shard_bytes"] == 87382
    # the in-checkout compile cache path, fixed relative to the tree
    total = next(x for x in rows if x.get("phase") == "compile-total")
    assert total["cache_dir"] == str(tree / ".jax_cache")
    assert total["programs"] > 0


def test_refuses_to_pass_without_a_tpu(tree):
    """As the driver runs it, in a sandbox with no accelerator."""
    r, rows = _smoke(tree, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert not any("ok" in x for x in rows), r.stdout[-2000:]
    assert "no TPU" in r.stderr and "platform=cpu" in r.stderr


def test_a_failed_rung_fails_the_run_and_is_named(tree):
    """The ladder answers every request correctly from the numpy rung;
    the script must fail anyway, and the server's stderr (relayed on
    failure) names the exception that was swallowed."""
    r, rows = _smoke(tree, "--rehearse", "--inject-fault", "device-lost")
    assert r.returncode != 0
    assert not any("ok" in x for x in rows), r.stdout[-2000:]
    assert "chip_smoke FAILED" in r.stderr
    assert "served by the numpy rung" in r.stderr
    assert "first failure on rung=device" in r.stderr
    assert "RuntimeError: injected TPU device loss" in r.stderr


def test_needs_the_checkout_beside_it(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repository it fails, with no result line."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0 and '"ok"' not in r.stdout
