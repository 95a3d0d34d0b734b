"""The device boundary speaks: compile-cache placement, one process per
chip in the worker pool, and the first swallowed failure of each ladder
rung on stderr (minio_tpu/ops/runtime.py, server/worker.py)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from minio_tpu import fault
from minio_tpu.ops import runtime
from minio_tpu.server import worker as workermod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, **env_overrides) -> dict:
    """Run `code` in a fresh interpreter on the CPU; it prints one JSON
    object. MINIO_* and the cache variable are scrubbed first."""
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("MINIO_") and k != runtime.CACHE_ENV
    }
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_overrides)
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


_ENSURE = """
import json, jax
updates = []
real = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real(k, v))[1]
from minio_tpu.ops import runtime
print(json.dumps({"dir": runtime.ensure_compile_cache(),
                  "again": runtime.ensure_compile_cache(),
                  "jax": jax.config.jax_compilation_cache_dir,
                  "updates": updates}))
"""


def test_cache_dir_from_environment_is_left_to_jax(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    out = _run_py(_ENSURE, **{runtime.CACHE_ENV: placed})
    # JAX read the variable itself; this code set no cache path at all
    assert out["dir"] == out["again"] == out["jax"] == placed
    assert "jax_compilation_cache_dir" not in out["updates"]


def test_cache_dir_default_is_fixed_inside_the_checkout():
    want = os.path.join(REPO, ".jax_cache")
    assert runtime.DEFAULT_CACHE_DIR == want
    first, second = _run_py(_ENSURE), _run_py(_ENSURE)
    # two processes, one path: the path is part of the cache key, so a
    # temp name, pid or time in it would mean the cache never hits
    assert first["dir"] == second["dir"] == first["jax"] == want
    assert first["updates"] == ["jax_compilation_cache_dir"]


def test_resolve_cache_dir(monkeypatch):
    monkeypatch.setenv(runtime.CACHE_ENV, "/somewhere/else")
    assert runtime.resolve_cache_dir() is None
    monkeypatch.delenv(runtime.CACHE_ENV)
    assert runtime.resolve_cache_dir() == runtime.DEFAULT_CACHE_DIR


def test_supervisor_path_never_imports_jax():
    """A parent that touched JAX holds the chip and its children then fail
    or hang: the pool supervisor (app.main -> worker.supervise) and every
    helper it resolves must leave jax unimported."""
    out = _run_py(
        """
import json, sys
import minio_tpu.server.app
from minio_tpu.server import worker
from minio_tpu.cluster.endpoint import parse_endpoints, remote_nodes
from minio_tpu.utils import ellipses
worker.resolve_worker_count(); worker.worker_identity()
worker.resolve_port_base(9000); worker.plane()
remote_nodes(parse_endpoints(list(ellipses.expand("/tmp/x/d{1...4}")), 9000))
env = worker.worker_env(dict(), 1)
print(json.dumps({"jax": "jax" in sys.modules, "env": env}))
""",
        MINIO_TPU_WORKERS="2",
    )
    assert out["jax"] is False
    assert out["env"][workermod.ENV_BACKEND] == "numpy"


def test_exactly_one_worker_keeps_the_configured_backend():
    base = {"PATH": "/bin", workermod.ENV_COUNT: "3"}
    envs = [workermod.worker_env(base, i) for i in range(3)]
    assert [e[workermod.ENV_INDEX] for e in envs] == ["0", "1", "2"]
    # the device worker inherits (unset -> jax default device) ...
    assert workermod.ENV_BACKEND not in envs[workermod.DEVICE_WORKER]
    # ... every other worker is pinned to the CPU plane, explicitly
    assert [e.get(workermod.ENV_BACKEND) for e in envs[1:]] == ["numpy", "numpy"]
    # an operator who pinned the whole pool to numpy gets exactly that
    pinned = [
        workermod.worker_env({workermod.ENV_BACKEND: "numpy"}, i)
        for i in range(2)
    ]
    assert all(e[workermod.ENV_BACKEND] == "numpy" for e in pinned)
    assert base == {"PATH": "/bin", workermod.ENV_COUNT: "3"}  # not mutated


def test_plane_names_what_the_process_runs(monkeypatch):
    monkeypatch.setenv(workermod.ENV_BACKEND, "numpy")
    assert workermod.plane().startswith("cpu plane")
    monkeypatch.delenv(workermod.ENV_BACKEND)
    assert workermod.plane().startswith("device plane")


@pytest.fixture
def _fresh_reports(monkeypatch):
    monkeypatch.setattr(runtime, "_reported", set())
    fault.clear()
    yield
    fault.clear()


def test_first_fused_rung_failure_is_written_once(
    _fresh_reports, monkeypatch, capsys
):
    """The existing `kernel-fail` rule fails the mega-kernel rung; the
    ladder serves the batch from the XLA rung byte-identically — and the
    FIRST swallowed exception lands on stderr with type and message,
    later ones only in the counter."""
    from minio_tpu.ops import fused_pallas as fp
    from minio_tpu.ops import rs, rs_jax
    from minio_tpu.parallel.dispatcher import TpuDispatcher

    # the shape gate is False off-TPU; open it so the rung is attempted
    monkeypatch.setattr(fp, "supports", lambda d, p, b, n: True)
    d, p, n = 4, 2, 1024
    disp = TpuDispatcher(rs_jax.get_tpu_codec(d, p), n, window_s=0.0)
    blocks = np.random.default_rng(21).integers(
        0, 256, size=(2, d, n), dtype=np.uint8
    )
    fault.inject({"boundary": "tpu", "mode": "kernel-fail", "seed": 1})
    ref = rs.get_codec(d, p)
    for _ in range(2):
        disp._fused_cooldown = 0  # re-attempt the rung: it fails again
        parity, _digests = disp.encode(blocks)
        for b in range(2):
            np.testing.assert_array_equal(
                parity[b], ref.encode(ref.split(blocks[b].tobytes()))[d:]
            )
    assert disp.stats["fused_failures"] == 2
    assert disp.stats["numpy_blocks"] == 0  # XLA rung served, not numpy
    err = capsys.readouterr().err
    lines = [ln for ln in err.splitlines() if "backend ladder" in ln]
    assert len(lines) == 1, err
    assert "rung=fused" in lines[0] and "shape=4+2x16x1024" in lines[0]
    assert "RuntimeError: injected TPU kernel fault" in lines[0]


def test_first_device_rung_failure_is_written_once(_fresh_reports, capsys):
    from minio_tpu.ops import rs_jax
    from minio_tpu.parallel.dispatcher import TpuDispatcher

    disp = TpuDispatcher(rs_jax.get_tpu_codec(4, 2), 1024, window_s=0.0)
    blocks = np.zeros((1, 4, 1024), dtype=np.uint8)
    fault.inject({"boundary": "tpu", "mode": "device-lost", "seed": 1})
    for _ in range(2):
        disp.encode(blocks)
    assert disp.stats["device_faults"] == 2
    assert disp.stats["numpy_blocks"] == 2
    lines = [
        ln for ln in capsys.readouterr().err.splitlines()
        if "backend ladder" in ln
    ]
    assert len(lines) == 1, lines
    assert "rung=device" in lines[0]
    assert "RuntimeError: injected TPU device loss" in lines[0]


def test_first_fused_decode_failure_is_written_once(
    _fresh_reports, monkeypatch, capsys
):
    from minio_tpu.ops import bitrot_jax
    from minio_tpu.ops import fused_pallas as fp
    from minio_tpu.ops.highwayhash import MINIO_KEY
    from minio_tpu.ops.rs_jax import get_tpu_codec

    monkeypatch.setattr(fp, "supports", lambda d, p, b, n: True)

    def boom(*_a, **_k):
        raise ValueError("Mosaic said no")

    monkeypatch.setattr(fp, "fused_decode_hash_cm", boom)
    monkeypatch.setattr(bitrot_jax, "_fused_dec_cooldown", 0)
    surv = np.zeros((3, 4, 1024), dtype=np.uint8)
    before = bitrot_jax.decode_stats_snapshot()
    for _ in range(2):
        monkeypatch.setattr(bitrot_jax, "_fused_dec_cooldown", 0)
        assert bitrot_jax._try_fused_decode(
            get_tpu_codec(4, 2), surv, (1, 2, 3, 4), (0,), MINIO_KEY
        ) is None  # -> the caller's XLA rung
    after = bitrot_jax.decode_stats_snapshot()
    assert after["failures"] - before["failures"] == 2
    lines = [
        ln for ln in capsys.readouterr().err.splitlines()
        if "backend ladder" in ln
    ]
    assert len(lines) == 1, lines
    assert "rung=fused-decode" in lines[0]
    assert "ValueError: Mosaic said no" in lines[0]


def test_decode_rung_counters_are_exported(monkeypatch):
    """A degraded read rebuilt on the device moves a rung counter that a
    client can scrape (/api/tpu); one rebuilt on the host moves neither."""
    from minio_tpu.erasure.coder import ErasureCoder
    from minio_tpu.ops import bitrot_jax
    from minio_tpu.server.metrics import _g_api_tpu

    def series(name, by="rung"):
        """{label value: the series summed over its other labels}: the
        decode counters are split by `missing` too, and their sums over it
        are what they were before they had that label."""
        out: dict = {}
        for ln in _g_api_tpu(None):
            if ln.startswith(name + "{"):
                labels = dict(kv.split("=") for kv in ln.split("{")[1].split("}")[0].split(","))
                key = labels[by].strip('"')
                out[key] = out.get(key, 0.0) + float(ln.rsplit(" ", 1)[1])
        return out

    monkeypatch.setenv("MINIO_TPU_BACKEND", "jax")
    coder = ErasureCoder(4, 2)
    rng = np.random.default_rng(5)
    d, w, per = 4, 16, 256  # w * t = 96 >= MINIO_TPU_DECODE_MIN_SHARDS
    data = rng.integers(0, 256, size=(d, w, per), dtype=np.uint8)
    full = np.stack([
        coder._np.encode(np.concatenate(
            [data[:, i], np.zeros((2, per), np.uint8)]))
        for i in range(w)
    ], axis=1)  # [t, w, per]
    present, missing = (1, 2, 3, 4), (0,)
    before = series("minio_tpu_decode_dispatches_total")
    by_m = series("minio_tpu_decode_dispatches_total", by="missing")
    host = series("minio_tpu_decode_host_blocks_total", by="family")
    rec = coder.reconstruct_data_flat(full[list(present)], present, missing)
    np.testing.assert_array_equal(rec[0], data[0])
    after = series("minio_tpu_decode_dispatches_total")
    assert after["xla"] - before["xla"] == 1  # off-TPU rung
    assert after["fused"] == before["fused"]
    now_m = series("minio_tpu_decode_dispatches_total", by="missing")
    assert now_m["1"] - by_m["1"] == 1 and now_m["2"] == by_m["2"]
    blocks = series("minio_tpu_decode_device_blocks_total")
    assert blocks["xla"] >= w
    # two shards rebuilt at once: another row of the same series
    rec2 = coder.reconstruct_data_flat(full[[2, 3, 4, 5]], (2, 3, 4, 5), (0, 1))
    np.testing.assert_array_equal(rec2, data[:2])
    assert series("minio_tpu_decode_dispatches_total", by="missing")["2"] == by_m["2"] + 1
    after = series("minio_tpu_decode_dispatches_total")
    assert series("minio_tpu_decode_host_blocks_total", by="family") == host
    # below the device floor the host rebuilds: no rung counter moves
    small = coder.reconstruct_data_flat(
        full[list(present)][:, :2], present, missing
    )
    np.testing.assert_array_equal(small[0], data[0, :2])
    assert series("minio_tpu_decode_dispatches_total") == after
    moved = series("minio_tpu_decode_host_blocks_total", by="family")
    assert moved["reedsolomon"] - host["reedsolomon"] == 2
    assert bitrot_jax.decode_stats_snapshot()["xla"] >= 1


def test_device_results_in_a_foreign_host_layout_still_frame():
    """Found on the chip (PR 21): np.asarray of a TPU array can come back
    in the device's own layout, not row-major. Digest and parity ROWS are
    handed to the drives as writev buffers, and a strided row is refused there
    ('memoryview: underlying buffer is not C-contiguous') — every drive
    append failed and the PUT answered 500. The dispatcher owns the D2H
    boundary: whatever layout arrives, waiters get C-contiguous arrays."""
    import io

    from minio_tpu.erasure.coder import ErasureCoder
    from minio_tpu.ops import rs_jax
    from minio_tpu.ops.highwayhash import hash256_batch_numpy
    from minio_tpu.parallel.dispatcher import TpuDispatcher

    d, p, n = 4, 2, 1024
    disp = TpuDispatcher(rs_jax.get_tpu_codec(d, p), n, window_s=0.0)
    real = disp._encode_and_hash

    def foreign_layout(codec, blocks):
        parity, digests = real(codec, blocks)
        # same values, column-major strides: rows are no longer contiguous
        return np.asfortranarray(np.asarray(parity)), np.asfortranarray(
            np.asarray(digests)
        )

    disp._encode_and_hash = foreign_layout
    blocks = np.random.default_rng(3).integers(
        0, 256, size=(4, d, n), dtype=np.uint8
    )
    parity, digests = disp.encode(blocks)
    assert disp.stats["numpy_blocks"] == 0  # the device rung served it
    assert parity.flags.c_contiguous and digests.flags.c_contiguous
    np.testing.assert_array_equal(
        digests[0], hash256_batch_numpy(np.concatenate([blocks[0], parity[0]]))
    )
    # the framing the streaming PUT does, into a real file object: a data
    # shard's rows come from `blocks`, a parity shard's from the dispatch
    vecs = [[] for _ in range(d + p)]
    ErasureCoder(d, p)._frame_into(vecs, blocks, parity, digests)
    for i, row in ((0, blocks[0, 0]), (d, parity[0, 0])):
        sink = io.BytesIO()
        sink.writelines(vecs[i])
        assert sink.getvalue()[: 32 + n] == digests[0, i].tobytes() + row.tobytes()
        assert len(sink.getvalue()) == 4 * (32 + n)
