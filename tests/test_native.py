"""Native C++ kernels must agree byte-for-byte with the Python references
(the role the reference's Go-asm deps play, SURVEY.md 2.9)."""

import numpy as np
import pytest

from minio_tpu import native
from minio_tpu.ops import gf, rs
from minio_tpu.ops.highwayhash import MINIO_KEY, hash256, hash256_batch_numpy

pytestmark = pytest.mark.skipif(not native.available(), reason="no native toolchain")

RNG = np.random.default_rng(3)


def _pure_matvec(m, data):
    r, k = m.shape
    out = np.zeros((r, data.shape[1]), dtype=np.uint8)
    for j in range(k):
        out ^= gf.MUL_TABLE[m[:, j][:, None], data[j][None, :]]
    return out


@pytest.mark.parametrize("d,p,n", [(2, 2, 1024), (8, 8, 131072), (12, 4, 87382), (5, 3, 33)])
def test_gf_apply_matches_pure(d, p, n):
    codec = rs.ReedSolomon(d, p)
    data = RNG.integers(0, 256, size=(d, n), dtype=np.uint8)
    np.testing.assert_array_equal(
        native.gf_apply(codec.parity_matrix, data),
        _pure_matvec(codec.parity_matrix, data),
    )


@pytest.mark.parametrize("n", [0, 1, 31, 32, 33, 100, 4097, 87382])
def test_hh256_matches_python(n):
    buf = RNG.integers(0, 256, size=n, dtype=np.uint8).tobytes()
    assert native.hh256(MINIO_KEY, buf) == hash256(buf)


def test_batch_and_fused():
    codec = rs.ReedSolomon(4, 2)
    data = RNG.integers(0, 256, size=(4, 4096), dtype=np.uint8)
    parity, digests = native.gf_encode_hash(codec.parity_matrix, data, MINIO_KEY)
    np.testing.assert_array_equal(parity, _pure_matvec(codec.parity_matrix, data))
    full = np.concatenate([data, parity])
    np.testing.assert_array_equal(digests, hash256_batch_numpy(full))
    np.testing.assert_array_equal(
        native.hh256_batch(MINIO_KEY, full), hash256_batch_numpy(full)
    )


def test_four_processes_importing_a_fresh_checkout_all_get_the_library(tmp_path):
    """A fresh checkout under xdist: every worker's first import finds no
    gfhash.so and builds it. Each builds to a temporary of its own and
    publishes a whole file, so none loads another's half-written output."""
    import os
    import shutil
    import subprocess
    import sys

    src = os.path.dirname(native.__file__)
    pkg = tmp_path / "minio_tpu" / "native"
    pkg.mkdir(parents=True)
    (tmp_path / "minio_tpu" / "__init__.py").write_text("")
    for name in os.listdir(src):
        if name.endswith((".py", ".cpp", ".h")):
            shutil.copy(os.path.join(src, name), pkg / name)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "MINIO_TPU_NO_NATIVE")}
    probe = ("import sys; from minio_tpu import native; "
             "assert native.__file__.startswith(sys.argv[1]), native.__file__; "
             "sys.exit(0 if native.available() else 3)")
    procs = [
        subprocess.Popen([sys.executable, "-c", probe, str(tmp_path)],
                         cwd=tmp_path, env=env, stderr=subprocess.PIPE)
        for _ in range(4)
    ]
    ends = [(p.wait(timeout=180), p.stderr.read().decode()) for p in procs]
    assert [rc for rc, _ in ends] == [0] * 4, ends
    assert sorted(n for n in os.listdir(pkg) if not n.endswith((".py", ".cpp", ".h"))
                  and n != "__pycache__") == ["gfhash.so"]
