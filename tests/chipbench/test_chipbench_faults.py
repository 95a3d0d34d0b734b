"""`correct` has teeth: the rest of a run is driven with the timed path
broken underneath (`broken_serve.py`), and must come out `correct: false`
with the count that catches the fault above its limit. `less-parity` is
the control — the deployment one step below what the configuration states —
kept here at a size a test run can hold; PERF.md gives its readings on the
chip at the cell's own size."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE) if HERE not in sys.path else None
from harness import bench  # noqa: E402

CASES = [
    # (cell, fault, a check that has to read above its limit)
    ("ec8p8-16d.speedtest-put", "less-parity", "degraded_read_wrong"),
    ("ec12p4-16d.speedtest-put", "less-parity", "ondrive_shards_wrong"),
    ("ec12p4-16d.speedtest-put", "parity-flip", "ondrive_shards_wrong"),
    ("ec8p8-16d.speedtest-put", "half-batch", "ondrive_shards_wrong"),
    ("ec12p4-16d.speedtest-put", "stale-write", "readback_wrong"),
    ("ec8p8-16d.speedtest-put", "wrong-etag", "answers_wrong"),
    ("ec12p4-16d.speedtest-put", "numpy-rung", "numpy_rung_blocks"),
]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-jax-cache")


@pytest.mark.parametrize("cell,fault,caught_by", CASES, ids=[f"{c[1]}-{c[0]}" for c in CASES])
def test_a_broken_path_is_not_correct(cell, fault, caught_by, cache):
    r, last = bench(cache, "--workload", cell, "--seed", "21", "--seconds", "2", "--trace", "0",
                    "--rehearse", "--launcher", "tests.chipbench.broken_serve",
                    CHIPBENCH_FAULT=fault)
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is False
    c = last["checks"][caught_by]
    assert c["value"] > c["limit"], last["checks"]
    assert f"chipbench check {caught_by}:" in r.stderr and "NOT CORRECT" in r.stderr
