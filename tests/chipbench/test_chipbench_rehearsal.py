"""The whole run, rehearsed on the CPU at tiny size: launcher, ladder,
window, verification, last line. `--rehearse` prints `"platform": "cpu"`
and never a device metric; without it, in a sandbox with no accelerator,
there is no result line and the exit code is not 0."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE) if HERE not in sys.path else None
from harness import REPO, RESULT_KEYS, bench  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-jax-cache")


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_one_cell_end_to_end(cell, cache, tmp_path):
    r, last = bench(cache, "--workload", cell, "--seed", str(2**31 + 77), "--seconds", "2",
                    "--trace", "0", "--rehearse", TMPDIR=str(tmp_path))
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert os.listdir(tmp_path) == []  # server log, control files, drives: all gone
    # the contract's keys, all of them; what else is there the driver ignores
    assert RESULT_KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    mine = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])]
    assert set(last["metrics"]) == {m["name"] for m in mine} >= {"setup_s", "s3_mib_s"}
    for m in mine:
        got = last["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    # it can never be read as a chip run
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    assert "memory_peak_bytes" in last["device"]
    # each number compared stands beside its limit, on stderr's last lines too
    assert all(set(c) == {"value", "limit"} and c["value"] <= c["limit"]
               for c in last["checks"].values())
    tail = r.stderr.strip().splitlines()[-len(last["checks"]):]
    assert all(ln.startswith("chipbench check ") and "(limit 0)" in ln for ln in tail)
    d = last["details"]
    assert d["ondrive_shards_compared"] == 32 and d["degraded_objects"] == 1
    assert d["keys_written_in_window"] >= 1 and d["readback_keys"] >= 2
    assert d["dispatcher_blocks_since_boot"] >= d["put_blocks_since_boot"] > 0
    # the drives were under this run's TMPDIR and nowhere else, and it says so
    assert last["drives_on"].endswith(f":{tmp_path}") and d["drives_gib_written"] > 0
    # an earlier line says how many requests the window held
    window = [json.loads(ln) for ln in r.stdout.splitlines()
              if ln.startswith('{"phase": "window"')]
    assert window and window[0]["requests"] == last["attempted"]


def test_traced_rehearsal_reports_counters_and_no_device_metric(cache):
    r, last = bench(cache, "--workload", CELLS[0], "--seed", "3", "--seconds", "2",
                    "--trace", "1", "--rehearse")
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    sys.path.insert(0, REPO) if REPO not in sys.path else None
    from chipbench.run import metric_names

    mine = metric_names(BENCH, "per_layer", CELLS[0])
    # every counter-fed reader of this cell reads something on the CPU too
    assert set(last["metrics"]) == {m["name"] for m in mine if m["source"] != "device_trace"}
    assert {"server_cpu_s_per_gib", "dispatch_queue_wait_ms", "window_compiles"} \
        <= set(last["metrics"])
    # the CPU has no device plane: a reader that finds nothing returns nothing
    assert not {"codec_roofline", "device_idle_share"} & set(last["metrics"])
    assert last["device"]["busy_s"] is None and last["device"]["window_s"] > 0
    assert "breakdown" not in last and last["correct"] is True


def test_refuses_to_pass_without_a_tpu(cache, tmp_path):
    """As the driver runs it, here, where JAX is held to the CPU."""
    r, last = bench(cache, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                    "--trace", "0", JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    assert r.returncode != 0 and last is None, r.stdout[-2000:]
    assert "no TPU" in r.stderr and "platform=cpu" in r.stderr
    assert os.listdir(tmp_path) == []  # and it leaves nothing behind


def test_needs_the_checkout_beside_it(tmp_path, cache):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` it fails, with no result line."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for top in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, top), tmp_path / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r, last = bench(cache, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                    "--trace", "0", cwd=tmp_path, timeout=120)
    assert r.returncode != 0 and last is None and '"correct"' not in r.stdout


def test_an_unknown_workload_fails(cache):
    r, last = bench(cache, "--workload", "no-such.cell", "--seed", "1", "--seconds", "1",
                    "--trace", "0", "--rehearse", timeout=120)
    assert r.returncode != 0 and last is None


def test_a_cell_added_as_data_files_is_found_without_an_edit(tmp_path, cache):
    """What a later PR does: a traffic file, a cell file and a BENCHMARK.json
    entry — no code. Here the same generator with other parameters: more
    clients (PERF.md's open question "clients 8 -> 16 -> 32")."""
    for top in ("chipbench", "tests/chipbench"):
        shutil.copytree(os.path.join(REPO, top), tmp_path / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "minio_tpu"), tmp_path / "minio_tpu")
    bench_json = json.loads(json.dumps(BENCH))
    bench_json["workloads"].append({
        "name": "ec12p4-16d.speedtest-put-16c", "config": "ec12p4-16d",
        "traffic": "speedtest-put-16c", "chips": 1, "why": "16 closed-loop clients"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench_json))
    with open(os.path.join(REPO, "chipbench", "traffic", "speedtest-put.json")) as f:
        mix = json.load(f)
    mix["clients"], mix["rehearse"]["clients"] = 16, 3
    (tmp_path / "chipbench" / "traffic" / "speedtest-put-16c.json").write_text(json.dumps(mix))
    (tmp_path / "chipbench" / "workloads" / "ec12p4-16d.speedtest-put-16c.json").write_text(
        json.dumps({"who": "a bigger ingest job"}))
    r, last = bench(cache, "--workload", "ec12p4-16d.speedtest-put-16c", "--seed", "9",
                    "--seconds", "3", "--trace", "0", "--rehearse", cwd=tmp_path)
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is True and last["metrics"]["s3_mib_s"]["value"] > 0
    assert last["details"]["readback_keys"] >= 3  # keys of all three clients


def test_a_traffic_file_that_names_no_generator_here_fails(tmp_path, cache):
    """A mix this tree has no generator for is an error, never another mix."""
    for top in ("chipbench", "tests/chipbench"):
        shutil.copytree(os.path.join(REPO, top), tmp_path / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "minio_tpu"), tmp_path / "minio_tpu")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    path = tmp_path / "chipbench" / "traffic" / "speedtest-put.json"
    mix = json.loads(path.read_text())
    mix["generator"] = "open_loop"
    path.write_text(json.dumps(mix))
    r, last = bench(cache, "--workload", CELLS[0], "--seed", "9", "--seconds", "1",
                    "--trace", "0", "--rehearse", cwd=tmp_path, timeout=120)
    assert r.returncode != 0 and last is None and "open_loop" in r.stderr
