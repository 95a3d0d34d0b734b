"""The whole run, rehearsed on the CPU at tiny size: launcher, ladder,
window, verification, last line. `--rehearse` prints `"platform": "cpu"`
and never a device metric; without it, in a sandbox with no accelerator,
there is no result line and the exit code is not 0.

Every cell of `BENCHMARK.json` is rehearsed. What the contract asks of a
run is asserted of every cell; what a check writes into `details` is
asserted where the cell's traffic file lists that check, so a cell with
other traffic brings its own data and edits nothing here."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE) if HERE not in sys.path else None
from harness import REPO, RESULT_KEYS, bench, scratch_copy  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def cell_data(cell: str) -> tuple[dict, dict]:
    """(the cell's traffic file at rehearsal size, its configuration's
    `deployment`), as the harness finds them: by the names in BENCHMARK.json."""
    sys.path.insert(0, REPO) if REPO not in sys.path else None
    from chipbench.traffic import load_mix

    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    cfg = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    with open(os.path.join(REPO, cfg["file"])) as f:
        return load_mix(entry["traffic"], rehearse=True), json.load(f)["deployment"]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-jax-cache")


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_one_cell_end_to_end(cell, cache, tmp_path):
    r, last = bench(cache, "--workload", cell, "--seed", str(2**31 + 77), "--seconds", "2",
                    "--trace", "0", "--rehearse", TMPDIR=str(tmp_path))
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert os.listdir(tmp_path) == []  # server log, control files, drives: all gone
    # -- what the contract asks of every cell
    # its keys, all of them; what else is there the driver ignores
    assert RESULT_KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    mine = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])]
    assert set(last["metrics"]) == {m["name"] for m in mine} >= {"setup_s"} and len(mine) >= 2
    for m in mine:
        got = last["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    # it can never be read as a chip run
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    assert "memory_peak_bytes" in last["device"]
    # each number compared stands beside its limit, on stderr's last lines too
    assert last["checks"] and all(set(c) == {"value", "limit"} and c["value"] <= c["limit"]
                                  for c in last["checks"].values())
    tail = r.stderr.strip().splitlines()[-len(last["checks"]):]
    for ln, (name, c) in zip(tail, last["checks"].items()):
        assert ln.startswith(f"chipbench check {name}: ") and f"(limit {c['limit']})" in ln
    # the drives were under this run's TMPDIR and nowhere else, and it says so
    assert last["drives_on"].endswith(f":{tmp_path}")
    # an earlier line says how many requests the window held
    window = [json.loads(ln) for ln in r.stdout.splitlines()
              if ln.startswith('{"phase": "window"')]
    assert window and window[0]["requests"] == last["attempted"]
    # -- what this cell's own data say: a detail is asserted where the traffic
    # file lists the check that writes it
    mix, dep = cell_data(cell)
    steps, d = mix["checks"], last["details"]
    assert all(f"{step}_s" in d for step in steps)  # every listed step ran
    if "ondrive_frames" in steps:  # every shard file of the sampled objects
        assert d["ondrive_shards_compared"] == mix["verify"]["ondrive_objects"] * dep["drives"]
    if "degraded_read" in steps:
        assert d["degraded_objects"] == mix["verify"]["degraded_objects"] >= 1
    if "readback" in steps:
        assert d["readback_keys"] >= 2
    if "blocks_dispatched" in steps:
        assert d["dispatcher_blocks_since_boot"] >= d["put_blocks_since_boot"] > 0
    if mix["generator"] == "closed_loop_put":
        assert d["keys_written_in_window"] >= 1 and d["drives_gib_written"] > 0
        # every step of these cells is an exact comparison
        assert all(c["limit"] == 0 for c in last["checks"].values())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]
                                  if w["traffic"] == "speedtest-put"])
def test_the_put_cells_assert_every_detail(cell):
    """For the cells that exist, the data select every assert above: two
    objects of 16 shard files each (32), one degraded object, `s3_mib_s`."""
    mix, dep = cell_data(cell)
    assert "s3_mib_s" in {m["name"] for m in BENCH["end_to_end"]
                          if cell in m.get("workloads", [cell])}
    assert {"ondrive_frames", "degraded_read", "readback", "blocks_dispatched"} <= set(
        mix["checks"]) and mix["generator"] == "closed_loop_put"
    assert mix["verify"]["ondrive_objects"] * dep["drives"] == 32
    assert mix["verify"]["degraded_objects"] == 1


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
    # the end-to-end metrics ride along on the traced line, the operations rate among them
    assert {m["name"] for m in BENCH["end_to_end"]} <= set(last["end_to_end_traced"])
    assert last["end_to_end_traced"]["s3_ops_s"] > 0


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
    bench_json = scratch_copy(tmp_path)
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
    scratch_copy(tmp_path)
    path = tmp_path / "chipbench" / "traffic" / "speedtest-put.json"
    mix = json.loads(path.read_text())
    mix["generator"] = "open_loop"
    path.write_text(json.dumps(mix))
    r, last = bench(cache, "--workload", CELLS[0], "--seed", "9", "--seconds", "1",
                    "--trace", "0", "--rehearse", cwd=tmp_path, timeout=120)
    assert r.returncode != 0 and last is None and "open_loop" in r.stderr
