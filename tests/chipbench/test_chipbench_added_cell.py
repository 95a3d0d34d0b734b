"""A cell with other traffic arrives as new files and appended entries.

What a later `model_config` PR may do to the benchmark is add files and
append entries; it may edit nothing that is there, these tests included. So
the benchmark's own tests must take such a PR as it comes. The proof: a
scratch copy of the benchmark gets a configuration, a traffic file with a
generator of its own (a closed loop of GETs: a window with no PUT, no
dispatch and no batch bucket), a cell and a per-layer metric, all from
`fixtures/added_cell/`; every file that was there keeps its sha256; and the
copy's own tests — the same files, finding the benchmark from where they
lie — pass there, the rehearsal of the new cell among them. And the pins
still bite where they should: what the tests say of the files that exist,
by name, fails when those files are changed.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE) if HERE not in sys.path else None
from harness import REPO, add_cell, bench, pytest_in, scratch_copy, tree_sha256  # noqa: E402

UNITS = "tests/chipbench/test_chipbench_units.py"
FOURTEEN = "tests/chipbench/test_chipbench_phase_metrics.py"
REHEARSAL = "tests/chipbench/test_chipbench_rehearsal.py"


def passed(out: str, test: str) -> bool:
    return any(test in ln and " PASSED" in ln for ln in out.splitlines())


def test_a_cell_with_its_own_generator_and_metric_is_added_without_an_edit(tmp_path):
    before = scratch_copy(tmp_path)
    was = tree_sha256(REPO)
    added = add_cell(tmp_path, "added_cell")
    cell, config = added["workloads"][0]["name"], added["configs"][0]["name"]
    metric = added["per_layer"][0]["name"]

    # nothing that was there changed: files by their sha256, entries by value
    now = tree_sha256(tmp_path)
    assert {k: now[k] for k in was if k != "BENCHMARK.json"} \
        == {k: v for k, v in was.items() if k != "BENCHMARK.json"}
    assert len(now) > len(was)
    with open(tmp_path / "BENCHMARK.json") as f:
        after = json.load(f)
    for key, value in before.items():
        kept = after[key][:len(value)] if isinstance(value, list) else after[key]
        assert kept == value, key
    assert after["workloads"][-1]["name"] == cell and after["per_layer"][-1]["name"] == metric
    assert after["per_layer"][-1]["workloads"] == [cell]

    # the copy's own policing tests, all three files, and the new cell's rehearsal
    r = pytest_in(tmp_path, UNITS, FOURTEEN,
                  f"{REHEARSAL}::test_rehearsal_runs_one_cell_end_to_end[{cell}]")
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-2000:]
    for test in (f"test_rehearsal_runs_one_cell_end_to_end[{cell}]",
                 f"test_config_entry_and_file[{config}]",
                 f"test_workload_entry_and_files[{cell}]",
                 f"test_metric_entry[{metric}]",
                 "test_the_benchmark_lists_exactly_these_fourteen",
                 "test_the_two_16_drive_sets_keep_their_shapes[ec8p8-16d-8-8-131072]",
                 "test_speedtest_put_is_8_clients_of_64_mib",
                 "test_every_cell_reports_what_its_per_layer_metrics_move",
                 "test_pairs_are_unique_and_every_config_is_used"):
        assert passed(r.stdout, test), test
    assert " FAILED" not in r.stdout and " ERROR" not in r.stdout

    # traced, the new cell reports the metric that came with it and none of
    # the fourteen, which list PUT cells only
    r, last = bench(tmp_path / "jax-cache", "--workload", cell, "--seed", str(2**31 + 78),
                    "--seconds", "2", "--trace", "1", "--rehearse", cwd=tmp_path)
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"][metric]["value"] > 0 and "put_ingest_ms" not in last["metrics"]
    assert set(last["metrics"]) <= {m["name"] for m in after["per_layer"]
                                    if cell in m.get("workloads", [cell])}
    # the own-loop warm-up ended on the counter the traffic file names, not at max_s
    mix = json.loads((tmp_path / "chipbench" / "traffic"
                      / f"{added['workloads'][0]['traffic']}.json").read_text())
    warm = mix["rehearse"]["warm"]
    assert warm["progress"] != "minio_tpu_dispatch_total"
    assert last["phases_s"]["warm_loop"] < warm["max_s"] / 2


def drop_a_cell_from_one_of_the_fourteen(root):
    path = root / "BENCHMARK.json"
    b = json.loads(path.read_text())
    m = next(m for m in b["per_layer"] if m["name"] == "put_md5_ms")
    m["workloads"].remove("ec12p4-16d.speedtest-put")
    path.write_text(json.dumps(b))


def swap_two_of_the_fourteen(root):
    path = root / "BENCHMARK.json"
    b = json.loads(path.read_text())
    names = [m["name"] for m in b["per_layer"]]
    i, j = names.index("put_md5_ms"), names.index("put_ingest_ms")
    b["per_layer"][i], b["per_layer"][j] = b["per_layer"][j], b["per_layer"][i]
    path.write_text(json.dumps(b))


def four_clients(root):
    path = root / "chipbench" / "traffic" / "speedtest-put.json"
    mix = json.loads(path.read_text())
    mix["clients"] = 4
    path.write_text(json.dumps(mix))


def eight_drives(root):
    path = root / "chipbench" / "configs" / "ec12p4-16d.json"
    cfg = json.loads(path.read_text())
    cfg["deployment"].update(drives=8, data_shards=4)
    path.write_text(json.dumps(cfg))


SPOILED = [
    (drop_a_cell_from_one_of_the_fourteen,
     f"{FOURTEEN}::test_the_benchmark_lists_exactly_these_fourteen"),
    (swap_two_of_the_fourteen, f"{FOURTEEN}::test_the_benchmark_lists_exactly_these_fourteen"),
    (four_clients, f"{UNITS}::test_speedtest_put_is_8_clients_of_64_mib"),
    (eight_drives, f"{UNITS}::test_the_two_16_drive_sets_keep_their_shapes"),
]


@pytest.mark.parametrize("spoil,test", SPOILED, ids=[s.__name__ for s, _ in SPOILED])
def test_the_pins_on_what_exists_still_bite(spoil, test, tmp_path):
    scratch_copy(tmp_path)
    spoil(tmp_path)
    r = pytest_in(tmp_path, test)
    # the test ran and failed (it passes in an unspoiled copy, above): exit
    # code 1, not a usage or collection error
    assert r.returncode == 1 and " FAILED" in r.stdout, r.stdout[-3000:] + r.stderr[-2000:]
