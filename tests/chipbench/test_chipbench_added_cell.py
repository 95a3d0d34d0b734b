"""A cell with other traffic arrives as new files and appended entries.

What a later `model_config` PR may do to the benchmark is add files and
append entries; it may edit nothing that is there, these tests included. So
the benchmark's own tests must take such a PR as it comes. The proof: a
scratch copy of the benchmark gets a configuration, a traffic file with a
generator of its own (a closed loop of GETs: a window with no PUT, no
dispatch and no batch bucket), a cell and a per-layer metric, all from
`fixtures/added_cell/`; every file that was there keeps its sha256; and the
copy's own tests — the same files, finding the benchmark from where they
lie — pass there, the rehearsal of the new cell among them. EVERY
`test_chipbench_*.py` the copy holds but this one is run, found by glob and
not listed, so the file a later cell brings is policed from the day it
arrives: a test of it that holds its entries by their distance from the end
of a list (`[-1]`) fails here. And the holds still bite where they should:
what the tests say of the files and entries that exist, by name, fails when
those are changed.
"""

import ast
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
DEGRADED = "tests/chipbench/test_chipbench_degraded_get.py"
ONE_CELL = "test_rehearsal_runs_one_cell_end_to_end"
HELD = f"{DEGRADED}::test_the_cell_its_configuration_and_its_metrics_are_held_by_name_and_order"


def static_tests(root, cells) -> tuple[list[str], list[str]]:
    """(every `test_chipbench_*.py` the copy in `root` holds but this one, as
    found by glob; the tests of them that boot a server, to deselect by
    name). A test boots a server where it calls `bench(…)`: it runs a cell,
    which the tier-1 run does once already outside the copy. The rehearsal
    is deselected for `cells` only, so a cell the copy has gained keeps it."""
    def calls_bench(f) -> bool:
        return any(isinstance(n, ast.Call) and getattr(n.func, "id", "") == "bench"
                   for n in ast.walk(f))

    files = sorted(p for p in (root / "tests" / "chipbench").glob("test_chipbench_*.py")
                   if p.name != os.path.basename(__file__))
    rel = [str(p.relative_to(root)) for p in files]
    booting = [f"{r}::{f.name}" for p, r in zip(files, rel) for f in ast.parse(p.read_text()).body
               if isinstance(f, ast.FunctionDef) and f.name.startswith("test_")
               and f.name != ONE_CELL and calls_bench(f)]
    return rel, booting + [f"{REHEARSAL}::{ONE_CELL}[{c}]" for c in cells]


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

    # the copy's own policing tests: every test file it holds but this one,
    # found where they lie. Deselected by name: the tests that boot a server —
    # all but the new cell's rehearsal
    rel, booting = static_tests(tmp_path, [w["name"] for w in before["workloads"]])
    assert {UNITS, FOURTEEN, REHEARSAL, DEGRADED} <= set(rel) and len(rel) >= 5
    assert {f"{DEGRADED}::test_a_broken_read_path_is_not_correct",
            "tests/chipbench/test_chipbench_faults.py::test_a_broken_path_is_not_correct",
            f"{REHEARSAL}::test_a_cell_added_as_data_files_is_found_without_an_edit"} <= set(booting)
    r = pytest_in(tmp_path, *rel, *(a for t in booting for a in ("--deselect", t)))
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-2000:]
    ran = [ln.split(" PASSED")[0] for ln in r.stdout.splitlines() if " PASSED" in ln]
    assert [t for t in ran if ONE_CELL in t] == [f"{REHEARSAL}::{ONE_CELL}[{cell}]"]
    assert not [t for t in ran for off in booting if t.split("[")[0] == off]
    for test in (f"{ONE_CELL}[{cell}]",
                 f"test_config_entry_and_file[{config}]",
                 f"test_workload_entry_and_files[{cell}]",
                 f"test_metric_entry[{metric}]",
                 "test_the_benchmark_lists_exactly_these_fourteen",
                 HELD.split("::")[1],
                 "test_the_tail_and_the_five_later_readers_are_this_cells_too",
                 "test_reader_reads_the_value[get_frames_per_read]",
                 "test_the_put_cells_assert_every_detail[ec8p8-16d.speedtest-put]",
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


def test_a_later_test_file_that_pins_its_entries_to_the_end_of_a_list_fails_in_the_copy(tmp_path):
    """What PR 29's file did, and what the proof above now polices in any
    file: found by the glob from the day it arrives, it fails once a cell
    is appended after the one it pins."""
    before = scratch_copy(tmp_path)
    pinned = "tests/chipbench/test_chipbench_pinned.py"
    (tmp_path / pinned).write_text(
        "import json, os\n"
        "ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))\n"
        "def test_my_cell_stands_last():\n"
        "    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:\n"
        f"        assert json.load(f)['workloads'][-1]['name'] == {before['workloads'][-1]['name']!r}\n")
    assert pytest_in(tmp_path, pinned).returncode == 0
    add_cell(tmp_path, "added_cell")
    rel, _ = static_tests(tmp_path, [])
    assert pinned in rel
    r = pytest_in(tmp_path, pinned)
    assert r.returncode == 1 and " FAILED" in r.stdout, r.stdout[-3000:] + r.stderr[-2000:]


GET_CELL = "ec8p8-16d-2off.degraded-get"


def rewrite(path, change) -> None:
    """The copy's JSON file at `path`, altered in place by `change(data)`."""
    data = json.loads(path.read_text())
    change(data)
    path.write_text(json.dumps(data))


def named(entries: list, name: str) -> dict:
    return next(e for e in entries if e["name"] == name)


def swap(entries: list, a: str, b: str) -> None:
    names = [e["name"] for e in entries]
    i, j = names.index(a), names.index(b)
    entries[i], entries[j] = entries[j], entries[i]


def drop_a_cell_from_one_of_the_fourteen(root):
    rewrite(root / "BENCHMARK.json", lambda b: named(b["per_layer"], "put_md5_ms")[
        "workloads"].remove("ec12p4-16d.speedtest-put"))


def swap_two_of_the_fourteen(root):
    rewrite(root / "BENCHMARK.json", lambda b: swap(b["per_layer"], "put_md5_ms", "put_ingest_ms"))


def the_degraded_get_cell_on_four_chips(root):
    rewrite(root / "BENCHMARK.json", lambda b: named(b["workloads"], GET_CELL).update(chips=4))


def swap_two_of_the_thirteen(root):
    rewrite(root / "BENCHMARK.json", lambda b: swap(b["per_layer"], "get_join_ms", "decode_link_ms"))


def drop_the_cell_from_one_of_the_thirteen(root):
    rewrite(root / "BENCHMARK.json", lambda b: named(b["per_layer"], "get_read_wait_ms")[
        "workloads"].remove(GET_CELL))


def four_clients(root):
    rewrite(root / "chipbench" / "traffic" / "speedtest-put.json", lambda mix: mix.update(clients=4))


def eight_drives(root):
    rewrite(root / "chipbench" / "configs" / "ec12p4-16d.json",
            lambda cfg: cfg["deployment"].update(drives=8, data_shards=4))


SPOILED = [
    (drop_a_cell_from_one_of_the_fourteen,
     f"{FOURTEEN}::test_the_benchmark_lists_exactly_these_fourteen"),
    (swap_two_of_the_fourteen, f"{FOURTEEN}::test_the_benchmark_lists_exactly_these_fourteen"),
    (four_clients, f"{UNITS}::test_speedtest_put_is_8_clients_of_64_mib"),
    (eight_drives, f"{UNITS}::test_the_two_16_drive_sets_keep_their_shapes"),
    (the_degraded_get_cell_on_four_chips, HELD),
    (swap_two_of_the_thirteen, HELD),
    (drop_the_cell_from_one_of_the_thirteen, HELD),
]


@pytest.mark.parametrize("spoil,test", SPOILED, ids=[s.__name__ for s, _ in SPOILED])
def test_the_pins_on_what_exists_still_bite(spoil, test, tmp_path):
    scratch_copy(tmp_path)
    spoil(tmp_path)
    r = pytest_in(tmp_path, test)
    # the test ran and failed (it passes in an unspoiled copy, above): exit
    # code 1, not a usage or collection error
    assert r.returncode == 1 and " FAILED" in r.stdout, r.stdout[-3000:] + r.stderr[-2000:]
