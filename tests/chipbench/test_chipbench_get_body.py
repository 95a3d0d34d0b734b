"""`get_body_starved_share` (PR 35): the share of a GET body's writer's time
spent waiting for the next piece, Δ`get`/`body_wait` ÷ (Δ`body_wait` +
Δ`body_write`), in the three cells that GET. Its value by hand from a made-up
phase table; None — never 0, never an exception — from a program without the
two rows (the parent under these benchmark files) and on a zero denominator;
its entry in `BENCHMARK.json` held by name and by its three cells, wherever
later PRs append; the traced rehearsal of each of the three cells reports it."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE) if HERE not in sys.path else None
from harness import REPO, bench  # noqa: E402

sys.path.insert(0, REPO) if REPO not in sys.path else None
from chipbench import metrics  # noqa: E402
from chipbench.procs import parse_metrics  # noqa: E402

NAME = "get_body_starved_share"
CELLS = ["ec8p8-16d-2off.degraded-get", "ec12p4-16d-4off.put-get", "ec12p4-16d.put-get"]
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def table(**seconds) -> dict:
    """A phase table with these `get` rows (wall seconds), as `/api/tpu`
    exports them."""
    return parse_metrics("\n".join(
        f'minio_tpu_phase_seconds_total{{layer="get",phase="{p}"}} {s}\n'
        f'minio_tpu_phase_calls_total{{layer="get",phase="{p}"}} 64'
        for p, s in seconds.items()))


def window(before, after):
    return metrics.Window(seconds=10.0, acked_bytes=2 << 30, server_cpu_s=30.0, before=before,
                          after=after, data_shards=8, parity_shards=8,
                          device_kind="TPU v5 lite")


def test_the_entry_is_held_by_name_and_by_its_three_cells():
    mine = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert mine == [{"name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
                     "layer": "front end", "moves": "s3_mib_s", "workloads": CELLS}]
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) > names.index("xla_decode_roofline")  # appended after PR 34's
    cells = {w["name"]: w for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for cell in CELLS:  # each reports the metric it moves
        assert cell in cells and cell in e2e["s3_mib_s"].get("workloads", [cell])
    # its layer is the one `get_respond_ms` named, letter for letter
    assert {m["layer"] for m in BENCH["per_layer"] if m["name"] == "get_respond_ms"} \
        == {"front end"}


def test_its_value_by_hand():
    before = table(respond=5.0, body_wait=1.0, body_write=10.0)
    after = table(respond=9.0, body_wait=1.6, body_write=17.4)
    # 0.6 s waited of 0.6 + 7.4 s: `respond` is the producer's and not in it
    assert metrics.reader(NAME).read(window(before, after)) == pytest.approx(7.5)
    serial = table(respond=9.0, body_wait=3.8, body_write=16.5)
    assert metrics.reader(NAME).read(window(before, serial)) == pytest.approx(100 * 2.8 / 9.3)
    assert metrics.reader(NAME).read(window(table(body_wait=0, body_write=0),
                                            table(body_wait=0, body_write=2.0))) == 0.0


@pytest.mark.parametrize("rows", [
    {"respond": 5.0, "start": 0.1, "native": 2.0},  # the parent: the read clock, no body rows
    {},                                            # a program without the phase clock
], ids=["parent", "no-clock"])
def test_a_program_without_the_rows_reads_nothing_and_does_not_raise(rows):
    old = table(**rows)
    assert metrics.reader(NAME).read(window(old, old)) is None
    assert NAME not in metrics.read_all([NAME, "window_compiles"], window(old, old))


def test_a_window_in_which_no_piece_was_written_reads_nothing():
    same = table(respond=5.0, body_wait=1.0, body_write=10.0)
    assert metrics.reader(NAME).read(window(same, same)) is None


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-jax-cache")


@pytest.mark.parametrize("cell", CELLS)
def test_the_traced_rehearsal_of_each_cell_that_gets_reports_it(cell, cache):
    r, last = bench(cache, "--workload", cell, "--seed", str(2 ** 31 + 135), "--seconds", "2",
                    "--trace", "1", "--rehearse")
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    got = last["metrics"][NAME]
    assert got["unit"] == "%" and 0.0 <= got["value"] <= 100.0
