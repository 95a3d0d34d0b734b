"""The two readers of the continuous sampler's probes: `gil_wait_ms`
(`minio_tpu_gil_wait_seconds`: how late the sampler's wake-up came back —
the OS wake-up and the queue for the interpreter lock) and `loop_lag_ms`
(`minio_tpu_loop_lag_seconds`: a callback's wait for the event loop). By
hand on synthetic scrapes; None from a program without the series (the
parent, over whose checkout these files are laid too) and from a window in
which the sampler did not tick. Their entries are appended to `per_layer`
after `put_arena_batch_share`, in this order, and list all six cells."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from chipbench import metrics  # noqa: E402
from chipbench.procs import parse_metrics  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

ALL = ["ec12p4-16d.speedtest-put", "ec8p8-16d.speedtest-put", "ec8p8-16d-2off.degraded-get",
       "ec12p4-16d-4off.put-get", "ec12p4-16d.put-get", "ec12p4-16d-warp.warp-mixed"]
ENTRIES = [("gil_wait_ms", "server process"), ("loop_lag_ms", "front end")]
NAMES = [e[0] for e in ENTRIES]

EXPO = """\
minio_tpu_dispatch_total {dispatches}
minio_tpu_dispatch_blocks_total{{class="foreground"}} {blocks}
minio_tpu_gil_wait_seconds_bucket{{le="0.0001"}} 0
minio_tpu_gil_wait_seconds_bucket{{le="+Inf"}} {ticks}
minio_tpu_gil_wait_seconds_sum {gil_s}
minio_tpu_gil_wait_seconds_count {ticks}
minio_tpu_loop_lag_seconds_bucket{{le="+Inf"}} {handed}
minio_tpu_loop_lag_seconds_sum {lag_s}
minio_tpu_loop_lag_seconds_count {handed}
"""
BEFORE = dict(dispatches=10, blocks=640, ticks=100, gil_s=0.05, handed=100, lag_s=0.1)
# 969 ticks of the sampler in the window, a hand-off of each to the loop
AFTER = dict(dispatches=20, blocks=1280, ticks=1069, gil_s=2.957, handed=1069, lag_s=1.07)
WANT = {"gil_wait_ms": 1e3 * 2.907 / 969,  # 3.0 ms a wake-up
        "loop_lag_ms": 1e3 * 0.97 / 969}   # 1.0 ms a hand-off


def window(before, after, expo=EXPO):
    return metrics.Window(seconds=51.0, acked_bytes=8 << 30, server_cpu_s=200.0,
                          before=parse_metrics(expo.format(**before)),
                          after=parse_metrics(expo.format(**after)),
                          data_shards=12, parity_shards=4, device_kind="TPU v5 lite")


def test_the_entries_are_appended_after_put_arena_batch_share_in_this_order():
    """Held by name and by their place after the last entry before them,
    not by the end of the list: a later PR appends after them."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("put_arena_batch_share") + 1
    assert names[at:at + len(NAMES)] == NAMES
    for (name, layer), entry in zip(ENTRIES, BENCH["per_layer"][at:]):
        # a later cell may be appended to a list; these six stay its first
        assert dict(entry, workloads=entry["workloads"][:len(ALL)]) == {
            "name": name, "unit": "ms", "better": "lower", "source": "program_counter",
            "layer": layer, "moves": "s3_mib_s", "workloads": ALL}
    assert set(ALL) <= {w["name"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_the_mean_wait(name):
    assert metrics.reader(name).read(window(BEFORE, AFTER)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_window_in_which_the_sampler_did_not_tick_reads_nothing(name):
    assert metrics.reader(name).read(window(BEFORE, BEFORE)) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_series_reads_nothing_and_does_not_raise(name):
    """The parent's `/api/tpu`: dispatches move, the probes are not there."""
    old = "\n".join(ln for ln in EXPO.splitlines()
                    if "gil_wait" not in ln and "loop_lag" not in ln) + "\n"
    w = window(BEFORE, AFTER, old)
    assert metrics.reader(name).read(w) is None
    assert metrics.read_all([name, "dispatch_blocks_per_call"], w) == {
        "dispatch_blocks_per_call": 64.0}
