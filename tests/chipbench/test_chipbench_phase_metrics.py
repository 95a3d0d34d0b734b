"""The phase-clock readers on a hand-written exposition: the value, 0.0 for
a phase that never ran, None on a zero denominator, and None from a program
that has no phase clock (an older commit under these benchmark files)."""

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

DISPATCH = ("wait", "window", "assemble", "pack", "h2d", "kernel", "d2h", "unpack", "frame",
            "numpy", "fanout")
PUT = ("ingest", "stage", "encode_wait", "frame", "md5", "drive_write", "commit", "drive_io")


def expo(seconds: dict, cpu: dict, calls: dict, first: dict) -> dict:
    """Every (layer, phase) row, as the program exports them: zero unless given."""
    lines = []
    for series, table in (("seconds", seconds), ("cpu_seconds", cpu), ("calls", calls)):
        for layer, names in (("dispatch", DISPATCH), ("put", PUT)):
            lines += [f'minio_tpu_phase_{series}_total{{layer="{layer}",phase="{p}"}} '
                      f'{table.get((layer, p), 0)}' for p in names]
    for (rung, bucket), (n, s) in first.items():
        lines.append(f'minio_tpu_dispatch_first_calls_total{{rung="{rung}",bucket="{bucket}"}} {n}')
        lines.append('minio_tpu_dispatch_first_call_seconds_total'
                     f'{{rung="{rung}",bucket="{bucket}"}} {s}')
    return parse_metrics("\n".join(lines))


BEFORE = expo(
    seconds={("dispatch", "wait"): 5.0, ("dispatch", "kernel"): 30.0, ("put", "ingest"): 1.0},
    cpu={("dispatch", "kernel"): 2.0, ("put", "drive_io"): 3.0},
    calls={("put", "commit"): 10},
    first={("fused", 64): (1, 5.0), ("fused", 256): (1, 20.0), ("xla", 1): (0, 0.0)})
AFTER = expo(
    seconds={("dispatch", "wait"): 5.5, ("dispatch", "window"): 0.5,
             ("dispatch", "assemble"): 0.25, ("dispatch", "pack"): 1.0,
             ("dispatch", "unpack"): 1.5, ("dispatch", "frame"): 0.25,
             ("dispatch", "h2d"): 0.5, ("dispatch", "d2h"): 2.0, ("dispatch", "kernel"): 33.0,
             ("put", "ingest"): 3.0, ("put", "encode_wait"): 16.0, ("put", "md5"): 1.0,
             ("put", "drive_write"): 3.0, ("put", "commit"): 1.0},
    cpu={("dispatch", "kernel"): 2.5, ("dispatch", "pack"): 1.0, ("dispatch", "wait"): 0.5,
         ("put", "drive_io"): 7.0, ("put", "md5"): 1.0, ("put", "ingest"): 2.0},
    calls={("put", "commit"): 20},
    first={("fused", 64): (1, 5.0), ("fused", 256): (1, 20.0), ("xla", 1): (1, 0.5)})


def window(before=BEFORE, after=AFTER, **kw):
    base = dict(seconds=10.0, acked_bytes=2 << 30, server_cpu_s=30.0, before=before,
                after=after, data_shards=8, parity_shards=8, device_kind="TPU v5 lite")
    base.update(kw)
    return metrics.Window(**base)


WANT = {
    "dispatch_thread_wait_share": 10.0,        # (0.5 + 0.5) / 10
    "dispatch_thread_host_copy_share": 30.0,   # 0.25 + 1.0 + 1.5 + 0.25
    "dispatch_thread_h2d_share": 5.0,
    "dispatch_thread_d2h_share": 20.0,
    "dispatch_thread_kernel_share": 30.0,
    "window_first_calls": 1.0,
    "first_call_s": 25.5,                      # since boot, not a delta
    "put_ingest_ms": 200.0,                    # 2 s over 10 commits
    "put_encode_wait_ms": 1600.0,
    "put_md5_ms": 100.0,
    "put_drive_write_ms": 400.0,               # drive_write + commit
    "put_threads_cpu_s_per_gib": 1.5,          # (1 + 2) CPU s over 2 GiB, drive_io left out
    "drive_io_cpu_s_per_gib": 2.0,
    "dispatch_thread_cpu_s_per_gib": 1.0,      # 0.5 + 1.0 + 0.5
}
NEW = [m for m in BENCH["per_layer"] if m["name"] in WANT]


def test_the_benchmark_lists_exactly_these_fourteen():
    """Each of the fourteen lists both `speedtest-put` cells (and no cell of
    other traffic but the warp cell in `window_first_calls`: its PUTs go
    through the dispatcher too), and they stand in `per_layer` in this order
    among themselves. What a later PR appends may stand after them, and a
    cell of other traffic is not theirs to list: `put_ingest_ms` finds
    nothing to read in a window of GETs."""
    assert len(NEW) == len(WANT) == 14
    put_cells = {w["name"] for w in BENCH["workloads"] if w["traffic"] == "speedtest-put"}
    both = {"ec12p4-16d.speedtest-put", "ec8p8-16d.speedtest-put"}
    also = {"window_first_calls": {"ec12p4-16d-warp.warp-mixed"}}
    for m in NEW:
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert both | also.get(m["name"], set()) <= set(m["workloads"]) \
            <= put_cells | also.get(m["name"], set())
        assert len(set(m["workloads"])) == len(m["workloads"])
        assert m["moves"] == ("setup_s" if m["name"] == "first_call_s" else "s3_mib_s")
        assert "roofline" not in m["name"] and "mfu" not in m["name"]
    # appended by PR 25 in this order (WANT's) after the seven that were there;
    # whatever comes later is appended after them
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:7] == [
        "server_cpu_s_per_gib", "dispatch_queue_wait_ms", "dispatch_blocks_per_call",
        "dispatch_thread_device_share", "window_compiles", "codec_roofline", "device_idle_share"]
    assert names[7:21] == [m["name"] for m in NEW] == list(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_the_value(name):
    assert metrics.reader(name).read(window()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_phase_that_never_ran_reads_zero(name):
    quiet = expo({}, {}, {("put", "commit"): 10}, {("xla", 1): (0, 0.0)})
    moved = expo({}, {}, {("put", "commit"): 12}, {("xla", 1): (0, 0.0)})
    assert metrics.reader(name).read(window(before=quiet, after=moved)) == 0.0


ZERO_DENOMINATOR = {
    "share": dict(seconds=0.0), "ms": dict(before=AFTER), "gib": dict(acked_bytes=0)}


@pytest.mark.parametrize("name", sorted(n for n in WANT if n not in
                                        ("window_first_calls", "first_call_s")))
def test_a_zero_denominator_reads_nothing(name):
    kind = "share" if name.endswith("_share") else "ms" if name.endswith("_ms") else "gib"
    w = window(**ZERO_DENOMINATOR[kind])
    assert metrics.reader(name).read(w) is None
    assert name not in metrics.read_all([name, "window_compiles"], w)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_the_phase_clock_reads_nothing_and_does_not_raise(name):
    """These files are laid over the parent's checkout too."""
    old = parse_metrics("minio_tpu_dispatch_total 3\nminio_tpu_device_seconds_total 1.5\n")
    assert metrics.reader(name).read(window(before=old, after=old)) is None
