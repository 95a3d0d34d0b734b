"""`put_arena_batch_share` (PR 37): the share of the streaming PUT's encode
batches that the pooled ingest arena made, from
`minio_tpu_put_batches_total{plane}`. By hand on synthetic scrapes: 100
where every batch took the arena, 0 where every one took the legacy copies,
a share between; None from a program without the counter (the parent, over
whose checkout these files are laid too) and from a window without a batch.
Its entry is appended to `per_layer` and lists the five cells that PUT."""

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

NAME = "put_arena_batch_share"
PUT_CELLS = ["ec12p4-16d.speedtest-put", "ec8p8-16d.speedtest-put", "ec12p4-16d.put-get",
             "ec12p4-16d-4off.put-get", "ec12p4-16d-warp.warp-mixed"]

EXPO = """\
minio_tpu_put_batches_total{{plane="arena"}} {arena}
minio_tpu_put_batches_total{{plane="copy"}} {copy}
minio_tpu_dispatch_pad_blocks_total 3
"""


def window(before, after):
    return metrics.Window(seconds=10.0, acked_bytes=4 << 30, server_cpu_s=30.0,
                          before=parse_metrics(EXPO.format(**before)),
                          after=parse_metrics(EXPO.format(**after)),
                          data_shards=12, parity_shards=4, device_kind="TPU v5 lite")


def test_the_entry_is_appended_and_lists_the_cells_that_put():
    """Held by name and by its place after PR 36's last entry, not by the
    end of the list: a later PR appends after it."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) == names.index("trash_worker_cpu_s_per_gib") + 1
    entry = BENCH["per_layer"][names.index(NAME)]
    # a later cell may be appended to its list; these five stay its first
    assert dict(entry, workloads=entry["workloads"][:5]) == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "server process", "moves": "s3_mib_s", "workloads": PUT_CELLS}
    assert set(PUT_CELLS) <= {w["name"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("before,after,want", [
    (dict(arena=3, copy=5), dict(arena=67, copy=5), 100.0),   # 64 batches, every one pooled
    (dict(arena=3, copy=5), dict(arena=3, copy=69), 0.0),     # the legacy plane
    (dict(arena=0, copy=0), dict(arena=30, copy=10), 75.0),
])
def test_reader_reads_the_share(before, after, want):
    assert metrics.reader(NAME).read(window(before, after)) == pytest.approx(want)


def test_a_window_without_a_batch_reads_nothing():
    w = window(dict(arena=7, copy=2), dict(arena=7, copy=2))
    assert metrics.reader(NAME).read(w) is None


def test_a_program_without_the_counter_reads_nothing_and_does_not_raise():
    old = parse_metrics("minio_tpu_dispatch_pad_blocks_total 3\n")
    w = metrics.Window(seconds=10.0, acked_bytes=4 << 30, server_cpu_s=30.0,
                       before=old, after=old, data_shards=12, parity_shards=4,
                       device_kind="TPU v5 lite")
    assert metrics.reader(NAME).read(w) is None
    assert NAME not in metrics.read_all([NAME, "window_compiles"],
                                        window(dict(arena=1, copy=1), dict(arena=1, copy=1)))
