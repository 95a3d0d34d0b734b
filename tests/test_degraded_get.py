"""A degraded GET on the EC 8+8 set of 16 drives, served over HTTP with drives
taken offline by the storage fault rule: the bytes returned are the body PUT
and are what the plain reference (`chipbench/reference_decode.py`) rebuilds
from the shard files on the surviving drives, for every pair (k, k+8) and for
1, 4 and 8 drives offline; the read-side phase clock (`obs.phase`, layers
`get` and `decode`) tiles such a GET; every new `/api/tpu` row is there from
the first scrape. CPU, seeded, small: the device plane on XLA's CPU backend,
as the chipbench rehearsals force it."""

import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from chipbench import reference_decode  # noqa: E402
from chipbench.procs import parse_metrics  # noqa: E402
from minio_tpu import fault, obs  # noqa: E402
from minio_tpu.client import S3Client  # noqa: E402

from test_s3_api import ServerThread  # noqa: E402

BUCKET, KEY, MIB = "degraded", "obj/0000", 1 << 20
PAIRS = [(k, k + 8) for k in range(8)]
MORE = [(5,), (0, 2, 9, 15), (1, 2, 3, 4, 10, 11, 12, 13)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One 16-drive EC:8 server and one 8 MiB object (8 stripe blocks: one
    read window of 128 shards, over the 64 that send it to the device rung)."""
    pytest.importorskip("jax")
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_TPU_BACKEND", "jax")
    mp.setenv("MINIO_STORAGE_CLASS_STANDARD", "EC:8")
    mp.setenv("MINIO_TPU_SCAN_INTERVAL", "0")
    # a drive's breaker re-probes at once, so that one case's offline drives
    # are back for the next (the probe fails while the rule is armed)
    mp.setenv("MINIO_TPU_DRIVE_COOLDOWN_S", "0.01")
    # counts here are exact: a hedge won on a loaded host puts a parity shard
    # in a straggler's place and the window rebuilds one shard more
    mp.setenv("MINIO_TPU_HEDGE", "0")
    mp.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    base = tmp_path_factory.mktemp("degraded-drives")
    drives = [str(base / f"d{i:02d}") for i in range(16)]
    first_scrape = None
    st = ServerThread(drives)
    try:
        cli = S3Client(f"127.0.0.1:{st.port}")
        first_scrape = cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode()
        assert cli.make_bucket(BUCKET).status == 200
        body = np.random.default_rng([2 ** 31 + 29, 7]).bytes(8 * MIB)
        r = cli.request("PUT", f"/{BUCKET}/{KEY}", body=body, unsigned_payload=True)
        assert r.status == 200
        yield st, cli, drives, body, first_scrape
    finally:
        fault.clear()
        st.stop()
        mp.undo()


def take_offline(cli, drives, which):
    for i in which:
        r = cli.admin("POST", "fault/inject", body={
            "boundary": "storage", "mode": "error", "target": drives[i]})
        assert r.status == 200, r.body
    assert cli.admin("POST", "cache/clear").status == 200


def scrape(cli) -> dict:
    return parse_metrics(cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode())


def total(series, name, **match):
    return sum(v for labels, v in series.get(name, [])
               if all(labels.get(k) == w for k, w in match.items()))


@pytest.mark.parametrize("offline", PAIRS + MORE, ids=lambda o: "off-" + "-".join(map(str, o)))
def test_served_degraded_get_is_the_body_and_the_references_reconstruction(served, offline):
    _, cli, drives, body, _ = served
    fault.clear()
    time.sleep(0.05)  # past the breakers' cooldown: the next call probes
    assert cli.request("GET", f"/{BUCKET}/{KEY}").body == body  # all 16 back
    take_offline(cli, drives, offline)
    before = scrape(cli)
    try:
        r = cli.request("GET", f"/{BUCKET}/{KEY}")
    finally:
        fault.clear()
    assert r.status == 200 and r.body == body
    files = reference_decode.read_shards(drives, BUCKET, KEY, skip=offline)
    assert len(files) == 16 - len(offline)
    assert reference_decode.decode_object(files, 8, 8) == body == r.body
    # a pair (k, k+8) holds one data and one parity shard whatever the key;
    # the other cases lose as many data shards as their drives hold
    order = reference_decode.shard_order(BUCKET, KEY, 16)
    lost = sum(1 for i in offline if order[i] < 8)
    after = scrape(cli)
    rebuilt = total(after, "minio_tpu_decode_blocks_total") \
        - total(before, "minio_tpu_decode_blocks_total")
    assert rebuilt == (8 if lost else 0)
    if offline in PAIRS:
        assert lost == 1
    if lost:
        # the device rebuilt it (off the TPU: the XLA rung), `lost` shards a block
        name = "minio_tpu_decode_device_blocks_total"
        on_device = total(after, name) - total(before, name)
        host = total(after, "minio_tpu_decode_host_blocks_total") \
            - total(before, "minio_tpu_decode_host_blocks_total")
        assert on_device + host == 8
        by_m = total(after, name, missing=str(lost)) - total(before, name, missing=str(lost))
        assert by_m == on_device


def test_the_reference_refuses_a_frame_whose_digest_is_wrong(served):
    _, _, drives, body, _ = served
    files = reference_decode.read_shards(drives, BUCKET, KEY, skip=(3, 11))
    assert reference_decode.decode_object(files, 8, 8) == body
    victim = sorted(files)[4]
    spoiled = bytearray(files[victim])
    spoiled[32 + 131072 + 40] ^= 0x01  # a byte of the second frame's block
    with pytest.raises(reference_decode.BadFrame, match=f"shard {victim + 1}, frame 1"):
        reference_decode.decode_object({**files, victim: bytes(spoiled)}, 8, 8)
    with pytest.raises(ValueError):
        reference_decode.decode_object(dict(list(files.items())[:7]), 8, 8)


def test_the_phases_tile_a_degraded_get(served):
    """`decode` leaves tile `get`/`decode_wait`; the `get` phases of the
    request side stay inside the GET's wall time."""
    _, cli, drives, body, _ = served
    fault.clear()
    time.sleep(0.05)
    take_offline(cli, drives, (3, 11))
    try:
        assert cli.request("GET", f"/{BUCKET}/{KEY}").body == body  # its kernel compiles here
        time.sleep(0.1)  # its generator books its last `respond` after the body is out
        before = obs.phases_snapshot()
        t0 = time.monotonic()
        for _ in range(3):
            assert cli.request("GET", f"/{BUCKET}/{KEY}").body == body
        wall = time.monotonic() - t0
    finally:
        fault.clear()
    time.sleep(0.1)  # the last GET's last `respond`, as above
    after = obs.phases_snapshot()
    moved = {k: tuple(a - b for a, b in zip(after[k], before[k])) for k in after}
    assert moved["get", "start"][2] == 3                    # once per GET
    assert moved["get", "read_wait"][2] == 3                # one window of 8 blocks each
    assert moved["get", "decode_wait"][2] == moved["get", "stack"][2] >= 3
    assert moved["get", "join"][2] == 3 * 8 == moved["get", "respond"][2]
    assert moved["get", "cache_fill"][2] == 3 * 8
    # a window's reads are runs: one read of each of the d shards it decodes
    # from (8 frames each), and a hedge that fires adds at most as many
    assert 3 * 8 <= moved["get", "shard_io"][2] <= 3 * 16 and moved["get", "shard_io"][1] > 0
    leaves = sum(moved["decode", p][0] for p in obs.PHASES["decode"])
    decode_wait = moved["get", "decode_wait"][0]
    # (on the chip the leaves hold 86-87 % of it, PERF.md §5; a loaded test
    # host leaves more between two phases)
    assert 0.75 * decode_wait <= leaves <= decode_wait
    # the device rung of a CPU process: nothing packed, the leaves around the call ran
    assert moved["decode", "pack"][2] == 0
    # (a hedge that wins splits a window into groups, and a group under the
    # device floor is the host's: each `decode_wait` is one or the other)
    on_device = moved["decode", "kernel"][2]
    assert on_device >= 1
    assert on_device + moved["decode", "host"][2] == moved["get", "decode_wait"][2]
    for p in ("pad", "h2d", "d2h", "unpack"):
        assert moved["decode", p][2] == on_device
    assert moved["get", "respond"][1] == 0.0  # wall only: the thread may change
    # (the read pool's `shard_io` and, since PR 35, the front end's writer —
    # `body_wait`, `body_write`, on the event loop beside the producer that
    # advances the read path — are not the request side's)
    request_side = sum(moved["get", p][0] for p in obs.PHASES["get"]
                       if p not in ("shard_io", "body_wait", "body_write"))
    assert decode_wait < request_side <= wall


def test_every_new_row_is_on_the_first_scrape(served):
    *_, first = served
    for layer in ("get", "decode"):
        for name in obs.PHASES[layer]:
            for series in ("seconds", "cpu_seconds", "calls"):
                # (at zero in a fresh process; a test process has run others)
                assert f'minio_tpu_phase_{series}_total{{layer="{layer}",phase="{name}"}} ' \
                    in first
    rows = parse_metrics(first)
    for name in ("minio_tpu_decode_dispatches_total", "minio_tpu_decode_device_blocks_total"):
        assert {(lb["rung"], lb["missing"]) for lb, _ in rows[name]} \
            >= {(r, str(m)) for r in ("fused", "xla") for m in range(1, 9)}
    assert {(lb["rung"], lb["missing"], lb["batch"])
            for lb, _ in rows["minio_tpu_decode_first_calls_total"]} \
        >= {("fused", str(m), "16") for m in range(1, 9)}
    assert rows["minio_tpu_decode_first_calls_total"][0][0].keys() \
        == rows["minio_tpu_decode_first_call_seconds_total"][0][0].keys()
    assert {lb["event"] for lb, _ in rows["minio_tpu_get_hedges_total"]} \
        == {"reads", "wins", "losses"}
    for name in ("minio_tpu_decode_pad_blocks_total", "minio_tpu_decode_host_blocks_total",
                 "minio_tpu_fused_decode_failures_total"):
        assert name in rows
    # nothing of this file has run on the fused rung so far: its rows stand
    # where the first scrape found them (at zero in a process of its own; under
    # xdist another file's stand-in for the kernel may have run here before)
    *_, cli, _, _, _ = served
    assert [v for lb, v in scrape(cli)["minio_tpu_decode_dispatches_total"]
            if lb["rung"] == "fused"] \
        == [v for lb, v in rows["minio_tpu_decode_dispatches_total"] if lb["rung"] == "fused"]
    assert "HELP minio_tpu_decode_first_calls_total" in first
    assert "HELP minio_tpu_get_hedges_total" in first


def test_a_decode_shape_counts_one_first_call_when_it_ends(served):
    _, cli, *_, first = served
    rows, was = scrape(cli), parse_metrics(first)
    # (since the first scrape: the counters are the process's, and under xdist
    # another file may have met shapes, on a stand-in for the fused rung too)
    calls = {(lb["rung"], lb["missing"], lb["batch"]): v - total(
        was, "minio_tpu_decode_first_calls_total", **lb)
        for lb, v in rows["minio_tpu_decode_first_calls_total"]}
    calls = {k: v for k, v in calls.items() if v}
    secs = {(lb["rung"], lb["missing"], lb["batch"]): v - total(
        was, "minio_tpu_decode_first_call_seconds_total", **lb)
        for lb, v in rows["minio_tpu_decode_first_call_seconds_total"]}
    secs = {k: v for k, v in secs.items() if v}
    # the cases above rebuilt one shard a block and more, in windows of 8
    # blocks (fewer where a hedge split one), on the XLA rung; each shape was
    # first met once however often it came back
    met = {(lb["rung"], lb["missing"]) for lb, v in rows["minio_tpu_decode_first_calls_total"] if v}
    assert ("xla", "1") in met and len({m for _, m in met}) >= 2
    assert all(v == 1 for v in calls.values()) and set(secs) == set(calls)
    assert total(rows, "minio_tpu_decode_dispatches_total", rung="xla") \
        - total(was, "minio_tpu_decode_dispatches_total", rung="xla") > len(calls)
    assert total(rows, "minio_tpu_decode_dispatches_total", rung="fused") \
        == total(was, "minio_tpu_decode_dispatches_total", rung="fused")


def test_the_profiler_gets_the_leaves_and_no_enclosing_phase():
    pytest.importorskip("jax")
    for layer, name in (("decode", "kernel"), ("decode", "host"), ("get", "read_wait"),
                        ("get", "join"), ("get", "stack")):
        assert obs.phase(layer, name)._ann is not None, (layer, name)
    # an enclosing phase would win every idle gap; the pool's threads would bury it
    assert obs.phase("get", "decode_wait")._ann is None
    assert obs.phase("get", "shard_io")._ann is None
    # the stopwatch across a yield may be resumed by another thread
    assert obs.PhaseClock("get", "respond")._ann is None


# --------------------------------------------------------------------------
# the packed path, end to end: the survivors written once as the decode
# mega-kernel takes them. Off the TPU `fp.supports` is false and the kernel
# does not lower, so both are stood in for; the served path around them is
# the shipped one. These run last: the tests above count a process that never
# ran the fused rung.
# --------------------------------------------------------------------------


def stack_copies(series) -> dict:
    return {(lb["unit"], lb["layout"]): v
            for lb, v in series["minio_tpu_get_stack_copies_total"]}


def test_the_stack_copies_counter_is_on_the_first_scrape(served):
    *_, first = served
    assert set(stack_copies(parse_metrics(first))) \
        == {(u, lay) for u in ("run", "block") for lay in ("packed", "rows")}
    assert "HELP minio_tpu_get_stack_copies_total" in first


def degraded_get_moves(cli, drives, body, offline=(3, 11)):
    """One degraded GET: what the counters and the phase table moved by."""
    fault.clear()
    time.sleep(0.05)
    take_offline(cli, drives, offline)
    try:
        before, phases = scrape(cli), obs.phases_snapshot()
        r = cli.request("GET", f"/{BUCKET}/{KEY}")
    finally:
        fault.clear()
    assert r.status == 200 and r.body == body
    time.sleep(0.1)  # the generator books its last `respond` after the body is out
    after, now = scrape(cli), obs.phases_snapshot()
    copies = {k: v - stack_copies(before)[k] for k, v in stack_copies(after).items()
              if v != stack_copies(before)[k]}
    calls = {k: now[k][2] - phases[k][2] for k in now}

    def moved(name, **match):
        return total(after, name, **match) - total(before, name, **match)

    return copies, calls, moved


def test_off_the_fused_rung_the_stack_is_rows_and_a_copy_a_run(served):
    _, cli, drives, body, _ = served
    copies, calls, moved = degraded_get_moves(cli, drives, body)
    # one window of 8 blocks, one run: a strided copy for each of the 8 survivors
    assert copies == {("run", "rows"): 8}
    assert calls["get", "stack"] == 1 and calls["decode", "pad"] == 1
    assert moved("minio_tpu_decode_dispatches_total", rung="xla") == 1


@pytest.fixture
def fused_rung_stood_in(monkeypatch):
    from minio_tpu.ops import bitrot_jax
    from minio_tpu.ops import fused_pallas as fp

    from test_survivor_stack import numpy_kernel, shapes_only

    calls: list = []
    monkeypatch.setattr(fp, "supports", shapes_only)
    monkeypatch.setattr(fp, "fused_decode_hash_cm", numpy_kernel(calls))
    # (restored when the test ends, whatever a failing stand-in left there)
    monkeypatch.setattr(bitrot_jax, "_fused_dec_cooldown", 0)
    monkeypatch.setattr(bitrot_jax, "_fused_dec_backoff", 8)
    return calls, fp, monkeypatch


@pytest.mark.parametrize("offline,m", [((3, 11), 1), ((0, 2, 9, 15), None),
                                       ((1, 2, 3, 4, 10, 11, 12, 13), None)],
                         ids=["pair", "four-off", "eight-off"])
def test_a_packed_group_is_written_once_and_skips_pad_and_pack(
        served, fused_rung_stood_in, offline, m):
    _, cli, drives, body, _ = served
    kernel_calls, *_ = fused_rung_stood_in
    copies, calls, moved = degraded_get_moves(cli, drives, body, offline)
    order = reference_decode.shard_order(BUCKET, KEY, 16)
    lost = sum(1 for i in offline if order[i] < 8)
    assert lost == (m or lost) >= 1
    # 8 copies a window, all `run`/`packed`: the kernel's input is the stack
    assert copies == {("run", "packed"): 8}
    assert [c[0] for c in kernel_calls] == [(128, 16, 8, 1024)]
    assert len(kernel_calls[0][2]) == lost
    assert calls["get", "stack"] == 1 == calls["get", "decode_wait"]
    assert calls["decode", "pad"] == 0 == calls["decode", "pack"]
    for p in ("h2d", "kernel", "d2h", "unpack"):
        assert calls["decode", p] == 1
    assert calls["decode", "host"] == 0
    # the decode counters move exactly as for a group packed by the entry
    assert moved("minio_tpu_decode_dispatches_total", rung="fused", missing=str(lost)) == 1
    assert moved("minio_tpu_decode_dispatches_total") == 1
    assert moved("minio_tpu_decode_device_blocks_total", rung="fused") == 8
    assert moved("minio_tpu_decode_pad_blocks_total") == 8
    assert moved("minio_tpu_decode_blocks_total") == 8
    assert moved("minio_tpu_decode_host_blocks_total") == 0
    assert moved("minio_tpu_fused_decode_failures_total") == 0


def test_a_kernel_that_raises_hands_the_rows_to_the_xla_rung(served, fused_rung_stood_in):
    _, cli, drives, body, _ = served
    _, fp, mp = fused_rung_stood_in

    def raises(*a, **k):
        raise RuntimeError("stand-in: the kernel call fails")

    mp.setattr(fp, "fused_decode_hash_cm", raises)
    copies, calls, moved = degraded_get_moves(cli, drives, body)
    # laid out packed, refused by the kernel, its rows taken back out for XLA
    assert copies == {("run", "packed"): 8}
    assert moved("minio_tpu_fused_decode_failures_total") == 1
    assert moved("minio_tpu_decode_dispatches_total", rung="xla") == 1
    assert moved("minio_tpu_decode_dispatches_total", rung="fused") == 0
    assert moved("minio_tpu_decode_device_blocks_total", rung="xla") == 8
    assert calls["decode", "pack"] == 0 and calls["decode", "pad"] == 1  # xla_decode's own
    # while the kernel cools down the next group is laid out as rows
    copies, calls, moved = degraded_get_moves(cli, drives, body)
    assert copies == {("run", "rows"): 8}
    assert moved("minio_tpu_decode_dispatches_total", rung="xla") == 1
    assert moved("minio_tpu_fused_decode_failures_total") == 0
