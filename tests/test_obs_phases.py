"""The phase clock (obs.phase): one primitive, three sinks — an always-on
table of wall seconds, thread CPU seconds and calls per (layer, phase);
the `Span` record when someone subscribes; a profiler TraceAnnotation for
the dispatch thread's leaves, only where jax is already loaded. And the
tree one streaming PUT yields with a subscriber attached."""

import os
import sys
import threading
import time

import numpy as np
import pytest

from minio_tpu import obs
from minio_tpu.client import S3Client
from minio_tpu.server.metrics import TracePubSub

from test_s3_api import ServerThread


@pytest.fixture()
def restore_publisher():
    prev = obs.publisher()
    yield
    obs.set_publisher(prev)


def _row(layer, name):
    return obs.phases_snapshot()[(layer, name)]


def _burn(seconds):
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        sum(range(1000))


def test_every_phase_is_there_before_it_ever_ran():
    snap = obs.phases_snapshot()
    assert set(snap) == {(layer, name) for layer, names in obs.PHASES.items()
                         for name in names}
    assert {"wait", "window", "assemble", "pack", "h2d", "kernel", "d2h", "unpack",
            "frame", "numpy", "fanout"} == set(obs.PHASES["dispatch"])
    assert {"ingest", "stage", "encode_wait", "frame", "md5", "drive_write", "commit",
            "drive_io"} == set(obs.PHASES["put"])
    with pytest.raises(KeyError):
        obs.phase("put", "no-such-phase")


def test_a_phase_books_wall_cpu_and_calls_with_no_publisher(restore_publisher):
    obs.set_publisher(None)
    w0, c0, n0 = _row("put", "md5")
    into = {}
    with obs.phase("put", "md5", into=into):
        _burn(0.02)
        time.sleep(0.03)
    with obs.phase("put", "md5", into=into):
        pass
    w1, c1, n1 = _row("put", "md5")
    assert n1 - n0 == 2
    assert w1 - w0 >= 0.05 and 0.02 <= c1 - c0 < w1 - w0  # the sleep burns no CPU
    assert into["md5"] == pytest.approx(w1 - w0)


def test_a_phase_books_its_time_when_the_body_raises(restore_publisher):
    obs.set_publisher(None)
    _, _, n0 = _row("dispatch", "kernel")
    with pytest.raises(ZeroDivisionError):
        with obs.phase("dispatch", "kernel"):
            1 / 0
    assert _row("dispatch", "kernel")[2] == n0 + 1


def test_a_phase_publishes_one_span_child_of_the_enclosing_span(restore_publisher):
    pub = TracePubSub()
    obs.set_publisher(pub)
    # attached but nobody subscribed: nothing is built, the table still moves
    _, _, n0 = _row("put", "frame")
    with obs.phase("put", "frame"):
        pass
    assert _row("put", "frame")[2] == n0 + 1
    sub = pub.subscribe()
    try:
        with obs.request_context("PHASE1"):
            with obs.span(obs.TYPE_INTERNAL, "erasure.put_object") as parent:
                with obs.phase("put", "frame", blocks=3):
                    with obs.span(obs.TYPE_STORAGE, "append_file"):
                        pass
                clock = obs.PhaseClock("put", "ingest")
                clock.book()
    finally:
        pub.unsubscribe(sub)
    recs = []
    while not sub.q.empty():
        recs.append(sub.q.get_nowait())
    by_name = {r["name"]: r for r in recs}
    assert set(by_name) == {"erasure.put_object", "put.frame", "append_file", "put.ingest"}
    frame = by_name["put.frame"]
    assert frame["type"] == "internal" and frame["reqId"] == "PHASE1"
    assert frame["parentId"] == parent.span_id and frame["blocks"] == 3
    assert by_name["append_file"]["parentId"] == frame["spanId"]
    # the stopwatch's record hangs off the same parent
    assert by_name["put.ingest"]["parentId"] == parent.span_id
    assert by_name["put.ingest"]["reqId"] == "PHASE1"
    # obs.span itself stays allocation-free when idle
    assert obs.span(obs.TYPE_TPU, "z") is obs.NOOP_SPAN


def test_the_snapshot_is_consistent_under_concurrent_writers(restore_publisher):
    obs.set_publisher(None)
    w0, c0, n0 = _row("put", "stage")
    threads, per, stop = 8, 400, threading.Event()
    torn = []

    def write():
        for _ in range(per):
            with obs.phase("put", "stage"):
                pass

    def read():
        last = n0
        while not stop.is_set():
            snap = obs.phases_snapshot()
            if len(snap) != sum(len(v) for v in obs.PHASES.values()):
                torn.append("size")
            n = snap[("put", "stage")][2]
            if n < last:
                torn.append("backwards")
            last = n

    reader = threading.Thread(target=read)
    reader.start()
    ts = [threading.Thread(target=write) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stop.set()
    reader.join()
    assert not torn
    assert _row("put", "stage")[2] - n0 == threads * per


def test_a_dispatch_phase_runs_with_jax_absent(monkeypatch, restore_publisher):
    obs.set_publisher(None)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    ph = obs.phase("dispatch", "d2h")
    assert ph._ann is None
    _, _, n0 = _row("dispatch", "d2h")
    with ph:
        pass
    assert _row("dispatch", "d2h")[2] == n0 + 1
    assert "jax" not in sys.modules  # and it did not import it


def test_only_dispatch_phases_go_to_the_profiler():
    pytest.importorskip("jax")
    assert obs.phase("dispatch", "h2d")._ann is not None
    # a request-thread phase encloses whole dispatches: it would win every
    # idle gap of a device trace and say nothing
    assert obs.phase("put", "encode_wait")._ann is None


# -- one streaming PUT, a subscriber attached: one tree -----------------------


@pytest.fixture(scope="module")
def device_server(tmp_path_factory):
    """The device plane on XLA's CPU backend, as the CPU rehearsals force it."""
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_TPU_BACKEND", "jax")
    mp.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    mp.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    base = tmp_path_factory.mktemp("phasedrives")
    st = ServerThread([str(base / f"d{i}") for i in range(4)])
    yield st
    st.stop()
    mp.undo()


def test_one_streaming_put_yields_one_tree(device_server):
    pytest.importorskip("jax")
    cli = S3Client(f"127.0.0.1:{device_server.port}")
    assert cli.make_bucket("phasebkt").status == 200
    body = np.random.default_rng(7).integers(0, 256, 9 << 20, np.uint8).tobytes()
    before = obs.phases_snapshot()
    sub = device_server.srv.trace.subscribe()
    try:
        r = cli.request("PUT", "/phasebkt/streamed", body=body, unsigned_payload=True)
        assert r.status == 200
        req_id = r.headers["x-amz-request-id"]
        recs, deadline = [], time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                rec = sub.q.get(timeout=0.25)
            except Exception:  # noqa: BLE001 — queue.Empty
                if any(x["type"] == "s3" for x in recs):
                    break
                continue
            if rec.get("reqId") == req_id or req_id in rec.get("reqIds", []):
                recs.append(rec)
    finally:
        device_server.srv.trace.unsubscribe(sub)
    s3 = [x for x in recs if x["type"] == "s3"]
    assert len(s3) == 1 and s3[0]["statusCode"] == 200
    put = [x for x in recs if x["name"] == "erasure.put_object"]
    assert len(put) == 1 and put[0]["parentId"] == 0  # a child of the S3 request
    kids = {x["name"] for x in recs if x.get("parentId") == put[0]["spanId"]}
    assert {"put.ingest", "put.encode_wait", "put.frame", "put.md5", "put.drive_write",
            "put.commit"} <= kids, kids
    # the drive pool's threads: drive_io under drive_write, the storage call under it
    write = next(x for x in recs if x["name"] == "put.drive_write")
    io = [x for x in recs if x["name"] == "put.drive_io"
          and x["parentId"] == write["spanId"]]
    assert len(io) == 4
    assert any(x["type"] == "storage" and x["parentId"] == io[0]["spanId"] for x in recs)
    batch = [x for x in recs if x["name"] == "dispatch.batch"]
    assert batch and all(
        abs(sum(b["phaseNs"].values()) - (b["deviceNs"] + b["hostNs"])) <= len(b["phaseNs"])
        for b in batch)
    assert {"assemble", "h2d", "kernel", "d2h", "unpack", "fanout"} \
        <= set(batch[0]["phaseNs"])
    assert "frame" not in batch[0]["phaseNs"]  # parity-only results: no concatenate
    # the always-on table moved for the same PUT
    after = obs.phases_snapshot()
    assert after[("put", "commit")][2] == before[("put", "commit")][2] + 1
    assert after[("put", "encode_wait")][0] > before[("put", "encode_wait")][0]
    assert after[("put", "drive_io")][1] > before[("put", "drive_io")][1]


def test_api_tpu_exports_every_phase_row_and_the_first_calls(device_server):
    cli = S3Client(f"127.0.0.1:{device_server.port}")
    text = cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode()
    for layer, names in obs.PHASES.items():
        for name in names:
            for series in ("seconds", "cpu_seconds", "calls"):
                assert f'minio_tpu_phase_{series}_total{{layer="{layer}",phase="{name}"}}' \
                    in text
    assert 'minio_tpu_dispatch_first_calls_total{rung="fused",bucket="256"}' in text
    assert 'minio_tpu_dispatch_first_call_seconds_total{rung="xla",bucket="1"}' in text
    # the help text says what the old counters sum, and no longer "device execute time"
    assert "not kernel time" in text and "Device execute time" not in text
    assert 'minio_tpu_queue_wait_seconds_distribution{le="32.0"}' in text
    assert 'minio_tpu_device_time_seconds_distribution{le="1.0"}' in text
    assert os.environ.get("MINIO_TPU_BACKEND") == "jax"
