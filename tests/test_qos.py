"""QoS subsystem: admission control (inflight caps -> SlowDown), dynamic
timeout adaptation, last-minute latency ring rollover, and priority-aware
TPU dispatch under mixed foreground/background load. All CPU-lane."""

import threading
import time

import numpy as np
import pytest

from minio_tpu.qos import QoS
from minio_tpu.qos.admission import (
    CLASS_ADMIN,
    CLASS_BACKGROUND,
    CLASS_S3,
    AdmissionController,
    ClassPolicy,
)
from minio_tpu.qos.context import (
    PRI_BACKGROUND,
    PRI_FOREGROUND,
    background_context,
    current_priority,
    in_background,
)
from minio_tpu.qos.dyntimeout import LOG_SIZE, DynamicTimeout
from minio_tpu.qos.lastminute import WINDOW, LastMinuteLatency


# -- admission control --------------------------------------------------------


def _ctrl(max_inflight=2, max_waiters=1, deadline=0.05):
    return AdmissionController({
        CLASS_S3: ClassPolicy(max_inflight, max_waiters, deadline),
    })


def test_admission_caps_and_deadline_timeout():
    adm = _ctrl(max_inflight=2, max_waiters=1, deadline=0.05)
    assert adm.acquire(CLASS_S3)
    assert adm.acquire(CLASS_S3)
    # at the cap: a waiter rides the bounded deadline, then rejects
    t0 = time.monotonic()
    assert not adm.acquire(CLASS_S3)
    assert 0.04 <= time.monotonic() - t0 < 2.0
    snap = adm.snapshot()[CLASS_S3]
    assert snap["inflight"] == 2
    assert snap["rejectedTimeout"] == 1


def test_admission_queue_full_rejects_instantly():
    adm = _ctrl(max_inflight=1, max_waiters=0, deadline=10.0)
    assert adm.acquire(CLASS_S3)
    t0 = time.monotonic()
    assert not adm.acquire(CLASS_S3)  # waiter cap 0: no 10s wait
    assert time.monotonic() - t0 < 1.0
    assert adm.snapshot()[CLASS_S3]["rejectedFull"] == 1


def test_admission_release_wakes_waiter():
    adm = _ctrl(max_inflight=1, max_waiters=2, deadline=5.0)
    assert adm.acquire(CLASS_S3)
    got = []

    def waiter():
        got.append(adm.acquire(CLASS_S3))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    adm.release(CLASS_S3)
    t.join(5)
    assert got == [True]
    assert adm.snapshot()[CLASS_S3]["inflight"] == 1


def test_admission_unlimited_class_counts_but_never_rejects():
    adm = AdmissionController({CLASS_ADMIN: ClassPolicy(0, 0, 0.0)})
    for _ in range(100):
        assert adm.try_acquire(CLASS_ADMIN)
    assert adm.snapshot()[CLASS_ADMIN]["inflight"] == 100


def test_admission_classes_isolated():
    adm = AdmissionController({
        CLASS_S3: ClassPolicy(1, 0, 0.0),
        CLASS_BACKGROUND: ClassPolicy(1, 0, 0.0),
    })
    assert adm.acquire(CLASS_S3)
    assert not adm.acquire(CLASS_S3)
    # the background class has its own slot pool
    assert adm.acquire(CLASS_BACKGROUND)


def test_admission_set_policy_unblocks_live_waiters():
    """Waiters re-read the policy each wakeup: an admin cap raise (or
    lift to unlimited) admits parked requests instead of letting them
    ride the deadline into a spurious 503."""
    adm = _ctrl(max_inflight=1, max_waiters=2, deadline=5.0)
    assert adm.acquire(CLASS_S3)
    got = []
    t = threading.Thread(target=lambda: got.append(adm.acquire(CLASS_S3)))
    t.start()
    time.sleep(0.05)
    adm.set_policy(CLASS_S3, ClassPolicy(0, 0, 0.0))  # lift the cap
    t.join(5)
    assert got == [True]


def test_admission_arrivals_do_not_barge_past_waiters():
    """A freed slot goes to a parked waiter, not to a fresh arrival —
    otherwise sustained saturation preferentially 503s the OLDEST
    requests (they burn their whole deadline while newcomers sail)."""
    adm = _ctrl(max_inflight=1, max_waiters=2, deadline=5.0)
    assert adm.acquire(CLASS_S3)
    dl = adm.begin_wait(CLASS_S3)  # a parked waiter now exists
    assert dl is not None
    adm.release(CLASS_S3)
    # slot is free, but the fast path must refuse while a waiter is parked
    assert not adm.try_acquire(CLASS_S3)
    assert adm.finish_wait(CLASS_S3, dl)  # the waiter gets the slot
    adm.release(CLASS_S3)
    assert adm.try_acquire(CLASS_S3)  # queue drained: fast path works again


def test_admission_begin_finish_wait_protocol():
    adm = _ctrl(max_inflight=1, max_waiters=1, deadline=0.05)
    assert adm.acquire(CLASS_S3)
    dl = adm.begin_wait(CLASS_S3)
    assert dl is not None
    assert adm.begin_wait(CLASS_S3) is None  # waiter queue full
    assert adm.snapshot()[CLASS_S3]["rejectedFull"] == 1
    assert not adm.finish_wait(CLASS_S3, dl)  # deadline passes
    assert adm.snapshot()[CLASS_S3]["waiting"] == 0
    # a wait whose deadline expired while queued rejects on entry
    dl2 = adm.begin_wait(CLASS_S3)
    assert dl2 is not None
    assert not adm.finish_wait(CLASS_S3, time.monotonic() - 1.0)
    # abort_wait undoes a reservation whose finish_wait never ran
    dl3 = adm.begin_wait(CLASS_S3)
    assert dl3 is not None
    adm.abort_wait(CLASS_S3)
    assert adm.snapshot()[CLASS_S3]["waiting"] == 0


def test_classify_qos_class_ignores_client_headers():
    from minio_tpu.server.handler_utils import classify_qos_class

    assert classify_qos_class("minio", "health/live") is None
    assert classify_qos_class("minio", "metrics/v3/api/qos") is None
    assert classify_qos_class("minio", "console/index.html") is None
    assert classify_qos_class("minio", "admin/v3/info") == CLASS_ADMIN
    assert classify_qos_class("minio", "kms/key/list") == CLASS_ADMIN
    assert classify_qos_class("bkt", "obj") == CLASS_S3
    # internode RPC planes stay unthrottled (they carry the locks/storage
    # traffic that foreground requests are already waiting on)
    assert classify_qos_class("minio", "grid/v1") is None
    assert classify_qos_class("minio", "lock/v1/lock") is None
    assert classify_qos_class("minio", "storage/v1/0/readfile") is None
    # but an unrecognized key under the reserved bucket is ordinary s3
    # traffic: objects in a bucket named "minio" must not dodge admission
    assert classify_qos_class("minio", "obj1") == CLASS_S3
    assert classify_qos_class("minio", "") == CLASS_S3
    # pre-auth classification must never trust wire signals: the
    # replication marker does not buy a different admission pool
    assert classify_qos_class(
        "bkt", "obj", {"x-minio-source-replication-request": "true"}
    ) == CLASS_S3


def test_from_env_policies(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_API_REQUESTS_MAX", "7")
    monkeypatch.setenv("MINIO_TPU_API_REQUESTS_DEADLINE", "2.5")
    adm = AdmissionController.from_env()
    s3 = adm.snapshot()[CLASS_S3]
    assert s3["maxInflight"] == 7
    assert s3["maxWaiters"] == 28
    assert s3["deadlineSeconds"] == 2.5


def test_from_env_divides_node_budget_across_workers(monkeypatch):
    """Caps are NODE-wide budgets: a worker pool of N must not multiply
    admission capacity — each worker gets budget // N."""
    monkeypatch.setenv("MINIO_TPU_API_REQUESTS_MAX", "100")
    monkeypatch.setenv("MINIO_TPU_API_ADMIN_REQUESTS_MAX", "8")
    monkeypatch.setenv("MINIO_TPU_WORKER_COUNT", "4")
    adm = AdmissionController.from_env()
    snap = adm.snapshot()
    assert snap[CLASS_S3]["maxInflight"] == 25
    assert snap["admin"]["maxInflight"] == 2
    assert snap["background"]["maxInflight"] == 16  # default 64 / 4


def test_from_env_worker_division_edge_cases(monkeypatch):
    import os as _os

    # auto-sized budget divides too
    monkeypatch.setenv("MINIO_TPU_API_REQUESTS_MAX", "0")
    monkeypatch.setenv("MINIO_TPU_WORKER_COUNT", "2")
    node = max(256, 32 * (_os.cpu_count() or 1))
    adm = AdmissionController.from_env()
    assert adm.snapshot()[CLASS_S3]["maxInflight"] == node // 2
    # unlimited stays unlimited; tiny caps floor at 1; malformed count = 1
    monkeypatch.setenv("MINIO_TPU_API_REQUESTS_MAX", "-1")
    assert AdmissionController.from_env().snapshot()[CLASS_S3]["maxInflight"] == -1
    monkeypatch.setenv("MINIO_TPU_API_REQUESTS_MAX", "3")
    monkeypatch.setenv("MINIO_TPU_WORKER_COUNT", "16")
    assert AdmissionController.from_env().snapshot()[CLASS_S3]["maxInflight"] == 1
    monkeypatch.setenv("MINIO_TPU_WORKER_COUNT", "junk")
    assert AdmissionController.from_env().snapshot()[CLASS_S3]["maxInflight"] == 3


# -- SlowDown over the wire ---------------------------------------------------


def test_slowdown_error_xml_and_status():
    from minio_tpu.server import s3err

    err = s3err.SlowDown
    assert err.http_status == 503
    xml = err.to_xml(resource="/b/k").decode()
    assert "<Code>SlowDown</Code>" in xml
    assert "<Resource>/b/k</Resource>" in xml


def test_server_answers_503_slowdown_when_class_saturated(tmp_path):
    """Acceptance: an over-cap request burst answers SlowDown (503) with
    the correct S3 error XML instead of queueing without bound."""
    from test_s3_api import ServerThread

    from minio_tpu.client import S3Client

    st = ServerThread([str(tmp_path / f"d{i}") for i in range(4)])
    try:
        cli = S3Client(f"127.0.0.1:{st.port}")
        assert cli.make_bucket("qos").status == 200
        # saturate the s3 class: cap 1, no waiters, zero deadline
        st.srv.qos.admission.set_policy(
            CLASS_S3, ClassPolicy(max_inflight=1, max_waiters=0, deadline_s=0.0)
        )
        assert st.srv.qos.admission.try_acquire(CLASS_S3)  # hold the slot
        try:
            burst = [cli.put_object("qos", f"k{i}", b"x") for i in range(8)]
            assert all(r.status == 503 for r in burst)
            body = burst[0].body.decode()
            assert "<Code>SlowDown</Code>" in body
            assert "<Error>" in body
            snap = st.srv.qos.admission.snapshot()[CLASS_S3]
            assert snap["rejectedFull"] >= 8
        finally:
            st.srv.qos.admission.release(CLASS_S3)
        # slot free again: traffic flows
        st.srv.qos.admission.set_policy(
            CLASS_S3, ClassPolicy(max_inflight=64, max_waiters=64, deadline_s=5.0)
        )
        assert cli.put_object("qos", "after", b"y").status == 200
        # admin plane exposes the QoS snapshot
        assert "s3" in st.srv.qos.snapshot()["admission"]
    finally:
        st.stop()


# -- dynamic timeouts ---------------------------------------------------------


def test_dynamic_timeout_grows_on_failures():
    dt = DynamicTimeout(1.0, minimum_s=0.5)
    for _ in range(LOG_SIZE):
        dt.log_failure()
    assert dt.timeout() == pytest.approx(1.25)
    for _ in range(LOG_SIZE):
        dt.log_failure()
    assert dt.timeout() == pytest.approx(1.25 * 1.25)


def test_dynamic_timeout_shrinks_toward_observed_max():
    dt = DynamicTimeout(10.0, minimum_s=0.5)
    for _ in range(LOG_SIZE):
        dt.log_success(0.1)  # slowest observed: 0.1s -> target 0.125s
    # halfway from 10 toward 0.125
    assert dt.timeout() == pytest.approx((10.0 + 0.125) / 2)
    for _ in range(20 * LOG_SIZE):
        dt.log_success(0.1)
    assert dt.timeout() == pytest.approx(0.5, abs=0.2)  # floored at minimum
    assert dt.timeout() >= 0.5


def test_dynamic_timeout_mixed_window_holds():
    dt = DynamicTimeout(4.0, minimum_s=0.5)
    # 25% failures: between the 10% decrease and 33% increase thresholds
    for i in range(LOG_SIZE):
        if i % 4 == 0:
            dt.log_failure()
        else:
            dt.log_success(0.2)
    assert dt.timeout() == pytest.approx(4.0)


def test_dynamic_timeout_registry_snapshot():
    from minio_tpu.qos import dyntimeout

    DynamicTimeout(3.0, minimum_s=1.0, name="test-reg-snap")
    assert dyntimeout.snapshot()["test-reg-snap"] == pytest.approx(3.0)
    # the namespace-lock timeout registers at erasure.set import time
    import minio_tpu.erasure.set  # noqa: F401

    assert "ns-lock" in dyntimeout.snapshot()


# -- last-minute latency ring -------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_last_minute_accumulates_and_averages():
    clk = FakeClock()
    lm = LastMinuteLatency(clock=clk)
    lm.add("PutObject", 0.2, ttfb=0.05)
    lm.add("PutObject", 0.4, ttfb=0.15)
    lm.add("GetObject", 1.0)
    tot = lm.totals()
    assert tot["PutObject"]["count"] == 2
    assert tot["PutObject"]["avg_seconds"] == pytest.approx(0.3)
    assert tot["PutObject"]["max_seconds"] == pytest.approx(0.4)
    assert tot["PutObject"]["ttfb_avg_seconds"] == pytest.approx(0.1)
    assert tot["GetObject"]["count"] == 1


def test_last_minute_ring_rollover_drops_stale_buckets():
    clk = FakeClock()
    lm = LastMinuteLatency(clock=clk)
    lm.add("GetObject", 1.0)
    clk.t += WINDOW - 1  # still inside the window
    lm.add("GetObject", 3.0)
    assert lm.totals()["GetObject"]["count"] == 2
    clk.t += 2  # first bucket now stale, second still live
    tot = lm.totals()
    assert tot["GetObject"]["count"] == 1
    assert tot["GetObject"]["max_seconds"] == pytest.approx(3.0)
    clk.t += 10 * WINDOW  # far future: everything stale
    assert lm.totals() == {}


def test_last_minute_same_second_merges():
    clk = FakeClock()
    lm = LastMinuteLatency(clock=clk)
    for _ in range(5):
        lm.add("HeadObject", 0.01)
    assert lm.totals()["HeadObject"]["count"] == 5


# -- priority context ---------------------------------------------------------


def test_background_context_scopes_priority():
    assert not in_background()
    assert current_priority() == PRI_FOREGROUND
    with background_context():
        assert in_background()
        assert current_priority() == PRI_BACKGROUND
        # fresh threads default to foreground regardless of the spawner
        seen = []
        t = threading.Thread(target=lambda: seen.append(current_priority()))
        t.start()
        t.join()
        assert seen == [PRI_FOREGROUND]
    assert not in_background()


# -- priority-aware dispatch --------------------------------------------------


def _dispatcher(window_s=0.02, max_shards=4096):
    from minio_tpu.ops import rs_jax
    from minio_tpu.parallel.dispatcher import TpuDispatcher

    codec = rs_jax.get_tpu_codec(4, 2)
    return TpuDispatcher(codec, 256, window_s=window_s, max_shards=max_shards)


RNG = np.random.default_rng(7)


def _blocks(k):
    return RNG.integers(0, 256, size=(k, 4, 256), dtype=np.uint8)


def test_dispatch_priority_foreground_never_behind_background():
    """Acceptance: 32 foreground blocks vs saturating background load —
    the stats witness (`fg_deferred_behind_bg`) must stay 0 and both
    lanes must complete."""
    disp = _dispatcher(window_s=0.005)
    disp.encode(_blocks(1))  # warm the jit

    stop = threading.Event()
    bg_done = []

    def bg_flood():
        with background_context():
            while not stop.is_set():
                disp.encode(_blocks(4))
                bg_done.append(4)

    flooders = [threading.Thread(target=bg_flood) for _ in range(3)]
    for t in flooders:
        t.start()
    time.sleep(0.05)  # background saturation established

    results = []

    def fg_put(i):
        results.append(disp.encode(_blocks(1)))

    fgs = [threading.Thread(target=fg_put, args=(i,)) for i in range(32)]
    for t in fgs:
        t.start()
    for t in fgs:
        t.join(30)
    stop.set()
    for t in flooders:
        t.join(30)

    assert len(results) == 32
    st = disp.stats
    assert st["fg_blocks"] >= 33  # 32 + warm-up
    assert st["bg_blocks"] > 0
    # the invariant: no dispatch ever granted background slots while
    # foreground blocks were still queued
    assert st["fg_deferred_behind_bg"] == 0
    # background never exceeded its per-dispatch slot cap
    assert st["bg_batch_max"] <= disp.bg_max_blocks


def test_dispatch_background_rides_leftover_capacity():
    disp = _dispatcher(window_s=0.05)
    disp.encode(_blocks(1))  # warm

    n_fg, n_bg = 6, 4
    barrier = threading.Barrier(n_fg + n_bg)
    outs = {}

    def fg(i):
        barrier.wait()
        outs[("fg", i)] = disp.encode(_blocks(2))

    def bg(i):
        with background_context():
            barrier.wait()
            outs[("bg", i)] = disp.encode(_blocks(2))

    ts = [threading.Thread(target=fg, args=(i,)) for i in range(n_fg)] + [
        threading.Thread(target=bg, args=(i,)) for i in range(n_bg)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert len(outs) == n_fg + n_bg
    st = disp.stats
    assert st["fg_blocks"] >= 2 * n_fg
    assert st["bg_blocks"] == 2 * n_bg
    assert st["fg_deferred_behind_bg"] == 0


def test_dispatch_lone_foreground_skips_window_despite_bg_backlog():
    """A lone foreground block must not be held for the batching window
    just because background work is queued — it dispatches immediately
    (with bg leftover fill), keeping fg latency flat under bg load."""
    disp = _dispatcher(window_s=0.5)
    for k in (1, 2, 3, 4):  # pre-compile every bucket the test can form
        disp.encode(_blocks(k))
    stop = threading.Event()

    def bg_flood():
        with background_context():
            while not stop.is_set():
                disp.encode(_blocks(2))

    t = threading.Thread(target=bg_flood)
    t.start()
    time.sleep(0.05)
    try:
        t0 = time.monotonic()
        disp.encode(_blocks(1))
        assert time.monotonic() - t0 < 0.4  # window (0.5s) was not paid
    finally:
        stop.set()
        t.join(30)


def test_dispatch_background_starvation_protection():
    """A background block older than the max age is force-promoted into
    the foreground lane (it would otherwise only ever ride leftover
    capacity). The item is enqueued with a back-dated timestamp so the
    promotion is deterministic, not a race against the worker."""
    from concurrent.futures import Future

    disp = _dispatcher(window_s=0.005)
    disp.encode(_blocks(1))  # warm

    aged_fut: Future = Future()
    blocks = _blocks(1)
    with disp._cv:
        # aged far past MINIO_TPU_QOS_BG_MAX_AGE_MS (default 50 ms)
        disp._bg.append(
            (blocks, aged_fut, PRI_BACKGROUND, time.monotonic() - 10.0,
             "", False, disp.codec)
        )
        disp._cv.notify()
    parity, digests = aged_fut.result(timeout=10)
    assert parity.shape == (1, 2, 256) and digests.shape == (1, 6, 32)
    assert disp.stats["bg_forced"] >= 1


def test_dispatch_priority_results_byte_identical():
    """Priority routing must not change results: both lanes produce the
    same parity/digests as the numpy reference codec."""
    from minio_tpu.ops import rs
    from minio_tpu.ops.highwayhash import hash256_batch_numpy

    disp = _dispatcher(window_s=0.0)
    ref = rs.get_codec(4, 2)
    data = _blocks(2)
    fg_parity, fg_digests = disp.encode(data)
    with background_context():
        bg_parity, bg_digests = disp.encode(data)
    for k in range(2):
        expect = ref.encode(
            np.concatenate([data[k], np.zeros((2, 256), np.uint8)])
        )
        np.testing.assert_array_equal(fg_parity[k], expect[4:])
        np.testing.assert_array_equal(bg_parity[k], expect[4:])
        np.testing.assert_array_equal(fg_digests[k], hash256_batch_numpy(expect))
        np.testing.assert_array_equal(bg_digests[k], hash256_batch_numpy(expect))


def test_dispatch_aggregate_stats():
    from minio_tpu.parallel import dispatcher as dmod

    agg = dmod.aggregate_stats()
    for key in ("fg_blocks", "bg_blocks", "fg_deferred_behind_bg"):
        assert key in agg or not dmod._dispatchers


# -- metrics & facade ---------------------------------------------------------


def test_qos_facade_snapshot_shape():
    q = QoS(admission=_ctrl())
    q.last_minute.add("PutObject", 0.1)
    snap = q.snapshot()
    assert CLASS_S3 in snap["admission"]
    assert "PutObject" in snap["lastMinute"]
    assert isinstance(snap["dynamicTimeouts"], dict)


def test_metrics_v3_qos_group_renders():
    from minio_tpu.server.metrics import render_v3

    class Srv:
        qos = QoS(admission=_ctrl())

    Srv.qos.last_minute.add("GetObject", 0.2, ttfb=0.01)
    text = render_v3(Srv(), "api/qos")
    assert 'minio_api_qos_inflight{class="s3"}' in text
    assert "minio_tpu_dispatch_blocks_total" in text
    assert 'minio_api_qos_last_minute_requests{name="GetObject"} 1' in text
    assert "minio_tpu_dispatch_fg_deferred_behind_bg_total" in text
