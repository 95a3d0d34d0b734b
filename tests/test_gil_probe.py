"""The continuous sampler's two probes (server/profiling.py): the lateness
of its wake-up (`minio_tpu_gil_wait_seconds`: the OS wake-up plus the wait
for the interpreter lock) and the wait of a callback it hands the event
loop (`minio_tpu_loop_lag_seconds`); every row of both is on `/api/tpu`
from a fresh server's first scrape. Asserted by orders of magnitude
against a control, never by value: a CPU run gives no time of the chip's
machine."""

import asyncio
import re
import sys
import threading
import time

import pytest

from minio_tpu.client import S3Client
from minio_tpu.server import profiling

from test_s3_api import ServerThread

HZ = 50.0


def _mean(h: dict) -> float:
    return h["sum_s"] / h["count"]


def _ticks(prof, key: str, n: int, timeout: float = 10.0) -> dict:
    """The probe's histogram once it holds `n` more samples than now."""
    start = prof.snapshot()[key]["count"]
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        h = prof.snapshot()[key]
        if h["count"] >= start + n:
            return h
        time.sleep(0.02)
    raise AssertionError(f"{key}: fewer than {n} samples in {timeout} s")


def _delta(a: dict, b: dict) -> dict:
    counts = [y - x for x, y in zip(a["counts"], b["counts"])]
    return {"counts": counts, "sum_s": b["sum_s"] - a["sum_s"], "count": sum(counts)}


@pytest.fixture()
def prof():
    p = profiling.ContinuousProfiler(HZ).start()
    yield p
    p.stop()
    assert not p._thread.is_alive()


@pytest.fixture()
def loop():
    lp = asyncio.new_event_loop()
    t = threading.Thread(target=lp.run_forever, daemon=True)
    t.start()
    yield lp
    if not lp.is_closed():
        lp.call_soon_threadsafe(lp.stop)
        t.join(timeout=5)
        lp.close()


def test_a_histogram_files_each_wait_under_the_first_edge_it_does_not_pass():
    h = profiling.WaitHistogram()
    for s in (0.00005, 0.0001, 0.0002, 0.7, 3.0):
        h.add(s)
    snap = h.copy()
    assert snap["count"] == 5 and snap["sum_s"] == pytest.approx(3.70035)
    edges = profiling.WAIT_BUCKETS
    assert edges[0] == 0.0001 and edges[-1] == 1.0
    assert snap["counts"][0] == 2  # 0.1 ms and under
    assert snap["counts"][edges.index(0.00025)] == 1
    assert snap["counts"][edges.index(1.0)] == 1 and snap["counts"][-1] == 1  # +Inf
    assert profiling.WaitHistogram().copy() == {"counts": [0] * (len(edges) + 1),
                                                "sum_s": 0.0, "count": 0}


def test_the_wake_up_waits_longer_behind_a_thread_that_spins_in_python(prof):
    idle = _delta(prof.snapshot()["gil_wait"], _ticks(prof, "gil_wait", 15))
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(200))

    old = sys.getswitchinterval()
    sys.setswitchinterval(0.02)  # a holder keeps the lock up to 20 ms
    spinner = threading.Thread(target=spin, daemon=True)
    try:
        spinner.start()
        before = prof.snapshot()["gil_wait"]
        busy = _delta(before, _ticks(prof, "gil_wait", 15))
    finally:
        stop.set()
        sys.setswitchinterval(old)
        spinner.join(timeout=5)
    assert not spinner.is_alive()
    # the floor is the OS wake-up; a spinner in pure Python adds a queue
    # for the lock of up to a switch interval at each wake-up
    assert _mean(busy) > 0.002
    assert _mean(busy) > 5 * _mean(idle)


def test_the_loop_lag_reads_a_deliberate_block_of_the_loop(prof, loop):
    prof.watch_loop(loop)
    quiet = _ticks(prof, "loop_lag", 5)
    assert quiet["count"] >= 5
    blocked = threading.Event()

    def block():
        blocked.set()
        time.sleep(0.4)  # the loop runs nothing else meanwhile

    loop.call_soon_threadsafe(block)
    assert blocked.wait(5)
    after = _ticks(prof, "loop_lag", 10)
    d = _delta(quiet, after)
    # hand-offs queued behind the block waited for it: the longest at
    # least most of its 0.4 s, past the 0.25 s edge
    over = sum(d["counts"][profiling.WAIT_BUCKETS.index(0.25) + 1:])
    assert over >= 1 and d["sum_s"] >= 0.25


def test_a_closed_loop_books_nothing_and_raises_nothing(prof, loop):
    prof.watch_loop(loop)
    _ticks(prof, "loop_lag", 3)
    loop.call_soon_threadsafe(loop.stop)
    deadline = time.monotonic() + 5
    while loop.is_running() and time.monotonic() < deadline:
        time.sleep(0.01)
    loop.close()
    n = prof.snapshot()["loop_lag"]["count"]
    _ticks(prof, "gil_wait", 10)  # the sampler ticks on past the closed loop
    assert prof.snapshot()["loop_lag"]["count"] <= n + 1  # one handed before the close
    assert prof._thread.is_alive()


def test_the_knob_turns_the_sampler_and_both_probes_off(monkeypatch):
    monkeypatch.setenv(profiling.CONTINUOUS_KNOB, "0")
    assert profiling.start_continuous_from_env() is None


@pytest.fixture(scope="module")
def fresh_server(tmp_path_factory):
    base = tmp_path_factory.mktemp("gildrives")
    st = ServerThread([str(base / f"d{i}") for i in range(4)])
    yield st
    st.stop()


def _rows(text: str, name: str) -> dict[str, float]:
    return {m.group(1) or "": float(m.group(2))
            for m in re.finditer(rf'^{name}(?:\{{le="([^"]+)"\}})? (\S+)$', text, re.M)}


@pytest.mark.parametrize("probe", ["gil_wait", "loop_lag"])
def test_every_row_of_both_probes_is_there_from_the_first_scrape(fresh_server, probe):
    """A server without the sampler (in-process: it starts in `main()`
    only) reports every row at zero; one with it reports its counts."""
    cli = S3Client(f"127.0.0.1:{fresh_server.port}")
    name = f"minio_tpu_{probe}_seconds"
    text = cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode()
    buckets = _rows(text, f"{name}_bucket")
    assert list(buckets) == [str(e) for e in profiling.WAIT_BUCKETS] + ["+Inf"]
    assert set(buckets.values()) == {0.0}
    assert _rows(text, f"{name}_sum") == {"": 0.0} and _rows(text, f"{name}_count") == {"": 0.0}

    prof = profiling.ContinuousProfiler(HZ).start()
    try:
        prof.watch_loop(fresh_server.loop)
        fresh_server.srv.cprofiler = prof
        _ticks(prof, probe, 5)
        text = cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode()
    finally:
        fresh_server.srv.cprofiler = None
        prof.stop()
    buckets = _rows(text, f"{name}_bucket")
    count = _rows(text, f"{name}_count")[""]
    assert count >= 5 and buckets["+Inf"] == count
    assert list(buckets.values()) == sorted(buckets.values())  # cumulative
    assert _rows(text, f"{name}_sum")[""] > 0

