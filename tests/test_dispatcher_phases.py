"""The dispatch thread's phase clock on the CPU rungs: every phase key is
there from the start, one dispatch's device phases ARE the device_s it
added, and a (rung, bucket) counts one first call however many follow."""

import numpy as np
import pytest

from minio_tpu import obs
from minio_tpu.ops import rs_jax
from minio_tpu.parallel import dispatcher as dmod
from minio_tpu.parallel.dispatcher import TpuDispatcher

RNG = np.random.default_rng(11)


def _dispatch_rows():
    return {name: row for (layer, name), row in obs.phases_snapshot().items()
            if layer == "dispatch"}


def _blocks(k, d=4, n=1024):
    return RNG.integers(0, 256, size=(k, d, n), dtype=np.uint8)


def test_device_and_host_phases_partition_the_dispatch_phases():
    assert set(dmod.DEVICE_PHASES) | set(dmod.HOST_PHASES) | {"wait", "window"} \
        == set(obs.PHASES["dispatch"])
    assert not set(dmod.DEVICE_PHASES) & set(dmod.HOST_PHASES)


@pytest.mark.parametrize("rung", ["xla", "numpy"])
def test_device_phases_sum_to_the_device_seconds_of_the_same_dispatches(rung):
    disp = TpuDispatcher(rs_jax.get_tpu_codec(4, 2), 1024, window_s=0.0)
    if rung == "numpy":
        disp.stats["backend_level"] = dmod.LEVEL_NUMPY
        disp._probe_after = disp._probe_countdown = 10**9  # stay demoted
    before = _dispatch_rows()
    assert set(before) == set(obs.PHASES["dispatch"])  # pre-seeded, all of them
    for k in (1, 3, 4):
        disp.encode(_blocks(k))
    after = _dispatch_rows()
    st = disp.stats_snapshot()
    moved = {name: after[name][0] - before[name][0] for name in after}
    assert sum(moved[p] for p in dmod.DEVICE_PHASES) == pytest.approx(st["device_s"])
    assert sum(moved[p] for p in dmod.HOST_PHASES) == pytest.approx(st["host_s"], abs=1e-3)
    assert st["dispatches"] == 3
    assert after["fanout"][2] - before["fanout"][2] == 3
    if rung == "xla":
        for p in ("h2d", "kernel", "d2h", "unpack"):
            assert after[p][2] - before[p][2] == 3 and moved[p] > 0
        # the CPU rung has no mega-kernel: nothing is packed, nothing on numpy
        assert moved["pack"] == 0 and moved["numpy"] == 0
        # results are parity only since PR 26: the row stays, nothing runs under it
        assert after["frame"][2] == before["frame"][2] and moved["frame"] == 0
        assert st["device_s"] > 0 and sum(st["device_time_hist"]) == 3
    else:
        assert st["device_s"] == 0 and st["numpy_blocks"] == 8
        assert moved["numpy"] > 0 and moved["kernel"] == 0


def test_a_rung_and_bucket_count_one_first_call_however_many_follow():
    disp = TpuDispatcher(rs_jax.get_tpu_codec(4, 2), 512, window_s=0.0)
    assert disp.stats["first_calls"] == {} and disp.stats["first_call_s"] == {}
    for _ in range(3):
        disp.encode(_blocks(2, n=512))
    st = disp.stats_snapshot()
    assert st["first_calls"] == {("xla", 2): 1}
    first_s = st["first_call_s"][("xla", 2)]
    assert first_s > 0  # its kernel phase: trace-and-lower + compile + run
    disp.encode(_blocks(3, n=512))  # bucket 4: another first call
    disp.encode(_blocks(2, n=512))
    st = disp.stats_snapshot()
    assert st["first_calls"] == {("xla", 2): 1, ("xla", 4): 1}
    assert st["first_call_s"][("xla", 2)] == first_s
    # demoted, the same bucket is a first call of the numpy rung, with no kernel time
    disp.stats["backend_level"] = dmod.LEVEL_NUMPY
    disp._probe_after = disp._probe_countdown = 10**9
    disp.encode(_blocks(2, n=512))
    disp.encode(_blocks(2, n=512))
    st = disp.stats_snapshot()
    assert st["first_calls"][("numpy", 2)] == 1 and st["first_call_s"][("numpy", 2)] == 0.0
    # the aggregate sums the tables of the registered dispatchers key by key
    codec = rs_jax.get_tpu_codec(4, 2)
    was = dmod.aggregate_stats().get("first_calls", {}).get(("xla", 2), 0)
    for n in (384, 640):
        for _ in range(2):
            dmod.get_dispatcher(codec, n).encode(_blocks(2, n=n))
    agg = dmod.aggregate_stats()
    assert agg["first_calls"][("xla", 2)] == was + 2
    assert agg["first_call_s"][("xla", 2)] > 0


def test_the_histograms_no_longer_saturate_at_half_a_second():
    for edges in (dmod.QUEUE_WAIT_BUCKETS, dmod.DEVICE_TIME_BUCKETS):
        assert edges[-6:] == (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
        assert list(edges) == sorted(edges) and edges[8] == 0.5
    hist = [0] * (len(dmod.DEVICE_TIME_BUCKETS) + 1)
    dmod._hist_add(hist, dmod.DEVICE_TIME_BUCKETS, 1.6)  # the chip's 256-block dispatch
    dmod._hist_add(hist, dmod.DEVICE_TIME_BUCKETS, 99.0)
    assert hist[dmod.DEVICE_TIME_BUCKETS.index(2.0)] == 1 and hist[-1] == 1


def test_a_waiting_dispatch_thread_books_wait_not_work():
    import time

    disp = TpuDispatcher(rs_jax.get_tpu_codec(4, 2), 256, window_s=0.0)
    disp.encode(_blocks(1, n=256))
    before = _dispatch_rows()
    time.sleep(0.2)
    disp.encode(_blocks(1, n=256))
    after = _dispatch_rows()
    # other dispatchers of this process wait too: at least this one's 0.2 s
    assert after["wait"][0] - before["wait"][0] >= 0.19
    assert after["wait"][1] - before["wait"][1] < 0.1  # waiting burns no CPU
