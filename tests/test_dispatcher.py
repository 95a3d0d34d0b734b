"""Batching dispatcher: concurrent requests must coalesce into single
device dispatches with byte-identical results."""

import threading

import numpy as np
import pytest

from minio_tpu.ops import rs, rs_jax
from minio_tpu.ops.highwayhash import hash256_batch_numpy
from minio_tpu.parallel.dispatcher import TpuDispatcher

RNG = np.random.default_rng(5)


def test_dispatch_correctness_and_batching():
    codec = rs_jax.get_tpu_codec(4, 2)
    ref = rs.get_codec(4, 2)
    n = 2048
    disp = TpuDispatcher(codec, n, window_s=0.05)
    # warm the jit so the batching window isn't swallowed by compile time
    disp.encode(RNG.integers(0, 256, size=(1, 4, n), dtype=np.uint8))

    inputs = [RNG.integers(0, 256, size=(2, 4, n), dtype=np.uint8) for _ in range(8)]
    results: list = [None] * 8
    barrier = threading.Barrier(8)

    def worker(i):
        barrier.wait()  # all submit inside one batching window
        results[i] = disp.encode(inputs[i])

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for i in range(8):
        parity, digests = results[i]
        assert parity.shape == (2, 2, n)  # parity rows only: data stays the caller's
        for k in range(2):
            expect = ref.encode(
                np.concatenate([inputs[i][k], np.zeros((2, n), np.uint8)])
            )
            np.testing.assert_array_equal(parity[k], expect[4:])
            np.testing.assert_array_equal(
                digests[k], hash256_batch_numpy(expect)
            )
    # the 8 concurrent submissions (16 blocks) must have shared dispatches
    assert disp.stats["blocks"] >= 17
    assert disp.stats["max_batch"] >= 4, disp.stats


def test_dispatch_error_propagates():
    codec = rs_jax.get_tpu_codec(4, 2)
    disp = TpuDispatcher(codec, 128, window_s=0.0)
    with pytest.raises(Exception):
        disp.encode(np.zeros((1, 3, 128), dtype=np.uint8))  # wrong d
