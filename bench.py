"""North-star benchmark: fused RS encode + bitrot hashing on TPU.

Measures the device-side throughput of the fused EC:8 (8 data + 8 parity)
encode+HighwayHash dispatch over 1 MiB stripe blocks — the hot loop of
PutObject (reference: /root/reference/cmd/erasure-encode.go:76-108 +
cmd/bitrot-streaming.go), the path BASELINE.md targets at >= 4x the
reference's AVX512 CPU pipeline.

The dispatch is the chunk-major Pallas mega-kernel (ops/fused_pallas.py):
one kernel reads each data byte from HBM once, produces parity via
bit-plane MXU matmuls, and hashes all d+p shards on the VPU while they are
resident in VMEM. Input is packed chunk-major on the host (the dispatcher
writes request payloads into the batch buffer in this layout — same
memcpy volume as any batch assembly).

Baseline: klauspost/reedsolomon AVX512 EC 8+8 encode measures ~10-14 GB/s
and asm HighwayHash ~10 GB/s per core; pipelined encode+hash(16 shards)
lands ~5 GiB/s single-core. BASELINE.json fixes the bar at the encode
benchmark's AVX512 number; we use 10 GiB/s as the reference value so
vs_baseline is conservative.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"platform", "device_kind"}. Needs a TPU: without one it refuses to run
(exit non-zero) rather than time another path under the same metric
name, and a phase that raises fails the run. Each epoch chains
dispatches and ends in ``jax.block_until_ready`` (chip_smoke.py's
`kernels` phase checks on the chip that it blocks). A correctness
spot-check against the independent numpy codec + numpy HighwayHash runs
before timing.
"""

import json
import statistics
import time

# Reference-pipeline denominator. 10 GiB/s is EXTRAPOLATED from
# klauspost/reedsolomon's published AVX-512 EC 8+8 numbers (no Go
# toolchain in this image to measure it); the honest same-host anchor is
# measured below at bench time: this build's own native C++ single-core
# encode+hash plane (GFNI/AVX2, minio_tpu/native) — 2.5 GiB/s recorded
# in PERF.md, re-measured on every run and reported as
# anchor_native_gibps / vs_native_anchor alongside vs_baseline.
BASELINE_GIBPS = 10.0
BASELINE_KIND = "extrapolated_avx512"
EPOCHS = 5  # median-of-5 with recorded spread (best-of overstates)


def _measure_native_anchor(np) -> float:
    """Measured same-host CPU anchor: the native fused encode+hash
    (single core, GFNI/AVX2) on the same EC 8+8 / 1 MiB-stripe shape the
    device benchmark uses. GiB/s of data bytes; 0.0 if the native plane
    is unavailable."""
    from minio_tpu import native
    from minio_tpu.ops.highwayhash import MINIO_KEY
    from minio_tpu.ops.rs import get_codec

    if not native.available():
        return 0.0
    d, n = D, N
    ref = get_codec(d, P)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(d, n), dtype=np.uint8)
    native.gf_encode_hash(ref.parity_matrix, data, MINIO_KEY)  # warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(8):
            native.gf_encode_hash(ref.parity_matrix, data, MINIO_KEY)
        best = min(best, time.perf_counter() - t0)
    return (8 * d * n / 2**30) / best


def _epochs(run, dd, iters: int) -> list[float]:
    """Per-epoch wall seconds for `iters` chained dispatches."""
    import jax

    times = []
    for _ in range(EPOCHS):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = run(dd)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return times
D, P = 8, 8            # EC 8+8
N = (1 << 20) // D     # 1 MiB stripe block -> 128 KiB shards
BATCH = 192            # concurrent stripe blocks per dispatch


def _fused_mega(jax, np):
    """(fn, device_input, data_bytes, verify) for the mega-kernel path."""
    from minio_tpu.ops import fused_pallas as fp

    d, p, n, B = D, P, N, BATCH
    data = np.random.default_rng(0).integers(0, 256, size=(B, d, n), dtype=np.uint8)
    dd = jax.device_put(fp.pack_chunk_major(data))

    def run(x):
        return fp.fused_encode_hash_cm(x, d, p)

    def verify(parity_cm, digests):
        from minio_tpu.ops.highwayhash import hash256_batch_numpy
        from minio_tpu.ops.rs import get_codec

        bsel = 0
        ref = get_codec(d, p)
        shards = ref.split(data[bsel].tobytes())
        ref.encode(shards)
        # slice device-side first: one block's parity is all the check needs
        got_par = fp.unpack_chunk_major(
            np.asarray(parity_cm[:, bsel:bsel + 1])
        )[0]
        assert (shards[d:] == got_par).all(), "parity mismatch vs numpy codec"
        want_dig = hash256_batch_numpy(shards)
        assert (want_dig == np.asarray(digests)[bsel]).all(), \
            "digest mismatch vs numpy HighwayHash"

    return run, dd, B * d * n, verify


def _bench_decode(jax, np) -> float:
    """On-chip decode mega-kernel throughput (VERDICT r3: decode metric
    next to encode): survivors in -> missing shards + digests out, 2 data
    shards lost. Returns GiB/s of survivor bytes, 0.0 if unsupported."""
    from minio_tpu.ops import fused_pallas as fp

    d, p, n, B = D, P, N, BATCH
    present = tuple(i for i in range(d + p) if i not in (1, 5))[:d]
    missing = (1, 5)
    if not fp.supports(d, len(missing), B, n):
        return 0.0
    surv = np.random.default_rng(3).integers(0, 256, size=(B, d, n), dtype=np.uint8)
    dd = jax.device_put(fp.pack_chunk_major(surv))

    def run(x):
        return fp.fused_decode_hash_cm(x, d, p, present, missing)

    out = jax.block_until_ready(run(dd))
    # correctness spot-check vs the numpy codec path
    from minio_tpu.ops.rs import get_codec

    ref = get_codec(d, p)
    mat = ref.reconstruct_rows_for(list(present), list(missing))
    from minio_tpu.ops import gf

    want0 = gf.gf_matvec_blocks(np.asarray(mat, dtype=np.uint8), surv[0])
    got0 = fp.unpack_chunk_major(np.asarray(out[0][:, :1]))[0]
    assert (got0 == want0).all(), "decode kernel mismatch vs numpy"

    iters = 15
    times = _epochs(run, dd, iters)
    gib = B * d * n / 2**30
    return gib * iters / statistics.median(times)


def _bench_qos_p99(np) -> dict:
    """Secondary metric: p99 foreground single-block encode latency via
    the priority-aware dispatcher (parallel/dispatcher.py), with and
    without saturating background load. QoS regressions (foreground
    blocks delayed behind background batches) show up as the `bg_on`
    number diverging from `bg_off` across BENCH_*.json rounds."""
    import threading

    from minio_tpu.ops.rs_jax import get_tpu_codec
    from minio_tpu.parallel.dispatcher import PRI_BACKGROUND, TpuDispatcher
    from minio_tpu.qos.context import background_context

    codec = get_tpu_codec(D, P)
    disp = TpuDispatcher(codec, N)
    rng = np.random.default_rng(11)
    fg_blk = rng.integers(0, 256, size=(1, D, N), dtype=np.uint8)
    bg_blk = rng.integers(0, 256, size=(8, D, N), dtype=np.uint8)
    disp.encode(fg_blk)  # warm/compile both shapes
    disp.encode(bg_blk, priority=PRI_BACKGROUND)

    def fg_p99(bg_load: bool, samples: int = 60) -> float:
        stop = threading.Event()
        flooders = []
        if bg_load:
            def flood():
                with background_context():
                    while not stop.is_set():
                        disp.encode(bg_blk)

            flooders = [threading.Thread(target=flood) for _ in range(2)]
            for t in flooders:
                t.start()
            time.sleep(0.1)  # saturation established
        lats = []
        try:
            for _ in range(samples):
                t0 = time.perf_counter()
                disp.encode(fg_blk)
                lats.append(time.perf_counter() - t0)
        finally:
            stop.set()
            for t in flooders:
                t.join()
        lats.sort()
        return lats[min(len(lats) - 1, int(len(lats) * 0.99))]

    off = fg_p99(False)
    on = fg_p99(True)
    # dispatcher efficiency stats (obs/): host orchestration vs device
    # execute split + batch occupancy — recorded in the BENCH trajectory
    # so kernel-time regressions and host-plumbing regressions are
    # distinguishable across rounds
    st = disp.stats
    n_disp = max(st["dispatches"], 1)
    n_items = max(sum(st["queue_wait_hist"]), 1)
    return {
        "qos_metric": "fg_encode_p99_ms",
        "qos_fg_p99_ms_bg_off": round(off * 1e3, 3),
        "qos_fg_p99_ms_bg_on": round(on * 1e3, 3),
        "qos_fg_deferred_behind_bg": st["fg_deferred_behind_bg"],
        "qos_bg_blocks": st["bg_blocks"],
        "dispatch_occupancy_pct": round(st["occupancy_pct_sum"] / n_disp, 1),
        "dispatch_device_ms_avg": round(st["device_s"] / n_disp * 1e3, 3),
        "dispatch_host_ms_avg": round(st["host_s"] / n_disp * 1e3, 3),
        "dispatch_queue_wait_ms_avg": round(
            st["queue_wait_s"] / n_items * 1e3, 3
        ),
    }


def _bench_degraded(np) -> dict:
    """Degraded-mode GET throughput: one drive injected at +400 ms
    (fault/registry.py), measured with the hedged-read path on and off.
    The hedge_on number staying near healthy throughput while hedge_off
    inherits the straggler's stall is the wire-visible proof of the
    hedge policy; regressions show up across BENCH_*.json rounds."""
    import os
    import shutil
    import tempfile

    from minio_tpu.erasure.set import ErasureSet
    from minio_tpu.fault import registry as freg
    from minio_tpu.fault.storage import FaultInjectedDisk
    from minio_tpu.storage.health import HealthCheckedDisk
    from minio_tpu.storage.xlstorage import XLStorage
    from minio_tpu.utils.hashing import hash_order

    base = tempfile.mkdtemp(prefix="bench-degraded-")
    saved = {
        k: os.environ.get(k)
        for k in ("MINIO_TPU_NATIVE_PLANE", "MINIO_TPU_HEDGE")
    }
    # the native pread plane bypasses the injection wrapper: force the
    # Python read path so the straggler actually stalls reads
    os.environ["MINIO_TPU_NATIVE_PLANE"] = "0"
    try:
        disks = [
            HealthCheckedDisk(FaultInjectedDisk(XLStorage(f"{base}/d{i}")))
            for i in range(8)
        ]
        es = ErasureSet(disks)
        es.make_bucket("bbkt")
        body = np.random.default_rng(1).integers(
            0, 256, size=16 << 20, dtype=np.uint8
        ).tobytes()
        es.put_object("bbkt", "obj", body)

        def measure() -> float:
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                _, it = es.get_object("bbkt", "obj")
                n = sum(len(c) for c in it)
                assert n == len(body)
                best = min(best, time.perf_counter() - t0)
            return (len(body) / 2**30) / best

        # straggle the drive holding data shard 0 (parity isn't read
        # eagerly, so a parity straggler would measure nothing)
        dist = hash_order("bbkt/obj", 8)
        freg.inject({
            "boundary": "storage", "mode": "latency", "latency_ms": 400,
            "target": disks[dist.index(1)].endpoint, "op": "read_file",
            "seed": 1,
        })
        os.environ["MINIO_TPU_HEDGE"] = "1"
        on = measure()
        wins = freg.COUNTERS.get("hedge_wins", 0)
        os.environ["MINIO_TPU_HEDGE"] = "0"
        off = measure()
        return {
            "degraded_get_gibps_hedge_on": round(on, 3),
            "degraded_get_gibps_hedge_off": round(off, 3),
            "degraded_hedge_wins": wins,
        }
    finally:
        freg.clear()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(base, ignore_errors=True)


def _bench_heal_repair(np) -> dict:
    """Round-9 tentpole metric: heal + degraded-GET cost per code family
    at EC 8+8 over 16 drives, with a fault-injected ~1.5 ms/shard-read
    RTT standing in for a real network hop (this container's drives are
    local tmpfs — without the injected latency every read is microsecond
    -class and the survivor-byte savings would be invisible in time,
    only in bytes).

    Emits per family: heal_ingress_bytes for a single-data-drive heal
    (THE acceptance number: cauchy must read >= 25% fewer survivor
    bytes), wall-clock heal seconds, and degraded ranged-GET MiB/s with
    the same drive lost. reedsolomon reads d full shard frames; cauchy's
    repair schedule reads sub-chunk frames (ops/cauchy.py)."""
    import os
    import shutil
    import tempfile

    from minio_tpu.erasure.coder import family_stats_snapshot
    from minio_tpu.erasure.set import ErasureSet
    from minio_tpu.fault import registry as freg
    from minio_tpu.fault.storage import FaultInjectedDisk
    from minio_tpu.storage.xlstorage import XLStorage

    MIB = 1 << 20
    SIZE = 32 * MIB
    RTT_MS = 1.5
    base = tempfile.mkdtemp(prefix="bench-heal-")
    saved = {
        k: os.environ.get(k)
        for k in ("MINIO_TPU_NATIVE_PLANE", "MINIO_TPU_EC_FAMILY",
                  "MINIO_TPU_CACHE")
    }
    # the native pread plane bypasses the injection wrapper AND the
    # frame-granular read path being measured; caches would hide the
    # degraded read entirely
    os.environ["MINIO_TPU_NATIVE_PLANE"] = "0"
    os.environ["MINIO_TPU_CACHE"] = "0"
    out: dict = {}
    try:
        body = np.random.default_rng(9).integers(
            0, 256, size=SIZE, dtype=np.uint8
        ).tobytes()
        for fam in ("reedsolomon", "cauchy"):
            os.environ["MINIO_TPU_EC_FAMILY"] = fam
            disks = [
                FaultInjectedDisk(XLStorage(f"{base}/{fam}/d{i}"))
                for i in range(16)
            ]
            es = ErasureSet(disks, default_parity=8)
            es.make_bucket("hbkt")
            es.put_object("hbkt", "obj", body)
            fi, _ = es._cached_fileinfo("hbkt", "obj", "")
            lost = fi.erasure.distribution.index(1)  # data shard 0
            for dsk in disks:
                freg.inject({
                    "boundary": "storage", "mode": "latency",
                    "latency_ms": RTT_MS, "target": dsk.endpoint,
                    "op": "read_file", "seed": 1,
                })
            # --- heal: single data drive lost (best-of-1 per epoch,
            # median across 3 — each epoch re-kills the healed drive)
            heal_times = []
            ingress = 0
            for _ in range(3):
                shutil.rmtree(f"{base}/{fam}/d{lost}/hbkt/obj")
                es.cache.clear()
                before = family_stats_snapshot()[fam]["heal_ingress_bytes"]
                t0 = time.perf_counter()
                res = es.heal_object("hbkt", "obj")
                heal_times.append(time.perf_counter() - t0)
                assert res["healed"], res
                ingress = (
                    family_stats_snapshot()[fam]["heal_ingress_bytes"] - before
                )
            # --- degraded ranged GETs with the drive lost again
            shutil.rmtree(f"{base}/{fam}/d{lost}/hbkt/obj")
            es.cache.clear()
            t0 = time.perf_counter()
            n_bytes = 0
            for off_mib in range(0, 16):
                _, h = es.open_object("hbkt", "obj")
                for c in h.read(off_mib * MIB, MIB):
                    n_bytes += len(c)
            deg_s = time.perf_counter() - t0
            # byte-identity spot check on the degraded path
            _, h = es.open_object("hbkt", "obj")
            got = b"".join(bytes(c) for c in h.read(0, 2 * MIB))
            assert got == body[: 2 * MIB]
            freg.clear()
            key = "rs" if fam == "reedsolomon" else "cauchy"
            out[f"heal_ingress_bytes_{key}"] = ingress
            out[f"heal_s_{key}"] = round(statistics.median(heal_times), 3)
            out[f"degraded_rget_mibs_{key}"] = round(n_bytes / MIB / deg_s, 1)
        out["heal_ingress_savings_pct"] = round(
            100.0 * (1 - out["heal_ingress_bytes_cauchy"]
                     / max(out["heal_ingress_bytes_rs"], 1)), 1
        )
        return out
    finally:
        freg.clear()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(base, ignore_errors=True)


def _bench_ranged_get(np) -> dict:
    """Ranged hot-GET metric (range-segment cache tentpole, round 8):
    p50/p99 latency + IOPS of 1 MiB ranged GETs over ONE 64 MiB object
    (far above MINIO_TPU_CACHE_OBJECT_MAX) at the erasure layer, through
    the same ``open_object(range_hint)`` API the S3 handler uses:

    - **cold**: segment tier off — every request pays ns-lock + N-drive
      FileInfo + shard reads + verify for its range;
    - **warm_memory**: segments filled and resident in memory — a hit
      skips open_object entirely;
    - **warm_disk**: a tiny memory budget + an NVMe-tier budget so the
      warm set lives in segment FILES — hits pay a read + sha256 verify
      + promote;
    - **prefetched**: a fresh sequential pass with read-ahead running
      ahead of the client (first requests excluded as warm-up).
    """
    import os
    import shutil
    import tempfile

    from minio_tpu.erasure.set import ErasureSet
    from minio_tpu.storage.xlstorage import XLStorage

    MIB = 1 << 20
    SIZE_MIB = 64
    keys = (
        "MINIO_TPU_CACHE", "MINIO_TPU_CACHE_SEGMENTS",
        "MINIO_TPU_CACHE_ADMIT_TOUCHES", "MINIO_TPU_CACHE_MEM_MB",
        "MINIO_TPU_CACHE_DISK_MB", "MINIO_TPU_CACHE_DISK_DIR",
        "MINIO_TPU_CACHE_PREFETCH_SEGMENTS",
    )
    saved = {k: os.environ.get(k) for k in keys}
    base = tempfile.mkdtemp(prefix="bench-ranged-")
    rng = np.random.default_rng(8)

    def rig(tag: str) -> ErasureSet:
        es = ErasureSet(
            [XLStorage(f"{base}/{tag}/d{i}") for i in range(8)]
        )
        es.make_bucket("rbkt")
        return es

    def measure(es, key: str, order, samples_per_off: int = 1):
        lats = []
        t_all0 = time.perf_counter()
        n_req = 0
        for _ in range(samples_per_off):
            for off_mib in order:
                off = off_mib * MIB
                t0 = time.perf_counter()
                _oi, h = es.open_object(
                    "rbkt", key, "", ("abs", off, off + MIB - 1)
                )
                n = 0
                for c in h.read(off, MIB):
                    n += len(c)
                lats.append(time.perf_counter() - t0)
                n_req += 1
                assert n == MIB
        total = time.perf_counter() - t_all0
        lats.sort()
        return (
            lats[len(lats) // 2],
            lats[min(len(lats) - 1, int(len(lats) * 0.99))],
            n_req / total,
            lats,
        )

    try:
        os.environ["MINIO_TPU_CACHE"] = "1"
        os.environ["MINIO_TPU_CACHE_ADMIT_TOUCHES"] = "2"
        os.environ["MINIO_TPU_CACHE_PREFETCH_SEGMENTS"] = "0"
        body = rng.integers(0, 256, size=SIZE_MIB * MIB, dtype=np.uint8).tobytes()
        order = list(range(SIZE_MIB))
        import random as _random

        _random.Random(42).shuffle(order)

        # cold: segment tier off
        es = rig("cold")
        es.put_object("rbkt", "big", body)
        os.environ["MINIO_TPU_CACHE_SEGMENTS"] = "0"
        cold_p50, cold_p99, cold_iops, _ = measure(es, "big", order)

        # warm memory: fill (two passes for admission), then measure
        os.environ["MINIO_TPU_CACHE_SEGMENTS"] = "1"
        os.environ["MINIO_TPU_CACHE_MEM_MB"] = "256"
        os.environ["MINIO_TPU_CACHE_DISK_MB"] = "0"
        for _ in range(2):
            measure(es, "big", order)
        from minio_tpu.cache import segment as segmod

        s0 = segmod.segment_cache().snapshot()
        wm_p50, wm_p99, wm_iops, _ = measure(es, "big", order, 3)
        s1 = segmod.segment_cache().snapshot()
        hit_ratio = (s1["range_hits"] - s0["range_hits"]) / max(
            (s1["range_hits"] - s0["range_hits"])
            + (s1["range_misses"] - s0["range_misses"]), 1
        )

        # the previous phase's 64 MiB of resident segments would eat the
        # tiny budget below (the cache is process-wide); phases and
        # repeat epochs must start clean
        es.cache.clear()

        # warm disk: tiny memory budget, NVMe budget — fill, let the
        # tier demote, measure (hits promote from files, digest-checked)
        os.environ["MINIO_TPU_CACHE_MEM_MB"] = "8"
        os.environ["MINIO_TPU_CACHE_DISK_MB"] = "512"
        os.environ["MINIO_TPU_CACHE_DISK_DIR"] = f"{base}/spool"
        es_d = rig("disk")
        es_d.put_object("rbkt", "big", body)
        for _ in range(2):
            measure(es_d, "big", order)
        d0 = segmod.segment_cache().snapshot()
        wd_p50, wd_p99, wd_iops, _ = measure(es_d, "big", order, 3)
        d1 = segmod.segment_cache().snapshot()
        promotes = d1["promotions"] - d0["promotions"]

        es_d.cache.clear()

        # prefetched: fresh object + sequential pass, read-ahead on
        os.environ["MINIO_TPU_CACHE_MEM_MB"] = "256"
        os.environ["MINIO_TPU_CACHE_DISK_MB"] = "0"
        os.environ["MINIO_TPU_CACHE_PREFETCH_SEGMENTS"] = "8"
        from minio_tpu.cache import prefetch as pfmod

        pf0 = pfmod.stats()
        es_p = rig("pf")
        es_p.put_object("rbkt", "pf", body)
        warmup = 4
        _p50, _p99, _iops, lats = measure(
            es_p, "pf", list(range(SIZE_MIB))
        )
        lats = sorted(lats[warmup:])
        pf_p50 = lats[len(lats) // 2]
        pf_p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
        pf_iops = len(lats) / max(sum(lats), 1e-9)
        pf_stats = pfmod.stats()
        es_p.cache.clear()  # repeat epochs start clean
        from minio_tpu.parallel import dispatcher as disp

        deferred = disp.aggregate_stats().get("fg_deferred_behind_bg", 0)

        return {
            "ranged_get_p50_ms_cold": round(cold_p50 * 1e3, 3),
            "ranged_get_p99_ms_cold": round(cold_p99 * 1e3, 3),
            "ranged_get_iops_cold": round(cold_iops, 1),
            "ranged_get_p50_ms_warm_mem": round(wm_p50 * 1e3, 3),
            "ranged_get_p99_ms_warm_mem": round(wm_p99 * 1e3, 3),
            "ranged_get_iops_warm_mem": round(wm_iops, 1),
            "ranged_get_p50_ms_warm_disk": round(wd_p50 * 1e3, 3),
            "ranged_get_p99_ms_warm_disk": round(wd_p99 * 1e3, 3),
            "ranged_get_iops_warm_disk": round(wd_iops, 1),
            "ranged_get_p50_ms_prefetched": round(pf_p50 * 1e3, 3),
            "ranged_get_p99_ms_prefetched": round(pf_p99 * 1e3, 3),
            "ranged_get_iops_prefetched": round(pf_iops, 1),
            "ranged_warm_hit_ratio": round(hit_ratio, 4),
            "ranged_disk_promotions": promotes,
            "ranged_prefetch_runs": pf_stats.get("runs_detected", 0)
            - pf0.get("runs_detected", 0),
            "ranged_prefetch_bytes": pf_stats.get("bytes_read", 0)
            - pf0.get("bytes_read", 0),
            "fg_deferred_behind_bg": deferred,
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(base, ignore_errors=True)


def _bench_hot_get(np) -> dict:
    """Hot-GET metric (cache/ tentpole): p50/p99 latency + IOPS of
    repeated full GETs of ONE 1 MiB object over 8 local drives, with the
    quorum-coherent cache on vs off. Cache-off pays the full per-request
    cost (N-drive FileInfo fan-out + shard reads + verify); cache-on
    serves the verified bytes from memory after admission. The on/off
    ratio is the wire-visible proof the metadata/data hot path — not the
    codec — was the remaining per-request wall."""
    import os
    import shutil
    import tempfile

    from minio_tpu.erasure.set import ErasureSet
    from minio_tpu.storage.xlstorage import XLStorage

    base = tempfile.mkdtemp(prefix="bench-hotget-")
    saved = {
        k: os.environ.get(k)
        for k in ("MINIO_TPU_CACHE", "MINIO_TPU_CACHE_ADMIT_TOUCHES")
    }
    try:
        es = ErasureSet([XLStorage(f"{base}/d{i}") for i in range(8)])
        es.make_bucket("hbkt")
        body = np.random.default_rng(2).integers(
            0, 256, size=1 << 20, dtype=np.uint8
        ).tobytes()
        es.put_object("hbkt", "hot", body)

        def measure(samples: int = 300) -> tuple[float, float, float]:
            lats = []
            t_all0 = time.perf_counter()
            for _ in range(samples):
                t0 = time.perf_counter()
                _, it = es.get_object("hbkt", "hot")
                n = sum(len(c) for c in it)
                lats.append(time.perf_counter() - t0)
                assert n == len(body)
            total = time.perf_counter() - t_all0
            lats.sort()
            p50 = lats[len(lats) // 2]
            p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
            return p50, p99, samples / total

        os.environ["MINIO_TPU_CACHE"] = "0"
        off_p50, off_p99, off_iops = measure()
        os.environ["MINIO_TPU_CACHE"] = "1"
        os.environ["MINIO_TPU_CACHE_ADMIT_TOUCHES"] = "2"
        for _ in range(3):  # warm: admission wants repeat reads
            _, it = es.get_object("hbkt", "hot")
            for _c in it:
                pass
        # the DataCache is process-wide: snapshot before/after and diff,
        # or counters accumulated by earlier benches skew the ratio
        from minio_tpu.cache import core as cache_core

        fi0 = dict(es.cache.snapshot()["fileinfo"])
        ds0 = cache_core.data_cache().stats.snapshot()
        on_p50, on_p99, on_iops = measure()
        fi1 = es.cache.snapshot()["fileinfo"]
        ds1 = cache_core.data_cache().stats.snapshot()
        hits = (fi1["hits"] - fi0["hits"]) + (ds1["hits"] - ds0["hits"])
        misses = (fi1["misses"] - fi0["misses"]) + (ds1["misses"] - ds0["misses"])
        return {
            "cache_hot_get_p50_ms_on": round(on_p50 * 1e3, 3),
            "cache_hot_get_p50_ms_off": round(off_p50 * 1e3, 3),
            "cache_hot_get_p99_ms_on": round(on_p99 * 1e3, 3),
            "cache_hot_get_p99_ms_off": round(off_p99 * 1e3, 3),
            "cache_hot_get_iops_on": round(on_iops, 1),
            "cache_hot_get_iops_off": round(off_iops, 1),
            "cache_hit_ratio": round(hits / max(hits + misses, 1), 4),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(base, ignore_errors=True)


def _bench_ingest(np) -> dict:
    """Ingest metric (zero-copy tentpole): streaming-PUT throughput at
    EC 8+8 over 16 local drives, pooled zero-copy plane vs the legacy
    copying path (MINIO_TPU_ZEROCOPY A/B). Runs the Python data plane
    (MINIO_TPU_NATIVE_PLANE=0) on the numpy codec rung — the
    memory-bandwidth-bound configuration where staging/concat/tobytes
    copies are the wall the pooled arenas remove. The zero-copy arm is
    GATED on staging == 0 per PUT: the claim is measured per epoch, not
    assumed. Median-of-5 each arm."""
    import os
    import shutil
    import tempfile

    from minio_tpu.erasure import bufpool
    from minio_tpu.erasure.set import ErasureSet
    from minio_tpu.storage.xlstorage import XLStorage

    base = tempfile.mkdtemp(prefix="bench-ingest-")
    saved = {
        k: os.environ.get(k)
        for k in ("MINIO_TPU_ZEROCOPY", "MINIO_TPU_NATIVE_PLANE",
                  "MINIO_TPU_BACKEND")
    }
    try:
        os.environ["MINIO_TPU_NATIVE_PLANE"] = "0"
        os.environ["MINIO_TPU_BACKEND"] = "numpy"
        size = 64 << 20
        body = np.random.default_rng(3).integers(
            0, 256, size=size, dtype=np.uint8
        ).tobytes()

        def gen():
            mv = memoryview(body)
            for i in range(0, size, 1 << 20):
                yield mv[i : i + (1 << 20)]

        speeds: dict[str, float] = {}
        for zc in ("1", "0"):
            os.environ["MINIO_TPU_ZEROCOPY"] = zc
            es = ErasureSet(
                [XLStorage(f"{base}/zc{zc}-d{i}") for i in range(16)],
                default_parity=8,  # EC 8+8: d divides the stripe block,
                # the geometry the zero-copy reshape serves (12+4 falls
                # back to the legacy path by design)
            )
            es.make_bucket("ibkt")
            es.put_object("ibkt", "warm", gen())  # warm pool + caches
            epochs = []
            for e in range(EPOCHS):
                bufpool.copies_reset()
                t0 = time.perf_counter()
                es.put_object("ibkt", f"obj{e}", gen())
                dt = time.perf_counter() - t0
                epochs.append((size / 2**30) / dt)
                if zc == "1":
                    staging = bufpool.copies_snapshot()["staging"]
                    assert staging == 0, (
                        f"zero-copy ingest counted {staging} staging copies"
                    )
            speeds[zc] = statistics.median(epochs)
        return {
            "ingest_put_ec8_16d_gibps_zc": round(speeds["1"], 3),
            "ingest_put_ec8_16d_gibps_legacy": round(speeds["0"], 3),
            "ingest_zc_speedup": round(speeds["1"] / max(speeds["0"], 1e-9), 3),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(base, ignore_errors=True)


def main() -> None:
    import sys

    from minio_tpu.ops import runtime

    runtime.ensure_compile_cache()
    import jax
    import numpy as np

    from minio_tpu.ops import fused_pallas as fp

    dev = runtime.device_info()
    if dev["platform"] != "tpu" or not fp.supports(D, P, BATCH, N):
        # the metric is the mega-kernel's: timing any other path under
        # its name (as the XLA fallback once did) is not a measurement
        print(
            f"bench.py needs a TPU: JAX reports platform={dev['platform']} "
            f"kind={dev['kind']!r}; refusing to time another path as "
            "rs_encode_bitrot_ec8_1mib_gibps",
            file=sys.stderr,
        )
        raise SystemExit(2)
    fused, dd, data_bytes, verify = _fused_mega(jax, np)

    # warmup/compile + correctness
    out = jax.block_until_ready(fused(dd))
    verify(*out)

    # amortize over chained dispatches; MEDIAN of 5 epochs with the
    # min..max spread recorded (best-of overstates — VERDICT r2)
    iters = 15
    times = _epochs(fused, dd, iters)
    gib = data_bytes / 2**30
    gibps = gib * iters / statistics.median(times)
    spread = [gib * iters / max(t, 1e-9) for t in times]
    # a phase that raises fails the run: a broken sub-bench must not
    # vanish from the JSON behind exit code 0
    decode_gibps = _bench_decode(jax, np)
    anchor = _measure_native_anchor(np)
    qos = _bench_qos_p99(np)
    degraded = _bench_degraded(np)
    hot_get = _bench_hot_get(np)
    ranged_get = _bench_ranged_get(np)
    heal_repair = _bench_heal_repair(np)
    ingest = _bench_ingest(np)
    print(
        json.dumps(
            {
                "metric": "rs_encode_bitrot_ec8_1mib_gibps",
                "value": round(gibps, 2),
                "unit": "GiB/s",
                "vs_baseline": round(gibps / BASELINE_GIBPS, 2),
                "baseline_gibps": BASELINE_GIBPS,
                "baseline_kind": BASELINE_KIND,
                "anchor_native_gibps": round(anchor, 2),
                "vs_native_anchor": round(gibps / anchor, 2) if anchor else None,
                "epochs": EPOCHS,
                "spread_min": round(min(spread), 2),
                "spread_max": round(max(spread), 2),
                "decode_metric": "rs_decode_verify_ec8_2lost_gibps",
                "decode_value": round(decode_gibps, 2),
                **qos,
                **degraded,
                **hot_get,
                **ranged_get,
                **heal_repair,
                **ingest,
                "platform": dev["platform"],
                "device_kind": dev["kind"],
                "device_count": dev["count"],
            }
        )
    )


if __name__ == "__main__":
    main()
